"""The one data generator: a design matrix and its labels, made on the
devices from ``--seed`` in one jitted call, in the type the fit reads.

What a cell's fit sweeps is its traffic, so the generator is the benchmark's
own (a copy of the idea of ``cycloneml_tpu/dataset/random.py``: per-shard
streams, a chunked f32 draw narrowed in place) and the program gets only the
arrays. Every shard draws rows from its own stream; the ground truth ``beta``
is one stream shared by all shards, scaled to unit signal variance so that
``label_noise`` is the noise-to-signal ratio of the labels.
"""

from __future__ import annotations

import numpy as np

#: f32 bytes one chunk of the draw may hold (the whole f32 X never exists)
DRAW_CHUNK_BYTES = 64 << 20
_SEED_MOD = 2 ** 31 - 1


def base_key(seed: int):
    """A key from any whole number: the driver's seeds pass 2**31."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed % _SEED_MOD, impl="rbg")
    return jax.random.fold_in(key, seed // _SEED_MOD)


def _draw_shard(key, order, beta, rows: int, n_cols: int, xdt, noise: float):
    """One shard's ``(rows, n_cols)`` standard-normal X at ``xdt`` and its f32
    margins ``x.beta + noise eps``, drawn in row chunks written in place.
    Chunk ``i`` of the shard is stream ``order[i]`` of ``key``: another
    ``order`` gives the same rows in another order."""
    import jax
    import jax.numpy as jnp

    chunk = min(rows, max(8, DRAW_CHUNK_BYTES // (4 * n_cols) // 8 * 8))
    n_full = (rows - 1) // chunk
    tail = rows - n_full * chunk

    def put(i, stream, n, carry):
        x, margin = carry
        kx, ke = jax.random.split(jax.random.fold_in(key, stream))
        # the labels follow the STORED values, as a real dataset's would
        xs = jax.random.normal(kx, (n, n_cols), jnp.float32).astype(xdt)
        m = jnp.dot(xs.astype(jnp.float32), beta,
                    precision=jax.lax.Precision.HIGHEST) \
            + noise * jax.random.normal(ke, (n,), jnp.float32)
        return (jax.lax.dynamic_update_slice(x, xs, (i * chunk, 0)),
                jax.lax.dynamic_update_slice(margin, m, (i * chunk,)))

    carry = (jnp.zeros((rows, n_cols), xdt), jnp.zeros((rows,), jnp.float32))
    perm = order(max(n_full, 1))       # traced even where no chunk is full
    carry = jax.lax.fori_loop(
        0, n_full, lambda i, c: put(i, perm[i], chunk, c), carry)
    return put(n_full, n_full, tail, carry)


def generate(mesh, row_axes, data_seed: int, order_seed: int,
             rows_per_shard: int, n_cols: int, task: str, label_noise: float,
             x_dtype: str):
    """``(x, y)`` row-sharded over ``row_axes`` of ``mesh``: x at ``x_dtype``,
    y f32 — ``1[x.beta + noise > 0]`` for ``classification``, ``x.beta +
    noise`` for ``regression``.

    ``data_seed`` fixes WHICH rows exist (it belongs to the cell);
    ``order_seed`` (a run's ``--seed``) fixes which shard holds which stream
    and the order of the chunks in it. An iterative fit's number of
    evaluations follows the data, so every seed gets the same rows in
    another order: the same work, the same optimum, other rounding."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if task not in ("classification", "regression"):
        raise ValueError(f"unknown task {task!r}")
    n_shards = int(np.prod([mesh.shape[a] for a in row_axes]))
    xdt = jnp.dtype(x_dtype)
    row = P(tuple(row_axes))

    # the keys are arguments, not constants: one program serves every seed
    def local(idx, key0, order_key):
        kb = jax.random.fold_in(key0, _SEED_MOD)
        beta = jax.random.normal(kb, (n_cols,), jnp.float32) / np.sqrt(n_cols)
        stream = jax.random.permutation(
            jax.random.fold_in(order_key, 0), n_shards)[idx[0]]
        x, margin = _draw_shard(
            jax.random.fold_in(key0, stream),
            lambda n: jax.random.permutation(
                jax.random.fold_in(order_key, 1), n),
            beta, rows_per_shard, n_cols, xdt, label_noise)
        y = (margin > 0).astype(jnp.float32) if task == "classification" \
            else margin
        return x, y

    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(row, P(), P()),
                               out_specs=(row, row), check_vma=False))
    return fn(jnp.arange(n_shards, dtype=jnp.int32), base_key(data_seed),
              base_key(order_seed))
