"""Clustered points and a stated starting set for a dense design the
generator made, as Spark MLlib's ``mllib/util/KMeansDataGenerator.scala``
draws them (``generateKMeansRDD(sc, numPoints, k, d, r, numPartitions)``: k
centres ``r N(0, I_d)``, every point its centre plus unit Gaussian noise):

    point_i = bf16( r mu_{c(i)} + x_i ),     c(i) = hash(bits of x_i) mod k,

with ``x_i`` the generator's STORED standard-normal row (the noise) and
``mu`` drawn from the traffic's ``data_seed``. The cluster of a row is a
function of the row's own bits, so the same rows give the same points in any
order and on any shard: ``--seed`` still only orders them. The starting set
is k fresh draws of the same mixture from ``data_seed`` alone (what
``initMode="random"`` gives in distribution: k sampled points), made a
stated set so that the program and the reference start alike and every
``--seed`` is the same work.

``datagen.generate`` knows a binary and a regression label and is not this
PR's to edit; a configuration that wants clustered points states
``data.kmeans_points`` (``k``, ``r`` and the ``traffic`` whose ``data_seed``
draws the centres and the starting set) and both its entry and its reference
build the points through :func:`block_points` — every operation in it is
exact (a one-hot product of bf16 pieces, a fixed order of f32 adds) but the
one rounding to bf16, made by ``reduce_precision``, so neither the block size
nor the fusion it lands in can change a bit.
"""

from __future__ import annotations

import functools

import numpy as np

from perfbench import datagen, manifest

#: rows one chunk of :func:`points` builds (its one-hot is chunk x k bf16)
CHUNK_ROWS = 32768


def spec(config_name: str) -> dict:
    """``{"k", "r", "data_seed"}`` of a configuration."""
    cfg = manifest.load_json(manifest.HERE, "configs", config_name + ".json")
    pts = cfg["data"]["kmeans_points"]
    traffic = manifest.load_json(manifest.HERE, "traffic",
                                 pts["traffic"] + ".json")
    return {"k": int(pts["k"]), "r": float(pts["r"]),
            "data_seed": int(traffic["data_seed"])}


def centres(data_seed: int, k: int, d: int, r: float):
    """The mixture's ``(k, d)`` float32 centres ``r N(0, I)``."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(datagen.base_key(data_seed), 11)
    return jnp.float32(r) * jax.random.normal(key, (k, d), jnp.float32)


def cluster_of(x_raw, k: int):
    """``(rows,)`` int32 cluster of every stored row: a multiplicative hash
    of its bits (wrapping uint32 arithmetic), mod k."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(
        x_raw.astype(jnp.bfloat16), jnp.uint16).astype(jnp.uint32)
    odd = (2 * jnp.arange(x_raw.shape[1], dtype=jnp.uint32) + 1) \
        * jnp.uint32(0x9E3779B1)
    h = jnp.sum(bits * odd[None, :], axis=1, dtype=jnp.uint32)
    h = (h ^ (h >> 15)) * jnp.uint32(0x2C1B3C6D)
    h = (h ^ (h >> 12)) * jnp.uint32(0x297A2D39)
    h = h ^ (h >> 15)
    return (h % jnp.uint32(k)).astype(jnp.int32)


def _round_bf16(a):
    """``a`` rounded to bfloat16's 8 bits, still float32: the rounding the
    compiler may not take back. A ``convert`` pair it may — XLA:TPU keeps a
    bf16 value of a fusion at f32, so a reference that re-built the points
    inside its sweep read them UNROUNDED (PR 39, on the chip: sum of
    squares 4.8e-6 off the stored points', ``objective_gap`` 7.9e-6)."""
    import jax
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _pieces(mu):
    """Three bfloat16 pieces whose sum is the float32 ``mu`` to its last
    bit."""
    import jax.numpy as jnp
    hi = _round_bf16(mu)
    mid = _round_bf16(mu - hi)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, mu - hi - mid))


def block_points(x_raw, mu):
    """``bf16(mu[cluster_of(x_raw)] + x_raw)`` of a block of stored rows,
    as bfloat16 values (a rehearsal's float32 rows count as their bfloat16
    rounding). The row of ``mu`` comes by a one-hot product with each bf16
    piece (one exact term an entry) and ``(hi + mid) + lo`` in float32 —
    exactly the float32 centre, with no gather and at any block size."""
    import jax
    import jax.numpy as jnp
    k = mu.shape[0]
    noise = x_raw.astype(jnp.bfloat16)
    hit = jax.nn.one_hot(cluster_of(noise, k), k, dtype=jnp.bfloat16)
    hi, mid, lo = (jnp.dot(hit, p, preferred_element_type=jnp.float32)
                   for p in _pieces(mu))
    return _round_bf16(
        (hi + mid) + lo + noise.astype(jnp.float32)).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=8)
def _program(mesh, row_axes, rows, n_cols):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    chunk = min(rows, CHUNK_ROWS)
    n_full = (rows - 1) // chunk
    tail = rows - n_full * chunk
    row = P(tuple(row_axes))

    def local(x, mu):
        def put(i, n, out):
            xs = jax.lax.dynamic_slice(x, (i * chunk, 0), (n, n_cols))
            return jax.lax.dynamic_update_slice(
                out, block_points(xs, mu).astype(x.dtype), (i * chunk, 0))

        out = jax.lax.fori_loop(
            0, n_full, lambda i, o: put(i, chunk, o),
            jnp.zeros((rows, n_cols), x.dtype))
        return put(n_full, tail, out)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(row, P()),
                                 out_specs=row, check_vma=False))


def points(x_raw, mesh, row_axes, *, k: int, r: float, data_seed: int):
    """``(n, d)`` points of ``x_raw``'s rows in its own type (bfloat16
    values), sharded as they are, written chunk by chunk in place (no
    ``(n, d)`` float32 value exists beside a bfloat16 X)."""
    n_shards = int(np.prod([mesh.shape[a] for a in row_axes]))
    fn = _program(mesh, tuple(row_axes), x_raw.shape[0] // n_shards,
                  x_raw.shape[1])
    return fn(x_raw, centres(data_seed, k, x_raw.shape[1], r))


def start(data_seed: int, k: int, d: int, r: float) -> np.ndarray:
    """The stated starting set, ``(k, d)`` float64 on the host: k fresh
    draws of the mixture (a uniform cluster, its centre plus unit noise,
    rounded to bfloat16 as a stored point is)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(datagen.base_key(data_seed), 12)
    kc, kn = jax.random.split(key)
    mu = centres(data_seed, k, d, r)
    which = jax.random.randint(kc, (k,), 0, k)
    noise = jax.random.normal(kn, (k, d), jnp.float32)
    drawn = _round_bf16(jnp.take(mu, which, axis=0)
                        + _round_bf16(noise))
    return np.asarray(drawn, np.float64)
