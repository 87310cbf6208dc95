"""Random dataset generators.

Re-design of ``mllib/random`` (ref: mllib/src/main/scala/org/apache/spark/
mllib/random/RandomRDDs.scala + RandomDataGenerator.scala). The reference
materializes random numbers partition-by-partition on executors with
per-partition XORShift seeds; here each mesh shard generates its rows
directly **on device** inside one shard_map program, with a
``fold_in(seed, shard_index)`` key per shard — same per-partition
reproducibility contract (ref RandomRDDs seed params), zero host↔device
transfer.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.mesh import DATA_AXIS, REPLICA_AXIS
from cycloneml_tpu.parallel.collectives import shard_map_compat


def _shard_generate(ctx, n_rows: int, seed: int, local_fn: Callable,
                    n_out: int):
    """Shared per-shard generation scaffolding: pad the row count to the
    blockify invariant, run ``local_fn(key, per_shard_rows)`` (key =
    ``fold_in(seed, shard_index)``) on every shard inside one shard_map
    program, and return ``(outputs, w_mask, total_rows, dtype)`` where
    ``w_mask`` zeroes the padding rows."""
    import jax
    from jax.sharding import PartitionSpec as P
    from cycloneml_tpu.dataset.instance import compute_dtype

    rt = ctx.mesh_runtime
    nd = rt.data_parallelism
    d_size = rt.mesh.devices.shape[1]
    per = max(((n_rows + nd - 1) // nd + 7) // 8 * 8, 8)
    total = per * nd
    dt = compute_dtype()

    def local(tok):
        idx = (jax.lax.axis_index(REPLICA_AXIS) * d_size
               + jax.lax.axis_index(DATA_AXIS))
        key = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
        return local_fn(key, per)

    row = P((REPLICA_AXIS, DATA_AXIS))
    tok = rt.device_put_sharded_rows(np.zeros(nd, dtype=np.float32))
    out_spec = row if n_out == 1 else (row,) * n_out
    out = jax.jit(shard_map_compat(local, rt.mesh, (row,), out_spec))(tok)
    w = np.zeros(total, dtype=dt)
    w[:n_rows] = 1.0
    return out, w, total, dt


def _generate(ctx, n_rows: int, n_cols: int, seed: int,
              sampler: Callable) -> InstanceDataset:
    """Run ``sampler(key, shape)`` per shard; returns an InstanceDataset with
    padding rows masked out via w=0 (the blockify invariant). X lands in the
    data-tier dtype (generated at f32 then narrowed ON DEVICE — no host
    round trip); y/w stay at accumulator width."""
    from cycloneml_tpu.dataset.instance import data_dtype

    xdt = data_dtype(getattr(ctx, "conf", None))
    x, w, total, dt = _shard_generate(
        ctx, n_rows, seed,
        lambda key, per: sampler(key, (per, n_cols)).astype(xdt), n_out=1)
    rt = ctx.mesh_runtime
    return InstanceDataset(ctx, x, rt.device_put_sharded_rows(np.zeros(total, dtype=dt)),
                           rt.device_put_sharded_rows(w), n_rows, n_cols)


#: f32 bytes one draw of :func:`_draw_projected` may hold at a time
_DRAW_CHUNK_BYTES = 64 << 20


def _draw_projected(key, per: int, n_cols: int, beta, xdt):
    """One shard's ``(per, n_cols)`` standard-normal X in the data-tier
    dtype together with its f32 projection ``x·beta``, drawn in ROW CHUNKS
    written in place into the outputs.

    The projection needs the f32 draw and the dataset keeps the narrowed
    copy, so drawing X whole holds both at once: XLA materializes the f32
    block (10.24 GB at 2M×1,280, beside the 5.12 GB bf16 result — measured
    with the compiler's memory analysis for a v5e) and a 16 GB chip has no
    room left. Chunked, the f32 draw never exceeds ``_DRAW_CHUNK_BYTES``.
    Chunk ``i`` draws from ``fold_in(key, i)``, so the stream depends on
    the chunk size but not on the mesh."""
    import jax
    import jax.numpy as jnp

    rows = min(per, max(8, _DRAW_CHUNK_BYTES // (4 * n_cols) // 8 * 8))
    # n_full whole chunks, then a last one of 1..rows rows (never empty)
    n_full = (per - 1) // rows
    tail = per - n_full * rows

    def put(i, n, carry):
        x, proj = carry
        xc = jax.random.normal(jax.random.fold_in(key, i), (n, n_cols),
                               dtype=jnp.float32)
        return (jax.lax.dynamic_update_slice(x, xc.astype(xdt),
                                             (i * rows, 0)),
                jax.lax.dynamic_update_slice(proj, xc @ beta, (i * rows,)))

    carry = (jnp.zeros((per, n_cols), xdt), jnp.zeros((per,), jnp.float32))
    carry = jax.lax.fori_loop(
        0, n_full, lambda i, c: put(i, rows, c), carry)
    return put(n_full, tail, carry)


def _generate_labelled(ctx, n_rows: int, n_cols: int, seed: int,
                       noise: float, label: Callable) -> InstanceDataset:
    """Shared body of the labelled generators: per shard, X from
    ``fold_in(seed, shard)`` and ``y = label(x·beta + noise·eps)`` with a
    ground-truth ``beta ~ N(0, 1)`` from ``fold_in(seed, 2**31 - 1)`` shared
    by every shard. Zero host→device transfer of X; only the (n,) labels
    are read back once, so estimators get their host label histogram (an
    (n,) readback, not (n, d)) without a device pass per fit."""
    import jax
    import jax.numpy as jnp

    from cycloneml_tpu.dataset.instance import compute_dtype, data_dtype
    dt = compute_dtype()
    xdt = data_dtype(getattr(ctx, "conf", None))

    def local(key, per):
        kx, ke = jax.random.split(key)
        beta = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(seed), 2 ** 31 - 1),
            (n_cols,), dtype=jnp.float32)
        x, proj = _draw_projected(kx, per, n_cols, beta, xdt)
        margin = proj + noise * jax.random.normal(ke, (per,),
                                                  dtype=jnp.float32)
        return x, label(margin).astype(dt)

    (x, y), w, total, dt = _shard_generate(ctx, n_rows, seed, local, n_out=2)
    rt = ctx.mesh_runtime
    ds = InstanceDataset(ctx, x, y, rt.device_put_sharded_rows(w),
                         n_rows, n_cols)
    return ds.attach_host_labels(np.asarray(y).astype(np.float64),
                                 w.astype(np.float64))


def generate_classification(ctx, n_rows: int, n_cols: int, seed: int = 0,
                            noise: float = 1.0) -> InstanceDataset:
    """Labeled synthetic binary-classification dataset, generated entirely
    on device (the benchmark/scale-test feeder; ref RandomRDDs +
    LogisticRegressionDataGenerator, mllib/util/LogisticRegressionDataGenerator.scala:33):
    ``y = 1[x·beta + noise·eps > 0]`` (see :func:`_generate_labelled`)."""
    return _generate_labelled(ctx, n_rows, n_cols, seed, noise,
                              lambda margin: margin > 0)


def generate_regression(ctx, n_rows: int, n_cols: int, seed: int = 0,
                        noise: float = 0.1) -> InstanceDataset:
    """Labeled synthetic linear-regression dataset generated entirely on
    device (ref mllib/util/LinearDataGenerator.scala:120 — the epsilon-shape
    BASELINE config-2 feeder): ``y = x·beta + noise·eps`` (see
    :func:`_generate_labelled`)."""
    return _generate_labelled(ctx, n_rows, n_cols, seed, noise,
                              lambda margin: margin)


class RandomDatasets:
    """Static factory surface mirroring RandomRDDs (vector variants; the
    scalar variants are n_cols=1)."""

    classification = staticmethod(generate_classification)
    regression = staticmethod(generate_regression)

    @staticmethod
    def normal(ctx, n_rows: int, n_cols: int = 1, seed: int = 0,
               mean: float = 0.0, std: float = 1.0) -> InstanceDataset:
        import jax
        return _generate(ctx, n_rows, n_cols, seed,
                         lambda k, s: jax.random.normal(k, s) * std + mean)

    @staticmethod
    def uniform(ctx, n_rows: int, n_cols: int = 1, seed: int = 0,
                low: float = 0.0, high: float = 1.0) -> InstanceDataset:
        import jax
        return _generate(ctx, n_rows, n_cols, seed,
                         lambda k, s: jax.random.uniform(k, s, minval=low, maxval=high))

    @staticmethod
    def log_normal(ctx, n_rows: int, n_cols: int = 1, seed: int = 0,
                   mean: float = 0.0, std: float = 1.0) -> InstanceDataset:
        import jax
        import jax.numpy as jnp
        return _generate(ctx, n_rows, n_cols, seed,
                         lambda k, s: jnp.exp(jax.random.normal(k, s) * std + mean))

    @staticmethod
    def poisson(ctx, n_rows: int, n_cols: int = 1, seed: int = 0,
                lam: float = 1.0) -> InstanceDataset:
        import jax
        return _generate(ctx, n_rows, n_cols, seed,
                         lambda k, s: jax.random.poisson(k, lam, s).astype("float32"))

    @staticmethod
    def exponential(ctx, n_rows: int, n_cols: int = 1, seed: int = 0,
                    mean: float = 1.0) -> InstanceDataset:
        import jax
        return _generate(ctx, n_rows, n_cols, seed,
                         lambda k, s: jax.random.exponential(k, s) * mean)

    @staticmethod
    def gamma(ctx, n_rows: int, n_cols: int = 1, seed: int = 0,
              shape: float = 1.0, scale: float = 1.0) -> InstanceDataset:
        import jax
        return _generate(ctx, n_rows, n_cols, seed,
                         lambda k, s: jax.random.gamma(k, shape, s) * scale)
