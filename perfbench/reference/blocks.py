"""Row-block sweeps for the plain references.

A reference reads the benchmark's row-sharded ``(x, y)`` shard by shard and
block by block, so that its f32 working set is one block (the whole f32 X
never exists). Each shard returns its own partial sums; the host adds them
in float64. No collective runs here: what the chips would exchange is what
the host adds, which is also how a fault that leaves the exchange out is
planted (``shards_used``).
"""

from __future__ import annotations

import functools

import numpy as np

#: the control's format: 4 exponent and 3 mantissa bits (IEEE-style e4m3,
#: largest finite value 240)
FP8_BITS = (4, 3)
FP8_MAX = 240.0


def inverse_or_zero(std: np.ndarray) -> np.ndarray:
    """``1 / std``, and 0 for a constant column (its coefficient is 0)."""
    return np.where(std > 0, 1.0 / np.where(std > 0, std, 1.0), 0.0)


def block_rows(rows: int, most: int = 65536) -> int:
    """Largest divisor of ``rows`` that is at most ``most``."""
    for b in range(min(rows, most), 0, -1):
        if rows % b == 0:
            return b
    return rows


def load_block(xb, quant, scale):
    """The block as float32 values: as stored, or (the control, ``quant`` =
    "fp8") rounded to 8 bits with a per-column scale first.
    ``reduce_precision`` is the rounding the compiler may not take back (a
    pair of converts it may: the TPU's default allows excess precision)."""
    import jax
    import jax.numpy as jnp
    xf = xb.astype(jnp.float32)
    if quant is None:
        return xf
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    return jax.lax.reduce_precision(xf / scale, *FP8_BITS) * scale


@functools.lru_cache(maxsize=64)
def _sweep_program(block_fn, mesh, row_axes, rows, n_cols, rows_used, quant):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    b = block_rows(rows_used)
    n_blocks = rows_used // b
    row = P(tuple(row_axes))

    def local(x, y, scale, consts):
        def body(carry, i):
            xb = jax.lax.dynamic_slice(x, (i * b, 0), (b, n_cols))
            yb = jax.lax.dynamic_slice(y, (i * b,), (b,))
            small, big = block_fn(load_block(xb, quant, scale), yb, *consts)
            return jax.tree.map(jnp.add, carry, big), small

        xb0 = jax.ShapeDtypeStruct((b, n_cols), jnp.float32)
        yb0 = jax.ShapeDtypeStruct((b,), y.dtype)
        _, big0 = jax.eval_shape(block_fn, xb0, yb0, *consts)
        zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), big0)
        big, small = jax.lax.scan(body, zero, jnp.arange(n_blocks))
        # a leading axis of one per shard: the host sees every shard's part
        return small, jax.tree.map(lambda a: a[None], big)

    return n_blocks, jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(row, row, P(), P()),
        out_specs=(row, row), check_vma=False))


def sweep(block_fn, data, consts=(), rows_used=None, shards_used=None,
          quant=None, scale=None):
    """Run ``block_fn(x_block_f32, y_block, *consts) -> (small, big)`` over
    every block of every shard. ``small`` leaves come back per block and are
    summed here in float64; ``big`` leaves (matrices) are added up in f32 on
    the device across a shard's blocks and in float64 across shards.
    ``rows_used`` reads only the first rows of each shard and
    ``shards_used`` only the first shards (the planted faults)."""
    import jax
    import jax.numpy as jnp

    x, y, mesh, row_axes = data
    n_shards = int(np.prod([mesh.shape[a] for a in row_axes]))
    rows = x.shape[0] // n_shards
    rows_used = rows if rows_used is None else int(rows_used)
    shards_used = n_shards if shards_used is None else int(shards_used)
    if scale is None:
        scale = np.ones(x.shape[1], np.float32)
    n_blocks, fn = _sweep_program(block_fn, mesh, tuple(row_axes), rows,
                                  x.shape[1], rows_used, quant)
    small, big = jax.device_get(fn(
        x, y, jnp.asarray(scale, jnp.float32),
        tuple(jnp.asarray(c, jnp.float32) for c in consts)))

    def total(a, n_keep):
        return np.sum(np.asarray(a, np.float64)[:n_keep], axis=0)

    return (jax.tree.map(lambda a: total(a, shards_used * n_blocks), small),
            jax.tree.map(lambda a: total(a, shards_used), big),
            rows_used * shards_used)


def _moment_block(xf, yb):
    import jax.numpy as jnp
    return ({"s1": jnp.sum(xf, axis=0), "s2": jnp.sum(xf * xf, axis=0),
             "y1": jnp.sum(yb), "y2": jnp.sum(yb * yb)}, {})


def fp8_scale(data):
    """Per-column ``absmax / 240`` of the stored X: the control's scale."""
    import jax
    import jax.numpy as jnp
    absmax = np.asarray(jax.device_get(jax.jit(
        lambda a: jnp.max(jnp.abs(a), axis=0).astype(jnp.float32))(data[0])),
        np.float64)
    return np.where(absmax > 0, absmax / FP8_MAX, 1.0)


def moments(data, **kw):
    """``n``, column means and unbiased standard deviations of X, and the
    label's mean and unbiased standard deviation, in float64."""
    small, _, n = sweep(_moment_block, data, **kw)
    mean = small["s1"] / n
    var = np.maximum((small["s2"] - n * mean * mean) / (n - 1), 0.0)
    y_mean = small["y1"] / n
    y_var = max((small["y2"] - n * y_mean * y_mean) / (n - 1), 0.0)
    return n, mean, np.sqrt(var), float(y_mean), float(np.sqrt(y_var))
