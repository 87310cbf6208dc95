"""One Lloyd step of k-means over a shard: nearest centre of every row and
the per-cluster sums, counts and cost, with no ``(rows, k)`` value anywhere.

Two forms of one map ``(x, w, centres) -> {sums (k, d), counts (k,), cost}``:

- :func:`fused_lloyd_step`, the Mosaic kernel ``kmeans_lloyd``: row-major
  ``(row_tile, d)`` blocks of a bfloat16 X as it is stored (nothing padded or
  copied; the rows of the last tile past n are selected out in that grid
  step alone), the centres resident in VMEM. Per tile the scores
  ``‖c‖² − 2 x·c`` of all centres on the MXU — the float32 centres ride as
  three bfloat16 pieces whose sum is the centre to its last bit, side by side
  on the contraction axis, so bf16 x bf16 products are exact and the MXU's
  own f32 accumulator adds the pieces — the argmin down the sublanes on the
  VPU (centres on the sublanes, rows on the lanes: the LOWEST index wins a
  tie, MLlib's ``findClosest``; a padded centre carries ``‖c‖² = inf`` and
  never wins), then ``onehot · x_tile`` on the MXU (0/1 times bf16: exact),
  the counts and ``Σ min d²``, Kahan-added into f32 accumulators across the
  sequential grid as the GLM sweeps' sums are. The ``(k_pad, row_tile)``
  score tile never leaves VMEM and X is read once a step at storage width.
- :func:`blocked_lloyd_step`, the row-blocked XLA twin: a ``lax.scan`` over
  row chunks with the same scores (three pieces on a bfloat16 X, ``highest``
  at the accumulator's width on every other storage) — the host platform's
  path, and the path of every shape the kernel refuses.

:func:`lloyd_step` picks: the kernel where :func:`lloyd_tile` finds a tile
and ``fused`` says the backend lowers Mosaic, and INSIDE the program the
weights decide — the kernel's one-hot product is exact only under a 0/1
mask, so a shard whose weights hold ONE live value ``c`` (every weight 0 or
c: unit weights, padding rows, a constant weight column) takes the kernel
under ``w > 0`` and scales by c; a second live value takes the twin.

The stated precision of both: the assignments are those of float32 scores
from the stored rows to float32 centres. Rounding the centres to one bf16
piece is a different result (``pieces=1`` exists for the tests that show it).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cycloneml_tpu.ops.kernels import (
    _NN, _NT, _VMEM_BUDGET, LANE, _compiler_params, _kahan_add, _lane_sums,
    _pad_to, _split3_rounded,
)

#: centres ride the sublanes in whole packed bf16 groups
CENTRE_GROUP = 16
#: bf16 pieces a float32 centre is split into (8 + 8 + 8 mantissa bits)
CENTRE_PIECES = 3
#: f32 bytes of scores one chunk of the XLA twin may hold
TWIN_CHUNK_BYTES = 64 << 20


def lloyd_tile(rows: int, d: int, k: int, dtype) -> Optional[int]:
    """Rows of X one grid step of :func:`fused_lloyd_step` takes, or None
    where the kernel cannot be built and the row-blocked twin is the path:
    storage other than bfloat16 (an f32 X would need pieces of its own, fp8
    its scale), a width that does not end on a lane (d % 128: a row-major
    block would be padded), fewer than 128 rows, or a ``(k, d)`` whose
    resident pieces, accumulators and score tile pass the VMEM budget
    (k = 1,000 at d = 128 takes a 1,024-row tile, k = 2,048 a 256-row one,
    k = 4,096 none)."""
    if np.dtype(dtype) != np.dtype(jnp.bfloat16) or rows < LANE or k < 1 \
            or d % LANE:
        return None
    kp = _pad_to(k, CENTRE_GROUP)
    # resident: the pieces (double-buffered), the sums, their compensation
    # and the pipeline's second output buffer, the lane-wise counts likewise
    fixed = kp * d * (2 * CENTRE_PIECES * 2 + 3 * 4) + kp * LANE * 3 * 4
    # per row of X: the double-buffered storage block, its three-fold copy
    # on the contraction axis, the f32 squares and their transpose; per
    # centre the scores, the tie-break select, the f32 and bf16 one-hot
    per_row = d * (2 * 2 + CENTRE_PIECES * 2 + 3 * 4) + kp * (4 + 4 + 4 + 2)
    for t in (1024, 512, 256, 128):
        if t <= rows and fixed + t * per_row <= _VMEM_BUDGET:
            return t
    return None


def _note(k: int, **attrs) -> None:
    """``kernel.kmeans_lloyd`` instant, one per program BUILT (this runs
    while the aggregation program is traced, not per dispatch)."""
    from cycloneml_tpu.observe import tracing
    tracing.instant("kernel.kmeans_lloyd", k=k, **attrs)


def _centre_operands(centres, kp: int, pieces: int):
    """``(pieces (kp, pieces·d) bf16 of −2c, ‖c‖² (kp, 1) f32)`` of float32
    centres padded to ``kp`` rows; a padded centre's norm is inf."""
    c = jnp.asarray(centres, jnp.float32)
    k, _ = c.shape
    cn = jnp.concatenate([jnp.sum(c * c, axis=1),
                          jnp.full((kp - k,), jnp.inf, jnp.float32)])
    # −2c is c's own bits with another exponent: its pieces are exact too
    split = _split3_rounded(jnp.pad(-2.0 * c, ((0, kp - k), (0, 0))))
    return jnp.concatenate(split[:pieces], axis=1), cn.reshape(kp, 1)


def fused_lloyd_step(x, w, centres, *, update: bool = True,
                     interpret: bool = False, tile: Optional[int] = None,
                     pieces: int = CENTRE_PIECES) -> Dict[str, jnp.ndarray]:
    """``{sums (k, d), counts (k,), cost}`` of the rows with ``w > 0`` —
    each counted ONCE, whatever its weight: the caller scales by the one
    live value (:func:`lloyd_step`). ``update=False`` is the assignment-only
    pass (``cost`` alone: no one-hot, no second product; the kernel is then
    named ``kmeans_lloyd_cost``). ``tile`` overrides the rows a grid step
    takes and ``pieces`` the bf16 pieces of a centre (tests)."""
    n, d = x.shape
    k = centres.shape[0]
    if tile is None:
        tile = lloyd_tile(n, d, k, x.dtype)
    if tile is None:
        raise ValueError(
            f"no Lloyd kernel for a {x.dtype} X of {n} x {d}, {k} centres: "
            f"ask lloyd_tile first and take blocked_lloyd_step")
    kp = _pad_to(k, CENTRE_GROUP)
    steps, tail = pl.cdiv(n, tile), n % tile
    p, cn = _centre_operands(centres, kp, pieces)
    _note(k, k_pad=kp, pieces=pieces, row_tile=tile, tail_rows=tail,
          orientation="row_major", update="onehot" if update else "none")

    def kmeans_lloyd(x_ref, w_ref, p_ref, cn_ref, *out):
        i = pl.program_id(0)
        half = len(out) // 2
        sums = tuple(zip(out[:half], out[half:]))

        @pl.when(i == 0)
        def _():
            for acc, comp in sums:
                acc[:] = jnp.zeros_like(acc)
                comp[:] = jnp.zeros_like(comp)

        def tile_sums(rows_left=None):
            xv = x_ref[:]
            live = w_ref[:] > 0                               # (1, tile)
            if rows_left is not None:
                # Pallas leaves the rows past n undefined, and 0 · NaN is
                # NaN: they go out of X itself and out of every sum
                rows = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
                xv = jnp.where(rows < rows_left, xv,
                               jnp.zeros((), xv.dtype))
                live &= jax.lax.broadcasted_iota(
                    jnp.int32, (1, tile), 1) < rows_left
            score = cn_ref[:] + jax.lax.dot_general(
                p_ref[:], jnp.concatenate([xv] * pieces, axis=1), _NT,
                preferred_element_type=jnp.float32)           # (kp, tile)
            best = jnp.min(score, axis=0, keepdims=True)
            xf = xv.astype(jnp.float32)
            x2 = jnp.sum((xf * xf).T, axis=0, keepdims=True)  # (1, tile)
            v_cost = jnp.where(live, jnp.maximum(x2 + best, 0.0), 0.0)
            values = [_lane_sums(v_cost, tile)]
            if update:
                klass = jax.lax.broadcasted_iota(
                    jnp.int32, (kp, tile), 0).astype(jnp.float32)
                first = jnp.min(jnp.where(score == best, klass, float(kp)),
                                axis=0, keepdims=True)
                hit = jnp.where(klass == jnp.where(live, first, float(kp)),
                                1.0, 0.0)
                values += [jax.lax.dot_general(
                    hit.astype(jnp.bfloat16), xv, _NN,
                    preferred_element_type=jnp.float32),
                    _lane_sums(hit, tile)]
            for (acc, comp), v in zip(sums, values):
                _kahan_add(acc, comp, v)

        if tail == 0:
            tile_sums()
        else:
            pl.when(i < steps - 1)(tile_sums)
            pl.when(i == steps - 1)(lambda: tile_sums(tail))

    shapes = [(1, LANE)] + ([(kp, d), (kp, LANE)] if update else [])
    out = pl.pallas_call(
        kmeans_lloyd,
        name="kmeans_lloyd" if update else "kmeans_lloyd_cost",
        grid=(steps,),
        in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, tile), lambda i: (0, i)),
                  pl.BlockSpec(p.shape, lambda i: (0, 0)),
                  pl.BlockSpec((kp, 1), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec(s, lambda i: (0, 0)) for s in shapes],
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in shapes],
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
    )(x, jnp.asarray(w, jnp.float32).reshape(1, n), p, cn)
    result = {"cost": jnp.sum(out[0])}
    if update:
        result.update(sums=out[1][:k], counts=jnp.sum(out[2][:k], axis=1))
    return result


def _scores(xb, centres, operands):
    """``‖c‖² − 2 x·c`` of a row chunk, ``(b, k)`` at the accumulator's
    width: the kernel's own product of bf16 pieces on a bfloat16 chunk
    (``operands``: :func:`_centre_operands`, made once a step), ``highest``
    on every other storage."""
    if operands is not None:
        p, cn = operands
        return cn.T + jax.lax.dot_general(
            jnp.concatenate([xb] * (p.shape[1] // xb.shape[1]), axis=1), p,
            _NT, preferred_element_type=jnp.float32)
    return jnp.sum(centres * centres, axis=1)[None, :] - 2.0 * jnp.dot(
        xb.astype(centres.dtype), centres.T,
        precision=jax.lax.Precision.HIGHEST)


def blocked_lloyd_step(x, w, centres, *, update: bool = True,
                       pieces: int = CENTRE_PIECES,
                       chunk: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """The weighted ``{sums, counts, cost}`` by a scan over row chunks of at
    most ``chunk`` rows (default: :data:`TWIN_CHUNK_BYTES` of scores): the
    only ``(·, k)`` value is one chunk's. Any weights, any storage."""
    n, d = x.shape
    k = centres.shape[0]
    narrow = x.dtype == jnp.bfloat16
    acc = jnp.float32 if narrow else centres.dtype
    centres = centres.astype(acc)
    w = jnp.asarray(w, acc)
    if chunk is None:
        chunk = max(8, TWIN_CHUNK_BYTES // (4 * k) // 8 * 8)
    chunk = min(chunk, n)
    hi = jax.lax.Precision.HIGHEST
    operands = _centre_operands(centres, k, pieces) if narrow else None

    def chunk_sums(xb, wb):
        score = _scores(xb, centres, operands)
        xf = xb.astype(acc)
        best = jnp.min(score, axis=1)
        out = {"cost": jnp.sum(wb * jnp.maximum(
            jnp.sum(xf * xf, axis=1) + best, 0.0))}
        if update:
            # argmin takes the lowest index on a tie
            hit = jax.nn.one_hot(jnp.argmin(score, axis=1), k, dtype=acc) \
                * wb[:, None]
            out.update(sums=jnp.dot(hit.T, xf, precision=hi),
                       counts=jnp.sum(hit, axis=0))
        return out

    def body(carry, i):
        part = chunk_sums(
            jax.lax.dynamic_slice(x, (i * chunk, 0), (chunk, d)),
            jax.lax.dynamic_slice(w, (i * chunk,), (chunk,)))
        return jax.tree.map(jnp.add, carry, part), None

    # chunk <= n: at least one whole chunk, then the rows left over
    n_full = n // chunk
    zero = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(chunk_sums, x[:chunk], w[:chunk]))
    total, _ = jax.lax.scan(body, zero, jnp.arange(n_full))
    rest = chunk_sums(x[n_full * chunk:], w[n_full * chunk:]) \
        if n % chunk else None
    return total if rest is None else jax.tree.map(jnp.add, total, rest)


def lloyd_step(x, w, centres, *, fused: bool, update: bool = True,
               interpret: bool = False) -> Dict[str, jnp.ndarray]:
    """One shard's ``{sums, counts, cost, kernel_shards}`` (``update=False``:
    ``cost`` and ``kernel_shards``). ``fused`` (static) is the caller's word
    that the backend lowers Mosaic; ``kernel_shards`` is 1.0 where this
    shard took the kernel — the psum over shards counts them."""
    n, d = x.shape
    k = centres.shape[0]
    tile = lloyd_tile(n, d, k, x.dtype) if fused else None
    if tile is None:
        _note(k, k_pad=k, row_tile=0, tail_rows=0, orientation="xla",
              pieces=CENTRE_PIECES if x.dtype == jnp.bfloat16 else None,
              update="onehot" if update else "none")
        out = blocked_lloyd_step(x, w, centres, update=update)
        return {**out, "kernel_shards": jnp.zeros((), out["cost"].dtype)}
    w = jnp.asarray(w, jnp.float32)
    c = jnp.max(w)
    one_value = jnp.isfinite(c) & (c >= 0) & jnp.all((w == 0) | (w == c))

    def kernel():
        out = fused_lloyd_step(x, w, centres, update=update, tile=tile,
                               interpret=interpret)
        # the mask form's sums times the one live value (1.0 · v is v)
        return jax.tree.map(lambda v: c * v, out)

    out = jax.lax.cond(
        one_value, kernel,
        lambda: blocked_lloyd_step(x, w, centres.astype(jnp.float32),
                                   update=update))
    return {**out, "kernel_shards": jnp.where(one_value, 1.0, 0.0)}
