"""Pallas TPU kernels.

Each kernel fuses one estimator hot loop into a single VMEM-resident pass
over row tiles (grid over the instance-block rows, accumulating into revisited
output blocks — the standard Pallas reduction pattern):

- ``fused_binary_logistic_scaled``: the north-star hot loop (ref:
  BinaryLogisticBlockAggregator.scala:41 — forward gemv :97, multiplier :112,
  transpose gemv :130) as margin→softplus-loss→multiplier→grad in one kernel.
- ``fused_least_squares_scaled``: the LinearRegression l-bfgs residual sweep
  on the same row pass. It and ``fused_binary_logistic_scaled`` take one of
  two forms by the X they are handed (``mxu_sweep_tile``): a bfloat16 X
  whose width the class sweep's tile admits (feature-major from 240
  features) meets the MXU as stored (the class sweep below with one
  model); every other X the VPU body (``_run_glm``).
- ``fused_multinomial_logistic_scaled``: the K-class sweep (ref:
  MultinomialLogisticBlockAggregator) — margins, softmax, loss and gradient
  from one read of a bfloat16 X, both products on the MXU with the f32
  operand of each as three bf16 pieces; tiled as the GLM sweep is.
- ``fused_stacked_binomial_scaled``: K independent binomial models over one
  X (OneVsRest's relabellings, a regParam grid) — the K-class sweep's body
  with the link changed from a softmax to K sigmoids: one read of X an
  evaluation for all K, the lane's 0/1 label made in the kernel.
- ``fused_moment_gramian``: the augmented Gramian ``[1|y|X]'W[1|y|X]`` —
  WeightedLeastSquares' moment pass and RowMatrix.computeGramianMatrix:130
  (the treeAggregate of spr:147 rank-1 updates) as upper-triangle MXU
  products of bf16 tiles; ``moment_sums`` is its front door.

Kernels take arrays and shapes; ``ml/optim/aggregators.dense_sweep``
chooses which GLM sweep a fit runs (a kernel here or the XLA-fused ``jnp``
aggregator, its tiling, its form) from the array rules here and
``cyclone.ml.usePallasKernels``. The Gramian has no such key:
``moment_sums`` takes the kernel wherever the array it is handed allows one
(PERF.md §6: 54 ms against 105 ms for XLA's contraction at
2,000,000 x 2,000 on the v5e).

The Gramian pads nothing (it tiles like the feature-major sweep
below, or transposes a row-major tile in VMEM). The GLM sweep has two tilings of one body, chosen by
the way X is stored (``stored_feature_major``): *row-major* — ``(row_tile,
d_pad)`` blocks, d on the lanes, padded to the 128-lane boundary only when
d is no multiple of 128 — and *feature-major* — ``(d, lane_tile)`` blocks of
the ``(d, n)`` view, rows on the lanes, d whole on the sublanes, no pad on
either axis. XLA:TPU stores a 2-D array whose minor dimension is no multiple
of 128 feature-major (``{0,1}``) once it has enough rows that padding THEM
to the lanes wastes less than padding the width (observed on the v5e:
(100000, 2000) and (4096, 200) yes, (4104, 2000) no), so for such an X the
row-major tiling costs a layout copy AND a lane pad of all of X per
evaluation, and the feature-major tiling costs nothing: ``x.T`` is a
bitcast. All lower to Mosaic. ``interpret=True`` runs the same kernel body
in the Pallas interpreter — that is the tests' choice to make (they pass
it explicitly); nothing in the package selects it, so a wrapper reached on
a backend that cannot lower Mosaic raises instead of silently interpreting.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 256
LANE = 128

#: scoped-VMEM limit every kernel here declares to Mosaic. The backend's own
#: default (16 MiB of a v5e core's 128 MiB) is an XLA flag the package does
#: not control; an explicit figure makes what compiles independent of it.
VMEM_LIMIT_BYTES = 32 << 20
#: the share of that limit a kernel's own working-set estimate may claim —
#: the rest is slack for Mosaic's internal scratch and for what the
#: estimates below do not model
_VMEM_BUDGET = 24 << 20


def pallas_available() -> bool:
    """True when the default backend lowers Pallas natively (TPU)."""
    return jax.default_backend() == "tpu"


def _compiler_params(semantics: str):
    """Mosaic parameters for a one-axis row grid: ``arbitrary`` for the
    kernels that accumulate into revisited output blocks (the axis must run
    in order on one core), ``parallel`` where every step owns its output
    block."""
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _storage_width(x):
    """Keep narrow (bf16/f16/fp8) DATA-tier blocks at storage width — the
    whole point of the tier is that HBM sees 1-2 bytes per element — and
    cast full-width inputs to the kernels' f32 accumulator dtype. The
    kernels upcast narrow tiles to f32 INSIDE VMEM (a vector convert per
    tile, never an HBM materialization); fp8 tiles additionally apply
    their per-column dequantization scale per VMEM block (the ``x_scale``
    operand — one VPU multiply on a resident tile)."""
    from cycloneml_tpu.dataset.instance import is_narrow_dtype
    x = jnp.asarray(x)
    if is_narrow_dtype(x.dtype):
        return x
    return x.astype(jnp.float32)


def _storage_dtype(dtype):
    """The dtype :func:`_storage_width` leaves an array of ``dtype`` in."""
    from cycloneml_tpu.dataset.instance import is_narrow_dtype
    return np.dtype(dtype) if is_narrow_dtype(dtype) else np.dtype(np.float32)


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _auto_row_tile(n: int, row_tile: int, dtype, row_bytes: int,
                   fixed_bytes: int = 0) -> int:
    """Row tile for an ``(n, ·)`` operand stored as ``dtype``.

    Candidates are the powers of two from 8 up to the requested tile, kept
    only while the kernel's VMEM estimate ``fixed_bytes + tile·row_bytes``
    fits the budget — the tile is a function of the WIDTH too: a tile that
    is right at d=1,280 is over the scoped limit near d≈16k. Among those,
    the largest that DIVIDES n wins: row padding copies the whole X operand
    (at HBM scale that is a second multi-GB allocation — an OOM, not a
    slowdown). Tiles that are whole packed sublane groups of ``dtype``
    (8 rows at 4 bytes, 16 at 2, 32 at 1) are preferred over narrower ones,
    which Mosaic accepts but loads as partial tiles. When nothing divides,
    the largest aligned candidate is returned and the caller pads — loudly
    when the operand is big enough for the copy to matter. A width no
    candidate fits raises: the caller asked for a kernel that cannot be
    built, and the XLA aggregator is the path for that shape."""
    fits = [t for t in (1024, 512, 256, 128, 64, 32, 16, 8)
            if t <= max(row_tile, 8)
            and fixed_bytes + t * row_bytes <= _VMEM_BUDGET]
    if not fits:
        raise ValueError(
            f"pallas kernel: an 8-row tile needs "
            f"{fixed_bytes + 8 * row_bytes} bytes of VMEM at this width, "
            f"over the {_VMEM_BUDGET}-byte budget; this shape is beyond "
            f"the fused kernels — set cyclone.ml.usePallasKernels=false")
    sublane = 32 // np.dtype(dtype).itemsize
    aligned = [t for t in fits if t % sublane == 0]
    for group in (aligned, fits):
        for t in group:
            if n % t == 0:
                return t
    if n > 1 << 20:
        import warnings
        warnings.warn(
            f"pallas kernel: no row tile divides n={n}; padding will COPY "
            "the full operand in HBM — pad the input to a multiple of 32 "
            "rows upstream to avoid it")
    return (aligned or fits)[0]


def stored_feature_major(x) -> bool:
    """Whether the concrete 2-D array ``x`` sits in device memory with its
    FIRST dimension minor — rows on the lanes, XLA's ``{0,1}`` — which is
    XLA:TPU's default for a tall array whose width is no multiple of 128
    (epsilon's 2,000, HIGGS's 28). That is the layout the feature-major
    GLM sweep reads as it lies. Anything that is not a committed 2-D
    ``jax.Array`` whose layout can be read (numpy arrays, tracers, host-
    platform arrays) answers False: the row-major sweep is right for
    them."""
    if not isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer) \
            or x.ndim != 2:
        return False
    try:
        if next(iter(x.sharding.device_set)).platform == "cpu":
            return False
        order = x.format.layout.major_to_minor
    except Exception:  # no layout to read (deleted, abstract, older PJRT)
        return False
    return tuple(order) == (1, 0)


def default_feature_major(d: int) -> bool:
    """The orientation XLA:TPU's DEFAULT layout gives a tall ``(n, d)``
    array — what :func:`stored_feature_major` observes on a dataset-sized
    array nobody laid out by hand (a few thousand rows can still be stored
    row-major: the compiler pads whichever axis wastes less). For callers
    that ask for an aggregator with no array in hand; the estimators pass
    what they observed, and a wrong guess costs a layout copy, never a
    result."""
    return pallas_available() and d % LANE != 0


def glm_sweep_orientation(x, has_scale: bool = False) -> str:
    """The tiling the fused GLM sweep takes for the concrete X of a fit:
    ``"feature_major"`` when X is stored that way AND a shard's ``(d,
    lane_tile)`` block can be built, else ``"row_major"`` — what the
    estimators hand their aggregator and write on their summary."""
    if stored_feature_major(x):
        rows, d = x.sharding.shard_shape(x.shape)
        if _auto_lane_tile(rows, d, _storage_dtype(x.dtype),
                           has_scale) is not None:
            return "feature_major"
    return "row_major"


#: the narrowest feature-major width at which the one-model sweep's MXU
#: form read faster than the VPU body on a v5e (240, 272, 400, 784, 2,000
#: did; 16 to 144 did not: there a 1,024-row grid step's fixed work binds
#: both forms and the MXU form's link over a 16-row model pad is the
#: larger; PERF.md §6, PR 43)
MXU_SWEEP_MIN_FEATURE_MAJOR_WIDTH = 240


def mxu_sweep_tile(rows: int, d: int, dtype, has_scale: bool,
                   feature_major: bool):
    """The one-model sweep's FORM, the one rule the wrappers below apply
    to the shard they are handed and ``aggregators.dense_sweep`` applies
    to a fit's shard geometry: rows a grid step of the MXU form takes —
    the class sweep (:func:`_class_sweep`) with one model — or None where
    the VPU body (:func:`_run_glm`) is the form: an fp8 X with its scale, a
    feature-major width under ``MXU_SWEEP_MIN_FEATURE_MAJOR_WIDTH``, and
    whatever :func:`multinomial_sweep_tile` refuses (storage other than
    bfloat16, feature-major widths off 16, row-major widths off 128, fewer
    than 128 rows, VMEM)."""
    if has_scale or (feature_major
                     and d < MXU_SWEEP_MIN_FEATURE_MAJOR_WIDTH):
        return None
    return multinomial_sweep_tile(rows, d, 1, dtype, feature_major)


def _auto_lane_tile(n: int, d: int, dtype, has_scale: bool):
    """Lane tile of the feature-major sweep — how many ROWS of X one
    ``(d, T)`` block holds on the lanes — or None when that block cannot
    be built. Candidates are 1024/512/256/128; the largest whose working
    set fits the budget and that does not exceed n wins: the double-
    buffered storage-width block plus three ``(d, T)`` f32 temporaries
    (upcast, x∘β, mult∘x), and the fixed ``(d, 128)`` blocks (grad,
    compensation: double-buffered outputs; the lane-padded ``(d, 1)`` β
    and scale). Fewer than 128 rows, or a d so large that not even a
    128-lane tile fits, is the row-major path's."""
    fixed = 4 * d * LANE * (4 + 2 * (2 if has_scale else 1)) + 4 * d * LANE
    per_lane = d * (2 * np.dtype(dtype).itemsize + 12) + 16 * 8 * 4
    for t in (1024, 512, 256, 128):
        if t <= n and fixed + t * per_lane <= _VMEM_BUDGET:
            return t
    return None


def _pad_scale(scale, d: int, d_pad: int):
    """Per-column fp8 dequant scales as a (1, d_pad) f32 block (padding
    columns carry 1.0 — their x entries are zero anyway)."""
    s = jnp.asarray(scale, jnp.float32).reshape(-1)
    if s.shape[0] != d:
        raise ValueError(f"x_scale has {s.shape[0]} entries, expected {d}")
    return jnp.pad(s, (0, d_pad - d), constant_values=1.0).reshape(1, d_pad)


def _pad_rows_cols(x, y, w, row_tile: int):
    """Row-major tiling's operands: zero-pad rows to the tile multiple and
    features to the lane multiple (nothing when d is a multiple of 128 and
    a tile divides n); padding rows carry w=0 so they contribute nothing to
    any sum. The row tile is re-chosen to DIVIDE n when possible (see
    _auto_row_tile) and returned — row padding copies the whole X operand
    otherwise. The feature-major tiling (:func:`_glm_sums`) never comes
    here: it pads neither axis."""
    n, d = x.shape
    d_pad = _pad_to(d, LANE)
    # GLM row-pass working set per tile row: the double-buffered storage-
    # width x block plus up to three (T, d_pad) f32 temporaries (upcast,
    # x∘β, mult∘x), and the lane-sparse (T, 1) y/w blocks and per-row
    # temporaries (a (T, 1) f32 occupies T/8 whole vregs: 512 B a row).
    # Fixed: the (1, d_pad) β / scale / grad / compensation rows.
    row_bytes = d_pad * (2 * x.dtype.itemsize + 12) + 12 * 512
    row_tile = _auto_row_tile(n, row_tile, x.dtype, row_bytes,
                              fixed_bytes=8 * 4 * d_pad)
    n_pad = _pad_to(max(n, row_tile), row_tile)
    if n_pad != n or d_pad != d:
        x = jnp.pad(x, ((0, n_pad - n), (0, d_pad - d)))
        y = jnp.pad(y, (0, n_pad - n))
        w = jnp.pad(w, (0, n_pad - n))
    return x, y, w, n_pad, d_pad, row_tile


def _glm_sums(x, y, w, beta, b0, ys, *, kind: str, d: int, row_tile: int,
              interpret: bool, x_scale, feature_major: bool):
    """One GLM sweep of the shard in the tiling the caller observed:
    ``(loss, Σ mult·x (d,), Σ mult, Σ w)``. ``feature_major`` says X is
    stored with the rows on the lanes, so the sweep reads ``x.T`` — a
    bitcast of such an array — in ``(d, lane_tile)`` blocks and nothing of
    X is padded, copied or reshaped; where that block cannot be built
    (:func:`_auto_lane_tile`) and for every other X it is the row-major
    tiling, as before."""
    n = x.shape[0]
    has_scale = x_scale is not None
    lane_tile = _auto_lane_tile(n, d, x.dtype, has_scale) \
        if feature_major else None
    if lane_tile is None:
        x, y, w, n_pad, d_pad, row_tile = _pad_rows_cols(x, y, w, row_tile)
        beta_p = jnp.pad(beta, (0, d_pad - d)).reshape(1, d_pad)
        _note_sweep(kind, "row_major", row_tile=row_tile, pieces=0,
                    pad_cols=d_pad - d, tail_rows=n_pad - n)
        loss, grad_row, aux = _run_glm(
            x, y, w, beta_p, b0, ys, kind=kind, tile=row_tile, width=d_pad,
            grid=(n_pad // row_tile,), interpret=interpret,
            scale=_pad_scale(x_scale, d, d_pad) if has_scale else None)
        grad = grad_row[0, :d]
    else:
        _note_sweep(kind, "feature_major", lane_tile=lane_tile, pieces=0,
                    pad_cols=0, tail_rows=n % lane_tile)
        loss, grad_lanes, aux = _run_glm(
            x.T, y, w, beta.reshape(d, 1), b0, ys, kind=kind,
            tile=lane_tile, width=d, grid=(pl.cdiv(n, lane_tile),),
            interpret=interpret, feature_major=True,
            scale=_pad_scale(x_scale, d, d).reshape(d, 1)
            if has_scale else None)
        # the kernel keeps 128 lane-wise partial sums a feature (VPU adds
        # only); the one cross-lane reduction of a sweep is this (d, 128)
        # XLA sum
        grad = jnp.sum(grad_lanes, axis=1)
    return loss[0, 0], grad, aux[0, 0], aux[0, 1]


def _note_sweep(kind: str, orientation: str, **attrs) -> None:
    """``kernel.glm_sweep`` instant, one per sweep BUILT (this runs while
    the aggregation program is traced, not per dispatch): which tiling the
    program got, what it pads and in how many bf16 pieces its products
    meet the MXU (0: the VPU body) — the counter of how often the
    feature-major tiling and the MXU form engage."""
    from cycloneml_tpu.observe import tracing
    tracing.instant("kernel.glm_sweep", kind=kind, orientation=orientation,
                    **attrs)


# -- fused binary logistic loss + gradient -------------------------------------

def fused_binary_logistic_scaled(x, y, w, inv_std, scaled_mean, coef,
                                 d: int, fit_intercept: bool = True,
                                 interpret: bool = False,
                                 row_tile: int = ROW_TILE,
                                 x_scale=None,
                                 feature_major: bool = False
                                 ) -> Dict[str, jnp.ndarray]:
    """The ``aggregators.binary_logistic_scaled`` block math as one pass
    over HBM computing {loss, grad, count} sums for the shard. Narrow
    (bf16/fp8) data-tier blocks are read at storage width and upcast to
    the f32 accumulator per VMEM tile — half (bf16) or a quarter (fp8) of
    the HBM traffic of an f32 sweep, no wide X copy anywhere. ``x_scale``
    is the fp8 tier's per-column dequantization vector, applied in-kernel
    per VMEM block. ``feature_major`` (static) is the caller's observation
    that X is stored rows-on-lanes (:func:`stored_feature_major`); it
    picks the tiling, never the result. The kernel reads RAW feature rows
    — no standardized copy — because the scaling is algebra OUTSIDE the
    row pass:

      margin = x·(inv_std∘β) + (β₀ − scaled_mean·β)   (scaled vector +
                                                       offset fold into the
                                                       kernel's β/β₀ slots)
      grad_β̂ = inv_std∘(Σ mult·x) − scaled_mean·Σmult (O(d) correction on
                                                       the kernel's raw sums)

    Same contract as ``aggregators.binary_logistic_scaled``. The form is
    read off X (:func:`mxu_sweep_tile`): a bfloat16 X the class sweep can
    tile is the one-model class sweep under the ``shared_sigmoid`` link —
    both products on the MXU, ``inv_std∘β`` and the multipliers as three
    bf16 pieces, X never upcast —, every other X the VPU body
    (:func:`_glm_sums`) with ``inv_std∘β`` and the offset in its β / β₀
    slots.
    """
    dtype = jnp.float32
    x = _storage_width(x)
    y = jnp.asarray(y, dtype)
    w = jnp.asarray(w, dtype)
    coef = jnp.asarray(coef, dtype)
    inv_std = jnp.asarray(inv_std, dtype)
    scaled_mean = jnp.asarray(scaled_mean, dtype)
    beta = coef[:d] if fit_intercept else coef
    tile = mxu_sweep_tile(x.shape[0], d, x.dtype, x_scale is not None,
                          feature_major)
    if tile is not None:
        loss, g, msum, count = _class_sweep(
            x, y, w, inv_std, scaled_mean, beta[None, :],
            coef[d:] if fit_intercept else None, link="shared_sigmoid",
            kind="logistic", interpret=interpret,
            feature_major=feature_major, tile=tile)
        grad = jnp.concatenate([g[0], msum]) if fit_intercept else g[0]
        return {"loss": loss[0], "grad": grad, "count": count}
    b0 = coef[d] if fit_intercept else jnp.zeros((), dtype)
    sb = inv_std * beta
    off = b0 - jnp.dot(scaled_mean, beta)

    loss, raw, msum, count = _glm_sums(
        x, y, w, sb, off, jnp.zeros((), dtype), kind="logistic", d=d,
        row_tile=row_tile, interpret=interpret, x_scale=x_scale,
        feature_major=feature_major)
    g = inv_std * raw - scaled_mean * msum
    if fit_intercept:
        grad = jnp.concatenate([g, msum[None]])
    else:
        grad = g
    return {"loss": loss, "grad": grad, "count": count}


def fused_least_squares_scaled(x, y, w, inv_std, scaled_mean, y_pars, coef,
                               d: int, interpret: bool = False,
                               row_tile: int = ROW_TILE,
                               x_scale=None,
                               feature_major: bool = False
                               ) -> Dict[str, jnp.ndarray]:
    """Fused least-squares loss/grad sweep — the kernel twin of
    ``aggregators.least_squares_scaled`` (the LinearRegression l-bfgs
    objective). The kernel reads RAW data-tier rows once (margin → residual
    → loss/multiplier/grad in one VMEM-resident pass); the doubly-
    standardized objective is algebra OUTSIDE the row pass:

      margin = x·(inv_std∘β) − (scaled_mean·β − ȳ̂)   (β/offset slots)
      err    = margin − y·(1/σ_y)                      (ys scalar slot)
      grad_β̂ = inv_std∘(Σ mult·x) − scaled_mean·Σmult

    ``y_pars = [1/σ_y, ȳ̂]``; no intercept coordinate exists (recovered in
    closed form by the caller). Same Kahan-compensated grid accumulation
    as the logistic kernel, and the same two forms read off X: the
    one-model class sweep under the ``squared`` link (ȳ̂ rides its bias,
    1/σ_y a ``(1, 1)`` operand) or the VPU body."""
    dtype = jnp.float32
    x = _storage_width(x)
    y = jnp.asarray(y, dtype)
    w = jnp.asarray(w, dtype)
    coef = jnp.asarray(coef, dtype)
    inv_std = jnp.asarray(inv_std, dtype)
    scaled_mean = jnp.asarray(scaled_mean, dtype)
    y_pars = jnp.asarray(y_pars, dtype)
    tile = mxu_sweep_tile(x.shape[0], d, x.dtype, x_scale is not None,
                          feature_major)
    if tile is not None:
        loss, g, _, count = _class_sweep(
            x, y, w, inv_std, scaled_mean, coef[None, :], y_pars[1:],
            link="squared", ys=y_pars[0], interpret=interpret,
            feature_major=feature_major, tile=tile)
        return {"loss": loss[0], "grad": g[0], "count": count}
    sb = inv_std * coef
    off = y_pars[1] - jnp.dot(scaled_mean, coef)  # rides the b0 slot

    loss, raw, msum, count = _glm_sums(
        x, y, w, sb, off, y_pars[0], kind="squared", d=d,
        row_tile=row_tile, interpret=interpret, x_scale=x_scale,
        feature_major=feature_major)
    g = inv_std * raw - scaled_mean * msum
    return {"loss": loss, "grad": g, "count": count}


def _kahan_add(acc, comp, v):
    """``acc += v`` across the (sequential) grid with the running
    compensation ``comp``: a plain f32 ``+=`` over thousands of row tiles
    drifts ~n_tiles ulps, which is enough to break the strong-Wolfe
    first-try acceptance when a sweep feeds the chunked device L-BFGS
    (measured: 46 line-search evals vs 10 for the tree-reducing XLA path
    at n=2M×d=1280). The compensation keeps the total at ~1 ulp — cheaper
    than the XLA tree and exact enough for the Wolfe tests."""
    yk = v - comp[:]
    t = acc[:] + yk
    comp[:] = (t - acc[:]) - yk
    acc[:] = t


def _lane_sums(v, tile: int):
    """``(r, tile) -> (r, 128)`` lane-wise partial sums by whole-vreg VPU
    adds; the one cross-lane reduction is left to the caller."""
    out = v[:, :LANE]
    for c in range(1, tile // LANE):
        out = out + v[:, c * LANE:(c + 1) * LANE]
    return out


def _sweep_name(kind: str) -> str:
    """The Mosaic call's name for a sweep of ``kind`` (the instant's): what
    a device trace and a metric's pattern call it, whichever body runs."""
    return "glm_sweep_" + ("least_squares" if kind == "squared" else kind)


def _run_glm(x, y, w, beta_p, b0, ys, *, kind, tile, width, grid,
             interpret, scale=None, feature_major=False):
    """Shared one-pass GLM sweep: margin → per-row loss/multiplier → grad,
    with ``kind`` selecting the link ("logistic" softplus/sigmoid,
    "squared" residual). ``ys`` is the label scale (squared only; the
    logistic path carries a zero). X tiles arrive at STORAGE width (bf16
    or fp8 when the data tier is narrow) and upcast to the f32
    accumulator in VMEM — the bytes HBM sees per sweep are exactly the
    tier's. ``scale`` (optional) is the fp8 tier's per-column
    dequantization vector, applied to every upcast VMEM block (one VPU
    broadcast-multiply per tile); ``scale=None`` compiles the pre-fp8
    kernel byte-for-byte.

    Two tilings of that one body (the link and the Kahan update are
    shared; only the order of the in-tile additions differs):

    - row-major (default): ``x`` is ``(n_pad, width)`` with ``width`` a
      multiple of 128, blocks ``(tile, width)``; y/w ride as ``(n, 1)``
      columns, β/scale/grad as ``(1, width)`` rows; the margin is a lane
      reduction a row, the gradient a sublane reduction.
    - ``feature_major``: ``x`` is the ``(width, n)`` view (``width`` = d,
      whole on the sublanes, NOT padded), blocks ``(width, tile)`` with
      the rows on the lanes; y/w ride as lane-dense ``(1, n)`` rows,
      β/scale as ``(width, 1)`` columns; the margin is a sum over
      sublanes (VPU adds), the gradient is kept as ``(width, 128)``
      lane-wise partial sums the caller reduces once. ``n`` need not fill
      the last tile: its lanes past n are masked in that grid step alone
      (Pallas leaves out-of-bounds lanes undefined, so a zero weight would
      not do), which counts the tail rows without copying X."""
    has_scale = scale is not None
    n = x.shape[1] if feature_major else x.shape[0]
    # feature-major: rows the last lane tile holds (0: every tile is full)
    tail = n % tile if feature_major else 0

    def link(margin, yv, wv, ys_ref):
        """Per-row multiplier and this tile's loss: one body, whatever
        axis the rows lie on."""
        if kind == "logistic":
            mult = wv * (jax.nn.sigmoid(margin) - yv)
            v_loss = jnp.sum(wv * (jax.nn.softplus(margin)
                                   - yv * margin)).reshape(1, 1)
        else:  # squared (least-squares residual)
            err = margin - ys_ref[0, 0] * yv
            mult = wv * err
            v_loss = (0.5 * jnp.sum(wv * err * err)).reshape(1, 1)
        v_aux = jnp.concatenate(
            [jnp.sum(mult)[None], jnp.sum(wv)[None]]).reshape(1, 2)
        return mult, v_loss, v_aux

    def glm_sweep(*refs):
        if has_scale:
            (b0_ref, ys_ref, x_ref, y_ref, w_ref, beta_ref, s_ref,
             loss_ref, grad_ref, aux_ref,
             closs_ref, cgrad_ref, caux_ref) = refs
        else:
            (b0_ref, ys_ref, x_ref, y_ref, w_ref, beta_ref,
             loss_ref, grad_ref, aux_ref,
             closs_ref, cgrad_ref, caux_ref) = refs
            s_ref = None
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            # full-block stores only: Mosaic rejects scalar VMEM stores
            loss_ref[:] = jnp.zeros_like(loss_ref)
            aux_ref[:] = jnp.zeros_like(aux_ref)
            grad_ref[:] = jnp.zeros_like(grad_ref)
            closs_ref[:] = jnp.zeros_like(closs_ref)
            cgrad_ref[:] = jnp.zeros_like(cgrad_ref)
            caux_ref[:] = jnp.zeros_like(caux_ref)

        def tile_sums(live=None):
            # fp32 accumulator tier from here on: the convert is a VPU op
            # on a VMEM-resident tile, not an HBM materialization
            xv = x_ref[:].astype(jnp.float32)
            if s_ref is not None:
                # fp8 dequant per VMEM block: codes * per-column scale
                xv = xv * s_ref[:]
            # (T, 1) columns / (1, T) rows — Mosaic rejects 1-D blocks that
            # don't align to the T(1024) XLA layout
            yv = y_ref[:]
            wv = w_ref[:]
            if live is not None:
                # the last lane tile's lanes past n hold whatever the
                # buffer held: select, never multiply (0 · NaN is NaN)
                xv = jnp.where(live, xv, 0.0)
                yv = jnp.where(live, yv, 0.0)
                wv = jnp.where(live, wv, 0.0)
            # matvecs with a width-1 output don't lower to the MXU (Mosaic:
            # non-constant reduction accumulator); broadcast-multiply +
            # reduce over the feature axis on the VPU instead — the pass is
            # HBM-bound, not FLOP-bound
            margin = jnp.sum(xv * beta_ref[:], axis=0 if feature_major else 1,
                             keepdims=True) + b0_ref[0, 0]
            mult, v_loss, v_aux = link(margin, yv, wv, ys_ref)
            gx = mult * xv
            if feature_major:
                v_grad = _lane_sums(gx, tile)      # (d, T) → (d, 128)
            else:
                v_grad = jnp.sum(gx, axis=0, keepdims=True)
            for acc, comp, v in ((loss_ref, closs_ref, v_loss),
                                 (grad_ref, cgrad_ref, v_grad),
                                 (aux_ref, caux_ref, v_aux)):
                _kahan_add(acc, comp, v)

        if tail == 0:
            tile_sums()
        else:
            last = grid[0] - 1
            pl.when(i < last)(tile_sums)
            pl.when(i == last)(lambda: tile_sums(
                jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) < tail))

    if feature_major:
        x_spec = pl.BlockSpec((width, tile), lambda i: (0, i))
        vec_spec = pl.BlockSpec((1, tile), lambda i: (0, i))     # y, w
        col_shape = (width, 1)                                   # β, scale
        grad_shape = (width, LANE)
    else:
        x_spec = pl.BlockSpec((tile, width), lambda i: (i, 0))
        vec_spec = pl.BlockSpec((tile, 1), lambda i: (i, 0))
        col_shape = (1, width)
        grad_shape = (1, width)
    in_specs = [
        pl.BlockSpec((1, 1), lambda i: (0, 0)),          # b0 / -offset
        pl.BlockSpec((1, 1), lambda i: (0, 0)),          # label scale
        x_spec,
        vec_spec,
        vec_spec,
        pl.BlockSpec(col_shape, lambda i: (0, 0)),       # beta
    ]
    # Mosaic rejects 1-D blocks (see glm_sweep). (n,) -> (n, 1) is a
    # relayout pass over the vector into a lane-sparse column on the
    # chip; (n,) -> (1, n) keeps it lane-dense
    vec_shape = (1, -1) if feature_major else (-1, 1)
    args = [b0.reshape(1, 1), ys.reshape(1, 1), x,
            y.reshape(vec_shape), w.reshape(vec_shape), beta_p]
    if has_scale:
        in_specs.append(pl.BlockSpec(col_shape, lambda i: (0, 0)))
        args.append(scale)
    # named by kind: what a device trace, the Mosaic dump and a metric's
    # pattern call this sweep
    name = _sweep_name(kind)
    glm_sweep.__name__ = glm_sweep.__qualname__ = name
    sweep = pl.pallas_call(
        glm_sweep,
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec(grad_shape, lambda i: (0, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec(grad_shape, lambda i: (0, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct(grad_shape, jnp.float32),
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct(grad_shape, jnp.float32),
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
        ],
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
    )
    return sweep(*args)[:3]


# -- fused multinomial (softmax) loss + gradient -------------------------------

#: classes ride the sublanes in whole packed bf16 groups, so that the three
#: pieces of an operand stack (and come apart) on tile boundaries
CLASS_GROUP = 16
#: bf16 pieces an f32 operand of either product is split into (8 + 8 + 8
#: mantissa bits: the sum of the pieces is the f32 value to its last bit)
SOFTMAX_PIECES = 3

_NN = (((1,), (0,)), ((), ()))     # (m, k) x (k, n)


def multinomial_sweep_tile(rows: int, d: int, k: int, dtype,
                           feature_major: bool):
    """Rows of X one grid step of :func:`fused_multinomial_logistic_scaled`
    takes, or None where the kernel cannot be built and the XLA aggregator
    is the path: storage other than bfloat16 (an f32 X would need its own
    pieces on both products, fp8 its scale), a width that does not end on
    a packed sublane group (feature-major: d % 16) or on a lane (row-major:
    d % 128), fewer than 128 rows, or a ``(d, k)`` whose working set passes
    the VMEM budget. At k ≤ 16 that admits d ≤ 5,600 or so; the class pad
    grows in sixteens and the budget holds k·d ≲ 1.0e5 (k = 100 at d = 784,
    k = 40 at d = 2,000). k = 1 is the one-model sweep's MXU form
    (:func:`mxu_sweep_tile`): 1,024 rows at d = 2,000."""
    if np.dtype(dtype) != np.dtype(jnp.bfloat16) or rows < LANE or k < 1:
        return None
    if d % (CLASS_GROUP if feature_major else LANE):
        return None
    kp = _pad_to(k, CLASS_GROUP)
    d_lanes = _pad_to(d, LANE)
    # resident: the coefficient pieces, gradient and compensation (out
    # blocks count twice), one tile's (pieces, d) f32 product
    fixed = kp * d_lanes * (2 * SOFTMAX_PIECES * 2 + 3 * 4
                            + SOFTMAX_PIECES * 4)
    # per row of X: the double-buffered storage block (and its masked copy
    # in the last step), the (pieces·kp) f32 margins and bf16 multipliers,
    # a dozen (kp, T) f32 softmax temporaries
    per_row = 3 * 2 * d + kp * (SOFTMAX_PIECES * 6 + 12 * 4)
    # the MXU sums a tile's rows in f32: 1,024 terms an entry at most, as
    # the moment Gramian's
    for t in (1024, 512, 256, 128):
        if t <= rows and fixed + t * per_row <= _VMEM_BUDGET:
            return t
    return None


def multinomial_sweep_orientation(x, k: int):
    """The tiling the fused multinomial sweep takes for the concrete X of
    a fit — ``"feature_major"`` / ``"row_major"`` by the layout X has, as
    :func:`glm_sweep_orientation` — or None where a shard of it admits no
    tile (:func:`multinomial_sweep_tile`) and the fit is the XLA
    aggregator's. A stacked fit of ONE model is the XLA aggregator's too:
    the K-row sweep serves k ≥ 2 (one model's MXU form is the serial
    wrappers', :func:`mxu_sweep_tile`)."""
    feature_major = stored_feature_major(x)
    rows, d = x.sharding.shard_shape(x.shape)
    if k < 2 or multinomial_sweep_tile(rows, d, k, x.dtype,
                                       feature_major) is None:
        return None
    return "feature_major" if feature_major else "row_major"


def fused_multinomial_logistic_scaled(x, y, w, inv_std, scaled_mean, coef,
                                      d: int, k: int,
                                      fit_intercept: bool = True,
                                      interpret: bool = False,
                                      feature_major: bool = False,
                                      tile: int = None
                                      ) -> Dict[str, jnp.ndarray]:
    """Softmax cross-entropy over ``k`` classes and its gradient from ONE
    read of a bfloat16 X at storage width — the kernel twin of
    ``aggregators.multinomial_logistic_scaled`` (ref
    MultinomialLogisticBlockAggregator: all k coefficient vectors kept),
    standardization folded around the row pass as in
    :func:`fused_binary_logistic_scaled`:

      margins = x·(W∘inv_std)ᵀ + (b − W·scaled_mean)
      grad_Ŵ  = inv_std∘(multᵀ·x) − Σmult ⊗ scaled_mean

    Both products run on the MXU and stay f32-faithful: X is bfloat16 and
    exact, and the f32 operand of each product — the scaled coefficient
    matrix going in, the multipliers ``w·(p − 1[y])`` coming out — is split
    into three bfloat16 pieces whose sum is the f32 value, stacked on the
    class axis (bf16 x bf16 products are exact in the f32 accumulator).
    The coefficient pieces are made here, in code XLA compiles, so by
    ``reduce_precision`` (:func:`_split3_rounded`); the multipliers' inside
    the kernel (:func:`_split3`).

    ``feature_major`` (static) is the caller's observation of how X is
    stored and picks the tiling, never the result; ``tile`` overrides the
    rows a grid step takes (tests). Which ``(d, k)`` fit:
    :func:`multinomial_sweep_tile`, which the caller asks first."""
    coef = jnp.asarray(coef, jnp.float32)
    wmat = coef[: d * k].reshape(k, d)
    b = coef[d * k:] if fit_intercept else None
    loss, gw, msum, count = _class_sweep(
        x, y, w, inv_std, scaled_mean, wmat, b, link="softmax",
        interpret=interpret, feature_major=feature_major, tile=tile)
    grad = jnp.concatenate([gw.reshape(-1), msum]) if fit_intercept \
        else gw.reshape(-1)
    return {"loss": jnp.sum(loss), "grad": grad, "count": count}


def fused_stacked_binomial_scaled(x, y, w, inv_std, scaled_mean, coef,
                                  d: int, k: int,
                                  fit_intercept: bool = True,
                                  shared_labels: bool = False,
                                  interpret: bool = False,
                                  feature_major: bool = False,
                                  tile: int = None
                                  ) -> Dict[str, jnp.ndarray]:
    """``k`` INDEPENDENT binomial losses and their gradients from ONE read
    of a bfloat16 X — the K-class sweep (:func:`fused_multinomial_logistic_
    scaled`: same tiling, same three-piece MXU products, same folded
    standardization) with the link a sigmoid a model in place of one
    softmax a row. ``coef`` is the ``(k, d [+ 1])`` stack, one model a row.

    The label of model ``j`` on a row is made in the kernel and exists
    nowhere else: ``1[y == j]`` for ``y`` the row's class index
    (OneVsRest's relabelling), or ``y`` itself for every model where
    ``shared_labels`` (models that differ in their penalty alone: a
    regParam grid). Returns ``{"loss": (k,), "grad": (k, d [+ 1]),
    "count"}``. Which ``(d, k)`` fit: :func:`multinomial_sweep_tile`."""
    coef = jnp.asarray(coef, jnp.float32)
    loss, gw, msum, count = _class_sweep(
        x, y, w, inv_std, scaled_mean, coef[:, :d],
        coef[:, d] if fit_intercept else None,
        link="shared_sigmoid" if shared_labels else "sigmoid",
        interpret=interpret, feature_major=feature_major, tile=tile)
    grad = jnp.concatenate([gw, msum[:, None]], axis=1) if fit_intercept \
        else gw
    return {"loss": loss, "grad": grad, "count": count}


#: the links of the class sweep — what a row's k margins turn into — with
#: the ``kind`` of the sweep's instant (and the Mosaic call's name,
#: :func:`_sweep_name`) and what the instant calls the k rows
_CLASS_LINKS = {
    "softmax": ("multinomial", "classes", "class_pad"),
    "sigmoid": ("stacked_binomial", "models", "model_pad"),
    "shared_sigmoid": ("stacked_binomial", "models", "model_pad"),
    "squared": ("squared", "models", "model_pad")}


def _class_sweep(x, y, w, inv_std, scaled_mean, wmat, b, *, link: str,
                 interpret: bool, feature_major: bool, tile,
                 kind: str = None, ys=None):
    """What the class-sweep wrappers share — the two K-row ones and the
    one-model sweep's MXU form: the ``(k, d)`` coefficient rows folded with
    the standardization and split into pieces, the kernel, and the
    gradient's un-folding. Returns ``(loss, grad_W (k, d), Σ mult (k,),
    Σ w)`` — ``loss`` the lane sums' total, one number under the softmax
    and ``(k,)`` under the other links. ``kind`` names the sweep in its
    instant and Mosaic call where the link's own name does not (the
    serial logistic sweep under ``shared_sigmoid``); ``ys`` is the
    ``squared`` link's label scale."""
    n, d = x.shape
    k = wmat.shape[0]
    if tile is None:
        tile = multinomial_sweep_tile(n, d, k, x.dtype, feature_major)
    if tile is None:
        raise ValueError(
            f"no class sweep for a {x.dtype} X of {n} x {d}, "
            f"{k} classes, feature_major={feature_major}: ask "
            f"multinomial_sweep_tile first and take the XLA aggregator")
    f32 = jnp.float32
    kp = _pad_to(k, CLASS_GROUP)
    inv_std = jnp.asarray(inv_std, f32)
    scaled_mean = jnp.asarray(scaled_mean, f32)
    shift = jnp.dot(wmat, scaled_mean, precision=jax.lax.Precision.HIGHEST)
    bias = -shift if b is None else b - shift
    scaled = jnp.pad(wmat * inv_std[None, :], ((0, kp - k), (0, 0)))
    pieces = jnp.concatenate(_split3_rounded(scaled), axis=0)
    bias = jnp.pad(bias, (0, kp - k)).reshape(kp, 1)
    link_kind, count_attr, pad_attr = _CLASS_LINKS[link]
    kind = kind or link_kind
    _note_sweep(kind, "feature_major" if feature_major else "row_major",
                pieces=SOFTMAX_PIECES, pad_cols=0, tail_rows=n % tile,
                **{count_attr: k, pad_attr: kp,
                   "lane_tile" if feature_major else "row_tile": tile})
    loss, raw, msum, count = _run_multinomial(
        x.T if feature_major else x, jnp.asarray(y, f32), jnp.asarray(w, f32),
        pieces, bias, k=k, tile=tile, feature_major=feature_major,
        link=link, name=_sweep_name(kind), interpret=interpret,
        ys=None if ys is None else jnp.asarray(ys, f32).reshape(1, 1),
        tile_totals=kind in ("logistic", "squared"))
    msum = jnp.sum(msum[:k], axis=1)
    gw = raw[:k] * inv_std[None, :] - msum[:, None] * scaled_mean[None, :]
    loss = jnp.sum(loss) if link == "softmax" else jnp.sum(loss[:k], axis=1)
    return loss, gw, msum, jnp.sum(count)


def _run_multinomial(x, y, w, pieces, bias, *, k, tile, feature_major,
                     link, name, interpret, ys=None, tile_totals=False):
    """The K-class GLM sweep: per grid step the margins of ``tile`` rows
    (MXU), their link, loss and multipliers with the classes on the
    sublanes and the rows on the lanes (VPU, a ``(class_pad, tile)``
    block), and the multipliers' product with the same X tile (MXU), Kahan-
    added across the sequential grid as :func:`_run_glm`'s sums are.

    ``link`` (static) is what the k margins of a row are: ``"softmax"`` —
    one model, the row's loss one number — or k models of their own,
    ``"sigmoid"`` (model j's label is ``1[y == j]``) / ``"shared_sigmoid"``
    (every model's label is ``y``) / ``"squared"`` (the residual against
    ``ys·y``, ``ys`` a ``(1, 1)`` operand only this link has), the row's
    loss one number a model. The products, the tiling, the tail and the
    sums are the same code; ``name`` is the Mosaic call's. ``tile_totals``
    (the serial sweep's) sums a tile's loss, multipliers and weights across
    its lanes before the Kahan add, as :func:`_run_glm` does: an L-BFGS
    stop at ``tol`` 1e-6 compares losses nine and ten f32 ulps apart, and
    128 lane partials summed afterwards move the total by an ulp with the
    row order.

    ``x`` is the ``(d, n)`` view (``feature_major``: blocks ``(d, tile)``)
    or the ``(n, d)`` array (blocks ``(tile, d)``); either way X meets the
    MXU as it is stored, y and w ride as lane-dense ``(1, n)`` rows, and
    nothing is padded: the rows of the last tile past n are selected out in
    that grid step alone (Pallas leaves them undefined, and 0 · NaN is NaN).
    Returns ``(loss (1, 128) — (class_pad, 128) under the sigmoids, whose
    rows past k are the padding's and the caller's to drop, as the other
    sums' are —, Σ mult·x (class_pad, d), Σ mult (class_pad, 128), Σ w
    (1, 128))`` with the lane-wise partial sums left to the caller."""
    kp = pieces.shape[0] // SOFTMAX_PIECES
    d = pieces.shape[1]
    n = x.shape[1] if feature_major else x.shape[0]
    steps = pl.cdiv(n, tile)
    tail = n % tile
    # which operand's lanes each product contracts: the stored tile is the
    # right operand of both
    margins_dims, grad_dims = (_NN, _NT) if feature_major else (_NT, _NN)

    def glm_sweep_multinomial(x_ref, y_ref, w_ref, p_ref, b_ref, *refs):
        if link == "squared":
            ys_ref, *refs = refs
        (loss_ref, grad_ref, msum_ref, count_ref,
         closs_ref, cgrad_ref, cmsum_ref, ccount_ref) = refs
        i = pl.program_id(0)
        sums = ((loss_ref, closs_ref), (grad_ref, cgrad_ref),
                (msum_ref, cmsum_ref), (count_ref, ccount_ref))

        @pl.when(i == 0)
        def _():
            for acc, comp in sums:
                acc[:] = jnp.zeros_like(acc)
                comp[:] = jnp.zeros_like(comp)

        def tile_sums(live=None):
            xv = x_ref[:]
            stacked = jax.lax.dot_general(
                p_ref[:], xv, margins_dims,
                preferred_element_type=jnp.float32)
            margins = b_ref[:] + sum(
                stacked[j * kp:(j + 1) * kp] for j in range(SOFTMAX_PIECES))
            klass = jax.lax.broadcasted_iota(jnp.int32, (kp, tile), 0)
            yv, wv = y_ref[:], w_ref[:]
            if link == "softmax":
                if k < kp:
                    margins = jnp.where(klass < k, margins, -jnp.inf)
                top = jnp.max(margins, axis=0, keepdims=True)
                e = jnp.exp(margins - top)
                z = jnp.sum(e, axis=0, keepdims=True)
                hit = klass == yv.astype(jnp.int32)
                picked = jnp.sum(jnp.where(hit, margins, 0.0), axis=0,
                                 keepdims=True)
                v_loss = wv * (top + jnp.log(z) - picked)
                mult = wv * (e / z - hit.astype(jnp.float32))
            elif link == "squared":
                # rows past k are the padding's (zero coefficients, zero
                # bias: finite, and dropped by the caller)
                err = margins - ys_ref[0, 0] * yv
                mult = wv * err
                v_loss = 0.5 * wv * err * err
            else:
                # a model a sublane: rows past k are the padding's (zero
                # coefficients: finite, and dropped by the caller)
                hit = yv == 1.0 if link == "shared_sigmoid" \
                    else klass == yv.astype(jnp.int32)
                e = jnp.exp(-jnp.abs(margins))
                v_loss = wv * (jnp.maximum(margins, 0.0) + jnp.log1p(e)
                               - jnp.where(hit, margins, 0.0))
                mult = wv * (jnp.where(margins >= 0.0, 1.0, e) / (1.0 + e)
                             - hit.astype(jnp.float32))
            if live is not None:
                v_loss = jnp.where(live, v_loss, 0.0)
                mult = jnp.where(live, mult, 0.0)
                wv = jnp.where(live, wv, 0.0)
                # the multipliers' product contracts the rows: X's own
                # dead rows go too
                rows = live if feature_major else jax.lax.broadcasted_iota(
                    jnp.int32, (tile, 1), 0) < tail
                xv = jnp.where(rows, xv, jnp.zeros((), xv.dtype))
            v_grad = jax.lax.dot_general(
                jnp.concatenate(_split3(mult), axis=0), xv, grad_dims,
                preferred_element_type=jnp.float32)
            v_grad = sum(v_grad[j * kp:(j + 1) * kp]
                         for j in range(SOFTMAX_PIECES))
            lanes = [_lane_sums(v, tile) for v in (v_loss, mult, wv)]
            if tile_totals:
                # one total a model and tile, in lane 0, Kahan-added as
                # _run_glm's: the caller's sum over lanes is then exact
                first = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1) == 0
                lanes = [jnp.where(first, jnp.sum(v, axis=1, keepdims=True),
                                   0.0) for v in lanes]
            for (acc, comp), v in zip(sums, (lanes[0], v_grad, *lanes[1:])):
                _kahan_add(acc, comp, v)

        if tail == 0:
            tile_sums()
        else:
            pl.when(i < steps - 1)(tile_sums)
            pl.when(i == steps - 1)(lambda: tile_sums(
                jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) < tail))

    x_spec = pl.BlockSpec((d, tile), lambda i: (0, i)) if feature_major \
        else pl.BlockSpec((tile, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    shapes = [(1 if link == "softmax" else kp, LANE), (kp, d), (kp, LANE),
              (1, LANE)]
    args = (x, y.reshape(1, n), w.reshape(1, n), pieces, bias)
    in_specs = [x_spec, vec_spec, vec_spec,
                pl.BlockSpec(pieces.shape, lambda i: (0, 0)),
                pl.BlockSpec((kp, 1), lambda i: (0, 0))]
    if link == "squared":
        args += (ys,)
        in_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0)))
    sweep = pl.pallas_call(
        glm_sweep_multinomial,
        name=name,
        grid=(steps,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(s, lambda i: (0, 0)) for s in shapes],
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in shapes],
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
    )
    return sweep(*args)


# -- moment Gramian (WeightedLeastSquares, RowMatrix) -----------------------------

#: rows the small moments take AHEAD of X in the kernel's tile: one packed
#: bf16 sublane group, so X starts on a group boundary whatever d is
MOMENT_ROWS = 16
#: rows / columns of one MXU product of the triangle: the MXU's own tile.
#: Smaller blocks hug the diagonal closer (136 of 256 products at d = 2,000
#: against 10 of 16 at 512); measured on the v5e at 2,000,000 x 2,000 with a
#: 1,024-row tile: 48.7 ms at 128, 51.3 at 256, 56.2 at 512, 66.1 at 1,024
GRAM_BLOCK = 128
#: scoped VMEM the Gramian declares: its accumulator, the compensation and
#: the pipeline's second output buffer are three resident (d, d) f32 blocks
#: (49.5 MB at d = 2,000) — beyond the 32 MiB of the sweeps, inside the
#: 128 MiB a v5e core has
GRAM_VMEM_LIMIT_BYTES = 100 << 20
_GRAM_VMEM_BUDGET = 88 << 20
#: lane tile of the three-piece form. The three bf16 pieces of a 128-row
#: block are 48 vector registers at 256 lanes and stay in the file of 64
#: while the block's products read them; at 1,024 lanes they are 192,
#: spilled and filled around every product, and the MXU waits. Measured on
#: the v5e at 2,000,000 x 2,000 (PERF.md §6, PR 38): 161.7 ms a pass at
#: 1,024, 146.4 at 512, 140.8 at 256 = three times the mask form's 46.9.
#: The mask form has one piece a block and keeps its 1,024 (48.0 ms; 48.5
#: at 512, 58.2 at 2,048).
GRAM_WEIGHTED_TILE = 256

_NT = (((1,), (1,)), ((), ()))     # contract the lanes of two (rows, T) tiles


def _split3(v):
    """A float32 array as three bfloat16 pieces whose sum is the array to
    its last bit (8 + 8 + 8 mantissa bits): what lets an f32 operand ride
    the MXU in one bf16 pass a piece with exact products."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _split3_rounded(v):
    """:func:`_split3` for code XLA compiles (outside a Mosaic kernel):
    the pieces are rounded by ``reduce_precision``, which the compiler may
    not take back. A ``convert`` pair it may: XLA:TPU keeps a bf16 value
    of a fusion at f32 (excess precision), so ``v - bf16(v)`` came out 0
    on the v5e while the stored piece WAS rounded — the two low pieces
    were lost (PR 33: margins 2e-3 off). Every piece is representable in
    bfloat16, so the final convert is exact however it is done."""
    def round_bf16(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    hi = round_bf16(v)
    rest = v - hi
    mid = round_bf16(rest)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, rest - mid))


def storage_matvec(x, beta):
    """``x @ beta`` for a replicated ``beta`` at the accumulator's width,
    reading X at storage width. A bfloat16 X meets ``beta`` as its three
    bfloat16 pieces (:func:`_split3_rounded`) in ONE ``(n, d) x (d, 3)``
    contraction — bf16 x bf16 products are exact in the f32 accumulator,
    so the margins are f32-faithful with no widened copy of X and no
    multi-pass ``highest`` product of an f32 operand (which read 3.8e-6
    low on the v5e, PR 29). Every other storage takes XLA's contraction
    at ``highest``."""
    if x.dtype == jnp.bfloat16:
        pieces = jnp.stack(
            _split3_rounded(jnp.asarray(beta, jnp.float32)), axis=1)
        return jnp.sum(jnp.dot(x, pieces,
                               preferred_element_type=jnp.float32), axis=1)
    from cycloneml_tpu.dataset.instance import is_narrow_dtype
    acc = jnp.float32 if is_narrow_dtype(x.dtype) else x.dtype
    return jnp.dot(x, jnp.asarray(beta, acc),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=acc)


def moment_gramian_tile(rows: int, d: int, dtype, feature_major: bool):
    """Lane tile of :func:`fused_moment_gramian` — how many rows of X one
    grid step takes — for a shard of ``rows`` x ``d`` stored as ``dtype``
    in the given orientation, or None where the kernel cannot be built and
    the XLA contraction (:func:`moment_sums`) is the path: storage other
    than bfloat16 (f32/f64 want multi-pass products on BOTH operands, fp8
    wants its scale), a width that does not end on a packed sublane group
    (feature-major: d % 16) or on a lane (row-major: d % 128), fewer than
    128 rows, or a d whose three resident ``(d, d)`` f32 blocks pass the
    VMEM the kernel declares (d ≈ 2,700)."""
    if np.dtype(dtype) != np.dtype(jnp.bfloat16) or rows < LANE:
        return None
    if d % (MOMENT_ROWS if feature_major else LANE):
        return None
    big = MOMENT_ROWS + d
    fixed = 3 * 4 * big * _pad_to(big, LANE)
    # per lane: the double-buffered x tile, the (big, T) bf16 work tile and
    # the weighted pass's f32 block with its three pieces
    per_lane = 2 * (2 * d) + 2 * big + GRAM_BLOCK * (4 + 4 + 6)
    # 2,048-row tiles measured slower than 1,024 (65.9 against 56.2 ms)
    for t in (1024, 512, 256, 128):
        if t <= rows and fixed + t * per_lane <= _GRAM_VMEM_BUDGET:
            return t
    return None


def fused_moment_gramian(x, y, w, *, feature_major: bool, lane_tile: int,
                         weighted: bool, interpret: bool = False):
    """``Z'WZ`` of the augmented design ``Z = [1 | y | X]`` in ONE read of
    a bfloat16 X, as the upper-triangular blocks of a ``(16 + d, 16 + d)``
    f32 matrix: row 0 holds ``Σw, Σwy, Σwx``, rows 1-3 (y as three bf16
    pieces, :func:`_split3`) ``Σwy², Σwyx``, the rest ``X'WX`` (ref:
    WeightedLeastSquares' Aggregator, RowMatrix.computeGramianMatrix:130).

    A grid step takes ``lane_tile`` rows of X — a ``(d, T)`` block of the
    ``(d, n)`` view when X is stored ``feature_major`` (``x.T`` is a
    bitcast of such an array; nothing of X is padded, copied or upcast in
    HBM), a ``(T, d)`` block transposed in VMEM otherwise — puts the
    moment rows ahead of it in a VMEM work tile and multiplies that tile
    with itself on the MXU, ``GRAM_BLOCK``-square products of the upper
    triangle only. Every product is a bf16 x bf16 product accumulated in
    f32, so it is exact. With ``weighted=False`` w is a presence mask
    (rows with w = 0, and the lanes of the last tile past n, are selected
    out of the tile): one MXU pass. With ``weighted=True`` the left
    operand is ``Z·w`` in f32, split into three bf16 pieces per VMEM
    block: three passes, f32-faithful, still no copy of X
    (:func:`moment_sums` picks the form from the weights it is handed, and
    gives this one a lane tile of ``GRAM_WEIGHTED_TILE``).

    Accumulation: one tile's products are summed by the MXU in f32
    (T ≤ 1,024 terms an entry), the tiles are added with Kahan
    compensation across the sequential grid, so against float64
    ``|err_ij| ≤ (T + 4) 2^-24 Σ_r w_r |z_ri z_rj|`` whatever n is — the
    worst case of the in-tile sum; observed on the v5e at 2,000,000 rows:
    7e-8 of the diagonal, where a plain ``+=`` over the grid read 1.5e-6.
    """
    n, d = x.shape
    big = MOMENT_ROWS + d
    edges = list(range(0, big, GRAM_BLOCK)) + [big]
    spans = list(zip(edges[:-1], edges[1:]))
    tile = lane_tile
    n_steps = pl.cdiv(n, tile)

    def moment_gramian(x_ref, y_ref, w_ref, acc_ref, comp_ref, z_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            comp_ref[:] = jnp.zeros_like(comp_ref)

        # lanes of the last tile past n hold whatever the buffer held:
        # select, never multiply (0 · NaN is NaN)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) + i * tile
        valid = lane < n
        wv = jnp.where(valid, w_ref[:], 0.0)
        keep = valid if weighted else wv > 0
        y_hi, y_mid, y_lo = (p.astype(jnp.float32) for p in _split3(
            jnp.where(keep, y_ref[:], 0.0)))
        row = jax.lax.broadcasted_iota(jnp.int32, (MOMENT_ROWS, tile), 0)
        moments = jnp.where(
            row == 0, keep.astype(jnp.float32),
            jnp.where(row == 1, y_hi, jnp.where(
                row == 2, y_mid, jnp.where(row == 3, y_lo, 0.0))))
        z_ref[0:MOMENT_ROWS, :] = moments.astype(jnp.bfloat16)
        xv = x_ref[:] if feature_major else x_ref[:].T
        z_ref[MOMENT_ROWS:big, :] = jnp.where(keep, xv,
                                              jnp.zeros((), xv.dtype))

        for a, (i0, i1) in enumerate(spans):
            if weighted:
                left = _split3(z_ref[i0:i1, :].astype(jnp.float32) * wv)
            else:
                left = (z_ref[i0:i1, :],)
            for j0, j1 in spans[a:]:
                right = z_ref[j0:j1, :]
                v = sum(jax.lax.dot_general(
                    piece, right, _NT, preferred_element_type=jnp.float32)
                    for piece in left)
                # Kahan across the grid, as the GLM sweep's sums
                yk = v - comp_ref[i0:i1, j0:j1]
                t = acc_ref[i0:i1, j0:j1] + yk
                comp_ref[i0:i1, j0:j1] = (t - acc_ref[i0:i1, j0:j1]) - yk
                acc_ref[i0:i1, j0:j1] = t

    if feature_major:
        x_arg, x_spec = x.T, pl.BlockSpec((d, tile), lambda i: (0, i))
    else:
        x_arg, x_spec = x, pl.BlockSpec((tile, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    return pl.pallas_call(
        moment_gramian,
        name="moment_gramian",
        grid=(n_steps,),
        in_specs=[x_spec, vec_spec, vec_spec],
        out_specs=pl.BlockSpec((big, big), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((big, big), jnp.float32),
        scratch_shapes=[pltpu.VMEM((big, big), jnp.float32),
                        pltpu.VMEM((big, tile), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=GRAM_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x_arg, jnp.asarray(y, jnp.float32).reshape(1, n),
      jnp.asarray(w, jnp.float32).reshape(1, n))


def moment_sums(x, y, w, *, feature_major: bool = False,
                interpret: bool = False) -> Dict[str, jnp.ndarray]:
    """A shard's weighted moments ``{w_sum, b_sum, bb_sum, a_sum, ab_sum,
    aa_sum}`` (``a`` the features, ``b`` the label: WeightedLeastSquares'
    names) from one read of X at storage width — the one Gramian of the
    package: WeightedLeastSquares aggregates it, ``RowMatrix.
    compute_gramian`` takes its ``aa_sum`` under a presence mask.

    Which form runs is read off the array, not off a conf key: where
    :func:`moment_gramian_tile` finds a tile (bfloat16 X on a backend
    that lowers Mosaic) it is :func:`fused_moment_gramian`, and INSIDE the
    program the weights pick its pass count. A shard whose weights hold
    ONE live value ``c = max(w)`` — every weight 0 or c, c finite: a
    presence mask (c = 1, every default-weight fit), a constant weight
    column, the first IRLS pass of a binomial-logit fit — takes ONE MXU
    pass under the mask ``w > 0`` and scales the block by c (``Σ c z_i z_j
    = c Σ z_i z_j``: one rounding an entry on top of the mask form's
    sums, none at c = 1); a second live value, a NaN or an infinite
    weight takes three. The count a shard took rides home with its
    moments as ``mxu_passes`` (1.0 or 3.0: the psum over shards adds
    them, :func:`mean_mxu_passes` divides). Every other X (the host
    platform, f32/f64/fp8 storage, widths the kernel's tile or VMEM
    cannot take) is XLA's own contraction at ``highest`` with the weight
    folded into one operand — no ``mxu_passes`` there: on the TPU an
    operand fusion of the convolution, so no weighted or widened copy of
    X there either (sandbox AOT at 2,000,000 x 2,000: 0 B of
    temporaries). ``feature_major`` is the caller's observation
    (:func:`stored_feature_major`) and picks the kernel's tiling, never
    the result."""
    from cycloneml_tpu.observe import tracing
    n, d = x.shape
    tile = moment_gramian_tile(n, d, x.dtype, feature_major) \
        if (interpret or pallas_available()) else None
    if tile is None:
        tracing.instant("kernel.wls_moments", orientation="xla",
                        mxu_passes="highest", pad_cols=0, tail_rows=0)
        from cycloneml_tpu.dataset.instance import is_narrow_dtype
        acc = jnp.float32 if is_narrow_dtype(x.dtype) else x.dtype
        hi = jax.lax.Precision.HIGHEST
        w = jnp.asarray(w, acc)
        wy = w * jnp.asarray(y, acc)
        xw = x.astype(acc) * w[:, None]
        return {"w_sum": jnp.sum(w), "b_sum": jnp.sum(wy),
                "bb_sum": jnp.sum(wy * jnp.asarray(y, acc)),
                # each sum reads X itself: a second consumer of ``xw``
                # would make XLA write the weighted copy out
                "a_sum": jnp.dot(w, x, precision=hi,
                                 preferred_element_type=acc),
                "ab_sum": jnp.dot(wy, x, precision=hi,
                                  preferred_element_type=acc),
                "aa_sum": jnp.einsum("bi,bj->ij", xw, x, precision=hi,
                                     preferred_element_type=acc)}
    tile_weighted = min(tile, GRAM_WEIGHTED_TILE)
    tracing.instant(
        "kernel.wls_moments",
        orientation="feature_major" if feature_major else "row_major",
        lane_tile=tile, lane_tile_weighted=tile_weighted, block=GRAM_BLOCK,
        mxu_passes="one_value:1|else:3", pad_cols=0, tail_rows=n % tile)
    w = jnp.asarray(w, jnp.float32)

    def run(weighted):
        return lambda: fused_moment_gramian(
            x, y, w, feature_major=feature_major, weighted=weighted,
            lane_tile=tile_weighted if weighted else tile,
            interpret=interpret)

    # c >= 0 and not c > 0: a shard of padding alone (c = 0) is a mask
    c = jnp.max(w)
    one_value = jnp.isfinite(c) & (c >= 0) & jnp.all((w == 0) | (w == c))
    upper = jax.lax.cond(one_value, run(False), run(True))
    k = MOMENT_ROWS
    y_rows, gram = upper[1:4], upper[k:, k:]
    sums = {"w_sum": upper[0, 0], "b_sum": jnp.sum(upper[0, 1:4]),
            # (y_hi + y_mid + y_lo)^2 from the pieces' upper triangle
            "bb_sum": jnp.sum(jnp.triu(y_rows[:, 1:4])
                              + jnp.triu(y_rows[:, 1:4], 1)),
            "a_sum": upper[0, k:], "ab_sum": jnp.sum(y_rows[:, k:], axis=0),
            # the lower half is the upper's mirror: symmetric to the bit
            "aa_sum": jnp.triu(gram) + jnp.triu(gram, 1).T}
    # the mask form's sums times c, AFTER the unpacking (a multiply of the
    # cond's own output made XLA keep the kernel's 16 MB block in VMEM and
    # the kernel 1 % slower: PERF.md §6, PR 38); 1.0 · x is x to the bit
    scale = jnp.where(one_value, c, 1.0)
    return {"mxu_passes": jnp.where(one_value, 1.0, 3.0),
            **{name: scale * v for name, v in sums.items()}}


def mean_mxu_passes(moments, shards: int):
    """MXU passes a shard's Gramian took, from the psum'd ``mxu_passes``
    of :func:`moment_sums` over ``shards`` row shards: 1 or 3 where they
    agree, their mean where they do not, None where XLA's contraction ran
    (no such count)."""
    total = moments.get("mxu_passes")
    if total is None:
        return None
    mean = float(total) / shards
    return int(mean) if mean.is_integer() else mean
