"""Differentiable block aggregators.

Replaces the reference's ``ml/optim/aggregator/*`` family
(ref: BinaryLogisticBlockAggregator.scala:41 with its forward ``gemv:97`` and
transpose-gemv backward ``:130``; siblings Multinomial, LeastSquares, Hinge,
Huber under ml/optim/aggregator/) with pure JAX functions over instance
blocks. The per-block math is identical — margins via a block matmul (MXU),
multipliers, gradient via the transpose matmul — but written once as a loss
whose gradient ``jax.grad`` (or the hand-derived closed form below, kept for
clarity and exact parity) produces.

Every aggregator has signature ``(x, y, w, coef) -> {"loss","grad","count"}``
where ``x:(b,d) y:(b,) w:(b,)`` is a (shard of a) block with zero-weight
padding rows and ``coef`` is the flat parameter vector. They are summed
across the mesh by ``collectives.tree_aggregate`` — the treeAggregate
replacement (ref RDDLossFunction.scala:61). Losses/gradients are SUMS, not
means; the caller divides by weightSum exactly like the reference.

Layout conventions (match the reference's flat coefficient layout):
- binary logistic / linear / hinge: ``[w_0..w_{d-1}, intercept?]``
- multinomial: ``[W.flatten(order=C) (k,d), intercepts(k)?]``
- huber: ``[w_0..w_{d-1}, intercept?, sigma]``
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

Agg = Callable[..., Dict[str, jnp.ndarray]]


def _named(name: str):
    """Give a factory's ``agg`` closure the factory's name: the aggregation
    program is named after it (``tree_aggregate__<name>``), which is what
    a device trace shows."""
    def rename(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return rename

def matmul_precision():
    """Matmul precision for the aggregator hot path, resolved from
    ``cyclone.compute.matmulPrecision`` when an aggregator is BUILT (each
    fit builds its aggregators, so a session change applies to the next
    fit). See the config entry's doc for the measured guidance: 'highest'
    is both the parity choice AND at least as fast for the gemv-shaped
    binary path on v5e (HBM-bound); 'default' exists for MXU-bound shapes
    like wide multinomial."""
    from cycloneml_tpu import context as _c
    from cycloneml_tpu.conf import CycloneConf, MATMUL_PRECISION
    conf = (_c._active_context.conf if _c._active_context is not None
            else CycloneConf())
    # a ValueError from an invalid setting must surface — silently falling
    # back would make the misconfiguration invisible for every fit
    name = conf.get(MATMUL_PRECISION)
    return (jax.lax.Precision.DEFAULT if name == "default"
            else jax.lax.Precision.HIGHEST)


def _split_coef(coef, d, fit_intercept):
    if fit_intercept:
        return coef[:d], coef[d]
    return coef, jnp.zeros((), coef.dtype)


def _narrow(dt) -> bool:
    from cycloneml_tpu.dataset.instance import is_narrow_dtype
    return is_narrow_dtype(dt)


def _tier_dot(a, b, prec, acc=None):
    """``jnp.dot`` across the data/accumulator tier boundary.

    Full-width (f32/f64) operands take the pre-tier path UNCHANGED — the
    ``cyclone.data.dtype=float32`` opt-out is bit-identical by
    construction. When either operand is narrow (bf16/f16/fp8 data tier),
    the other is cast DOWN to the storage width (dtype promotion would
    otherwise upcast — and re-materialize — the whole X block) and the dot
    accumulates into ``acc`` via ``preferred_element_type``: narrow
    multiplicands, fp32 accumulation — the Micikevicius et al. (2018)
    mixed-precision recipe, natively an MXU bf16×bf16→f32 matmul on TPU.
    ``acc`` defaults to the full-width operand's dtype (the optimizer's
    accumulator tier: f32, or f64 under x64).

    The fp8 rung (``float8_e4m3fn``) rides the SAME recipe one step
    narrower: X holds per-column-scaled e4m3 codes (the scale folds into
    the replicated ``inv_std`` operand — dequant-in-kernel, no wide X
    anywhere), and the vector operand (coefficients forward, multipliers
    backward) is cast to e4m3 per evaluation. That cast is the fp8 tier's
    accuracy boundary — ~2^-4 relative rounding per element, NaN past
    ±448 (e4m3fn has no inf) — which is exactly what the per-fit envelope
    probe (``instance.fp8_probe_ok``) and the bf16 fallback police; the
    byte ledger (``costs.sweep_cost``) is why no in-graph clamp exists
    here: any extra (n,)-pass would cost the very bytes the tier saves.
    When the two operands sit in DIFFERENT narrow tiers (fp8 X against a
    bf16 label stack), the dot runs at the NARROWEST width — bf16→e4m3 is
    the only lossy direction, and it is the one the recipe already takes
    for f32 operands.
    """
    if not (_narrow(a.dtype) or _narrow(b.dtype)):
        return jnp.dot(a, b, precision=prec)
    if acc is None:
        acc = b.dtype if _narrow(a.dtype) else a.dtype
        if _narrow(acc):
            acc = jnp.float32
    if _narrow(a.dtype) and _narrow(b.dtype):
        nt = a.dtype if (jnp.dtype(a.dtype).itemsize
                         <= jnp.dtype(b.dtype).itemsize) else b.dtype
    else:
        nt = a.dtype if _narrow(a.dtype) else b.dtype
    return jnp.dot(a.astype(nt), b.astype(nt), precision=prec,
                   preferred_element_type=acc)


def binary_logistic(d: int, fit_intercept: bool = True) -> Agg:
    """Binomial logistic loss (ref BinaryLogisticBlockAggregator.scala:41).

    loss_i = w_i * (softplus(m_i) - y_i * m_i) with margin m = x·β + β₀ —
    algebraically the same stable form the reference branches on label.
    """
    return _binary_logistic(d, fit_intercept, matmul_precision())


@functools.lru_cache(maxsize=None)
def _binary_logistic(d: int, fit_intercept: bool, prec) -> Agg:
    # factories are lru-cached on their semantic parameters so repeated fits
    # hand tree_aggregate the SAME function object — program-cache identity
    # (collectives._program_cache) is what prevents a recompile per fit

    @_named("binary_logistic")
    def agg(x, y, w, coef):
        beta, b0 = _split_coef(coef, d, fit_intercept)
        margin = _tier_dot(x, beta, prec) + b0                  # forward gemv:97
        loss = jnp.sum(w * (jax.nn.softplus(margin) - y * margin))
        multiplier = w * (jax.nn.sigmoid(margin) - y)          # :112 multiplier
        g = _tier_dot(x.T, multiplier, prec)                    # backward gemv:130
        grad = jnp.concatenate([g, jnp.sum(multiplier)[None]]) if fit_intercept else g
        return {"loss": loss, "grad": grad, "count": jnp.sum(w)}

    return agg


def binary_logistic_scaled(d: int, fit_intercept: bool = True) -> Agg:
    """Binomial logistic loss over RAW feature blocks with standardization
    folded into the read: margin = x·(inv_std∘β̂) − scaled_mean·β̂ + β₀ and
    grad_β̂ = inv_std∘(xᵀmult) − scaled_mean·Σmult are algebraically the
    aggregation over x̂ = (x−μ)/σ without EVER materializing x̂ — the
    standardized copy (2× the HBM working set and one full read+write
    pass per fit) disappears (r3 verdict item 4: "fold standardization
    into the aggregator read"; the reference instead persists scaled
    instance blocks, LogisticRegression.scala:968).

    Signature: ``agg(x, y, w, inv_std, scaled_mean, coef)`` — inv_std and
    scaled_mean ride as REPLICATED arguments (not closure constants), so
    the compiled program is reused across datasets. Pass
    ``scaled_mean=zeros`` when not centering (fitWithMean off).
    """
    return _binary_logistic_scaled(d, fit_intercept, matmul_precision())


@functools.lru_cache(maxsize=None)
def _binary_logistic_scaled(d: int, fit_intercept: bool, prec) -> Agg:

    @_named("binary_logistic_scaled")
    def agg(x, y, w, inv_std, scaled_mean, coef):
        beta, b0 = _split_coef(coef, d, fit_intercept)
        sb = inv_std * beta
        margin = (_tier_dot(x, sb, prec)
                  - jnp.dot(scaled_mean, beta, precision=prec) + b0)
        loss = jnp.sum(w * (jax.nn.softplus(margin) - y * margin))
        multiplier = w * (jax.nn.sigmoid(margin) - y)
        msum = jnp.sum(multiplier)
        g = (inv_std * _tier_dot(x.T, multiplier, prec)
             - scaled_mean * msum)
        grad = jnp.concatenate([g, msum[None]]) if fit_intercept else g
        return {"loss": loss, "grad": grad, "count": jnp.sum(w)}

    return agg


def multinomial_logistic(d: int, k: int, fit_intercept: bool = True) -> Agg:
    """Softmax cross-entropy over k classes with k full coefficient vectors
    (ref MultinomialLogisticBlockAggregator.scala; the reference also keeps
    all k vectors rather than k-1, making the problem over-parameterised
    exactly like this)."""
    return _multinomial_logistic(d, k, fit_intercept, matmul_precision())


@functools.lru_cache(maxsize=None)
def _multinomial_logistic(d: int, k: int, fit_intercept: bool, prec) -> Agg:

    @_named("multinomial_logistic")
    def agg(x, y, w, coef):
        if fit_intercept:
            wmat = coef[: d * k].reshape(k, d)
            b = coef[d * k:]
        else:
            wmat = coef.reshape(k, d)
            b = jnp.zeros((k,), coef.dtype)
        margins = _tier_dot(x, wmat.T, prec) + b                # (bsz, k)
        log_z = jax.nn.logsumexp(margins, axis=1)
        y_idx = y.astype(jnp.int32)
        picked = jnp.take_along_axis(margins, y_idx[:, None], axis=1)[:, 0]
        loss = jnp.sum(w * (log_z - picked))
        probs = jax.nn.softmax(margins, axis=1)
        onehot = jax.nn.one_hot(y_idx, k, dtype=probs.dtype)  # {0,1} exact; fp8 x refuses implicit promotion
        mult = w[:, None] * (probs - onehot)                   # (bsz, k)
        gw = _tier_dot(mult.T, x, prec)                         # (k, d)
        if fit_intercept:
            grad = jnp.concatenate([gw.reshape(-1), jnp.sum(mult, axis=0)])
        else:
            grad = gw.reshape(-1)
        return {"loss": loss, "grad": grad, "count": jnp.sum(w)}

    return agg


def multinomial_logistic_scaled(d: int, k: int,
                                fit_intercept: bool = True) -> Agg:
    """Multinomial twin of :func:`binary_logistic_scaled`: softmax
    cross-entropy over RAW feature blocks with standardization (and
    fitWithMean centering) folded into the read — margins are
    x·(W∘inv_std)ᵀ − W·scaled_mean + b, gradients unscale per class. The
    standardized copy never materializes for multinomial fits either."""
    return _multinomial_logistic_scaled(d, k, fit_intercept,
                                        matmul_precision())


@functools.lru_cache(maxsize=None)
def _multinomial_logistic_scaled(d: int, k: int, fit_intercept: bool,
                                 prec) -> Agg:

    @_named("multinomial_logistic_scaled")
    def agg(x, y, w, inv_std, scaled_mean, coef):
        if fit_intercept:
            wmat = coef[: d * k].reshape(k, d)
            b = coef[d * k:]
        else:
            wmat = coef.reshape(k, d)
            b = jnp.zeros((k,), coef.dtype)
        wmat_s = wmat * inv_std[None, :]
        offset = jnp.dot(wmat, scaled_mean, precision=prec)      # (k,)
        margins = (_tier_dot(x, wmat_s.T, prec)
                   - offset[None, :] + b)                        # (bsz, k)
        log_z = jax.nn.logsumexp(margins, axis=1)
        y_idx = y.astype(jnp.int32)
        picked = jnp.take_along_axis(margins, y_idx[:, None], axis=1)[:, 0]
        loss = jnp.sum(w * (log_z - picked))
        probs = jax.nn.softmax(margins, axis=1)
        onehot = jax.nn.one_hot(y_idx, k, dtype=probs.dtype)  # {0,1} exact; fp8 x refuses implicit promotion
        mult = w[:, None] * (probs - onehot)                     # (bsz, k)
        msum = jnp.sum(mult, axis=0)                             # (k,)
        gw = (_tier_dot(mult.T, x, prec) * inv_std[None, :]
              - msum[:, None] * scaled_mean[None, :])            # (k, d)
        if fit_intercept:
            grad = jnp.concatenate([gw.reshape(-1), msum])
        else:
            grad = gw.reshape(-1)
        return {"loss": loss, "grad": grad, "count": jnp.sum(w)}

    return agg


def least_squares(d: int, fit_intercept: bool = True) -> Agg:
    """Squared loss ½ w (x·β + β₀ − y)² (ref LeastSquaresBlockAggregator)."""
    return _least_squares(d, fit_intercept, matmul_precision())


@functools.lru_cache(maxsize=None)
def _least_squares(d: int, fit_intercept: bool, prec) -> Agg:

    @_named("least_squares")
    def agg(x, y, w, coef):
        beta, b0 = _split_coef(coef, d, fit_intercept)
        err = _tier_dot(x, beta, prec) + b0 - y
        loss = 0.5 * jnp.sum(w * err * err)
        mult = w * err
        g = _tier_dot(x.T, mult, prec)
        grad = jnp.concatenate([g, jnp.sum(mult)[None]]) if fit_intercept else g
        return {"loss": loss, "grad": grad, "count": jnp.sum(w)}

    return agg


def least_squares_scaled(d: int) -> Agg:
    """Least-squares twin of :func:`binary_logistic_scaled`: squared loss
    over RAW feature blocks with the doubly-standardized objective folded
    into the read. The LinearRegression l-bfgs path trains on
    x̂ = (x−μ)/σ_x (centered only when fitting an intercept) against
    ŷ = y/σ_y − ȳ̂; with ``sb = inv_std∘β`` the residual is

      err = x·sb − (μ̂·β − ȳ̂) − y·(1/σ_y)        (μ̂ = scaled mean; the
                                                 whole centering is a scalar
                                                 offset outside the row pass)
      grad_β̂ = inv_std∘(xᵀmult) − μ̂·Σmult

    so neither the standardized X copy nor the scaled-y copy ever
    materializes — the fit's HBM working set is the raw data tier itself.

    Signature ``agg(x, y, w, inv_std, scaled_mean, y_pars, coef)`` with
    ``y_pars = [1/σ_y, ȳ̂]`` riding as a replicated (2,) runtime argument
    (program identity is dataset-generic, like inv_std/scaled_mean). Pass
    ``scaled_mean = zeros`` and ``y_pars[1] = 0`` for the no-intercept
    (uncentered) objective. No intercept coordinate exists: the intercept
    is recovered in closed form ȳ − β·x̄ after optimization.
    """
    return _least_squares_scaled(d, matmul_precision())


@functools.lru_cache(maxsize=None)
def _least_squares_scaled(d: int, prec) -> Agg:

    @_named("least_squares_scaled")
    def agg(x, y, w, inv_std, scaled_mean, y_pars, coef):
        sb = inv_std * coef
        off = jnp.dot(scaled_mean, coef, precision=prec) - y_pars[1]
        err = _tier_dot(x, sb, prec) - off - y * y_pars[0]
        loss = 0.5 * jnp.sum(w * err * err)
        mult = w * err
        msum = jnp.sum(mult)
        g = inv_std * _tier_dot(x.T, mult, prec) - scaled_mean * msum
        return {"loss": loss, "grad": g, "count": jnp.sum(w)}

    return agg


def hinge(d: int, fit_intercept: bool = True) -> Agg:
    """Hinge loss for LinearSVC (ref HingeBlockAggregator): labels in {0,1}
    mapped to ±1 as 2y−1; loss_i = w_i max(0, 1 − ŷ_i m_i)."""
    return _hinge(d, fit_intercept, matmul_precision())


@functools.lru_cache(maxsize=None)
def _hinge(d: int, fit_intercept: bool, prec) -> Agg:

    @_named("hinge")
    def agg(x, y, w, coef):
        beta, b0 = _split_coef(coef, d, fit_intercept)
        margin = _tier_dot(x, beta, prec) + b0
        ysign = 2.0 * y - 1.0
        active = (1.0 - ysign * margin) > 0
        loss = jnp.sum(w * jnp.maximum(0.0, 1.0 - ysign * margin))
        mult = jnp.where(active, -ysign * w, 0.0)
        g = _tier_dot(x.T, mult, prec)
        grad = jnp.concatenate([g, jnp.sum(mult)[None]]) if fit_intercept else g
        return {"loss": loss, "grad": grad, "count": jnp.sum(w)}

    return agg


def huber(d: int, fit_intercept: bool = True, epsilon: float = 1.35) -> Agg:
    """Huber loss with jointly-optimised scale σ (ref HuberBlockAggregator,
    following Owen 2007 as the reference does): coef = [β, β₀?, σ];
    loss_i = w_i (σ + ℓ_ε((y−μ)/σ) σ)."""
    return _huber(d, fit_intercept, float(epsilon), matmul_precision())


@functools.lru_cache(maxsize=None)
def _huber(d: int, fit_intercept: bool, epsilon: float, prec) -> Agg:

    @_named("huber")
    def agg(x, y, w, coef):
        beta, b0 = _split_coef(coef[:-1], d, fit_intercept)
        sigma = coef[-1]
        mu = _tier_dot(x, beta, prec) + b0
        r = (y - mu) / sigma
        abs_r = jnp.abs(r)
        outlier = abs_r > epsilon
        loss_i = jnp.where(
            outlier,
            sigma + (2.0 * epsilon * abs_r - epsilon * epsilon) * sigma,
            sigma + r * r * sigma)
        loss = jnp.sum(w * loss_i)
        # d/dmu and d/dsigma — matches the reference's piecewise gradients
        dmu = jnp.where(outlier, -2.0 * epsilon * jnp.sign(r), -2.0 * r)
        mult = w * dmu
        g = _tier_dot(x.T, mult, prec)
        dsig_i = jnp.where(outlier,
                           1.0 - epsilon * epsilon,
                           1.0 - r * r)
        dsig = jnp.sum(w * dsig_i)
        parts = [g]
        if fit_intercept:
            parts.append(jnp.sum(mult)[None])
        parts.append(dsig[None])
        return {"loss": loss, "grad": jnp.concatenate(parts), "count": jnp.sum(w)}

    return agg


@functools.lru_cache(maxsize=None)
def stack_aggregator(agg: Agg) -> Agg:
    """Model-axis twin of a plain ``(x, y, w, coef)`` aggregator.

    ``vmap`` pushes a leading model axis through the block matmuls
    mechanically (Frostig, Johnson & Leary, SysML 2018): the stacked twin
    takes a ``(b, K)`` label matrix (axis 1 — labels stay ROW-sharded like
    every other dataset array) and ``(K, n_coef)`` coefficients, with
    ``x``/``w`` shared, and returns ``{loss (K,), grad (K, n_coef),
    count (K,)}`` — so ``tree_aggregate`` reduces all K models' partials in
    ONE psum with a leading model axis. lru-cached on the base aggregator so
    repeated stacked fits keep program-cache identity (one XLA compile per
    (mesh, K, shapes), amortized over all K models)."""
    return jax.vmap(agg, in_axes=(None, 1, None, 0))


#: bytes of ``(rows, K)`` float32 margins one chunk of the row-blocked
#: stacked twin may hold (its multipliers are as large again)
STACKED_CHUNK_BYTES = 4 << 20


def stacked_binary_logistic_scaled(d: int, k: int, fit_intercept: bool = True,
                                   shared_labels: bool = False) -> Agg:
    """``k`` independent binomial models over ONE X as one aggregator:
    ``agg(x, y, w, inv_std, scaled_mean, coef (k, d [+ 1]))`` →
    ``{loss (k,), grad (k, d [+ 1]), count}``, model j's objective
    :func:`binary_logistic_scaled`'s on the labels ``1[y == j]`` — ``y`` the
    row's class index, OneVsRest's relabelling — or, with ``shared_labels``,
    on ``y`` itself (models that differ in their penalty alone: a regParam
    grid). The labels are made a chunk of rows at a time inside the pass:
    no ``(n, k)`` array exists, on the host or the device. The XLA twin of
    ``kernels.fused_stacked_binomial_scaled`` — what the CPU runs, and a
    TPU for the shapes ``kernels.multinomial_sweep_tile`` refuses; a narrow
    X rounds the coefficients to its tier here (``_tier_dot``), which the
    kernel does not."""
    return _stacked_binary_logistic_scaled(d, k, fit_intercept,
                                           bool(shared_labels),
                                           matmul_precision())


@functools.lru_cache(maxsize=None)
def _stacked_binary_logistic_scaled(d: int, k: int, fit_intercept: bool,
                                    shared_labels: bool, prec) -> Agg:

    @_named("stacked_binary_logistic_scaled")
    def agg(x, y, w, inv_std, scaled_mean, coef):
        n = x.shape[0]
        wmat = coef[:, :d]
        scaled = wmat * inv_std[None, :]
        bias = -jnp.dot(wmat, scaled_mean, precision=prec)        # (k,)
        if fit_intercept:
            bias = bias + coef[:, d]
        chunk = min(n, max(8, STACKED_CHUNK_BYTES // (4 * k) // 8 * 8))

        def chunk_sums(xb, yb, wb):
            margins = _tier_dot(xb, scaled.T, prec) + bias[None, :]
            hit = (yb == 1)[:, None] if shared_labels else \
                yb.astype(jnp.int32)[:, None] == jnp.arange(k)[None, :]
            loss = wb[:, None] * (jax.nn.softplus(margins)
                                  - jnp.where(hit, margins, 0.0))
            mult = wb[:, None] * (jax.nn.sigmoid(margins)
                                  - hit.astype(margins.dtype))
            return {"loss": jnp.sum(loss, axis=0),
                    "raw": _tier_dot(mult.T, xb, prec),           # (k, d)
                    "msum": jnp.sum(mult, axis=0),
                    "count": jnp.sum(wb)}

        def body(carry, i):
            part = chunk_sums(*(jax.lax.dynamic_slice_in_dim(a, i * chunk,
                                                             chunk)
                                for a in (x, y, w)))
            return jax.tree.map(jnp.add, carry, part), None

        # chunk <= n: at least one whole chunk, then the rows left over
        zero = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(chunk_sums, x[:chunk], y[:chunk], w[:chunk]))
        total, _ = jax.lax.scan(body, zero, jnp.arange(n // chunk))
        if n % chunk:
            lo = n - n % chunk
            total = jax.tree.map(jnp.add, total,
                                 chunk_sums(x[lo:], y[lo:], w[lo:]))
        msum = total["msum"]
        grad = (total["raw"] * inv_std[None, :]
                - msum[:, None] * scaled_mean[None, :])
        if fit_intercept:
            grad = jnp.concatenate([grad, msum[:, None]], axis=1)
        return {"loss": total["loss"], "grad": grad, "count": total["count"]}

    return agg


def autodiff_check(agg_loss_only: Callable, d: int):
    """Return jax.grad of a loss-only aggregator — used in tests to verify the
    hand-derived gradients above (SURVEY §7 step 5: 'where jax.grad can
    replace hand-written gradients (verify parity!)')."""
    return jax.grad(agg_loss_only)


def binary_logistic_pallas_scaled(d: int, fit_intercept: bool = True,
                                  feature_major=None) -> Agg:
    """Pallas twin of :func:`binary_logistic_scaled`: raw feature blocks,
    standardization folded around the kernel's row pass
    (ops/kernels.fused_binary_logistic_scaled) — the kernel path no longer
    needs the standardized copy either.

    ``feature_major`` is the tiling of the sweep (ops/kernels: rows on the
    lanes, for an X stored that way). An estimator passes what it observed
    on its X (``kernels.glm_sweep_orientation``); ``None`` — a caller with
    no array in hand — means what the device's default layout gives an
    ``(n, d)`` array, so asking again with the same ``d`` returns the very
    aggregator a fit of a default-layout X used (one program per
    orientation in the program cache, keyed by this function's identity)."""
    return _binary_logistic_pallas_scaled(
        d, fit_intercept, _feature_major(d, feature_major))


def _feature_major(d: int, feature_major) -> bool:
    if feature_major is None:
        from cycloneml_tpu.ops.kernels import default_feature_major
        return default_feature_major(d)
    return bool(feature_major)


@functools.lru_cache(maxsize=None)
def _binary_logistic_pallas_scaled(d: int, fit_intercept: bool,
                                   feature_major: bool) -> Agg:
    from cycloneml_tpu.ops.kernels import fused_binary_logistic_scaled

    @_named("binary_logistic_pallas_scaled")
    def agg(x, y, w, inv_std, scaled_mean, coef):
        return fused_binary_logistic_scaled(
            x, y, w, inv_std, scaled_mean, coef, d, fit_intercept,
            feature_major=feature_major)

    return agg


def multinomial_logistic_pallas_scaled(d: int, k: int,
                                       fit_intercept: bool = True,
                                       feature_major=None) -> Agg:
    """Pallas twin of :func:`multinomial_logistic_scaled`
    (ops/kernels.fused_multinomial_logistic_scaled): one read of a bf16 X
    an evaluation, both products on the MXU with the f32 operand of each
    in three bf16 pieces — the coefficient matrix is NOT rounded to the
    data tier, which the XLA twin's ``_tier_dot`` does. For the shapes
    ``kernels.multinomial_sweep_tile`` admits; the caller asks it first.
    ``feature_major``: as :func:`binary_logistic_pallas_scaled`."""
    return _multinomial_logistic_pallas_scaled(
        d, k, fit_intercept, _feature_major(d, feature_major))


@functools.lru_cache(maxsize=None)
def _multinomial_logistic_pallas_scaled(d: int, k: int, fit_intercept: bool,
                                        feature_major: bool) -> Agg:
    from cycloneml_tpu.ops.kernels import fused_multinomial_logistic_scaled

    @_named("multinomial_logistic_pallas_scaled")
    def agg(x, y, w, inv_std, scaled_mean, coef):
        return fused_multinomial_logistic_scaled(
            x, y, w, inv_std, scaled_mean, coef, d, k, fit_intercept,
            feature_major=feature_major)

    return agg


def stacked_binary_logistic_pallas_scaled(d: int, k: int,
                                          fit_intercept: bool = True,
                                          shared_labels: bool = False,
                                          feature_major=None) -> Agg:
    """Pallas twin of :func:`stacked_binary_logistic_scaled`
    (ops/kernels.fused_stacked_binomial_scaled): the K-class sweep's body
    under K sigmoids — one read of a bf16 X an evaluation for all k models,
    both products on the MXU in three bf16 pieces. For the shapes
    ``kernels.multinomial_sweep_tile`` admits; the caller asks it first.
    ``feature_major``: as :func:`binary_logistic_pallas_scaled`."""
    return _stacked_binary_logistic_pallas_scaled(
        d, k, fit_intercept, bool(shared_labels),
        _feature_major(d, feature_major))


@functools.lru_cache(maxsize=None)
def _stacked_binary_logistic_pallas_scaled(d: int, k: int,
                                           fit_intercept: bool,
                                           shared_labels: bool,
                                           feature_major: bool) -> Agg:
    from cycloneml_tpu.ops.kernels import fused_stacked_binomial_scaled

    @_named("stacked_binary_logistic_pallas_scaled")
    def agg(x, y, w, inv_std, scaled_mean, coef):
        return fused_stacked_binomial_scaled(
            x, y, w, inv_std, scaled_mean, coef, d, k, fit_intercept,
            shared_labels=shared_labels, feature_major=feature_major)

    return agg


def least_squares_pallas_scaled(d: int, feature_major=None) -> Agg:
    """Pallas twin of :func:`least_squares_scaled`: the residual sweep
    (margin → err → loss/mult/grad) runs as one VMEM-resident row pass
    (ops/kernels.fused_least_squares_scaled); standardization and the
    label scaling are algebra outside it, so the kernel reads the raw
    data-tier blocks exactly once per evaluation. ``feature_major``: as
    :func:`binary_logistic_pallas_scaled`."""
    return _least_squares_pallas_scaled(d, _feature_major(d, feature_major))


@functools.lru_cache(maxsize=None)
def _least_squares_pallas_scaled(d: int, feature_major: bool) -> Agg:
    from cycloneml_tpu.ops.kernels import fused_least_squares_scaled

    @_named("least_squares_pallas_scaled")
    def agg(x, y, w, inv_std, scaled_mean, y_pars, coef):
        return fused_least_squares_scaled(
            x, y, w, inv_std, scaled_mean, y_pars, coef, d,
            feature_major=feature_major)

    return agg
