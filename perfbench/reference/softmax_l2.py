"""Plain reference of multinomial (softmax) logistic regression with an L2
penalty, as Spark MLlib's ``LogisticRegression`` states it for more than two
classes (``MultinomialLogisticBlockAggregator``: all K coefficient vectors
kept; ``standardization=true``, ``fitIntercept=true``, ``elasticNetParam=0``):

    f(W, b) = 1/n sum_i [logsumexp_k(m_ik) - m_i,y_i] + regParam/2 |W|_F^2,
    m_ik = sum_j W_kj (x_ij - mean_j) / std_j + b_k

with ``std`` the unbiased sample deviation; the model's coefficient matrix is
``W / std`` and its intercepts ``b - (W / std) mean``, CENTRED (they are not
penalised, so their common constant is free: MLlib subtracts their mean).
The penalty makes ``W`` unique, the centring ``b``.

The labels are ``perfbench.class_labels`` of the stored X (the generator's
own label is ignored). The optimum is found by Newton's method with the
Newton system solved by conjugate gradients on Hessian-vector sweeps — the
(K d)^2 Hessian is never built. Margins and gradient in float32 at
``highest`` in row blocks, sums over blocks and shards and the iteration in
float64. The Hessian-vector products only steer the iteration (default
matmul precision): the point it stops at is where the ``highest`` gradient
vanishes.

The model is one flat vector for ``judge.compare``: ``coef`` =
``[W.ravel(), b]`` (K d + K numbers), ``intercept`` = 0.0.
"""

from __future__ import annotations

import numpy as np

from perfbench import class_labels
from perfbench.reference import blocks

CONFIG = "lr_mnist8m_multinomial"


def _margins(xf, mean, inv_std, wmat, icpt, margin_bits):
    """``(x_hat, margins)`` of a block. ``margin_bits`` (a planted fault:
    0 = none) rounds the coefficient matrix to that many mantissa bits in
    the margins alone, which is what a sweep does that hands the MXU the
    coefficients as one bfloat16 piece (7 bits)."""
    import jax
    import jax.numpy as jnp
    xh = (xf - mean) * inv_std
    if margin_bits:
        wmat = jax.lax.reduce_precision(wmat, 8, margin_bits)
    return xh, jnp.dot(xh, wmat.T, precision=jax.lax.Precision.HIGHEST) + icpt


def _hit(yb, k):
    import jax.numpy as jnp
    return yb[:, None] == jnp.arange(k, dtype=yb.dtype)[None, :]


def _loss_grad_block(margin_bits):
    def block(xf, yb, mean, inv_std, wmat, icpt):
        import jax
        import jax.numpy as jnp
        xh, m = _margins(xf, mean, inv_std, wmat, icpt, margin_bits)
        hit = _hit(yb, wmat.shape[0])
        loss = jnp.sum(jax.nn.logsumexp(m, axis=1)
                       - jnp.sum(jnp.where(hit, m, 0.0), axis=1))
        r = jax.nn.softmax(m, axis=1) - hit
        g = jnp.dot(r.T, xh, precision=jax.lax.Precision.HIGHEST)
        return {"loss": loss, "grad": g, "grad0": jnp.sum(r, axis=0)}, {}
    return block


_LOSS_GRAD = {bits: _loss_grad_block(bits) for bits in (0, 7)}


def _hessian_vector_block(xf, yb, mean, inv_std, wmat, icpt, vmat, vcpt):
    import jax
    import jax.numpy as jnp
    xh, m = _margins(xf, mean, inv_std, wmat, icpt, 0)
    p = jax.nn.softmax(m, axis=1)
    u = jnp.dot(xh, vmat.T) + vcpt
    r = p * (u - jnp.sum(p * u, axis=1, keepdims=True))
    return {"hv": jnp.dot(r.T, xh), "hv0": jnp.sum(r, axis=0)}, {}


def _losses_block(xf, yb, mean, inv_std, wmats, icpts):
    import jax
    import jax.numpy as jnp
    xh = (xf - mean) * inv_std
    m = jnp.einsum("nd,mkd->nmk", xh, wmats,
                   precision=jax.lax.Precision.HIGHEST) + icpts[None]
    hit = _hit(yb, wmats.shape[1])[:, None, :]
    return {"loss": jnp.sum(jax.nn.logsumexp(m, axis=2)
                            - jnp.sum(jnp.where(hit, m, 0.0), axis=2),
                            axis=0)}, {}


class Problem:
    """The objective over one dataset: labels and moments once, then any
    number of evaluations. ``kw`` plants the control (``quant``) or a fault
    (``rows_used``, ``shards_used``); ``margin_bits`` the one-piece
    margins."""

    def __init__(self, data, params: dict, margin_bits: int = 0, **kw):
        x, _, mesh, axes = data
        labels = class_labels.spec(CONFIG)
        self.k = labels["classes"]
        # the labels follow the STORED values, whatever the control rounds
        data = (x, class_labels.of(x, mesh, axes, **labels), mesh, axes)
        if kw.get("quant") is not None:
            kw["scale"] = blocks.fp8_scale(data)
        self.data, self.kw = data, kw
        self.margin_bits = int(margin_bits)
        self.reg = float(params["regParam"])
        self.n, self.mean, self.std, _, _ = blocks.moments(data, **kw)
        self.inv_std = blocks.inverse_or_zero(self.std)
        self.d = self.mean.shape[0]

    def _sweep(self, block_fn, *more):
        s, _, n = blocks.sweep(block_fn, self.data,
                               (self.mean, self.inv_std) + more, **self.kw)
        return s, n

    def loss_grad(self, wmat, icpt):
        s, n = self._sweep(_LOSS_GRAD[self.margin_bits], wmat, icpt)
        loss = s["loss"] / n + 0.5 * self.reg * float(np.sum(wmat * wmat))
        return loss, s["grad"] / n + self.reg * wmat, s["grad0"] / n

    def hessian_vector(self, wmat, icpt, vmat, vcpt):
        s, n = self._sweep(_hessian_vector_block, wmat, icpt, vmat, vcpt)
        return s["hv"] / n + self.reg * vmat, s["hv0"] / n

    def _newton_step(self, wmat, icpt, g, g0, rel_tol, max_cg):
        """Conjugate gradients on ``H s = -g`` from zero. The intercepts'
        common constant is the Hessian's null direction; the gradient has
        no part along it, so the iteration never enters it."""
        sw, s0 = np.zeros_like(g), np.zeros_like(g0)
        rw, r0 = -g, -g0
        pw, p0 = rw, r0
        rr = float(np.sum(rw * rw) + r0 @ r0)
        stop = rel_tol * rel_tol * rr
        for _ in range(max_cg):
            hw, h0 = self.hessian_vector(wmat, icpt, pw, p0)
            curv = float(np.sum(pw * hw) + p0 @ h0)
            if curv <= 0:
                break
            alpha = rr / curv
            sw, s0 = sw + alpha * pw, s0 + alpha * p0
            rw, r0 = rw - alpha * hw, r0 - alpha * h0
            new = float(np.sum(rw * rw) + r0 @ r0)
            if new <= stop:
                break
            pw, p0 = rw + (new / rr) * pw, r0 + (new / rr) * p0
            rr = new
        return sw, s0

    def solve(self, max_iter: int = 30, tol: float = 1e-5, max_cg: int = 40):
        """Standardized-space optimum ``(W, b, objective)``: inexact Newton
        steps (halved while the loss does not fall, as far from the optimum
        a full step can overshoot) until the float32 gradient stops
        shrinking — its rounding floor — which has to lie under ``tol`` of
        the first gradient."""
        wmat, icpt = np.zeros((self.k, self.d)), np.zeros(self.k)
        loss, g, g0 = self.loss_grad(wmat, icpt)
        first = norm = np.sqrt(float(np.sum(g * g) + g0 @ g0))
        best = (wmat, icpt, float(loss))
        for _ in range(max_iter):
            sw, s0 = self._newton_step(wmat, icpt, g, g0, 1e-2, max_cg)
            slope = float(np.sum(g * sw) + g0 @ s0)
            step = 1.0
            trial = self.loss_grad(wmat + sw, icpt + s0)
            # near the optimum the decrease on offer is under what the
            # float32 loss resolves: the full step stands
            while trial[0] > loss + 1e-4 * step * slope and step > 1e-3 \
                    and -step * slope > 1e-6 * abs(loss):
                step *= 0.5
                trial = self.loss_grad(wmat + step * sw, icpt + step * s0)
            wmat, icpt = wmat + step * sw, icpt + step * s0
            loss, g, g0 = trial
            new = np.sqrt(float(np.sum(g * g) + g0 @ g0))
            if new < norm:
                best = (wmat, icpt, float(loss))
            if new > 0.5 * norm and norm <= tol * first:
                return best
            norm = min(norm, new)
        raise RuntimeError("the reference's Newton iteration did not converge")

    # original space <-> standardized space; one flat vector a model
    def to_model(self, wmat, icpt):
        coefs = wmat * self.inv_std[None, :]
        icpts = icpt - coefs @ self.mean
        return np.concatenate([coefs.ravel(), icpts - icpts.mean()])

    def objective_of(self, models: np.ndarray, _intercepts=None):
        """The objective at flat original-space models ``(m, K d + K)``
        (``judge.compare``'s scalar intercept carries nothing)."""
        models = np.asarray(models, np.float64)
        coefs = models[:, :self.k * self.d].reshape(-1, self.k, self.d)
        wmats = coefs * self.std[None, None, :]
        icpts = models[:, self.k * self.d:] + coefs @ self.mean
        s, n = self._sweep(_losses_block, wmats, icpts)
        return s["loss"] / n + 0.5 * self.reg * np.sum(wmats * wmats,
                                                       axis=(1, 2))


def fit(data, params: dict, **kw):
    """``{"coef", "intercept", "objective", "problem"}``: the flat model in
    the original space and the objective it reaches."""
    prob = Problem(data, params, **kw)
    # margins of rounded coefficients have no stationary point: that
    # iteration stalls on the rounding's steps, far above the float32 floor
    wmat, icpt, obj = prob.solve(tol=1e-2 if prob.margin_bits else 1e-5)
    return {"coef": prob.to_model(wmat, icpt), "intercept": 0.0,
            "objective": obj, "problem": prob}
