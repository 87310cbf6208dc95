"""Plain reference of one-vs-rest logistic regression: K INDEPENDENT
L2-penalised binomial problems on one X, as Spark MLlib's ``OneVsRest`` over
``LogisticRegression`` states them (``ml/classification/OneVsRest.scala``:
one binary copy of the base classifier a class over relabelled data;
``standardization=true``, ``fitIntercept=true``, ``elasticNetParam=0``).
Problem k is ``reference/logistic_l2.py``'s statement on the labels
``1[class == k]``:

    f_k(b_k, c_k) = 1/n sum_i [log(1 + exp(m_ik)) - 1[y_i = k] m_ik]
                    + regParam/2 |b_k|^2,
    m_ik = sum_j b_kj (x_ij - mean_j) / std_j + c_k

with ``std`` the unbiased sample deviation; model k's coefficients are
``b_k / std`` and its intercept ``c_k - sum_j b_kj mean_j / std_j``. K
optima, none tied to another: no centring, no common constant.

The class labels are ``perfbench.class_labels`` of the stored X (the
generator's own label is ignored). Each optimum is found by Newton's method
from zero, all K at once so that a step reads X once: margins, losses and
gradients in float32 at ``highest`` in row blocks, sums over blocks and
shards and the K linear solves in float64. The K Hessians only steer the
iteration (default matmul precision): a problem stops where ITS ``highest``
gradient stops shrinking, as ``logistic_l2.Problem.solve`` stops its one.
(That solver's interface takes one label vector and reads X some 25 times
a problem; ``perfbench/tests/test_ovr_cell.py`` holds this module to it
class by class at a small size.)

The model is one flat vector for ``judge.compare``: ``coef`` =
``[B.ravel(), c]`` (K d + K numbers, row k the k-th binary model in the
original space), ``intercept`` = 0.0; the objective is ``sum_k f_k``.
"""

from __future__ import annotations

import numpy as np

from perfbench import class_labels
from perfbench.reference import blocks

CONFIG = "ovr_lr_mnist8m"


def _margins(xf, mean, inv_std, wmat, icpt, margin_bits):
    """``(x_hat, margins (b, K))`` of a block. ``margin_bits`` (a planted
    fault: 0 = none) rounds the coefficient stack to that many mantissa
    bits in the margins alone, which is what a sweep does that hands the
    MXU the coefficients as one bfloat16 piece (7 bits)."""
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
        loss = jnp.sum(jnp.logaddexp(0.0, m) - jnp.where(hit, m, 0.0), axis=0)
        r = jax.nn.sigmoid(m) - hit
        g = jnp.dot(r.T, xh, precision=jax.lax.Precision.HIGHEST)
        return {"loss": loss, "grad": g, "grad0": jnp.sum(r, axis=0)}, {}
    return block


_LOSS_GRAD = {bits: _loss_grad_block(bits) for bits in (0, 7)}


def _hessian_block(xf, yb, mean, inv_std, wmat, icpt):
    """The K Hessians' sums: ``X' S_k X`` (default precision: they steer),
    its border ``X' s_k`` and ``sum s_k``."""
    import jax
    import jax.numpy as jnp
    xh, m = _margins(xf, mean, inv_std, wmat, icpt, 0)
    p = jax.nn.sigmoid(m)
    s = p * (1.0 - p)                                         # (b, K)
    # a model at a time: the only (b, d) temporary is one model's
    h = jax.lax.map(lambda sk: jnp.dot((xh * sk[:, None]).T, xh), s.T)
    return ({"h0": jnp.dot(s.T, xh), "h00": jnp.sum(s, axis=0)}, {"h": h})


def _losses_block(xf, yb, mean, inv_std, wmats, icpts):
    import jax
    import jax.numpy as jnp
    xh = (xf - mean) * inv_std
    m = jnp.einsum("nd,mkd->nmk", xh, wmats,
                   precision=jax.lax.Precision.HIGHEST) + icpts[None]
    hit = _hit(yb, wmats.shape[1])[:, None, :]
    return {"loss": jnp.sum(jnp.logaddexp(0.0, m) - jnp.where(hit, m, 0.0),
                            axis=0)}, {}


class Problem:
    """The K objectives over one dataset: labels and moments once, then any
    number of evaluations, each one read of X for all K. ``kw`` plants the
    control (``quant``) or a fault (``rows_used``, ``shards_used``);
    ``margin_bits`` the one-piece margins."""

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
        return blocks.sweep(block_fn, self.data,
                            (self.mean, self.inv_std) + more, **self.kw)

    def loss_grad(self, wmat, icpt):
        """``(f (K,), grad_B (K, d), grad_c (K,))`` at the K models."""
        s, _, n = self._sweep(_LOSS_GRAD[self.margin_bits], wmat, icpt)
        loss = s["loss"] / n + 0.5 * self.reg * np.sum(wmat * wmat, axis=1)
        return loss, s["grad"] / n + self.reg * wmat, s["grad0"] / n

    def hessians(self, wmat, icpt):
        """``(K, d + 1, d + 1)``: each model's own, bordered by its
        intercept."""
        s, b, n = self._sweep(_hessian_block, wmat, icpt)
        d = self.d
        h = np.empty((self.k, d + 1, d + 1))
        h[:, :d, :d] = b["h"] / n + self.reg * np.eye(d)[None]
        h[:, :d, d] = h[:, d, :d] = s["h0"] / n
        h[:, d, d] = s["h00"] / n
        return h

    def solve(self, max_iter: int = 25, tol: float = 1e-5):
        """Standardized-space optima ``(B, c, f (K,))``: Newton steps, every
        problem its own, until ITS float32 gradient stops shrinking (its
        rounding floor), which has to lie under ``tol`` of its first
        gradient. A problem that has stopped keeps its best point and
        rides along unchanged while the others finish."""
        k, d = self.k, self.d
        wmat, icpt = np.zeros((k, d)), np.zeros(k)
        loss, g, g0 = self.loss_grad(wmat, icpt)
        first = norm = np.sqrt(np.sum(g * g, axis=1) + g0 * g0)
        best_w, best_c, best_f = wmat.copy(), icpt.copy(), loss.copy()
        done = np.zeros(k, bool)
        for _ in range(max_iter):
            h = self.hessians(wmat, icpt)
            step = np.stack([np.linalg.solve(h[j], np.append(g[j], g0[j]))
                             for j in range(k)])
            step[done] = 0.0
            wmat, icpt = wmat - step[:, :d], icpt - step[:, d]
            loss, g, g0 = self.loss_grad(wmat, icpt)
            new = np.sqrt(np.sum(g * g, axis=1) + g0 * g0)
            better = ~done & (new < norm)
            best_w[better], best_c[better] = wmat[better], icpt[better]
            best_f[better] = loss[better]
            done |= (new > 0.5 * norm) & (norm <= tol * first)
            if done.all():
                return best_w, best_c, best_f
            norm = np.where(done, norm, np.minimum(norm, new))
        raise RuntimeError("the reference's Newton iteration did not "
                           f"converge for classes {np.flatnonzero(~done)}")

    # original space <-> standardized space; one flat vector for K models
    def to_model(self, wmat, icpt):
        coefs = wmat * self.inv_std[None, :]
        return np.concatenate([coefs.ravel(), icpt - coefs @ self.mean])

    def objectives_of(self, models: np.ndarray):
        """``(m, K)``: every model's own objective at flat original-space
        model stacks ``(m, K d + K)``."""
        models = np.asarray(models, np.float64)
        coefs = models[:, :self.k * self.d].reshape(-1, self.k, self.d)
        wmats = coefs * self.std[None, None, :]
        icpts = models[:, self.k * self.d:] + coefs @ self.mean
        s, _, n = self._sweep(_losses_block, wmats, icpts)
        return s["loss"] / n + 0.5 * self.reg * np.sum(wmats * wmats, axis=2)

    def objective_of(self, models: np.ndarray, _intercepts=None):
        """``sum_k f_k`` at each stack (``judge.compare``'s scalar
        intercept carries nothing)."""
        return np.sum(self.objectives_of(models), axis=1)


def fit(data, params: dict, **kw):
    """``{"coef", "intercept", "objective", "problem"}``: the flat stack of
    the K models in the original space and the sum of the objectives they
    reach."""
    prob = Problem(data, params, **kw)
    # margins of rounded coefficients have no stationary point: that
    # iteration stalls on the rounding's steps, far above the float32 floor
    wmat, icpt, objs = prob.solve(tol=1e-2 if prob.margin_bits else 1e-5)
    return {"coef": prob.to_model(wmat, icpt), "intercept": 0.0,
            "objective": float(np.sum(objs)), "problem": prob}
