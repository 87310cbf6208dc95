"""Plain reference of binomial logistic regression with an L2 penalty, as
Spark MLlib's ``LogisticRegression`` states it (``standardization=true``,
``fitIntercept=true``, ``elasticNetParam=0``):

    f(b, b0) = 1/n sum_i [log(1 + exp(m_i)) - y_i m_i] + regParam/2 |b|^2,
    m_i = sum_j b_j (x_ij - mean_j) / std_j + b0

with ``std`` the unbiased sample deviation; the model's coefficients are
``b / std`` and its intercept ``b0 - sum_j b_j mean_j / std_j``. The optimum
is found by Newton's method from zero: margins and gradient in float32 at
``highest``, sums over blocks and shards and the linear solve in float64.
The Hessian only steers the iteration (default matmul precision): the point
it stops at is where the ``highest`` gradient vanishes.
"""

from __future__ import annotations

import numpy as np

from perfbench.reference import blocks


def _centered(xf, mean, inv_std):
    return (xf - mean) * inv_std


def _loss_grad_block(xf, yb, mean, inv_std, coef, icpt):
    import jax
    import jax.numpy as jnp
    xh = _centered(xf, mean, inv_std)
    m = jnp.dot(xh, coef, precision=jax.lax.Precision.HIGHEST) + icpt
    loss = jnp.sum(jnp.logaddexp(0.0, m) - yb * m)
    r = jax.nn.sigmoid(m) - yb
    g = jnp.dot(r, xh, precision=jax.lax.Precision.HIGHEST)
    return {"loss": loss, "grad": g, "grad0": jnp.sum(r)}, {}


def _hessian_block(xf, yb, mean, inv_std, coef, icpt):
    import jax
    import jax.numpy as jnp
    xh = _centered(xf, mean, inv_std)
    m = jnp.dot(xh, coef, precision=jax.lax.Precision.HIGHEST) + icpt
    p = jax.nn.sigmoid(m)
    s = p * (1.0 - p)
    xs = xh * s[:, None]
    return ({"h0": jnp.dot(s, xh), "h00": jnp.sum(s)},
            {"h": jnp.dot(xs.T, xh)})


def _losses_block(xf, yb, mean, inv_std, coefs, icpts):
    import jax
    import jax.numpy as jnp
    xh = _centered(xf, mean, inv_std)
    m = jnp.dot(xh, coefs.T, precision=jax.lax.Precision.HIGHEST) + icpts
    return {"loss": jnp.sum(jnp.logaddexp(0.0, m) - yb[:, None] * m,
                            axis=0)}, {}


class Problem:
    """The objective over one dataset: moments once, then any number of
    evaluations. ``kw`` plants the control (``quant``) or a fault
    (``rows_used``, ``shards_used``)."""

    def __init__(self, data, params: dict, **kw):
        if kw.get("quant") is not None:
            kw["scale"] = blocks.fp8_scale(data)
        self.data, self.kw = data, kw
        self.reg = float(params["regParam"])
        self.n, self.mean, self.std, _, _ = blocks.moments(data, **kw)
        self.inv_std = blocks.inverse_or_zero(self.std)

    def _consts(self, *more):
        return (self.mean, self.inv_std) + more

    def loss_grad(self, coef, icpt):
        s, _, n = blocks.sweep(_loss_grad_block, self.data,
                               self._consts(coef, icpt), **self.kw)
        loss = s["loss"] / n + 0.5 * self.reg * float(coef @ coef)
        return loss, s["grad"] / n + self.reg * coef, s["grad0"] / n

    def hessian(self, coef, icpt):
        s, b, n = blocks.sweep(_hessian_block, self.data,
                               self._consts(coef, icpt), **self.kw)
        d = coef.shape[0]
        h = np.empty((d + 1, d + 1))
        h[:d, :d] = b["h"] / n + self.reg * np.eye(d)
        h[:d, d] = h[d, :d] = s["h0"] / n
        h[d, d] = s["h00"] / n
        return h

    def solve(self, max_iter: int = 25, tol: float = 1e-5):
        """Standardized-space optimum ``(coef, icpt, objective)``: Newton
        steps until the float32 gradient stops shrinking (its rounding
        floor), which has to lie under ``tol`` of the first gradient."""
        d = self.mean.shape[0]
        coef, icpt = np.zeros(d), 0.0
        loss, g, g0 = self.loss_grad(coef, icpt)
        first = norm = np.sqrt(float(g @ g) + g0 * g0)
        best = (coef, icpt, float(loss))
        for _ in range(max_iter):
            step = np.linalg.solve(self.hessian(coef, icpt),
                                   np.append(g, g0))
            coef, icpt = coef - step[:d], icpt - step[d]
            loss, g, g0 = self.loss_grad(coef, icpt)
            new = np.sqrt(float(g @ g) + g0 * g0)
            if new < norm:
                best = (coef, icpt, float(loss))
            if new > 0.5 * norm and norm <= tol * first:
                return best
            norm = min(norm, new)
        raise RuntimeError("the reference's Newton iteration did not converge")

    # original space <-> standardized space
    def to_model(self, coef, icpt):
        beta = coef * self.inv_std
        return beta, float(icpt - beta @ self.mean)

    def objective_of(self, betas: np.ndarray, intercepts: np.ndarray):
        """The objective at original-space models ``(k, d)``, ``(k,)``."""
        coefs = np.asarray(betas, np.float64) * self.std[None, :]
        icpts = np.asarray(intercepts, np.float64) + \
            np.asarray(betas, np.float64) @ self.mean
        s, _, n = blocks.sweep(_losses_block, self.data,
                               self._consts(coefs, icpts), **self.kw)
        return s["loss"] / n + 0.5 * self.reg * np.sum(coefs * coefs, axis=1)


def fit(data, params: dict, **kw):
    """``{"coef", "intercept", "objective", "problem"}``: the model in the
    original space and the objective it reaches."""
    prob = Problem(data, params, **kw)
    coef, icpt, obj = prob.solve()
    beta, b0 = prob.to_model(coef, icpt)
    return {"coef": beta, "intercept": b0, "objective": obj, "problem": prob}
