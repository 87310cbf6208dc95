"""Plain reference of elastic-net linear regression, as Spark MLlib's
``LinearRegression`` states it (``standardization=true``,
``fitIntercept=true``; glmnet's convention, features AND label standardized):

    f(b) = 1/(2n) sum_i (sum_j b_j (x_ij - mean_j)/std_j - (y_i - ybar)/std_y)^2
           + regParam/std_y (alpha |b|_1 + (1 - alpha)/2 |b|^2)

with unbiased deviations; the model's coefficients are ``b std_y / std`` and
its intercept ``ybar - coef . mean``. The loss is quadratic, so one sweep
gives all the data ever says about it: the Gramian ``X'X``, ``X'y`` and the
first moments, in float32 at ``highest`` on the device and float64 from
there on. The optimum is then found on the host by proximal gradient steps
(ISTA, step 1/L) on that exact quadratic.
"""

from __future__ import annotations

import numpy as np

from perfbench.reference import blocks


def _gram_block(xf, yb):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    return ({"s1": jnp.sum(xf, axis=0), "xy": jnp.dot(yb, xf, precision=hi),
             "y1": jnp.sum(yb), "y2": jnp.sum(yb * yb)},
            {"xx": jnp.dot(xf.T, xf, precision=hi)})


class Problem:
    def __init__(self, data, params: dict, **kw):
        if kw.get("quant") is not None:
            kw["scale"] = blocks.fp8_scale(data)
        s, b, n = blocks.sweep(_gram_block, data, **kw)
        self.n = n
        self.mean = s["s1"] / n
        cov = (b["xx"] - n * np.outer(self.mean, self.mean))
        self.std = np.sqrt(np.maximum(np.diag(cov) / (n - 1), 0.0))
        self.inv_std = blocks.inverse_or_zero(self.std)
        self.y_mean = s["y1"] / n
        self.y_std = float(np.sqrt(max(
            (s["y2"] - n * self.y_mean ** 2) / (n - 1), 0.0)))
        # the quadratic in standardized space: 1/2 b'Ab - q'b + c
        self.a = cov * np.outer(self.inv_std, self.inv_std) / n
        self.q = (s["xy"] - n * self.mean * self.y_mean) \
            * self.inv_std / self.y_std / n
        self.c = 0.5 * (s["y2"] - n * self.y_mean ** 2) / self.y_std ** 2 / n
        reg = float(params["regParam"]) / self.y_std
        alpha = float(params["elasticNetParam"])
        self.l1, self.l2 = alpha * reg, (1.0 - alpha) * reg

    def objective_std(self, b):
        return float(0.5 * b @ self.a @ b - self.q @ b + self.c
                     + self.l1 * np.sum(np.abs(b)) + 0.5 * self.l2 * b @ b)

    def solve(self, max_iter: int = 20000, tol: float = 1e-13):
        d = self.q.shape[0]
        lip = float(np.linalg.eigvalsh(self.a)[-1]) + self.l2
        b = np.zeros(d)
        for _ in range(max_iter):
            u = b - (self.a @ b - self.q + self.l2 * b) / lip
            b_new = np.sign(u) * np.maximum(np.abs(u) - self.l1 / lip, 0.0)
            moved = np.linalg.norm(b_new - b)
            b = b_new
            if moved <= tol * max(np.linalg.norm(b), 1.0):
                return b, self.objective_std(b)
        raise RuntimeError("the reference's proximal iteration did not "
                           "converge")

    def to_model(self, b):
        beta = b * self.inv_std * self.y_std
        return beta, float(self.y_mean - beta @ self.mean)

    def objective_of(self, betas: np.ndarray, intercepts: np.ndarray):
        """The objective at original-space models; the intercept the
        estimator states is the closed form, so only ``betas`` enter."""
        bs = np.asarray(betas, np.float64) * self.std[None, :] / self.y_std
        return np.array([self.objective_std(b) for b in bs])


def fit(data, params: dict, **kw):
    prob = Problem(data, params, **kw)
    b, obj = prob.solve()
    beta, b0 = prob.to_model(b)
    return {"coef": beta, "intercept": b0, "objective": obj, "problem": prob}
