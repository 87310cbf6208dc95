"""Plain reference of ridge linear regression by the normal equations, as
Spark MLlib's ``WeightedLeastSquares`` states it (the solver
``LinearRegression`` takes at ``solver=auto`` for a squared-error fit with
no L1 share and at most 4,096 features; ``standardization=true``,
``fitIntercept=true``, unit weights):

    f(b, b0) = 1/(2n) sum_i (sum_j b_j x_ij/std_j + b0 - y_i/std_y)^2
               + regParam/std_y * 1/2 |b|^2

with POPULATION deviations (``std^2 = E[x^2] - E[x]^2``: the moments are
divided by n, glmnet's convention) — where ``linreg_enet.py`` has the
unbiased ones (n - 1) of MLlib's quasi-Newton path: the two solvers of one
estimator do not state the same problem, and at 2,000,000 rows they differ
in the seventh digit. The intercept is the closed form ``b0 = ybar/std_y -
sum_j b_j mean_j/std_j``, so the objective is a quadratic in ``b`` alone; a
constant column gets the coefficient 0. The model's coefficients are ``b
std_y / std`` and its intercept ``ybar - coef . mean``.

One sweep gives all the data ever says: the Gramian ``X'X``, ``X'y`` and the
first moments, in float32 at ``highest`` in row blocks on the device and
float64 from there on. The optimum is the closed form: ONE float64 Cholesky
solve of ``(A + l2 I) b = q`` — no iteration whose stopping rule the answer
could depend on.
"""

from __future__ import annotations

import numpy as np

from perfbench.reference import blocks
from perfbench.reference.linreg_enet import _gram_block


class Problem:
    def __init__(self, data, params: dict, **kw):
        if float(params.get("elasticNetParam", 0.0)) != 0.0:
            raise ValueError("the normal-equation reference has no L1 share")
        if kw.get("quant") is not None:
            kw["scale"] = blocks.fp8_scale(data)
        s, b, n = blocks.sweep(_gram_block, data, **kw)
        self.n = n
        self.mean = s["s1"] / n
        cov = b["xx"] / n - np.outer(self.mean, self.mean)
        self.std = np.sqrt(np.maximum(np.diag(cov), 0.0))
        self.inv_std = blocks.inverse_or_zero(self.std)
        self.y_mean = s["y1"] / n
        y_var = max(s["y2"] / n - self.y_mean ** 2, 0.0)
        self.y_std = float(np.sqrt(y_var))
        # the quadratic in standardized space: 1/2 b'Ab - q'b + c
        self.a = cov * np.outer(self.inv_std, self.inv_std)
        self.q = (s["xy"] / n - self.mean * self.y_mean) \
            * self.inv_std / self.y_std
        self.c = 0.5 * y_var / self.y_std ** 2
        self.l2 = float(params["regParam"]) / self.y_std

    def objective_std(self, b):
        return float(0.5 * b @ self.a @ b - self.q @ b + self.c
                     + 0.5 * self.l2 * b @ b)

    def solve(self):
        """The optimum over the columns that vary (a constant column's
        row of ``a`` is zero, its coefficient 0), by Cholesky."""
        live = self.std > 0
        h = self.a[np.ix_(live, live)] + self.l2 * np.eye(int(live.sum()))
        chol = np.linalg.cholesky(h)
        half = np.linalg.solve(chol, self.q[live])
        b = np.zeros_like(self.q)
        b[live] = np.linalg.solve(chol.T, half)
        return b, self.objective_std(b)

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
