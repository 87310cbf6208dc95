"""Plain reference of the binomial generalised linear model with the logit
link, as Spark MLlib's ``GeneralizedLinearRegression(family="binomial")``
states it (``fitIntercept=true``, ``regParam=0``, unit weights, no offset):
the maximum-likelihood problem itself,

    D(b, b0) = 2 sum_i [log(1 + exp(m_i)) - y_i m_i],   m_i = x_i . b + b0

the binomial deviance of 0/1 labels over the design ``[X | 1]`` — what R's
``glm(family = binomial)`` minimises. Where this departs from MLlib's
description of its own algorithm:

- MLlib finds the optimum by iteratively reweighted least squares; this
  reference runs Newton's method on ``D`` from zero. Both converge to the
  one point where the score ``[X | 1]'(mu - y)`` vanishes; the reference
  reproduces neither IRLS's path (its start ``mu0 = (y + 0.5) / 2``, its
  working response) nor its stopping rule (a relative coefficient change
  under ``tol``): it stops where the float32 score stops shrinking, which
  lies under any ``tol`` a fit is run at.
- Nothing is standardised: an unpenalised optimum does not move under a
  rescaling of the columns (MLlib's WeightedLeastSquares standardises only
  to condition its solve).
- The standard errors are ``sqrt(diag(([X | 1]' S [X | 1])^-1))`` with
  ``S = diag(mu (1 - mu))`` AT the optimum; MLlib reports them at the
  working weights of its last pass, one Newton step short of it.

Margins, score and the information matrix in float32 at ``highest`` in
row blocks; sums over blocks and shards, the linear solves and the inverse
in float64. The Hessian that only steers the iteration is at the default
matmul precision.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench.reference import blocks


def _margins(xf, coef, icpt):
    import jax
    import jax.numpy as jnp
    return jnp.dot(xf, coef, precision=jax.lax.Precision.HIGHEST) + icpt


def _score_block(xf, yb, coef, icpt):
    import jax
    import jax.numpy as jnp
    m = _margins(xf, coef, icpt)
    r = jax.nn.sigmoid(m) - yb
    return {"dev": 2.0 * jnp.sum(jnp.logaddexp(0.0, m) - yb * m),
            "grad": jnp.dot(r, xf, precision=jax.lax.Precision.HIGHEST),
            "grad0": jnp.sum(r)}, {}


def _information(xf, coef, icpt, precision):
    import jax
    import jax.numpy as jnp
    p = jax.nn.sigmoid(_margins(xf, coef, icpt))
    s = p * (1.0 - p)
    return ({"h0": jnp.dot(s, xf, precision=precision), "h00": jnp.sum(s)},
            {"h": jnp.dot((xf * s[:, None]).T, xf, precision=precision)})


def _steering_block(xf, yb, coef, icpt):
    return _information(xf, coef, icpt, None)


def _information_block(xf, yb, coef, icpt):
    import jax
    return _information(xf, coef, icpt, jax.lax.Precision.HIGHEST)


def _deviances_block(xf, yb, coefs, icpts):
    import jax
    import jax.numpy as jnp
    m = jnp.dot(xf, coefs.T, precision=jax.lax.Precision.HIGHEST) + icpts
    return {"dev": 2.0 * jnp.sum(jnp.logaddexp(0.0, m) - yb[:, None] * m,
                                 axis=0)}, {}


class Problem:
    """The deviance over one dataset. ``kw`` plants the control (``quant``)
    or a fault (``rows_used``, ``shards_used``)."""

    def __init__(self, data, params: dict, **kw):
        if params.get("family") != "binomial" or \
                params.get("link", "logit") != "logit" or \
                float(params.get("regParam", 0.0)) != 0.0:
            raise ValueError("this reference states the unpenalised "
                             "binomial / logit model only")
        if kw.get("quant") is not None:
            kw["scale"] = blocks.fp8_scale(data)
        self.data, self.kw = data, kw
        self.d = data[0].shape[1]
        self.optimum = None

    def score(self, coef, icpt):
        s, _, _ = blocks.sweep(_score_block, self.data, (coef, icpt),
                               **self.kw)
        return float(s["dev"]), np.append(s["grad"], s["grad0"])

    def information(self, coef, icpt, block=_information_block):
        """``[X | 1]' S [X | 1]`` at a model, ``(d + 1, d + 1)`` float64."""
        s, b, _ = blocks.sweep(block, self.data, (coef, icpt), **self.kw)
        d = self.d
        h = np.empty((d + 1, d + 1))
        h[:d, :d] = b["h"]
        h[:d, d] = h[d, :d] = s["h0"]
        h[d, d] = s["h00"]
        return h

    def solve(self, max_iter: int = 25, tol: float = 1e-5):
        """``(coef, icpt, deviance)`` where the score vanishes: Newton
        steps until the float32 score stops shrinking (its rounding floor),
        which has to lie under ``tol`` of the first score."""
        coef, icpt = np.zeros(self.d), 0.0
        dev, g = self.score(coef, icpt)
        first = norm = float(np.linalg.norm(g))
        best = (coef, icpt, dev)
        for _ in range(max_iter):
            step = np.linalg.solve(
                self.information(coef, icpt, _steering_block), g)
            coef, icpt = coef - step[:self.d], icpt - step[self.d]
            dev, g = self.score(coef, icpt)
            new = float(np.linalg.norm(g))
            if new < norm:
                best = (coef, icpt, dev)
            if new > 0.5 * norm and norm <= tol * first:
                self.optimum = best
                return best
            norm = min(norm, new)
        raise RuntimeError("the reference's Newton iteration did not converge")

    def objective_of(self, betas: np.ndarray, intercepts: np.ndarray):
        """The deviance at models ``(k, d)``, ``(k,)``."""
        s, _, _ = blocks.sweep(
            _deviances_block, self.data,
            (np.asarray(betas, np.float64),
             np.asarray(intercepts, np.float64)), **self.kw)
        return s["dev"]

    def standard_errors(self) -> np.ndarray:
        """``sqrt(diag(([X | 1]' S [X | 1])^-1))`` at the optimum, in the
        order coefficients, intercept."""
        coef, icpt, _ = self.optimum
        return np.sqrt(np.diag(np.linalg.inv(self.information(coef, icpt))))


def fit(data, params: dict, **kw):
    """``{"coef", "intercept", "objective", "problem"}``: the model and
    the deviance it reaches."""
    t0 = time.perf_counter()
    prob = Problem(data, params, **kw)
    coef, icpt, dev = prob.solve()
    prob.solve_seconds = time.perf_counter() - t0
    return {"coef": coef, "intercept": float(icpt), "objective": dev,
            "problem": prob}
