"""Generalized linear regression via distributed IRLS.

Re-design of the reference estimator (ref: ml/regression/
GeneralizedLinearRegression.scala:246 — families/links at :557-990,
IRLS driver at ml/optim/IterativelyReweightedLeastSquares.scala) on the
package's normal aggregation path: the dataset is a device-resident
``InstanceDataset`` (``fit(ds)``; a frame builds one and takes the same
loop), and each IRLS iteration is ONE ``tree_aggregate`` program
(``irls_aggregator``: eta, mu, the working response ``z`` and the working
weights ``omega`` from one sweep of X at storage width, then the weighted
moments of ``(X, z, omega)`` through ``ops/kernels.moment_sums`` — the
Gramian the normal equations of ``LinearRegression`` run) followed by
``WeightedLeastSquares.solve`` on the driver, as the reference's IRLS hands
every reweighted problem to its WeightedLeastSquares. Nothing of X, y or w
comes to the host; the standard errors are the last solve's
``diag_inv_atwa`` and every other summary statistic is a device reduction,
most of them lazy.

Families: gaussian, binomial, poisson, gamma, tweedie(variancePower).
Links: identity, log, logit, inverse, sqrt, probit, cloglog, power(p).
The offset column (frame path) rides as a fourth row-sharded vector.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.linalg.vectors import DenseVector, Vectors
from cycloneml_tpu.ml.base import PredictionModel, Predictor
from cycloneml_tpu.ml.shared import (
    HasAggregationDepth, HasFitIntercept, HasLabelCol, HasMaxIter,
    HasRegParam, HasSolver, HasTol,
)
from cycloneml_tpu.ml.util_io import MLReadable, MLWritable, load_arrays, save_arrays
from cycloneml_tpu.observe import tracing
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

_EPS = 1e-16


# -- families (ref GeneralizedLinearRegression.scala:557-848) -----------------

class Family:
    """Variance/deviance structure of the response distribution.

    All callables take/return jnp arrays so the IRLS aggregation jits.
    ``unit_deviance`` is the per-instance term; ``deviance`` sums w·unit.
    """

    name = "family"
    default_link = "identity"

    def initialize(self, y, w):
        raise NotImplementedError

    def variance(self, mu):
        raise NotImplementedError

    def unit_deviance(self, y, mu):
        raise NotImplementedError

    def deviance(self, y, mu, w):
        import jax.numpy as jnp
        return jnp.sum(w * self.unit_deviance(y, mu))

    def aic(self, rows: dict, n: int, w_sum: float, deviance: float,
            rank: int) -> float:
        """Akaike's criterion from the device-resident ``rows`` (``y``,
        ``mu``, ``w`` and ``valid``, the mask of real rows): jnp
        reductions, only scalars come to the driver."""
        return float("nan")

    def clean_mu(self, mu):
        return mu

    def label_floor(self):
        """``(bound, strict)`` the labels must stay above (Tweedie
        enforces the reference's require()s), or None: no check, and no
        reduction over the labels is launched for it."""
        return None

    # value identity: the cached aggregator factories key on the family
    # and the link, so every fit of one configuration asks tree_aggregate
    # for the same function and gets the same program
    def _key(self):
        return (type(self).__name__, getattr(self, "variance_power", None))

    def __eq__(self, other):
        return isinstance(other, Family) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())


class Tweedie(Family):
    def __init__(self, variance_power: float):
        self.variance_power = float(variance_power)
        self.name = "tweedie"
        self.default_link = "log" if variance_power != 0 else "identity"

    def initialize(self, y, w):
        import jax.numpy as jnp
        if self.variance_power >= 1.0:
            return jnp.maximum(y, 0.1)
        return y

    def label_floor(self):
        # label-domain validation (ref Tweedie.initialize:624-632): the
        # compound-Poisson band allows y=0; p>=2 needs strictly positive
        # labels — without this, y=0 at p>2 silently NaNs the deviance.
        # (initialize runs inside jit, so the check is a reduction of its own)
        p = self.variance_power
        return None if p < 1.0 else (0.0, p >= 2.0)

    def variance(self, mu):
        import jax.numpy as jnp
        return jnp.power(jnp.maximum(mu, _EPS), self.variance_power)

    def unit_deviance(self, y, mu):
        # ref :646 — 2[y(y1^{1-p}−mu^{1-p})/(1−p) − (y^{2-p}−mu^{2-p})/(2−p)];
        # the p∈{0,1,2} limit cases are the Gaussian/Poisson/Gamma
        # subclasses. y floors to delta ONLY in the first term and only
        # for compound-Poisson 1<=p<2 (the reference's deviance:648 — the
        # second term must keep RAW y so a y=0 row contributes its full
        # mu^{2-p}/(2-p) deviance, not a delta-perturbed ~0)
        import jax.numpy as jnp
        p = self.variance_power
        y1 = jnp.maximum(y, 0.1) if 1.0 <= p < 2.0 else y
        return 2.0 * (y * (jnp.power(y1, 1 - p) - jnp.power(mu, 1 - p)) / (1 - p)
                      - (jnp.power(y, 2 - p) - jnp.power(mu, 2 - p)) / (2 - p))

    def clean_mu(self, mu):
        import jax.numpy as jnp
        return jnp.maximum(mu, _EPS) if self.variance_power >= 1 else mu


class Gaussian(Tweedie):
    def __init__(self):
        super().__init__(0.0)
        self.name = "gaussian"
        self.default_link = "identity"

    def initialize(self, y, w):
        return y

    def variance(self, mu):
        import jax.numpy as jnp
        return jnp.ones_like(mu)

    def unit_deviance(self, y, mu):
        return (y - mu) ** 2

    def aic(self, rows, n, w_sum, deviance, rank):
        # ref :704-711 (+ summary's 2·rank): numInstances (row COUNT, not
        # weight sum) scales the log-likelihood term, and Σlog w subtracts
        # — R's weighted-gaussian aic
        import jax.numpy as jnp
        log_w = jnp.sum(jnp.where(
            rows["valid"], jnp.log(jnp.maximum(rows["w"], _EPS)), 0.0))
        return (n * (math.log(deviance / n * 2.0 * math.pi) + 1.0) + 2.0
                - float(log_w) + 2.0 * rank)

    def clean_mu(self, mu):
        return mu


class Binomial(Family):
    name = "binomial"
    default_link = "logit"

    def initialize(self, y, w):
        return (w * y + 0.5) / (w + 1.0)

    def variance(self, mu):
        return mu * (1.0 - mu)

    def unit_deviance(self, y, mu):
        import jax.numpy as jnp

        def ylogy(yy, m):
            return jnp.where(yy > 0, yy * jnp.log(jnp.maximum(yy / m, _EPS)), 0.0)
        return 2.0 * (ylogy(y, mu) + ylogy(1.0 - y, 1.0 - mu))

    def aic(self, rows, n, w_sum, deviance, rank):
        # ref :745-759 — wt=round(w) trials, but successes round y*w with
        # the RAW weight (y=0.7, w=0.7: round(0.49)=0 successes of 1
        # trial, not round(0.7·1)=1)
        import jax.numpy as jnp
        from jax.scipy import stats as jsps
        y, mu, w = rows["y"], rows["mu"], rows["w"]
        # Java math.round = floor(x + 0.5) (half-UP), not numpy's
        # half-even — they diverge on exact .5 trials/successes
        wt = jnp.floor(w + 0.5)
        ok = rows["valid"] & (wt > 0)
        ll = jsps.binom.logpmf(jnp.floor(y * w + 0.5),
                               jnp.where(ok, wt, 1.0), self.clean_mu(mu))
        return -2.0 * float(jnp.sum(jnp.where(ok, ll, 0.0))) + 2.0 * rank

    def clean_mu(self, mu):
        # the clip has to survive the accumulator's width: 1 - 1e-16 is
        # 1.0 in float32, and mu = 1 makes the working weight of a
        # saturated row 1e16 instead of ~0
        import jax.numpy as jnp
        eps = max(_EPS, float(jnp.finfo(mu.dtype).eps) / 2)
        return jnp.clip(mu, eps, 1.0 - eps)


class Poisson(Tweedie):
    def __init__(self):
        super().__init__(1.0)
        self.name = "poisson"
        self.default_link = "log"

    def initialize(self, y, w):
        import jax.numpy as jnp
        return jnp.maximum(y, 0.1)

    def variance(self, mu):
        return mu

    def unit_deviance(self, y, mu):
        import jax.numpy as jnp
        t = jnp.where(y > 0, y * jnp.log(jnp.maximum(y, _EPS) / mu), 0.0)
        return 2.0 * (t - (y - mu))

    def aic(self, rows, n, w_sum, deviance, rank):
        import jax.numpy as jnp
        from jax.scipy import stats as jsps
        ll = rows["w"] * jsps.poisson.logpmf(jnp.round(rows["y"]), rows["mu"])
        return -2.0 * float(jnp.sum(jnp.where(rows["valid"], ll, 0.0))) \
            + 2.0 * rank


class Gamma(Tweedie):
    def __init__(self):
        super().__init__(2.0)
        self.name = "gamma"
        self.default_link = "inverse"

    def initialize(self, y, w):
        import jax.numpy as jnp
        return jnp.maximum(y, 0.1)

    def variance(self, mu):
        return mu * mu

    def unit_deviance(self, y, mu):
        import jax.numpy as jnp
        return -2.0 * (jnp.log(jnp.maximum(y, _EPS) / mu) - (y - mu) / mu)

    def aic(self, rows, n, w_sum, deviance, rank):
        import jax.numpy as jnp
        from jax.scipy import stats as jsps
        disp = deviance / w_sum
        valid = rows["valid"]
        ll = rows["w"] * jsps.gamma.logpdf(
            jnp.where(valid, rows["y"], 1.0), 1.0 / disp,
            scale=rows["mu"] * disp)
        # +2 for the estimated dispersion
        return -2.0 * float(jnp.sum(jnp.where(valid, ll, 0.0))) \
            + 2.0 * rank + 2.0


def _make_family(name: str, variance_power: float) -> Family:
    name = name.lower()
    if name == "gaussian":
        return Gaussian()
    if name == "binomial":
        return Binomial()
    if name == "poisson":
        return Poisson()
    if name == "gamma":
        return Gamma()
    if name == "tweedie":
        if variance_power in (0.0, 1.0, 2.0):
            return {0.0: Gaussian(), 1.0: Poisson(), 2.0: Gamma()}[variance_power]
        if variance_power < 0 or 0 < variance_power < 1:
            raise ValueError("variancePower must be 0 or >= 1")
        return Tweedie(variance_power)
    raise ValueError(f"unknown family {name}")


# -- links (ref :850-990) -----------------------------------------------------

class Link:
    name = "link"

    def link(self, mu):
        raise NotImplementedError

    def unlink(self, eta):
        raise NotImplementedError

    def deriv(self, mu):
        """d eta / d mu."""
        raise NotImplementedError

    def _key(self):
        return (type(self).__name__, getattr(self, "p", None))

    def __eq__(self, other):
        return isinstance(other, Link) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())


class Identity(Link):
    name = "identity"

    def link(self, mu):
        return mu

    def unlink(self, eta):
        return eta

    def deriv(self, mu):
        import jax.numpy as jnp
        return jnp.ones_like(mu)


class Log(Link):
    name = "log"

    def link(self, mu):
        import jax.numpy as jnp
        return jnp.log(jnp.maximum(mu, _EPS))

    def unlink(self, eta):
        import jax.numpy as jnp
        return jnp.exp(eta)

    def deriv(self, mu):
        return 1.0 / _clip_pos(mu)


class Logit(Link):
    name = "logit"

    def link(self, mu):
        import jax.numpy as jnp
        return jnp.log(mu / (1.0 - mu))

    def unlink(self, eta):
        import jax

        return jax.nn.sigmoid(eta)

    def deriv(self, mu):
        return 1.0 / _clip_pos(mu * (1.0 - mu))


class Inverse(Link):
    name = "inverse"

    def link(self, mu):
        return 1.0 / _clip_pos(mu)

    def unlink(self, eta):
        return 1.0 / _clip_pos(eta)

    def deriv(self, mu):
        return -1.0 / _clip_pos(mu * mu)


class Sqrt(Link):
    name = "sqrt"

    def link(self, mu):
        import jax.numpy as jnp
        return jnp.sqrt(jnp.maximum(mu, 0.0))

    def unlink(self, eta):
        return eta * eta

    def deriv(self, mu):
        import jax.numpy as jnp
        return 0.5 / jnp.sqrt(_clip_pos(mu))


class Probit(Link):
    name = "probit"

    def link(self, mu):
        from jax.scipy.stats import norm
        import jax.scipy.special as jsp
        return jsp.ndtri(mu) if hasattr(jsp, "ndtri") else norm.ppf(mu)

    def unlink(self, eta):
        from jax.scipy.stats import norm
        return norm.cdf(eta)

    def deriv(self, mu):
        from jax.scipy.stats import norm
        import jax.numpy as jnp
        import jax.scipy.special as jsp
        q = jsp.ndtri(mu) if hasattr(jsp, "ndtri") else norm.ppf(mu)
        return 1.0 / jnp.maximum(jnp.exp(norm.logpdf(q)), _EPS)


class CLogLog(Link):
    name = "cloglog"

    def link(self, mu):
        import jax.numpy as jnp
        return jnp.log(-jnp.log(jnp.maximum(1.0 - mu, _EPS)))

    def unlink(self, eta):
        import jax.numpy as jnp
        return 1.0 - jnp.exp(-jnp.exp(eta))

    def deriv(self, mu):
        import jax.numpy as jnp
        om = _clip_pos(1.0 - mu)
        return 1.0 / _clip_pos(-om * jnp.log(om))


class Power(Link):
    def __init__(self, p: float):
        self.p = float(p)
        self.name = f"power({p})"

    def link(self, mu):
        import jax.numpy as jnp
        if self.p == 0.0:
            return jnp.log(_clip_pos(mu))
        return jnp.power(_clip_pos(mu), self.p)

    def unlink(self, eta):
        import jax.numpy as jnp
        if self.p == 0.0:
            return jnp.exp(eta)
        return jnp.power(_clip_pos(eta), 1.0 / self.p)

    def deriv(self, mu):
        import jax.numpy as jnp
        if self.p == 0.0:
            return 1.0 / _clip_pos(mu)
        return self.p * jnp.power(_clip_pos(mu), self.p - 1.0)


def _clip_pos(x):
    import jax.numpy as jnp
    return jnp.where(jnp.abs(x) > _EPS, x, jnp.sign(x) * _EPS + (x == 0) * _EPS)


def _make_link(name: str) -> Link:
    table = {"identity": Identity, "log": Log, "logit": Logit,
             "inverse": Inverse, "sqrt": Sqrt, "probit": Probit,
             "cloglog": CLogLog}
    name = name.lower()
    if name not in table:
        raise ValueError(f"unknown link {name}")
    return table[name]()


_SUPPORTED = {  # ref FamilyAndLink supported combos :532
    "gaussian": {"identity", "log", "inverse"},
    "binomial": {"logit", "probit", "cloglog"},
    "poisson": {"log", "identity", "sqrt"},
    "gamma": {"inverse", "identity", "log"},
}


class _GLRParams(HasMaxIter, HasRegParam, HasTol, HasFitIntercept,
                 HasSolver, HasAggregationDepth, HasLabelCol):
    def _declare_glr_params(self):
        self._p_label_col()
        self._p_max_iter(25)
        self._p_reg_param(0.0)
        self._p_tol(1e-6)
        self._p_fit_intercept(True)
        self._p_solver(["irls"], "irls")
        self._p_aggregation_depth(2)
        from cycloneml_tpu.ml.param import ParamValidators as V
        self._param("family", "response distribution",
                    V.in_array(["gaussian", "binomial", "poisson", "gamma",
                                "tweedie"]), default="gaussian")
        self._param("link", "link function name", default="")
        self._param("variancePower", "tweedie variance power", default=0.0)
        self._param("linkPower", "tweedie link power", default=float("nan"))
        self._param("offsetCol", "offset column", default="")
        self._param("linkPredictionCol", "eta output column", default="")


class GeneralizedLinearRegression(Predictor, _GLRParams, MLWritable, MLReadable):
    """IRLS-trained GLM (ref GeneralizedLinearRegression.scala:246)."""

    MAX_FEATURES = 4096  # ref: WeightedLeastSquares.MAX_NUM_FEATURES

    def __init__(self, uid=None, **kwargs):
        super().__init__(uid)
        self._declare_glr_params()
        for k, v in kwargs.items():
            self.set(k, v)

    def set_family(self, v):
        return self.set("family", v)

    def set_link(self, v):
        return self.set("link", v)

    def set_variance_power(self, v):
        return self.set("variancePower", v)

    def set_link_power(self, v):
        return self.set("linkPower", v)

    def set_reg_param(self, v):
        return self.set("regParam", v)

    def set_max_iter(self, v):
        return self.set("maxIter", v)

    def set_offset_col(self, v):
        return self.set("offsetCol", v)

    def _family_link(self):
        fam = _make_family(self.get("family"), self.get("variancePower"))
        link_name = self.get("link")
        if self.get("family") == "tweedie":
            lp = self.get("linkPower")
            if link_name:
                raise ValueError("use linkPower with the tweedie family")
            if lp != lp:  # nan → canonical 1 - variancePower... ref default log-ish
                lp = 1.0 - self.get("variancePower")
            link = {1.0: Identity(), 0.0: Log(), -1.0: Inverse(), 0.5: Sqrt()}.get(
                lp, Power(lp))
        elif link_name:
            if link_name not in _SUPPORTED.get(fam.name, set()):
                raise ValueError(f"link {link_name} unsupported for {fam.name}")
            link = _make_link(link_name)
        else:
            link = _make_link(fam.default_link)
        return fam, link

    def _fit(self, frame: MLFrame) -> "GeneralizedLinearRegressionModel":
        """A frame becomes the device-resident dataset every estimator
        trains on (cached on the frame) and takes the loop ``fit(ds)``
        takes; the offset column is placed beside it as a fourth
        row-sharded vector."""
        ds = frame.to_instance_dataset(
            self.get("featuresCol"), self.get("labelCol"),
            self.get("weightCol") or None)
        ocol = self.get("offsetCol")
        offset = None
        if ocol:
            if isinstance(frame, InstanceDataset):
                raise ValueError("offsetCol names a frame column; an "
                                 "InstanceDataset carries none")
            ofs = np.zeros(ds.y.shape[0], dtype=str(ds.y.dtype))
            ofs[ds.valid_indices()] = np.asarray(frame[ocol])
            offset = ds.ctx.mesh_runtime.device_put_sharded_rows(ofs)
        return self._fit_dataset(ds, offset)

    def _fit_dataset(self, ds: InstanceDataset, offset=None
                     ) -> "GeneralizedLinearRegressionModel":
        """IRLS over a device-resident dataset: per iteration one
        aggregation program (dispatch + readback of the ``(d+1)``-sized
        moments) and one ``WeightedLeastSquares.solve`` on the driver —
        exactly what the reference's IterativelyReweightedLeastSquares
        does with its reweighted instances — then one margin-only pass for
        the returned model's deviance."""
        import jax
        import jax.numpy as jnp
        from cycloneml_tpu.ml.optim.wls import AUTO, WeightedLeastSquares
        from cycloneml_tpu.ops.kernels import (mean_mxu_passes,
                                               stored_feature_major)
        from cycloneml_tpu.parallel import collectives

        with tracing.span("phase", "fit.prepare"):
            fam, link = self._family_link()
            d = ds.n_features
            if d > self.MAX_FEATURES:
                raise ValueError(
                    f"GLM supports at most {self.MAX_FEATURES} features")
            fit_icpt = self.get("fitIntercept")
            tol = self.get("tol")
            rt = ds.ctx.mesh_runtime
            rows = (ds.x, ds.y, ds.w) + (() if offset is None else (offset,))
            _check_labels(fam, ds)
            irls = collectives.tree_aggregate(
                irls_aggregator(fam, link, stored_feature_major(ds.x),
                                offset is not None), rt, *rows)
            # ref IterativelyReweightedLeastSquares.scala: every reweighted
            # problem goes to WeightedLeastSquares(fitIntercept, regParam,
            # elasticNetParam = 0, standardizeFeatures = false,
            # standardizeLabel = false) — regParam is the plain
            # 0.5·regParam·|β|² of the estimator's documentation
            wls = WeightedLeastSquares(
                fit_intercept=fit_icpt, reg_param=self.get("regParam"),
                elastic_net_param=0.0, standardize_features=False,
                standardize_label=False, solver_type=AUTO)

        n_dispatches = 0
        mxu_passes = []

        def dispatch(program, name, coef, icpt, first):
            """One launch and its one readback; ``coef``, the intercept
            and the first-pass flag travel as ONE replicated vector."""
            nonlocal n_dispatches
            n_dispatches += 1
            params = jnp.asarray(np.concatenate([coef, [icpt, first]]))
            with tracing.span("dispatch", f"irls.{name}", passes=1) as dsp:
                out_dev = program(*rows, params)    # 'collective' inside
                with tracing.span("transfer", "irls.readback") as tsp:
                    out = jax.device_get(out_dev)
                    tsp.annotate_bytes(out)
                if name == "pass":
                    # which form of the Gramian this pass's working
                    # weights took: chosen on the device, so known now
                    # (None: XLA's contraction)
                    mxu_passes.append(
                        mean_mxu_passes(out, rt.data_parallelism))
                    dsp.annotate(mxu_passes=mxu_passes[-1])
            if hasattr(ds.ctx, "record_step"):
                ds.ctx.record_step({"irls_passes": 1.0})
            return out

        coef, icpt = np.zeros(d), 0.0
        history, wm = [], None
        for it in range(max(self.get("maxIter"), 1)):
            with tracing.span("phase", "irls.iteration", iteration=it) as isp:
                out = dispatch(irls, "pass", coef, icpt, float(it == 0))
                with tracing.span("phase", "fit.solve") as ssp:
                    wm = wls.solve(out, d)
                    ssp.annotate(system=wm.system)
                old = np.append(coef, icpt)
                coef, icpt = wm.coefficients, float(wm.intercept)
                # ref IRLS convergence (IterativelyReweightedLeastSquares
                # .scala:105-114): the largest ABSOLUTE change of a
                # coefficient or of the intercept
                delta = float(np.max(np.abs(np.append(coef, icpt) - old)))
                history.append(float(out["dev"]))
                isp.annotate(delta=delta, deviance=history[-1])
            if it > 0 and delta < tol:
                break

        with tracing.span("phase", "fit.finish"):
            # the deviance the passes report is the PREVIOUS model's: the
            # returned one gets a margin-only pass of its own
            last = dispatch(
                collectives.tree_aggregate(
                    deviance_aggregator(fam, link, offset is not None),
                    rt, *rows), "deviance", coef, icpt, 0.0)
            model = GeneralizedLinearRegressionModel(coef, icpt, uid=self.uid)
            self._copy_values(model)
            model._set_parent(self)
            model.summary = GLMTrainingSummary(
                model, ds, offset, fam, link, wm, fit_icpt,
                deviance=float(last["dev"]), pearson=float(last["pearson"]),
                deviance_history=history, total_dispatches=n_dispatches,
                mxu_passes=mxu_passes)
            return model


# -- the device programs of a fit ---------------------------------------------

def _working_point(fam: Family, link: Link, eta, y, w, ofs, mu=None):
    """``(mu, z, omega)`` at the linear predictor ``eta`` (offset
    included): the mean, the working response (offset taken out again) and
    the working weight of every row (ref FamilyAndLink.reweightFunc).
    ``mu`` is the mean where the caller HAS it (the starting point: the
    family's ``mu0``, of which ``eta`` is the link) — ``unlink(link(mu0))``
    is the identity on paper and an ulp or more off in float32, which
    gives the rows of one ``mu0`` several working weights."""
    import jax.numpy as jnp
    if mu is None:
        mu = fam.clean_mu(link.unlink(eta))
    g = link.deriv(mu)
    z = (eta - ofs) + (y - mu) * g
    omega = w / jnp.maximum(g * g * fam.variance(mu), _EPS)
    # a row that carries no weight (padding, w = 0) must not carry a NaN
    # into a sum either: 0 · NaN is NaN
    return mu, jnp.where(omega > 0, z, 0.0), omega


def _starting_mu(fam: Family, y, w):
    """The mean IRLS starts from: the family's ``mu0`` (ref
    FamilyAndLink.initialize; R's ``mustart``)."""
    import jax.numpy as jnp
    return fam.clean_mu(fam.initialize(y, jnp.maximum(w, _EPS)))


def _starting_eta(fam: Family, link: Link, y, w):
    """The linear predictor IRLS starts from: the link of ``mu0``."""
    return link.link(_starting_mu(fam, y, w))


def _margins(x, params, ofs, dtype):
    from cycloneml_tpu.ops.kernels import storage_matvec
    d = x.shape[1]
    return storage_matvec(x, params[:d]).astype(dtype) + params[d] + ofs


@functools.lru_cache(maxsize=None)
def irls_aggregator(fam: Family, link: Link, feature_major: bool = False,
                    has_offset: bool = False):
    """One IRLS pass over a shard, ``irls_pass(x, y, w, [offset], params)``
    with ``params = [beta | intercept | first]``: the linear predictor from
    one sweep of X at storage width (the FIRST pass starts from the
    family's ``mu0`` instead and skips the sweep), the working point, then
    the weighted moments ``{w_sum, b_sum, bb_sum, a_sum, ab_sum, aa_sum}``
    of ``(X, z, omega)`` by ``ops/kernels.moment_sums`` — no ``(rows, d)``
    value is ever formed — and the deviance at the pass's ``beta``. Cached
    by value of family and link, so every fit of one configuration asks
    ``tree_aggregate`` for the same function and gets the same program
    (``jit_tree_aggregate__irls_pass`` in a device capture);
    ``feature_major`` is the caller's observation of how X is stored."""
    def irls_pass(x, y, w, *rest):
        import jax
        from cycloneml_tpu.ops.kernels import moment_sums
        ofs = rest[0] if has_offset else 0.0
        params = rest[-1]

        def start():
            # the working point AT mu0: a binomial-logit fit of 0/1 labels
            # and equal weights then has ONE working weight, which the
            # moment pass observes (one MXU pass for three)
            mu = _starting_mu(fam, y, w)
            return _working_point(fam, link, link.link(mu), y, w, ofs, mu)

        mu, z, omega = jax.lax.cond(
            params[-1] > 0, start,
            lambda: _working_point(
                fam, link, _margins(x, params, ofs, y.dtype), y, w, ofs))
        out = dict(moment_sums(x, z, omega, feature_major=feature_major))
        out["dev"] = fam.deviance(y, mu, w)
        return out
    return irls_pass


@functools.lru_cache(maxsize=None)
def deviance_aggregator(fam: Family, link: Link, has_offset: bool = False):
    """The margin-only pass for a given model (same arguments as the IRLS
    pass): its deviance and its Pearson chi-square."""
    def irls_deviance(x, y, w, *rest):
        import jax.numpy as jnp
        ofs = rest[0] if has_offset else 0.0
        mu = fam.clean_mu(link.unlink(_margins(x, rest[-1], ofs, y.dtype)))
        return {"dev": fam.deviance(y, mu, w),
                "pearson": jnp.sum(w * (y - mu) ** 2
                                   / jnp.maximum(fam.variance(mu), _EPS))}
    return irls_deviance


@functools.lru_cache(maxsize=None)
def _label_min():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda y, w: jnp.min(jnp.where(w > 0, y, jnp.inf)))


def _check_labels(fam: Family, ds: InstanceDataset) -> None:
    """The family's label domain, by one device reduction over the rows
    that carry weight (families with no bound launch nothing)."""
    floor = fam.label_floor()
    if floor is None:
        return
    bound, strict = floor
    low = float(_label_min()(ds.y, ds.w))
    if low < bound or (strict and low <= bound):
        raise ValueError(
            f"{fam.name}({fam.variance_power}) labels must be "
            f"{'positive' if strict else 'non-negative'}")


@functools.lru_cache(maxsize=None)
def _row_program(fam: Family, link: Link):
    """``(x, y, offset, params) -> mu`` row by row, at the labels' width
    (the summary's lazy statistics start from it)."""
    import jax
    return jax.jit(lambda x, y, ofs, params: fam.clean_mu(
        link.unlink(_margins(x, params, ofs, y.dtype))))


@functools.lru_cache(maxsize=None)
def _null_program(fam: Family, link: Link, fit_intercept: bool,
                  has_offset: bool):
    """``(y, w, offset) -> deviance`` of the null model (ref nullDeviance):
    no intercept — eta is the offset alone; an intercept and no offset —
    the closed form, mu = the weighted mean of the labels; both — the
    intercept-only refit, scalar IRLS on the device."""
    import jax
    import jax.numpy as jnp

    def intercept_only(y, w, ofs):
        def step(state):
            k, icpt, eta, _ = state
            _, z, omega = _working_point(fam, link, eta, y, w, ofs)
            new = jnp.sum(omega * z) / jnp.maximum(jnp.sum(omega), _EPS)
            done = jnp.abs(new - icpt) < 1e-10 * jnp.maximum(jnp.abs(icpt),
                                                             1.0)
            return k + 1, new, new + ofs, done

        _, icpt, _, _ = jax.lax.while_loop(
            lambda s: (s[0] < 50) & ~s[3], step,
            (0, jnp.zeros((), y.dtype), _starting_eta(fam, link, y, w),
             False))
        return link.unlink(icpt + ofs)

    def null_model_deviance(y, w, ofs):
        if not fit_intercept:
            mu = link.unlink(ofs + jnp.zeros_like(y))
        elif not has_offset:
            mu = jnp.sum(w * y) / jnp.sum(w) + jnp.zeros_like(y)
        else:
            mu = intercept_only(y, w, ofs)
        return fam.deviance(y, fam.clean_mu(mu), w)
    return jax.jit(null_model_deviance)


class GeneralizedLinearRegressionModel(PredictionModel, _GLRParams,
                                       MLWritable, MLReadable):
    def __init__(self, coefficients: Optional[np.ndarray] = None,
                 intercept: float = 0.0, uid=None):
        super().__init__(uid)
        self._declare_glr_params()
        self._coef = np.asarray(coefficients) if coefficients is not None else None
        self._icpt = float(intercept)
        self.summary: Optional[GLMTrainingSummary] = None

    @property
    def coefficients(self) -> DenseVector:
        return Vectors.dense(self._coef)

    @property
    def intercept(self) -> float:
        return self._icpt

    @property
    def num_features(self) -> int:
        return self._coef.shape[0]

    def _predict_batch(self, x: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        fam, link = GeneralizedLinearRegression._family_link(self)
        eta = x @ self._coef + self._icpt
        return np.asarray(link.unlink(jnp.asarray(eta)))

    def predict_link(self, x: np.ndarray) -> np.ndarray:
        return x @ self._coef + self._icpt

    def _transform(self, frame: MLFrame) -> MLFrame:
        # offset-trained models add the offset to eta at predict time
        # (ref GeneralizedLinearRegressionModel.predict w/ offset)
        import jax.numpy as jnp
        x = frame[self.get("featuresCol")]
        if x.ndim == 1:
            x = x[:, None]
        eta = x @ self._coef + self._icpt
        ocol = self.get("offsetCol")
        if ocol:
            eta = eta + np.asarray(frame[ocol], dtype=np.float64)
        fam, link = GeneralizedLinearRegression._family_link(self)
        out = frame.with_column(self.get("predictionCol"),
                                np.asarray(link.unlink(jnp.asarray(eta))))
        lcol = self.get("linkPredictionCol")
        if lcol:
            out = out.with_column(lcol, eta)
        return out

    def _save_data(self, path: str) -> None:
        save_arrays(path, coef=self._coef, icpt=np.array(self._icpt))

    def _load_data(self, path: str, meta) -> None:
        arrs = load_arrays(path)
        self._coef = arrs["coef"]
        self._icpt = float(arrs["icpt"])


class GLMTrainingSummary:
    """ref GeneralizedLinearRegressionTrainingSummary. What the fit
    already paid for is here when the fit returns — the returned model's
    ``deviance`` (one margin-only pass), ``dispersion`` (its Pearson
    chi-square from the same pass), the counters and ``deviance_history``
    (the deviance each IRLS pass saw: the previous model's). Everything
    else is lazy, as in the reference: ``coefficient_standard_errors`` /
    ``t_values`` / ``p_values`` take the LAST solve's ``diag_inv_atwa``
    (the reference's ``diagInvAtWA``; LAPACK ``potri`` on the driver's
    factor, no second Gramian), ``null_deviance`` / ``aic`` /
    ``residuals`` / ``prediction_mean`` are device reductions or maps over
    the dataset the summary keeps — nothing row-sized is held on the host.

    ``total_passes`` counts weighted-Gramian passes over X (one an
    iteration), ``total_dispatches`` every launch of the fit (the passes
    and the deviance pass), ``mxu_passes`` the MXU passes each of those
    Gramians took for its working weights (1: one live value, 3: more;
    None where XLA's contraction ran — ``kernels.moment_sums``)."""

    def __init__(self, model, ds, offset, fam: Family, link: Link,
                 wls_model, fit_intercept: bool, *, deviance: float,
                 pearson: float, deviance_history, total_dispatches: int,
                 mxu_passes=()):
        self._model, self._ds, self._offset = model, ds, offset
        self._fam, self._link, self._wls_model = fam, link, wls_model
        self._fit_intercept = fit_intercept
        self.family, self.link = fam.name, link.name
        self.deviance = deviance
        self.deviance_history = list(deviance_history)
        self.num_iterations = self.total_passes = len(self.deviance_history)
        self.total_dispatches = total_dispatches
        self.mxu_passes = list(mxu_passes)
        n = ds.n_rows
        self.rank = ds.n_features + (1 if fit_intercept else 0)
        self.degrees_of_freedom = n - 1 if fit_intercept else n
        self.residual_degree_of_freedom = n - self.rank
        estimated = fam.name in ("gaussian", "gamma", "tweedie")
        self.dispersion = pearson / max(self.residual_degree_of_freedom, 1) \
            if estimated else 1.0

    # -- from the last solve -------------------------------------------
    @functools.cached_property
    def coefficient_standard_errors(self) -> np.ndarray:
        """sqrt(diag((AᵀΩA)⁻¹)·φ) at the last pass's working weights, in
        the order coefficients, intercept; NaN where the last solve has
        no inverse to offer (a singular system went to quasi-Newton)."""
        with tracing.span("phase", "fit.solve", part="diag_inv_atwa"):
            # the solver's lazy half: potri over the last factor
            diag = np.asarray(self._wls_model.diag_inv_atwa, np.float64)
        if diag.shape != (self.rank,):
            return np.full(self.rank, float("nan"))
        return np.sqrt(np.clip(diag * self.dispersion, 0, None))

    @functools.cached_property
    def t_values(self) -> np.ndarray:
        coefs = np.append(self._model._coef, self._model._icpt) \
            if self._fit_intercept else self._model._coef
        return coefs / np.maximum(self.coefficient_standard_errors, _EPS)

    @functools.cached_property
    def p_values(self) -> np.ndarray:
        from scipy import stats as sps
        if self._fam.name in ("binomial", "poisson"):
            return 2.0 * sps.norm.sf(np.abs(self.t_values))
        return 2.0 * sps.t.sf(np.abs(self.t_values),
                              max(self.residual_degree_of_freedom, 1))

    # -- from the device, when read ------------------------------------
    def _ofs(self):
        return 0.0 if self._offset is None else self._offset

    @functools.cached_property
    def prediction_mean(self):
        """The fitted means as a device array in the dataset's padded,
        row-sharded row space (``ds.unpad`` trims a host copy)."""
        import jax.numpy as jnp
        ds, m = self._ds, self._model
        params = jnp.asarray(np.concatenate([m._coef, [m._icpt, 0.0]]))
        return _row_program(self._fam, self._link)(
            ds.x, ds.y, self._ofs(), params)

    @functools.cached_property
    def null_deviance(self) -> float:
        import jax
        ds = self._ds
        program = _null_program(self._fam, self._link, self._fit_intercept,
                                self._offset is not None)
        return float(jax.device_get(program(ds.y, ds.w, self._ofs())))

    @functools.cached_property
    def aic(self) -> float:
        import jax.numpy as jnp
        ds = self._ds
        valid = np.zeros(ds.y.shape[0], bool)
        valid[ds.valid_indices()] = True
        rows = {"y": ds.y, "w": ds.w, "mu": self.prediction_mean,
                "valid": ds.ctx.mesh_runtime.device_put_sharded_rows(valid)}
        return self._fam.aic(rows, float(ds.n_rows), float(jnp.sum(ds.w)),
                             self.deviance, self.rank)

    def residuals(self, residuals_type: str = "deviance") -> np.ndarray:
        """One residual per real row, computed on the device and brought
        home as the n-vector the caller asked for."""
        import jax.numpy as jnp
        ds = self._ds
        y, mu, w = ds.y, self.prediction_mean, ds.w
        if residuals_type == "response":
            r = y - mu
        elif residuals_type == "working":
            r = (y - mu) * self._link.deriv(mu)
        elif residuals_type == "pearson":
            r = (y - mu) * jnp.sqrt(w) / jnp.sqrt(
                jnp.maximum(self._fam.variance(mu), _EPS))
        elif residuals_type == "deviance":
            r = jnp.sign(y - mu) * jnp.sqrt(jnp.clip(
                w * self._fam.unit_deviance(y, mu), 0, None))
        else:
            raise ValueError(residuals_type)
        return ds.unpad(np.asarray(r, dtype=np.float64))
