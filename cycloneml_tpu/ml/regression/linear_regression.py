"""Linear regression with elastic-net.

Re-design of the reference estimator (ref: ml/regression/LinearRegression.scala,
1,079 LoC): identical objective —

  f(β̂) = 1/(2n) Σ wᵢ((x̂ᵢ−x̄̂)·β̂ − (ŷᵢ−ȳ̂))² + regParam·(α‖β̄‖₁ + (1−α)/2‖β̄‖²)

in doubly-standardized space (features AND label divided by their std, the
glmnet convention the reference follows). ``standardization=false``
penalises original-space β exactly as the reference's
DifferentiableRegularization does. Solvers mirror ``solver``: "l-bfgs"/
OWL-QN trains without an intercept via the centering trick (intercept
recovered in closed form ȳ − β·x̄, Summarizer unbiased std — the
reference's l-bfgs path); "normal" DELEGATES to the
``ml.optim.wls.WeightedLeastSquares`` component exactly as the reference
does (LinearRegression.scala:446-448 — population-weighted moments,
appended-bias standardized system, Cholesky with singular→quasi-Newton
fallback); "auto" picks normal when d ≤ 4096 and α·regParam == 0.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.linalg.vectors import DenseVector, Vectors
from cycloneml_tpu.ml.base import PredictionModel, Predictor
from cycloneml_tpu.ml.optim import aggregators
from cycloneml_tpu.ml.optim.lbfgs import optimizer_for
from cycloneml_tpu.ml.optim.loss import DistributedLossFunction, l2_regularization
from cycloneml_tpu.ml.shared import (
    HasAggregationDepth, HasElasticNetParam, HasFitIntercept, HasLabelCol,
    HasMaxIter, HasRegParam, HasSolver, HasStandardization, HasTol,
)
from cycloneml_tpu.ml.stat import Summarizer
from cycloneml_tpu.ml.util_io import MLReadable, MLWritable, load_arrays, save_arrays
from cycloneml_tpu.observe import tracing
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

# the component owns the real cap (wls.py raises at fit time) — this
# alias only steers the auto-solver choice
from cycloneml_tpu.ml.optim.wls import \
    MAX_NUM_FEATURES as MAX_FEATURES_FOR_NORMAL  # noqa: E402


class _LinearRegressionParams(HasMaxIter, HasRegParam, HasElasticNetParam,
                              HasTol, HasFitIntercept, HasStandardization,
                              HasSolver, HasAggregationDepth, HasLabelCol):
    def _declare_linreg_params(self):
        self._p_label_col()
        self._p_max_iter(100)
        self._p_reg_param(0.0)
        self._p_elastic_net(0.0)
        self._p_tol(1e-6)
        self._p_fit_intercept(True)
        self._p_standardization(True)
        self._p_solver(["auto", "l-bfgs", "normal"], "auto")
        self._p_aggregation_depth(2)


class LinearRegression(Predictor, _LinearRegressionParams, MLWritable, MLReadable):
    def __init__(self, uid=None, **kwargs):
        super().__init__(uid)
        self._declare_linreg_params()
        for k, v in kwargs.items():
            self.set(k, v)

    def set_max_iter(self, v):
        return self.set("maxIter", v)

    def set_reg_param(self, v):
        return self.set("regParam", v)

    def set_elastic_net_param(self, v):
        return self.set("elasticNetParam", v)

    def set_solver(self, v):
        return self.set("solver", v)

    def _fit(self, frame: MLFrame) -> "LinearRegressionModel":
        # fp8-capable: the l-bfgs path folds the per-column dequant scales
        # into inv_std; the normal (WLS) solver is NOT fp8-eligible and
        # dequantizes back to bf16 below (a visible PrecisionFallback)
        ds = frame.to_instance_dataset(
            self.get("featuresCol"), self.get("labelCol"),
            self.get("weightCol") or None, fp8_capable=True)
        return self._fit_dataset(ds)

    def _fit_dataset(self, ds: InstanceDataset) -> "LinearRegressionModel":
        from cycloneml_tpu.oocore import StreamingDataset, streaming_mode
        streamed = isinstance(ds, StreamingDataset)
        force = not streamed and \
            streaming_mode(getattr(ds.ctx, "conf", None)) == "force"

        d = ds.n_features
        reg = self.get("regParam")
        alpha = self.get("elasticNetParam")
        solver = self.get("solver")
        if solver == "auto":
            # streamed fits always take the quasi-Newton path: the normal
            # solver's moment system wants the in-core design matrix
            solver = "normal" if (alpha * reg == 0.0
                                  and d <= MAX_FEATURES_FOR_NORMAL
                                  and not (streamed or force)) else "l-bfgs"
        if (streamed or force) and solver == "normal":
            # validated BEFORE any force-mode spill: an explicit normal
            # request must not pay an O(n·d) shard write just to raise
            raise ValueError(
                "solver='normal' requires an in-core dataset; streamed "
                "fits use solver='l-bfgs' (or 'auto')")
        if force:
            from cycloneml_tpu.oocore import shard_dataset
            sds = shard_dataset(ds)
            try:
                return self._fit_dataset(sds)
            finally:
                sds.close()

        if solver == "normal":
            return self._fit_normal(ds, reg, alpha)

        cached = streamed or Summarizer.is_cached(ds)
        with tracing.span("phase", "fit.stats", cached=cached):
            stats = ds.summary() if streamed else Summarizer.summarize(ds)
        with tracing.span("phase", "fit.prepare"):
            if not streamed:
                # fp8 safety rail: envelope probe, bf16 fallback on failure
                from cycloneml_tpu.dataset.dataset import resolve_fp8_fit
                ds = resolve_fp8_fit(ds, stats, "LinearRegression")
            x_mean, x_std = stats.mean, stats.std
            w_sum = stats.weight_sum

            # label moments: harvested by the pass that made ``stats`` (the
            # Summarizer's, cached on the dataset; the shard write pass for
            # a streamed one) — nothing is traced, launched or read back here
            y_mean = stats.label_sum / w_sum
            denom = w_sum - stats.weight_sq_sum / w_sum
            y_var = max((stats.label_sq_sum - w_sum * y_mean ** 2) / denom, 0.0) if denom > 0 else 0.0
            y_std = float(np.sqrt(y_var))
            if y_std == 0.0:
                # constant label (ref LinearRegression.scala:388-414, mirroring
                # WeightedLeastSquares.scala:117-141): with an intercept (or an
                # all-zero label) the exact fit is zero coefficients; WITHOUT
                # an intercept a nonzero constant label still needs solving —
                # the reference sets yStd = |yMean| so the label is "not scaled
                # anymore" and proceeds, and REFUSES regularization because the
                # label-standardized penalty is undefined at σy=0
                if self.get("fitIntercept") or y_mean == 0.0:
                    model = LinearRegressionModel(
                        np.zeros(d), y_mean if self.get("fitIntercept") else 0.0,
                        uid=self.uid)
                    self._copy_values(model)
                    model._set_parent(self)
                    model.summary = LinearRegressionTrainingSummary([0.0], 0)
                    return model
                if reg > 0.0:
                    raise ValueError(
                        "The standard deviation of the label is zero. Model "
                        "cannot be regularized when labels are standardized "
                        "(ref WeightedLeastSquares require)")
                y_std = abs(y_mean)

            # glmnet semantics (the reference's parity target): the penalty is
            # applied on the label-standardized problem, so the user's regParam
            # is divided by the label std (ref LinearRegression.scala:396
            # effectiveRegParam = regParam / yStd; WeightedLeastSquares.scala:209)
            eff_reg = reg / y_std
        coef, icpt, state, loss_fn, orientation = self._solve_quasi_newton(
            ds, stats, y_mean, y_std, eff_reg, alpha)
        history = list(state.loss_history)

        with tracing.span("phase", "fit.finish"):
            model = LinearRegressionModel(coef, icpt, uid=self.uid)
            self._copy_values(model)
            model._set_parent(self)
            model.summary = LinearRegressionTrainingSummary(
                history, max(len(history) - 1, 0),
                total_evals=loss_fn.n_evals,
                total_dispatches=loss_fn.n_dispatches, streamed=streamed,
                orientation=orientation, total_passes=loss_fn.n_evals,
                search_evals=list(state.search_evals) or None)
            return model

    # -- normal equations: one moment pass, then the driver's solve ------------
    def _fit_normal(self, ds, reg, alpha) -> "LinearRegressionModel":
        """Delegate to the WLS COMPONENT exactly as the reference does
        (LinearRegression.scala:446-448: WeightedLeastSquares with
        solverType=Auto, standardizeLabel=true) — population-weighted
        moments, appended-bias system, Cholesky with singular→QN fallback,
        and the constant-label/zero-variance degeneracies live in ONE
        place (ml/optim/wls.py). A fit is one aggregation program over X
        (dispatch + readback spans of its own), then ``fit.solve`` on the
        host; every fit pays its pass."""
        from cycloneml_tpu.ml.optim.wls import AUTO, WeightedLeastSquares
        with tracing.span("phase", "fit.prepare"):
            if getattr(ds, "x_scale", None) is not None:
                # the moment pass reads ds.x directly; e4m3 codes are
                # not values — leave the fp8 rung, visibly
                from cycloneml_tpu.dataset.dataset import fp8_fallback
                ds = fp8_fallback(ds, "LinearRegression",
                                  "solver='normal' is not fp8-eligible")
            wls = WeightedLeastSquares(
                fit_intercept=self.get("fitIntercept"), reg_param=reg,
                elastic_net_param=alpha,
                standardize_features=self.get("standardization"),
                standardize_label=True, solver_type=AUTO,
                max_iter=self.get("maxIter"), tol=self.get("tol"))
        moments = wls.moments(ds)
        with tracing.span("phase", "fit.solve") as ssp:
            wm = wls.solve(moments, ds.n_features)
            ssp.annotate(system=wm.system)
        with tracing.span("phase", "fit.finish"):
            model = LinearRegressionModel(wm.coefficients, wm.intercept,
                                          uid=self.uid)
            self._copy_values(model)
            model._set_parent(self)
            model.summary = LinearRegressionTrainingSummary(
                wm.objective_history,
                max(len(wm.objective_history) - 1, 0),
                total_dispatches=wls.n_passes, solver="normal",
                total_passes=wls.n_passes)
            return model

    # -- quasi-Newton in doubly standardized space -----------------------------
    def _solve_quasi_newton(self, ds, stats, y_mean, y_std, reg, alpha):
        import jax
        import jax.numpy as jnp

        with tracing.span("phase", "fit.prepare"):
            d = ds.n_features
            fit_intercept = self.get("fitIntercept")
            standardize = self.get("standardization")
            x_mean, x_std = stats.mean, stats.std
            inv_std = np.where(x_std > 0, 1.0 / np.where(x_std > 0, x_std, 1.0), 0.0)

            # the doubly-standardized objective folds INTO the aggregator read
            # (aggregators.least_squares_scaled): err = x·(inv_std∘β) −
            # (μ̂·β − ȳ̂) − y/σ_y, grad unscales by inv_std — algebraically the
            # aggregation over (x̂−μ̂, ŷ−ȳ̂) without EVER materializing the
            # standardized X copy or the scaled-y vector (pre-tier this path
            # re-wrote both, a full read+write X sweep and 2x the HBM working
            # set per fit). Raw data-tier blocks (bf16 by default) are read at
            # storage width with fp32 accumulation inside the kernel; the
            # fused Pallas kernel is the default sweep on native backends.
            from cycloneml_tpu.dataset.instance import compute_dtype
            from cycloneml_tpu.ops.kernels import use_fused_kernels
            adt = compute_dtype()
            scaled_mean = (x_mean * inv_std) if fit_intercept else np.zeros(d)
            y_mean_std = (y_mean / y_std) if fit_intercept else 0.0
            y_pars = np.array([1.0 / y_std, y_mean_std])
            # fp8 tier: the per-column dequant scale folds into the
            # aggregator-side inv_std (x̂ = codes∘(scale/σ) − μ/σ); the final
            # unscaling keeps the original inv_std
            fp8_scale = getattr(ds, "x_scale", None)
            inv_std_agg = inv_std * fp8_scale if fp8_scale is not None \
                else inv_std
            from cycloneml_tpu.oocore import StreamingDataset
            streamed = isinstance(ds, StreamingDataset)
            orientation = None
            if use_fused_kernels(ds.ctx):
                # the sweep's tiling follows the way X is stored (a
                # streamed fit's shards are staged per dispatch: no
                # resident array to observe, row-major as before)
                from cycloneml_tpu.ops.kernels import glm_sweep_orientation
                orientation = "row_major" if streamed else \
                    glm_sweep_orientation(ds.x, fp8_scale is not None)
                agg = aggregators.least_squares_pallas_scaled(
                    d, feature_major=orientation == "feature_major")
            else:
                agg = aggregators.least_squares_scaled(d)

            l2 = (1.0 - alpha) * reg
            l1 = alpha * reg
            l2_fn = l2_regularization(l2, d, False, features_std=x_std,
                                      standardize=standardize) if l2 > 0 else None
            extras = (jnp.asarray(inv_std_agg.astype(adt)),
                      jnp.asarray(scaled_mean.astype(adt)),
                      jnp.asarray(y_pars.astype(adt)))
            if streamed:
                # the streamed twin: same scaled aggregator, same extras —
                # each loss/grad evaluation is one double-buffered epoch
                from cycloneml_tpu.oocore import StreamingLossFunction
                loss_fn = StreamingLossFunction(ds, agg, l2_fn,
                                                stats.weight_sum,
                                                extra_args=extras)
            else:
                loss_fn = DistributedLossFunction(ds, agg, l2_fn,
                                                  stats.weight_sum,
                                                  extra_args=extras)

            opt = optimizer_for(
                self.get("maxIter"), self.get("tol"), d, l1=l1, n_penalized=d,
                penalty_std=None if standardize else x_std)
        with tracing.span("phase", "fit.optimize",
                          optimizer=type(opt).__name__):
            state = opt.minimize(loss_fn, np.zeros(d))
        if state.converged_reason == "max iterations reached":
            logger.warning("LinearRegression did not converge in %d iterations",
                           self.get("maxIter"))
        if fp8_scale is not None and not np.all(np.isfinite(state.x)):
            # overflowed e4m3 surfaces as NaN — refit on the bf16 rung
            from cycloneml_tpu.dataset.dataset import fp8_fallback
            return self._solve_quasi_newton(
                fp8_fallback(ds, "LinearRegression",
                             "non-finite fp8 solution"),
                stats, y_mean, y_std, reg, alpha)

        with tracing.span("phase", "fit.finish"):
            beta_hat = state.x  # standardized-space coefficients
            coef = beta_hat * inv_std * y_std
            icpt = y_mean - float(coef @ x_mean) if fit_intercept else 0.0
        return coef, icpt, state, loss_fn, orientation


class LinearRegressionModel(PredictionModel, _LinearRegressionParams,
                            MLWritable, MLReadable):
    def __init__(self, coefficients: Optional[np.ndarray] = None,
                 intercept: float = 0.0, uid=None):
        super().__init__(uid)
        self._declare_linreg_params()
        self._coef = np.asarray(coefficients) if coefficients is not None else None
        self._icpt = float(intercept)
        self.summary: Optional[LinearRegressionTrainingSummary] = None

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
        return x @ self._coef + self._icpt

    def evaluate(self, frame: MLFrame):
        """RegressionSummary metrics on a frame (ref LinearRegressionSummary)."""
        x = frame[self.get("featuresCol")]
        y = frame[self.get("labelCol")]
        pred = self._predict_batch(x)
        resid = y - pred
        sse = float(resid @ resid)
        sst = float(((y - y.mean()) ** 2).sum())
        n = len(y)
        return {
            "rmse": float(np.sqrt(sse / n)),
            "mse": sse / n,
            "mae": float(np.abs(resid).mean()),
            "r2": 1.0 - sse / sst if sst > 0 else float("nan"),
        }

    def _save_data(self, path: str) -> None:
        save_arrays(path, coef=self._coef, icpt=np.array(self._icpt))

    def _load_data(self, path: str, meta) -> None:
        arrs = load_arrays(path)
        self._coef = arrs["coef"]
        self._icpt = float(arrs["icpt"])


class LinearRegressionTrainingSummary:
    def __init__(self, objective_history, total_iterations,
                 total_evals=None, total_dispatches=None, streamed=False,
                 orientation=None, solver="l-bfgs", total_passes=None,
                 search_evals=None):
        # the objective per iteration (quasi-Newton), or the one value the
        # closed-form solution reaches (normal: the standardised
        # quadratic, penalty included)
        self.objective_history = objective_history
        self.total_iterations = total_iterations
        # which solver ran ("normal" / "l-bfgs") and how often it read X:
        # one pass for the normal equations, one per evaluation otherwise
        self.solver = solver
        self.total_passes = total_passes
        # optimizer-path telemetry, as LogisticRegressionTrainingSummary
        # carries it: loss/grad evaluations and host->device round trips
        # (the normal solver evaluates no loss function: total_evals None,
        # one dispatch for its moment pass; the constant-label shortcut
        # states neither)
        self.total_evals = total_evals
        self.total_dispatches = total_dispatches
        # OWL-QN only (None otherwise): evaluations per turn, [1] for the
        # initial one and then one entry per iteration's line search;
        # sums to total_evals ("4 iterations, 1+1+1+1+1")
        self.search_evals = search_evals
        # True when the fit ran on the out-of-core streaming engine
        self.streamed = streamed
        # tiling of the fused GLM sweep the fit ran ("feature_major" /
        # "row_major", ops/kernels.glm_sweep_orientation); None when the
        # sweep was not the fused kernel
        self.orientation = orientation
