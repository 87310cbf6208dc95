"""Logistic regression (binomial + multinomial).

TPU-native re-design of the reference estimator
(ref: ml/classification/LogisticRegression.scala:286; train path
``trainImpl:935``): the same statistical semantics — label histogram +
feature std via one summarizer pass, training in standardized feature space,
elastic-net with the L1/L2 split handled by OWL-QN/L-BFGS
(``createOptimizer:777-814``), log-odds intercept initialisation, coefficient
unscaling back to original space, objective history in the summary — but the
per-iteration gradient is ONE jit-compiled XLA program: block margins on the
MXU, hierarchical psum instead of treeAggregate (SURVEY §3.3's hot loop).

Feature blocks stay resident in device HBM across iterations (the analog of
persisting standardized blocks MEMORY_AND_DISK at :968).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.linalg.matrices import DenseMatrix
from cycloneml_tpu.linalg.vectors import DenseVector, Vectors
from cycloneml_tpu.ml.base import Predictor, ProbabilisticClassificationModel
from cycloneml_tpu.ml.optim import LBFGS, aggregators
from cycloneml_tpu.ml.optim.lbfgs import optimizer_for
from cycloneml_tpu.ml.optim.loss import (
    DistributedLossFunction, l2_regularization,
)
from cycloneml_tpu.ml.param import ParamValidators as V
from cycloneml_tpu.ml.shared import (
    HasAggregationDepth, HasElasticNetParam, HasFitIntercept, HasLabelCol,
    HasMaxBlockSizeInMB, HasMaxIter, HasRegParam, HasStandardization,
    HasThreshold, HasTol,
)
from cycloneml_tpu.ml.stat import Summarizer
from cycloneml_tpu.ml.util_io import MLReadable, MLWritable, load_arrays, save_arrays
from cycloneml_tpu.observe import tracing
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)


class _LogisticRegressionParams(HasMaxIter, HasRegParam, HasElasticNetParam,
                                HasTol, HasFitIntercept, HasStandardization,
                                HasThreshold, HasAggregationDepth,
                                HasMaxBlockSizeInMB):
    def _declare_lr_params(self):
        self._p_max_iter(100)
        self._p_reg_param(0.0)
        self._p_elastic_net(0.0)
        self._p_tol(1e-6)
        self._p_fit_intercept(True)
        self._p_standardization(True)
        self._p_threshold(0.5)
        self._p_aggregation_depth(2)
        self._p_max_block_size(0.0)
        self.family = self._param(
            "family", "label distribution family",
            V.in_array(["auto", "binomial", "multinomial"]), default="auto")
        # step-level training checkpoints — the improvement SURVEY §5.4
        # flags over the reference, which only persists finished models (the
        # param NAME mirrors the reference's checkpointInterval on ALS/trees)
        self.checkpointDir = self._param(
            "checkpointDir", "directory for mid-training optimizer "
            "checkpoints; fit() resumes from the newest one", default="")
        self.checkpointInterval = self._param(
            "checkpointInterval", "iterations between checkpoints",
            V.gt(0), default=10)
        # box constraints on the solution select the bound-constrained
        # optimizer, exactly as the reference's createOptimizer does
        # (LogisticRegression.scala:777-814, BreezeLBFGSB at :788);
        # shapes follow the reference: coefficient bounds are
        # (numClasses-ish, d) matrices (binomial: (1, d)), intercept
        # bounds are vectors
        self.lowerBoundsOnCoefficients = self._param(
            "lowerBoundsOnCoefficients",
            "(k, d) lower bounds on coefficients", default=None)
        self.upperBoundsOnCoefficients = self._param(
            "upperBoundsOnCoefficients",
            "(k, d) upper bounds on coefficients", default=None)
        self.lowerBoundsOnIntercepts = self._param(
            "lowerBoundsOnIntercepts", "(k,) lower bounds on intercepts",
            default=None)
        self.upperBoundsOnIntercepts = self._param(
            "upperBoundsOnIntercepts", "(k,) upper bounds on intercepts",
            default=None)

    def _opt(self, name):
        """Optional param: None when never set (these have no default)."""
        return self.get(name) if self.is_defined(self.get_param(name)) else None

    def _has_bounds(self) -> bool:
        return any(self._opt(p) is not None for p in (
            "lowerBoundsOnCoefficients", "upperBoundsOnCoefficients",
            "lowerBoundsOnIntercepts", "upperBoundsOnIntercepts"))


class LogisticRegression(Predictor, _LogisticRegressionParams,
                         MLWritable, MLReadable):
    def __init__(self, uid=None, **kwargs):
        super().__init__(uid)
        self._declare_lr_params()
        for k, v in kwargs.items():
            self.set(k, v)

    # fluent setters (PySpark-style camelCase params, snake-case methods)
    def set_max_iter(self, v):
        return self.set("maxIter", v)

    def set_reg_param(self, v):
        return self.set("regParam", v)

    def set_elastic_net_param(self, v):
        return self.set("elasticNetParam", v)

    def set_tol(self, v):
        return self.set("tol", v)

    def set_fit_intercept(self, v):
        return self.set("fitIntercept", v)

    def set_standardization(self, v):
        return self.set("standardization", v)

    def set_family(self, v):
        return self.set("family", v)

    def set_threshold(self, v):
        return self.set("threshold", v)

    def _flat_bounds(self, alpha, d, num_classes, is_multinomial,
                     fit_intercept, n_coef, features_std):
        """User bounds flattened into the optimizer's coefficient layout
        as ``(lower, upper)``, or None when none are set. In STANDARDIZED
        space: β_std = β_orig·std, so coefficient bounds scale by
        featuresStd exactly as the reference's createBounds does
        (LogisticRegression.scala:2085-2156). Intercepts are unscaled."""
        if not self._has_bounds():
            return None
        if alpha != 0.0:
            # bounds are only legal with none/L2 regularization: the
            # reference rejects ANY nonzero elasticNetParam with bounds,
            # regardless of regParam
            raise ValueError(
                "coefficient bounds are only supported with none or L2 "
                "regularization (elasticNetParam must be 0, as the "
                "reference enforces)")
        k_rows = num_classes if is_multinomial else 1
        n_feat = d * k_rows
        out = []
        for cp, ip, fill in (
                ("lowerBoundsOnCoefficients", "lowerBoundsOnIntercepts",
                 -np.inf),
                ("upperBoundsOnCoefficients", "upperBoundsOnIntercepts",
                 np.inf)):
            b = np.full(n_coef, fill)
            cb = self._opt(cp)
            if cb is not None:
                cb = np.asarray(cb, dtype=np.float64)
                if cb.ndim == 1 and k_rows == 1 and cb.size == d:
                    cb = cb[None, :]  # binomial convenience: a plain vector
                if cb.shape != (k_rows, d):
                    # exact-shape check: size alone would silently accept a
                    # TRANSPOSED multinomial matrix and scramble the box
                    raise ValueError(
                        f"{cp} must have shape ({k_rows}, {d}); "
                        f"got {cb.shape}")
                b[:n_feat] = (cb
                              * np.asarray(features_std)[None, :]).reshape(-1)
            ib = self._opt(ip)
            if ib is not None:
                if not fit_intercept:
                    raise ValueError(
                        f"{ip} requires fitIntercept=True")
                ib = np.asarray(ib, dtype=np.float64).reshape(-1)
                if ib.size != k_rows:
                    raise ValueError(
                        f"{ip} must have {k_rows} entries; got {ib.size}")
                b[n_feat:] = ib
            out.append(b)
        return out[0], out[1]

    def _optimize(self, opt, loss_fn, x0, fp_parts):
        """Shared optimize tail for the dense and sparse fit paths:
        checkpointed training (fingerprint-bound to dataset+params) when a
        checkpointDir is set, plain minimize otherwise, plus the
        non-convergence warning."""
        if self.get("checkpointDir"):
            import hashlib
            from cycloneml_tpu.parallel.resilience import (
                train_with_checkpoints)
            from cycloneml_tpu.util.checkpoint import TrainingCheckpointer
            # resuming someone else's checkpoint would silently return the
            # wrong model — bind the dir to this dataset+params
            fp = hashlib.sha1(repr(fp_parts).encode()).hexdigest()[:16]
            state = train_with_checkpoints(
                opt, loss_fn, x0,
                TrainingCheckpointer(self.get("checkpointDir")),
                interval=self.get("checkpointInterval"), fingerprint=fp)
        else:
            state = opt.minimize(loss_fn, x0)
        if state.converged_reason == "max iterations reached":
            logger.warning(
                "LogisticRegression did not converge in %d iterations",
                self.get("maxIter"))
        return state

    def _fit(self, frame) -> "LogisticRegressionModel":
        from cycloneml_tpu.dataset.sparse import SparseInstanceDataset
        if isinstance(frame, SparseInstanceDataset):
            # the reference trains transparently on sparse vectors; here
            # the sparse tier has its own fit path (ELL/hybrid aggregators)
            return self._fit_sparse(frame)
        # fp8-capable: the scaled aggregators fold the per-column dequant
        # scales into inv_std, so this fit may ride the e4m3 rung of the
        # data tier (cyclone.data.dtype=auto8/float8)
        ds = frame.to_instance_dataset(
            self.get("featuresCol"), self.get("labelCol"),
            self.get("weightCol") or None, fp8_capable=True)
        return self._fit_dataset(ds)

    # -- stacked (model-axis) fits -------------------------------------------
    def can_fit_stacked(self) -> bool:
        """Param-level eligibility for the stacked (vmapped model-axis)
        fit: binomial objective, pure L2 (``elasticNetParam == 0``), no
        coefficient bounds, no mid-training checkpointing — the same
        preconditions as the chunked device optimizer the stacked engine
        drives. Data-level checks ({0, 1} labels, dense tier) happen inside
        :meth:`fit_stacked`."""
        return (self.get("family") != "multinomial"
                and float(self.get("elasticNetParam")) == 0.0
                and not self._has_bounds()
                and not self.get("checkpointDir"))

    def fit_stacked(self, frame, reg_params=None,
                    num_classes: Optional[int] = None):
        """Fit K binomial models over ONE shared design matrix as ONE
        gang-scheduled SPMD program (the sanctioned parallel path — see
        ``mesh.safe_fit_parallelism`` and docs/multi-model.md).

        The K objectives are ONE aggregator with a leading model axis
        (``aggregators.stacked_binary_logistic_*``): an evaluation reads X
        once for all K models, every ``tree_aggregate`` psum carries all K
        gradients, the K L-BFGS lanes share one compiled chunk program
        (``StackedDeviceLBFGS``) and per-model convergence masks freeze
        early-converged models on device. No cross-program collective
        rendezvous exists, so — unlike thread-pool fan-out (the PR-2
        deadlock) — full model-parallelism is safe on any mesh.

        Which K models, for a dataset (a frame builds one):

        - ``num_classes=K``: the dataset's labels are class indices and
          model j's label is ``1[y == j]`` (OneVsRest's relabelling), made
          inside the sweep from the label vector the dataset already holds:
          no ``(n, K)`` array exists on the host or the device;
        - ``reg_params``: per-model L2 strength over the dataset's own 0/1
          labels (CrossValidator's regParam grid); with ``num_classes`` it
          is model j's strength, default this estimator's ``regParam``.

        A ``StreamingDataset`` (or ``cyclone.oocore.mode=force``) takes the
        out-of-core leg: the same aggregator a staged shard at a time, the
        labels each shard's own. Returns a list of K
        :class:`LogisticRegressionModel` (summaries carry ``n_models`` and
        ``stacked_evals``).
        """
        import jax.numpy as jnp

        from cycloneml_tpu.dataset.sparse import SparseInstanceDataset
        from cycloneml_tpu.ml.optim.device_lbfgs import StackedDeviceLBFGS
        from cycloneml_tpu.ml.optim.loss import (
            StackedDistributedLossFunction, stacked_l2_scale)

        if not self.can_fit_stacked():
            raise ValueError(
                "fit_stacked requires a binomial, pure-L2, unbounded, "
                "non-checkpointed configuration (can_fit_stacked)")
        if isinstance(frame, SparseInstanceDataset):
            raise ValueError("stacked fits are dense-tier only")
        if num_classes is None and reg_params is None:
            raise ValueError("fit_stacked needs num_classes or reg_params")
        shared_labels = num_classes is None
        n_models = len(reg_params) if shared_labels else int(num_classes)
        if reg_params is None:
            reg_params = np.full(n_models, float(self.get("regParam")))
        reg_params = np.asarray(reg_params, dtype=np.float64)
        if len(reg_params) != n_models:
            raise ValueError("reg_params length != number of stacked models")
        ds = frame.to_instance_dataset(
            self.get("featuresCol"), self.get("labelCol"),
            self.get("weightCol") or None, fp8_capable=True)
        # streamed stacked fits: ONE double-buffered epoch serves all K
        # models (the K-model grid/OvR fit reads the spill once per
        # optimizer round instead of K times)
        from cycloneml_tpu.oocore import (StreamingDataset, shard_dataset,
                                          streaming_mode)
        if isinstance(ds, StreamingDataset):
            return self._fit_stacked_streamed(ds, reg_params, shared_labels)
        if streaming_mode(getattr(ds.ctx, "conf", None)) == "force":
            sds = shard_dataset(ds)
            try:
                return self._fit_stacked_streamed(sds, reg_params,
                                                  shared_labels)
            finally:
                sds.close()

        d = ds.n_features
        with tracing.span("phase", "fit.stats",
                          cached=Summarizer.is_cached(ds)):
            stats = Summarizer.summarize(ds)
            # weighted class masses and the labels' check, one host pass a
            # DATASET (cached on it), not K float64 relabellings a fit
            hist = ds.label_histogram()
            if not ds.labels_are_class_indices():
                raise ValueError("fit_stacked requires class-index labels "
                                 "(whole numbers >= 0)")
        with tracing.span("phase", "fit.prepare"):
            from cycloneml_tpu.dataset.dataset import resolve_fp8_fit
            ds = resolve_fp8_fit(ds, stats, "LogisticRegression(stacked)")
            fp8_scale = ds.x_scale
            std = self._stacked_standardization(stats, fp8_scale)
            x0 = self._stacked_start(hist, n_models, shared_labels, stats)

            # the fused stacked sweep where X's storage admits one (a
            # resident bf16 X: kernels.multinomial_sweep_tile), in the
            # tiling its layout dictates; the row-blocked XLA twin
            # otherwise. Either reads X once an evaluation for all K models
            # and makes model j's label from the dataset's own label vector
            from cycloneml_tpu.ops import kernels
            orientation = None
            if kernels.use_fused_kernels(ds.ctx):
                orientation = kernels.multinomial_sweep_orientation(
                    ds.x, n_models)
            loss_fn = StackedDistributedLossFunction(
                ds, self._stacked_aggregator(d, n_models, shared_labels,
                                             orientation),
                n_models, reg=reg_params,
                l2_scale=stacked_l2_scale(d, x0.shape[1], stats.std,
                                          self.get("standardization")),
                weight_sum=stats.weight_sum,
                extra_args=tuple(jnp.asarray(v) for v in std.extra_args))

            from cycloneml_tpu.conf import LBFGS_DEVICE_CHUNK
            chunk = int(ds.ctx.conf.get(LBFGS_DEVICE_CHUNK)) \
                if hasattr(ds.ctx, "conf") else 0
            # deviceChunk=0 means "one dispatch per iteration"; the stacked
            # engine has no host loop, so honor it as chunk=1 (per-iteration
            # dispatches) rather than silently running the default chunk
            opt = StackedDeviceLBFGS(max_iter=self.get("maxIter"),
                                     tol=self.get("tol"),
                                     chunk=max(chunk, 1))
        with tracing.span("phase", "fit.optimize",
                          optimizer=type(opt).__name__, n_models=n_models):
            res = opt.minimize(loss_fn, x0)
        if fp8_scale is not None \
                and not np.all(np.isfinite(np.asarray(res.x))):
            from cycloneml_tpu.dataset.dataset import fp8_fallback
            return self.fit_stacked(
                fp8_fallback(ds, "LogisticRegression(stacked)",
                             "non-finite fp8 solution"),
                reg_params=reg_params, num_classes=num_classes)
        with tracing.span("phase", "fit.finish"):
            return self._stacked_models(
                res, loss_fn, std, orientation=orientation,
                pieces=kernels.SOFTMAX_PIECES
                if orientation is not None else None)

    # what the two legs of a stacked fit (in-core above, streamed below)
    # share: standardization vectors, starting point, sweep, models
    def _stacked_standardization(self, stats, fp8_scale):
        """``inv_std`` / ``scaled_mean`` of a stacked fit, and as
        ``extra_args`` what its aggregator reads at the accumulator tier
        (an fp8 X's dequant scale folded into ``inv_std``, as in
        ``_fit_dataset``; the models are unscaled by the plain one)."""
        from types import SimpleNamespace

        from cycloneml_tpu.dataset.instance import compute_dtype
        from cycloneml_tpu.ml.optim.loss import inv_std_vector
        inv_std = inv_std_vector(stats.std)
        # bounds are excluded by eligibility: the mean is folded exactly
        # where an intercept is fitted
        scaled_mean = stats.mean * inv_std if self.get("fitIntercept") \
            else np.zeros(len(inv_std))
        inv_std_agg = inv_std * fp8_scale if fp8_scale is not None \
            else inv_std
        adt = compute_dtype()
        return SimpleNamespace(
            inv_std=inv_std, scaled_mean=scaled_mean,
            extra_args=(inv_std_agg.astype(adt), scaled_mean.astype(adt)))

    def _stacked_start(self, hist, n_models: int, shared_labels: bool,
                       stats) -> np.ndarray:
        """The ``(K, n_coef)`` starting point — zeros, each intercept at
        its model's log-odds, the positive mass a label-histogram entry —
        after checking that the histogram holds no more classes than the
        fit has labels for."""
        if len(hist) > (2 if shared_labels else n_models):
            raise ValueError(
                "fit_stacked requires "
                + ("binary {0, 1} labels" if shared_labels else
                   f"class-index labels below {n_models}")
                + f"; the dataset's histogram has {len(hist)} entries")
        d = len(stats.std)
        fit_intercept = self.get("fitIntercept")
        x0 = np.zeros((n_models, d + (1 if fit_intercept else 0)))
        if fit_intercept:
            mass = np.append(hist, np.zeros(max(n_models, 2) - len(hist)))
            pos = np.full(n_models, mass[1:].sum()) if shared_labels \
                else mass[:n_models]
            ok = (pos > 0) & (pos < stats.weight_sum)
            p1 = np.where(ok, pos / stats.weight_sum, 0.5)
            x0[:, d] = np.where(ok, np.log(p1 / (1.0 - p1)), 0.0)
        return x0

    def _stacked_aggregator(self, d: int, n_models: int, shared_labels: bool,
                            orientation: Optional[str]):
        """The fused stacked sweep in the tiling ``orientation`` names, or
        (None) its row-blocked XLA twin."""
        if orientation is None:
            return aggregators.stacked_binary_logistic_scaled(
                d, n_models, self.get("fitIntercept"), shared_labels)
        return aggregators.stacked_binary_logistic_pallas_scaled(
            d, n_models, self.get("fitIntercept"), shared_labels,
            feature_major=orientation == "feature_major")

    def _stacked_models(self, res, loss_fn, std, **summary):
        """The K models of a finished stacked run, in original space."""
        n_models = len(res.x)
        n_unconverged = sum(
            1 for r in res.converged_reasons if r == "max iterations reached")
        if n_unconverged:
            logger.warning(
                "stacked LogisticRegression: %d of %d models did not "
                "converge in %d iterations", n_unconverged, n_models,
                self.get("maxIter"))
        d = len(std.inv_std)
        models = []
        for kk in range(n_models):
            sol = res.x[kk]
            beta = sol[:d] * std.inv_std
            icpt = 0.0
            if self.get("fitIntercept"):
                icpt = float(sol[d]) - float(sol[:d] @ std.scaled_mean)
            model = LogisticRegressionModel(
                coefficient_matrix=beta[None, :],
                intercept_vector=np.array([icpt]),
                num_classes=2, is_multinomial=False)
            self._copy_values(model)
            model._set_parent(self)
            model.summary = LogisticRegressionTrainingSummary(
                objective_history=list(res.loss_histories[kk]),
                total_iterations=int(res.iterations[kk]),
                total_evals=int(res.evals[kk]),
                total_dispatches=loss_fn.n_dispatches,
                n_models=n_models, stacked_evals=loss_fn.n_evals, **summary)
            models.append(model)
        return models

    def _fit_stacked_streamed(self, sds, reg_params: np.ndarray,
                              shared_labels: bool):
        """The out-of-core leg of :meth:`fit_stacked`: K binomial models
        over ONE shard set, each optimizer round ONE streamed epoch whose
        per-shard program is the stacked aggregator the in-core leg runs
        (``StackedStreamingLossFunction``; model j's label made from the
        shard's own label vector) — so the spill is read once per round,
        not once per model, and no label stack is staged. The optimizer is
        :class:`StackedHostLBFGS`: K serial L-BFGS coroutines whose
        pending trial points batch into each epoch, every model making
        exactly the decisions its serial streamed fit would (the parity
        test pins rtol 1e-9 under the f64 config)."""
        import jax.numpy as jnp

        from cycloneml_tpu.ml.optim.device_lbfgs import StackedHostLBFGS
        from cycloneml_tpu.ml.optim.loss import stacked_l2_scale
        from cycloneml_tpu.oocore import StackedStreamingLossFunction
        from cycloneml_tpu.ops import kernels

        n_models = len(reg_params)
        d = sds.n_features
        stats = sds.summary()   # write-pass moments: no stats epoch
        # the fp8 decision already ran at spill time (the
        # materialization-time envelope probe in shards._finalize_fp8);
        # the dequant scale folds into inv_std exactly like in-core
        fp8_scale = getattr(sds, "x_scale", None)
        std = self._stacked_standardization(stats, fp8_scale)
        # the write pass's histogram (it raises on labels that are no class
        # indices): zero label epochs
        x0 = self._stacked_start(sds.label_histogram(), n_models,
                                 shared_labels, stats)

        # a staged shard lies as the device's default layout puts it
        orientation = None
        if kernels.use_fused_kernels(sds.ctx):
            feature_major = kernels.default_feature_major(d)
            rows = sds.pad_rows // sds.ctx.mesh_runtime.data_parallelism
            if kernels.multinomial_sweep_tile(
                    rows, d, n_models,
                    getattr(sds, "x_dtype", np.float64),
                    feature_major) is not None:
                orientation = "feature_major" if feature_major \
                    else "row_major"
        loss_fn = StackedStreamingLossFunction(
            sds, self._stacked_aggregator(d, n_models, shared_labels,
                                          orientation),
            n_models, reg=reg_params,
            l2_scale=stacked_l2_scale(d, x0.shape[1], stats.std,
                                      self.get("standardization")),
            weight_sum=stats.weight_sum,
            extra_args=tuple(jnp.asarray(v) for v in std.extra_args))

        opt = StackedHostLBFGS(max_iter=self.get("maxIter"),
                               tol=self.get("tol"))
        res = opt.minimize(loss_fn, x0)
        if fp8_scale is not None \
                and not np.all(np.isfinite(np.asarray(res.x))):
            # e4m3 has no inf: overflow surfaces as NaN — re-spill the
            # shard set at the bf16 rung (PrecisionFallback event) and
            # refit
            bf16 = sds.to_instance_dataset(fp8_capable=False)
            try:
                return self._fit_stacked_streamed(bf16, reg_params,
                                                  shared_labels)
            finally:
                bf16.close()
        return self._stacked_models(res, loss_fn, std, streamed=True,
                                    orientation=orientation)

    def _fit_sparse(self, ds) -> "LogisticRegressionModel":
        """Binomial logistic regression over the sparse (ELL / ELL+COO
        hybrid) tier: same statistical semantics as the dense path —
        std-only standardization (sparsity-preserving, as the reference),
        log-odds intercept init, elastic net via OWL-QN/L-BFGS, LBFGS-B
        under bounds — with gather/segment-sum aggregators instead of
        block matmuls."""
        from cycloneml_tpu.dataset.sparse import (sparse_feature_std,
                                                  standardize_sparse_dataset)
        from cycloneml_tpu.ml.optim.sparse_aggregators import (
            binary_logistic_sparse, binary_logistic_sparse_hybrid)

        d = ds.n_features
        w_host = np.asarray(ds.w)
        y_host = np.asarray(ds.y)
        mask = w_host > 0
        num_classes = int(y_host[mask].max()) + 1 if mask.any() else 2
        family = self.get("family")
        if family == "multinomial" or (family == "auto" and num_classes > 2):
            raise NotImplementedError(
                "sparse-tier training is binomial only; hash or densify "
                "for multinomial")
        if num_classes > 2:
            # family="binomial" with >2 label classes: reject exactly as
            # the dense path (and the reference) does
            raise ValueError(
                f"Binomial family requires <= 2 label classes, found "
                f"{num_classes} (the reference rejects this too)")
        histogram = np.bincount(y_host[mask].astype(np.int64),
                                weights=w_host[mask], minlength=2)[:2]
        weight_sum = float(w_host[mask].sum())

        fit_intercept = self.get("fitIntercept")
        standardize = self.get("standardization")
        reg = self.get("regParam")
        alpha = self.get("elasticNetParam")
        l2 = (1.0 - alpha) * reg
        l1 = alpha * reg

        features_std = sparse_feature_std(ds)
        ds_std, inv_std = standardize_sparse_dataset(ds, features_std)

        agg = (binary_logistic_sparse_hybrid(d, fit_intercept)
               if ds.is_hybrid else binary_logistic_sparse(d, fit_intercept))
        n_coef = d + (1 if fit_intercept else 0)
        x0 = np.zeros(n_coef)
        if fit_intercept and 0 < histogram[1] < weight_sum:
            p1 = histogram[1] / weight_sum
            x0[d] = np.log(p1 / (1.0 - p1))
        l2_fn = l2_regularization(
            l2, d, fit_intercept, features_std=features_std,
            standardize=standardize) if l2 > 0 else None
        loss_fn = DistributedLossFunction(ds_std, agg, l2_fn, weight_sum)

        opt = optimizer_for(
            self.get("maxIter"), self.get("tol"), n_coef,
            bounds=self._flat_bounds(alpha, d, 2, False, fit_intercept,
                                     n_coef, features_std),
            l1=l1, n_penalized=d,
            penalty_std=None if standardize else features_std)
        state = self._optimize(opt, loss_fn, x0, (
            ds.n_rows, d, 2, float(weight_sum),
            np.asarray(histogram).round(6).tolist(),
            np.asarray(features_std).round(6).tolist(),
            reg, alpha, self.get("tol"), fit_intercept, standardize,
            "sparse",
        ))

        sol = state.x
        beta = sol[:d] * inv_std
        icpt = float(sol[d]) if fit_intercept else 0.0
        model = LogisticRegressionModel(
            coefficient_matrix=beta[None, :],
            intercept_vector=np.array([icpt]),
            num_classes=2, is_multinomial=False, uid=self.uid)
        self._copy_values(model)
        model._set_parent(self)
        model.summary = LogisticRegressionTrainingSummary(
            objective_history=list(state.loss_history),
            total_iterations=state.iteration,
            total_evals=loss_fn.n_evals,
            total_dispatches=loss_fn.n_dispatches,
            search_evals=list(state.search_evals) or None)
        return model

    def _fit_dataset(self, ds: InstanceDataset) -> "LogisticRegressionModel":
        import jax
        import jax.numpy as jnp

        from cycloneml_tpu.oocore import StreamingDataset, streaming_mode
        streamed = isinstance(ds, StreamingDataset)
        if not streamed and \
                streaming_mode(getattr(ds.ctx, "conf", None)) == "force":
            # explicit streaming mode: spill the in-core dataset to shards
            # and run the same fit over streamed epochs; the spill is owned
            # by THIS fit, so its files are removed once the model is built
            from cycloneml_tpu.oocore import shard_dataset
            sds = shard_dataset(ds)
            try:
                return self._fit_dataset(sds)
            finally:
                sds.close()

        d = ds.n_features
        # streamed datasets carry their Summarizer moments and the label
        # histogram from the shard WRITE pass — no stats epoch is paid
        cached = streamed or Summarizer.is_cached(ds)
        with tracing.span("phase", "fit.stats", cached=cached):
            stats = ds.summary() if streamed else Summarizer.summarize(ds)
        with tracing.span("phase", "fit.prepare"):
            if not streamed:
                # fp8 safety rail: the envelope probe may swap the quantized
                # dataset for its bf16 dequantization (event + profile field)
                from cycloneml_tpu.dataset.dataset import resolve_fp8_fit
                ds = resolve_fp8_fit(ds, stats, "LogisticRegression")
            fp8_scale = getattr(ds, "x_scale", None)
            features_std = stats.std
            weight_sum = stats.weight_sum

            # weighted class histogram (≈ the summary treeAggregate at
            # LogisticRegression.scala:515 area): a streamed dataset's from
            # its shard write pass, an in-core one's from its host labels,
            # cached on the immutable dataset either way
            hist = ds.label_histogram()
            num_classes = max(len(hist), 2) if ds.n_rows else 2
            family = self.get("family")
            if family == "auto":
                is_multinomial = num_classes > 2
            else:
                is_multinomial = family == "multinomial"
                if not is_multinomial and num_classes > 2:
                    raise ValueError(
                        f"Binomial family requires <= 2 label classes, found "
                        f"{num_classes} (the reference rejects this too)")
                num_classes = max(num_classes, 2)
            # one entry a class: a dataset of one label still has two
            histogram = np.append(hist, np.zeros(num_classes - len(hist)))

            fit_intercept = self.get("fitIntercept")
            standardize = self.get("standardization")
            reg = self.get("regParam")
            alpha = self.get("elasticNetParam")
            l2 = (1.0 - alpha) * reg
            l1 = alpha * reg

            # fitWithMean (ref LogisticRegression.scala:946-955, SPARK-34448):
            # with a free intercept, train on CENTERED standardized features —
            # decorrelates the intercept from offset features so small-variance
            # columns condition properly. Allowed exactly when the intercept is
            # unbounded; the intercept is mapped back after optimization.
            fit_with_mean = fit_intercept and all(
                self._opt(p) is None for p in ("lowerBoundsOnIntercepts",
                                               "upperBoundsOnIntercepts"))

            rt = ds.ctx.mesh_runtime
            from cycloneml_tpu.ops.kernels import use_fused_kernels
            from cycloneml_tpu.parallel import feature_sharding as fs
            m = fs.model_parallelism(rt)
            tp_active = (not is_multinomial) and m > 1 and d % m == 0 \
                and not streamed
            # fused Pallas kernels are the DEFAULT sweep on natively-lowered
            # backends (usePallasKernels=auto): one VMEM-resident row pass per
            # evaluation, bf16 blocks read at storage width with fp32 in-kernel
            # accumulation; the XLA-fused jnp aggregator stays as the fallback
            # (and the only path on CPU, where the interpreter is for tests)
            use_pallas = use_fused_kernels(ds.ctx)
            orientation = None  # tiling of the fused sweep, if it runs
            # EVERY fit path folds standardization (and fitWithMean centering)
            # INTO the aggregator read — no standardized copy exists anywhere:
            # replicated binomial/multinomial since r4; the feature-sharded TP
            # program and the Pallas kernel path since r5 (r4 verdict item 3 —
            # the paths that exist for models too big for one chip must not
            # carry 2× the memory they need). The fit's HBM working set is X
            # itself and the pre-fit standardize pass disappears.
            from cycloneml_tpu.ml.optim.loss import inv_std_vector
            inv_std = inv_std_vector(features_std)
            scaled_mean = stats.mean * inv_std if fit_with_mean else None
            # fp8 tier: dequantization folds into the replicated inv_std the
            # aggregators already carry — x̂ = (codes∘scale − μ)/σ =
            # codes∘(scale/σ) − μ/σ, so the AGGREGATOR sees scale∘inv_std
            # while scaled_mean (μ/σ) and the final unscaling (β/σ) keep the
            # original inv_std. The wide X never re-materializes.
            inv_std_agg = inv_std * fp8_scale if fp8_scale is not None \
                else inv_std

            if is_multinomial:
                # the fused K-class sweep where X's storage admits one (a
                # resident bf16 X: kernels.multinomial_sweep_tile), in the
                # tiling its layout dictates; the XLA aggregator otherwise
                # (always scaled: the TP alternative is binomial-only)
                if use_pallas and not streamed:
                    from cycloneml_tpu.ops.kernels import \
                        multinomial_sweep_orientation
                    orientation = multinomial_sweep_orientation(
                        ds.x, num_classes)
                if orientation is not None:
                    agg = aggregators.multinomial_logistic_pallas_scaled(
                        d, num_classes, fit_intercept,
                        feature_major=orientation == "feature_major")
                else:
                    agg = aggregators.multinomial_logistic_scaled(
                        d, num_classes, fit_intercept)
                n_coef = d * num_classes + (num_classes if fit_intercept else 0)
                x0 = np.zeros(n_coef)
                if fit_intercept and histogram.min() > 0:
                    logs = np.log(histogram / histogram.sum())
                    x0[d * num_classes:] = logs - logs.mean()
                l2_fn = l2_regularization(
                    l2, d * num_classes, fit_intercept,
                    features_std=np.tile(features_std, num_classes),
                    standardize=standardize) if l2 > 0 else None
            else:
                if use_pallas:
                    # the sweep's tiling follows the way X is stored (a
                    # streamed fit's shards are staged per dispatch: no
                    # resident array to observe, row-major as before)
                    from cycloneml_tpu.ops.kernels import \
                        glm_sweep_orientation
                    tiling = "row_major" if streamed else \
                        glm_sweep_orientation(ds.x, fp8_scale is not None)
                    agg = aggregators.binary_logistic_pallas_scaled(
                        d, fit_intercept,
                        feature_major=tiling == "feature_major")
                    if not tp_active:   # the TP program has its own sweep
                        orientation = tiling
                else:
                    agg = aggregators.binary_logistic_scaled(d, fit_intercept)
                n_coef = d + (1 if fit_intercept else 0)
                x0 = np.zeros(n_coef)
                if fit_intercept and 0 < histogram[1:].sum() < weight_sum:
                    p1 = histogram[1:].sum() / weight_sum
                    x0[d] = np.log(p1 / (1.0 - p1))
                l2_fn = l2_regularization(
                    l2, d, fit_intercept, features_std=features_std,
                    standardize=standardize) if l2 > 0 else None

            mu_or_zero = scaled_mean if fit_with_mean else np.zeros(d)
            if tp_active:
                # model axis present: feature-shard the RAW blocks, the
                # coefficients, AND the standardization vectors (SURVEY §5.7a
                # — the path for d beyond one device's HBM; binomial only, the
                # multinomial aggregator stays replicated for now). Narrow
                # data-tier blocks upcast at the TP boundary
                # (fs.accumulator_width — the engine keys optimizer state off
                # X's dtype).
                x_tp = fs.feature_sharded_put(rt, fs.accumulator_width(ds.x))
                loss_fn = fs.FeatureShardedLossFunction(
                    rt, x_tp, ds.y, ds.w, d, fit_intercept, l2_fn,
                    weight_sum, ctx=ds.ctx, inv_std=inv_std_agg,
                    scaled_mean=mu_or_zero)
            else:
                import jax.numpy as jnp
                from cycloneml_tpu.dataset.instance import compute_dtype
                # standardization vectors ride in the ACCUMULATOR tier: (d,)
                # replicated vectors are free next to X, and the fold's
                # corrections (inv_std∘g − μ̂·Σmult) must not round through the
                # bf16 data tier
                adt = compute_dtype()
                extras = (jnp.asarray(inv_std_agg.astype(adt)),
                          jnp.asarray(mu_or_zero.astype(adt)))
                if streamed:
                    # the streamed twin: SAME aggregator, same extras, same
                    # normalization — one loss/grad evaluation is one
                    # double-buffered epoch over the shard set
                    from cycloneml_tpu.oocore import StreamingLossFunction
                    loss_fn = StreamingLossFunction(
                        ds, agg, l2_fn, weight_sum, extra_args=extras)
                else:
                    loss_fn = DistributedLossFunction(
                        ds, agg, l2_fn, weight_sum, extra_args=extras)

            k_rows = num_classes if is_multinomial else 1
            opt = optimizer_for(
                self.get("maxIter"), self.get("tol"), n_coef,
                bounds=self._flat_bounds(
                    alpha, d, num_classes, is_multinomial, fit_intercept,
                    n_coef, features_std),
                l1=l1, n_penalized=d * k_rows,
                penalty_std=None if standardize else np.tile(features_std,
                                                             k_rows))
            if type(opt) is LBFGS:
                # chunked device optimizer: K whole iterations per dispatch
                # (two-loop + Wolfe + convergence all on device). Eligible when
                # the loss is the dense replicated tier with a standardized (or
                # no) L2, and no checkpointing (checkpoints want per-iteration
                # states).
                from cycloneml_tpu.conf import LBFGS_DEVICE_CHUNK
                chunk = int(ds.ctx.conf.get(LBFGS_DEVICE_CHUNK)) \
                    if hasattr(ds.ctx, "conf") else 0
                if (chunk > 0 and not self.get("checkpointDir")
                        and isinstance(loss_fn, DistributedLossFunction)
                        and (l2_fn is None or hasattr(l2_fn, "traceable"))):
                    from cycloneml_tpu.ml.optim.device_lbfgs import DeviceLBFGS
                    opt = DeviceLBFGS(max_iter=self.get("maxIter"),
                                      tol=self.get("tol"), chunk=chunk)
                    # this fit HAS a streaming twin: when chunk-halving bottoms
                    # out still over budget, degrade to it instead of
                    # warn-proceeding toward an OOM (cyclone.oocore.mode=auto)
                    opt.oocore_fallback = True

        from cycloneml_tpu.observe.costs import OutOfCoreRequired
        try:
            with tracing.span("phase", "fit.optimize",
                              optimizer=type(opt).__name__):
                state = self._optimize(opt, loss_fn, x0, (
                    ds.n_rows, d, num_classes, float(weight_sum),
                    np.asarray(histogram).round(6).tolist(),
                    np.asarray(features_std).round(6).tolist(),
                    reg, alpha, self.get("tol"), fit_intercept, standardize,
                    fit_with_mean,
                ))
        except OutOfCoreRequired as e:
            # the budget guard's terminal degradation: re-route the whole
            # fit through the streaming epoch engine (same objective, host
            # optimizer, O(shard) peak HBM) instead of OOMing/raising
            logger.warning("LogisticRegression: %s", e)
            from cycloneml_tpu.oocore import shard_dataset
            sds = shard_dataset(ds)
            try:
                return self._fit_dataset(sds)
            finally:
                sds.close()

        if fp8_scale is not None and not np.all(np.isfinite(state.x)):
            # e4m3 has no inf: an overflowing fp8 fit surfaces as NaN in
            # the solution — refit on the bf16 rung (belt to the probe's
            # braces; same event + profile surfacing)
            from cycloneml_tpu.dataset.dataset import fp8_fallback
            return self._fit_dataset(fp8_fallback(
                ds, "LogisticRegression", "non-finite fp8 solution"))

        with tracing.span("phase", "fit.finish"):
            sol = state.x
            if is_multinomial:
                wmat = sol[: d * num_classes].reshape(num_classes, d) * inv_std[None, :]
                icpt = sol[d * num_classes:] if fit_intercept else np.zeros(num_classes)
                if fit_with_mean:
                    # un-adapt: centered-problem intercepts back to original
                    # space (ref LogisticRegression.scala:1018-1024 dgemv adapt)
                    icpt = icpt - sol[: d * num_classes].reshape(
                        num_classes, d) @ scaled_mean
                if not self._has_bounds():
                    if reg == 0.0:
                        # center coefficients for identifiability, as the
                        # reference does when the multinomial problem has no
                        # regularization (LogisticRegression.scala:656-674,
                        # following glmnet)
                        wmat = wmat - wmat.mean(axis=0, keepdims=True)
                    # intercepts are NEVER regularized, so their additive
                    # constant stays free under ANY regParam — the reference
                    # centers them unconditionally for multinomial
                    # (LogisticRegression.scala:676-681); without this, L1
                    # fits match glmnet in coefficients but drift in
                    # intercepts by a shared constant
                    if fit_intercept:
                        icpt = icpt - icpt.mean()
                model = LogisticRegressionModel(
                    coefficient_matrix=wmat, intercept_vector=icpt,
                    num_classes=num_classes, is_multinomial=True, uid=self.uid)
            else:
                beta = sol[:d] * inv_std
                icpt = float(sol[d]) if fit_intercept else 0.0
                if fit_with_mean:
                    # ref LogisticRegression.scala:1027-1031: solution(num) -= adapt
                    icpt -= float(sol[:d] @ scaled_mean)
                model = LogisticRegressionModel(
                    coefficient_matrix=beta[None, :], intercept_vector=np.array([icpt]),
                    num_classes=2, is_multinomial=False, uid=self.uid)
            self._copy_values(model)
            model._set_parent(self)
            model.summary = LogisticRegressionTrainingSummary(
                objective_history=list(state.loss_history),
                total_iterations=state.iteration,
                total_evals=loss_fn.n_evals,
                total_dispatches=loss_fn.n_dispatches,
                streamed=streamed, orientation=orientation,
                num_classes=num_classes,
                search_evals=list(state.search_evals) or None)
            return model

    def copy(self, extra=None) -> "LogisticRegression":
        return super().copy(extra)


class LogisticRegressionModel(ProbabilisticClassificationModel,
                              _LogisticRegressionParams, HasLabelCol,
                              MLWritable, MLReadable):
    """Fitted model (ref LogisticRegressionModel at
    ml/classification/LogisticRegression.scala:1106-ish): margins, sigmoid/
    softmax probabilities, threshold-aware binary prediction."""

    def __init__(self, coefficient_matrix: Optional[np.ndarray] = None,
                 intercept_vector: Optional[np.ndarray] = None,
                 num_classes: int = 2, is_multinomial: bool = False, uid=None):
        super().__init__(uid)
        self._declare_lr_params()
        # the model carries labelCol so evaluate() scores the right column
        # (ref: LogisticRegressionModel extends HasLabelCol via its summary)
        self._p_label_col()
        self._coef = np.asarray(coefficient_matrix) if coefficient_matrix is not None else None
        self._icpt = np.asarray(intercept_vector) if intercept_vector is not None else None
        self._num_classes = num_classes
        self._is_multinomial = is_multinomial
        self.summary: Optional[LogisticRegressionTrainingSummary] = None

    # -- reference accessors ---------------------------------------------------
    @property
    def coefficients(self) -> DenseVector:
        if self._is_multinomial:
            raise ValueError("use coefficientMatrix for multinomial models")
        return Vectors.dense(self._coef[0])

    @property
    def intercept(self) -> float:
        if self._is_multinomial:
            raise ValueError("use interceptVector for multinomial models")
        return float(self._icpt[0])

    @property
    def coefficient_matrix(self) -> DenseMatrix:
        return DenseMatrix.from_array(self._coef)

    @property
    def intercept_vector(self) -> DenseVector:
        return Vectors.dense(self._icpt)

    def evaluate(self, frame: MLFrame) -> "BinaryLogisticRegressionSummary":
        return _lr_evaluate(self, frame)

    @property
    def num_classes(self) -> int:
        return self._num_classes

    @property
    def num_features(self) -> int:
        return self._coef.shape[1]

    def _raw_prediction(self, x: np.ndarray) -> np.ndarray:
        if self._is_multinomial:
            return x @ self._coef.T + self._icpt[None, :]
        m = x @ self._coef[0] + self._icpt[0]
        return np.stack([-m, m], axis=1)

    def _raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        if not self._is_multinomial:
            # binomial raw is (-m, m): probability is sigmoid(m), NOT softmax
            # of the pair (which would be sigmoid(2m)) — matches the
            # reference's raw2probabilityInPlace
            p1 = 1.0 / (1.0 + np.exp(-raw[:, 1]))
            return np.stack([1.0 - p1, p1], axis=1)
        z = raw - raw.max(axis=1, keepdims=True)
        e = np.exp(z)
        return e / e.sum(axis=1, keepdims=True)

    def _raw_to_prediction(self, raw: np.ndarray) -> np.ndarray:
        if not self._is_multinomial:
            t = self.get("threshold")
            prob1 = 1.0 / (1.0 + np.exp(-raw[:, 1]))
            return (prob1 > t).astype(np.float64)
        return np.argmax(raw, axis=1).astype(np.float64)

    def _save_data(self, path: str) -> None:
        save_arrays(path, coef=self._coef, icpt=self._icpt,
                    num_classes=np.array(self._num_classes),
                    is_multinomial=np.array(self._is_multinomial))

    def _load_data(self, path: str, meta) -> None:
        arrs = load_arrays(path)
        self._coef = arrs["coef"]
        self._icpt = arrs["icpt"]
        self._num_classes = int(arrs["num_classes"])
        self._is_multinomial = bool(arrs["is_multinomial"])

    def __repr__(self) -> str:
        return (f"LogisticRegressionModel(uid={self.uid}, "
                f"numClasses={self._num_classes}, numFeatures={self.num_features})")


def _lr_evaluate(model, frame: MLFrame) -> "BinaryLogisticRegressionSummary":
    """(ref LogisticRegressionModel.evaluate) — score the frame and return
    the binary metrics summary."""
    if model._is_multinomial:
        raise ValueError("evaluate() summary is binary-only "
                         "(ref BinaryLogisticRegressionSummary)")
    out = model.transform(frame)
    probs = np.asarray(out[model.get("probabilityCol")])
    scores = probs[:, 1] if probs.ndim == 2 else probs
    label_col = model.get("labelCol")
    labels = np.asarray(frame[label_col], dtype=np.float64)
    preds = np.asarray(out[model.get("predictionCol")], dtype=np.float64)
    return BinaryLogisticRegressionSummary(scores, labels, predictions=preds)


class LogisticRegressionTrainingSummary:
    """Objective history + iteration count (ref LogisticRegressionSummary /
    BinaryLogisticRegressionTrainingSummary — the optimizer trace; rich
    binary metrics come from ``model.evaluate(frame)``)."""

    def __init__(self, objective_history, total_iterations,
                 total_evals=None, total_dispatches=None, n_models=1,
                 streamed=False, orientation=None, search_evals=None,
                 num_classes=2, stacked_evals=None, pieces=None):
        self.objective_history = objective_history
        self.total_iterations = total_iterations
        # optimizer-path telemetry: loss/grad evaluations and host->device
        # round trips (the fused line search makes dispatches ~ iterations,
        # not ~ evals)
        self.total_evals = total_evals
        self.total_dispatches = total_dispatches
        # OWL-QN only (None otherwise): evaluations per turn, [1] for the
        # initial one and then one entry per iteration's line search;
        # sums to total_evals
        self.search_evals = search_evals
        # >1 when this model trained inside a stacked (vmapped model-axis)
        # fit: its compiles AND dispatches were shared by n_models models
        self.n_models = n_models
        # True when the fit ran on the out-of-core streaming engine —
        # explicitly (oocore.mode=force / a StreamingDataset input) or by
        # budget-guard degradation; dispatches then count SHARD dispatches
        self.streamed = streamed
        # tiling of the fused GLM sweep the fit ran ("feature_major" /
        # "row_major", ops/kernels.glm_sweep_orientation); None when the
        # sweep was not the fused kernel
        self.orientation = orientation
        # classes the fit's objective ran over (2: a binomial fit): with
        # ``orientation`` it says which fused sweep that was
        self.num_classes = num_classes
        # a stacked fit's shared evaluations: sweeps of X, each of which
        # served all n_models lanes (``total_evals`` is this model's own:
        # the ones it was still live for); None outside a stacked fit
        self.stacked_evals = stacked_evals
        # bf16 pieces an f32 operand of the fused class sweep's products
        # rode the MXU in (ops/kernels.SOFTMAX_PIECES); None elsewhere
        self.pieces = pieces


class BinaryLogisticRegressionSummary:
    """Binary metrics over a scored frame (ref:
    BinaryLogisticRegressionSummary — roc/pr curves, areaUnderROC,
    threshold sweeps; computed vectorized from one sorted pass)."""

    def __init__(self, scores: np.ndarray, labels: np.ndarray,
                 predictions: Optional[np.ndarray] = None):
        if len(scores) == 0:
            raise ValueError("cannot summarize an empty frame")
        self._predictions = predictions
        from cycloneml_tpu.ml.evaluation.evaluators import binary_curve_points
        (self._thresholds, self._tps, self._fps,
         self._p, self._n) = binary_curve_points(scores, labels)
        self._total = len(labels)
        self._labels = labels
        self._scores = scores

    @property
    def roc(self) -> np.ndarray:
        """(FPR, TPR) points including the (0,0) and (1,1) endpoints."""
        fpr = np.concatenate([[0.0], self._fps / self._n, [1.0]])
        tpr = np.concatenate([[0.0], self._tps / self._p, [1.0]])
        return np.column_stack([fpr, tpr])

    @property
    def area_under_roc(self) -> float:
        r = self.roc
        return float(np.trapezoid(r[:, 1], r[:, 0]))

    areaUnderROC = area_under_roc

    @property
    def pr(self) -> np.ndarray:
        """(recall, precision) points, starting at recall 0 (ref prepends
        (0, p) with the first point's precision)."""
        recall = self._tps / self._p
        precision = self._tps / np.maximum(self._tps + self._fps, 1e-300)
        return np.column_stack([np.concatenate([[0.0], recall]),
                                np.concatenate([[precision[0]], precision])])

    def precision_by_threshold(self) -> np.ndarray:
        p = self._tps / np.maximum(self._tps + self._fps, 1e-300)
        return np.column_stack([self._thresholds, p])

    def recall_by_threshold(self) -> np.ndarray:
        return np.column_stack([self._thresholds, self._tps / self._p])

    def f_measure_by_threshold(self, beta: float = 1.0) -> np.ndarray:
        p = self._tps / np.maximum(self._tps + self._fps, 1e-300)
        r = self._tps / self._p
        b2 = beta * beta
        f = (1 + b2) * p * r / np.maximum(b2 * p + r, 1e-300)
        return np.column_stack([self._thresholds, f])

    @property
    def accuracy(self) -> float:
        # the model's own predictions (threshold-aware) when available
        pred = (self._predictions if self._predictions is not None
                else (self._scores > 0.5).astype(np.float64))
        return float((pred == self._labels).mean())
