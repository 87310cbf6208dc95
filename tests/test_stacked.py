"""Stacked (vmapped model-axis) multi-model training: seeded equivalence
against the serial loop, per-model convergence masks, and the
compile-amortization contract (one optimizer-step compile for K models).

The equivalence fits run with ``tol=0`` and a fixed iteration budget:
stacked and serial trajectories are then step-aligned and agree to within
accumulated-ulp noise (~1e-9), far inside the 1e-5 acceptance tolerance.
(With a finite tol, a last-ulp difference in one loss value can flip the
convergence test one iteration early/late — both results are within tol of
the optimum, but the comparison would measure the flip, not the engine.)
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.ml.classification import LogisticRegression, OneVsRest
from cycloneml_tpu.ml.evaluation import BinaryClassificationEvaluator
from cycloneml_tpu.ml.tuning import (
    CrossValidator, ParamGridBuilder, TrainValidationSplit,
)
from cycloneml_tpu.observe import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _multiclass(seed=20, n=400, k=4):
    rng = np.random.RandomState(seed)
    centers = rng.randn(k, 3) * 4.0
    y = rng.randint(0, k, n).astype(np.float64)
    x = centers[y.astype(int)] + 0.6 * rng.randn(n, 3)
    return x, y


def _binary_frame(ctx, seed=21, n=400):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4)
    y = (x @ rng.randn(4) + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return MLFrame(ctx, {"features": x, "label": y})


class TestStackedOneVsRest:
    def test_matches_serial_loop(self, ctx):
        x, y = _multiclass()
        frame = MLFrame(ctx, {"features": x, "label": y})
        clf = LogisticRegression(maxIter=60, tol=0.0, regParam=0.01)
        stacked = OneVsRest(classifier=clf, parallelism=4).fit(frame)
        serial = OneVsRest(classifier=clf, parallelism=1).fit(frame)
        assert stacked.num_classes == serial.num_classes == 4
        for ms, mr in zip(stacked.models, serial.models):
            # the stacked engine must reproduce the serial loop, not just
            # some optimum (acceptance: within 1e-5; observed ~1e-9)
            np.testing.assert_allclose(ms._coef, mr._coef, atol=1e-5)
            np.testing.assert_allclose(ms._icpt, mr._icpt, atol=1e-5)
            assert ms.summary.n_models == 4
            assert mr.summary.n_models == 1
        np.testing.assert_array_equal(
            stacked.transform(frame)["prediction"],
            serial.transform(frame)["prediction"])

    def test_one_compile_for_k_models(self, ctx):
        """Acceptance: K >= 4 classes, parallelism 4 — the optimizer step
        compiles ONCE, proven by program-cache/compile spans and
        FitProfile.n_models."""
        from cycloneml_tpu.parallel import collectives

        x, y = _multiclass(seed=33, n=320, k=5)
        frame = MLFrame(ctx, {"features": x, "label": y})
        # drop programs cached by earlier tests so THIS fit pays (and
        # records) the one compile the acceptance criterion counts
        collectives.clear_program_cache()
        tracer = tracing.enable()
        mark = tracer.mark()
        try:
            ovr = OneVsRest(
                classifier=LogisticRegression(maxIter=40, tol=0.0),
                parallelism=4).fit(frame)
        finally:
            tracing.disable()
        assert ovr.num_classes == 5
        prof = tracer.profile_for(since=mark)
        assert prof.n_models == 5
        chunk_compiles = [
            s for s in tracer.snapshot(mark)
            if s.kind == "compile" and s.name == "lbfgs.stacked_chunk"]
        assert len(chunk_compiles) == 1, (
            "the stacked optimizer step must compile exactly once for all "
            f"K models, saw {len(chunk_compiles)}")
        # and the whole fit's compile count is O(1), never O(K): the psum
        # aggregation + the chunk program (+ at most one summary pass)
        assert prof.compile_count <= 4

    def test_parallelism_one_stays_serial(self, ctx):
        x, y = _multiclass(seed=5, n=200, k=3)
        frame = MLFrame(ctx, {"features": x, "label": y})
        m = OneVsRest(classifier=LogisticRegression(maxIter=20),
                      parallelism=1).fit(frame)
        assert all(mm.summary.n_models == 1 for mm in m.models)

    def test_ineligible_classifier_falls_back(self, ctx):
        # elastic net has an L1 component -> OWLQN -> serial fallback
        x, y = _multiclass(seed=6, n=200, k=3)
        frame = MLFrame(ctx, {"features": x, "label": y})
        clf = LogisticRegression(maxIter=20, regParam=0.1,
                                 elasticNetParam=0.5)
        m = OneVsRest(classifier=clf, parallelism=4).fit(frame)
        assert m.num_classes == 3
        assert all(mm.summary.n_models == 1 for mm in m.models)

    def test_label_matrix_uses_data_tier_dtype(self, ctx, monkeypatch):
        """The OvR relabel materializes ONE (n, K) matrix in the data-tier
        dtype — not K fp64 host vectors."""
        from cycloneml_tpu.dataset.instance import compute_dtype
        x, y = _multiclass(seed=7, n=150, k=3)
        frame = MLFrame(ctx, {"features": x, "label": y})
        seen = []
        orig = MLFrame.with_column

        def spy(self, name, values):
            if name == "_ovr_label":
                seen.append(np.asarray(values).dtype)
            return orig(self, name, values)

        monkeypatch.setattr(MLFrame, "with_column", spy)
        OneVsRest(classifier=LogisticRegression(maxIter=5),
                  parallelism=1).fit(frame)
        assert seen and all(dt == np.dtype(compute_dtype()) for dt in seen)


class TestStackedTuning:
    def _grid(self, lr):
        return ParamGridBuilder().add_grid(
            lr.regParam, [0.0, 0.1, 1.0]).build()

    def test_cross_validator_matches_serial(self, ctx):
        frame = _binary_frame(ctx)
        lr = LogisticRegression(maxIter=40, tol=0.0)
        ev = BinaryClassificationEvaluator()
        grid = self._grid(lr)
        stacked = CrossValidator(estimator=lr, estimator_param_maps=grid,
                                 evaluator=ev, parallelism=4,
                                 numFolds=3).fit(frame)
        serial = CrossValidator(estimator=lr, estimator_param_maps=grid,
                                evaluator=ev, parallelism=1,
                                numFolds=3).fit(frame)
        np.testing.assert_allclose(stacked.avg_metrics, serial.avg_metrics,
                                   atol=1e-8)
        np.testing.assert_allclose(
            stacked.best_model._coef, serial.best_model._coef, atol=1e-5)

    def test_train_validation_split_matches_serial(self, ctx):
        frame = _binary_frame(ctx, seed=31)
        lr = LogisticRegression(maxIter=40, tol=0.0)
        ev = BinaryClassificationEvaluator()
        grid = self._grid(lr)
        stacked = TrainValidationSplit(
            estimator=lr, estimator_param_maps=grid, evaluator=ev,
            parallelism=4).fit(frame)
        serial = TrainValidationSplit(
            estimator=lr, estimator_param_maps=grid, evaluator=ev,
            parallelism=1).fit(frame)
        np.testing.assert_allclose(stacked.validation_metrics,
                                   serial.validation_metrics, atol=1e-8)

    def test_heterogeneous_maps_fall_back(self, ctx):
        """Maps varying a non-vmappable param (maxIter) must take the
        serial path and still produce correct results."""
        frame = _binary_frame(ctx, seed=32)
        lr = LogisticRegression(tol=0.0)
        grid = ParamGridBuilder().add_grid(lr.maxIter, [5, 15]).build()
        cv = CrossValidator(estimator=lr, estimator_param_maps=grid,
                            evaluator=BinaryClassificationEvaluator(),
                            parallelism=4, numFolds=2)
        assert cv._stack_plan(frame) is None
        model = cv.fit(frame)
        assert len(model.avg_metrics) == 2

    def test_array_valued_param_falls_back_cleanly(self, ctx):
        """Regression: a grid carrying an array-valued param (even held
        constant) must fall back serially, not crash on the ambiguous
        ndarray truth value while planning."""
        frame = _binary_frame(ctx, seed=33, n=120)
        lr = LogisticRegression(maxIter=5, tol=0.0)
        bounds = np.full((1, 4), -10.0)
        grid = (ParamGridBuilder()
                .add_grid(lr.regParam, [0.0, 0.1])
                .add_grid(lr.lowerBoundsOnCoefficients, [bounds])
                .build())
        cv = CrossValidator(estimator=lr, estimator_param_maps=grid,
                            evaluator=BinaryClassificationEvaluator(),
                            parallelism=4, numFolds=2)
        assert cv._stack_plan(frame) is None  # bounded fits are serial
        model = cv.fit(frame)
        assert len(model.avg_metrics) == 2

    def test_multiclass_labels_fall_back(self, ctx):
        x, y = _multiclass(seed=34, n=200, k=3)
        frame = MLFrame(ctx, {"features": x, "label": y})
        lr = LogisticRegression(maxIter=10)
        grid = self._grid(lr)
        cv = CrossValidator(estimator=lr, estimator_param_maps=grid,
                            evaluator=BinaryClassificationEvaluator(),
                            parallelism=4, numFolds=2)
        # binomial-only: a multiclass label column disables the plan
        assert cv._stack_plan(frame) is None


class TestConvergenceMasks:
    def _stacked_loss(self, ctx, regs):
        import jax.numpy as jnp

        from cycloneml_tpu.ml.optim import aggregators
        from cycloneml_tpu.ml.optim.loss import (
            StackedDistributedLossFunction, inv_std_vector,
            stacked_l2_scale)
        from cycloneml_tpu.ml.stat import Summarizer

        frame = _binary_frame(ctx, seed=40)
        ds = frame.to_instance_dataset("features", "label", None)
        stats = Summarizer.summarize(ds)
        inv_std = inv_std_vector(stats.std)
        scaled_mean = stats.mean * inv_std
        d = ds.n_features
        K = len(regs)
        xdt = np.dtype(str(ds.x.dtype))
        # K models over the dataset's own 0/1 labels (a regParam grid)
        agg = aggregators.stacked_binary_logistic_scaled(
            d, K, True, shared_labels=True)
        loss = StackedDistributedLossFunction(
            ds, agg, K, reg=np.asarray(regs),
            l2_scale=stacked_l2_scale(d, d + 1),
            weight_sum=stats.weight_sum,
            extra_args=(jnp.asarray(inv_std.astype(xdt)),
                        jnp.asarray(scaled_mean.astype(xdt))))
        return loss, d

    def test_models_freeze_at_their_own_iteration(self, ctx):
        """Models converging at different iterations: heavier L2 converges
        first and freezes; the rest keep iterating (no lockstep stop)."""
        from cycloneml_tpu.ml.optim.device_lbfgs import StackedDeviceLBFGS

        regs = np.array([0.0, 0.1, 5.0])
        loss, d = self._stacked_loss(ctx, regs)
        x0 = np.zeros((3, d + 1))
        res = StackedDeviceLBFGS(max_iter=100, tol=1e-6,
                                 chunk=8).minimize(loss, x0)
        iters = np.asarray(res.iterations)
        assert (iters > 0).all()
        # different objectives converge at different iterations — the masks
        # must record each model's OWN stop, not a lockstep count
        assert len(set(iters.tolist())) > 1, iters
        assert all(r in ("function value converged", "gradient converged")
                   for r in res.converged_reasons)
        # a frozen model's history stops where it converged: history is
        # f(x0) plus one entry per LIVE iteration
        for kk in range(3):
            assert len(res.loss_histories[kk]) == iters[kk] + 1
        # per-model eval ledgers: every live iteration costs at least one
        # evaluation (plus the fused initial one), and the loss function's
        # global ledger counts batched steps, so it bounds every per-model
        # count (frozen lanes never out-accrue the batched step count)
        evals = np.asarray(res.evals)
        assert (evals >= iters + 1).all()
        assert loss.n_evals >= int(evals.max())

    def test_freeze_is_chunk_size_invariant(self, ctx):
        """Regression: per-model convergence codes must carry ACROSS chunk
        dispatches. Without that, every chunk boundary un-freezes converged
        models for one spurious iteration and the result depends on the
        chunk size."""
        from cycloneml_tpu.ml.optim.device_lbfgs import StackedDeviceLBFGS

        regs = np.array([0.0, 5.0])
        loss, d = self._stacked_loss(ctx, regs)
        x0 = np.zeros((2, d + 1))
        a = StackedDeviceLBFGS(max_iter=100, tol=1e-6,
                               chunk=8).minimize(loss, x0)
        b = StackedDeviceLBFGS(max_iter=100, tol=1e-6,
                               chunk=2).minimize(loss, x0)
        np.testing.assert_array_equal(a.iterations, b.iterations)
        np.testing.assert_array_equal(a.x, b.x)
        for ha, hb in zip(a.loss_histories, b.loss_histories):
            np.testing.assert_allclose(ha, hb, rtol=0)

    def test_frozen_models_stay_frozen(self, ctx):
        """Once a model's convergence code fires, further chunks must leave
        its state bitwise untouched: running the SAME stacked program with
        the budget cut exactly at that model's convergence iteration yields
        the identical per-model solution and history."""
        from cycloneml_tpu.ml.optim.device_lbfgs import StackedDeviceLBFGS

        regs = np.array([0.0, 5.0])
        loss, d = self._stacked_loss(ctx, regs)
        x0 = np.zeros((2, d + 1))
        full = StackedDeviceLBFGS(max_iter=100, tol=1e-6,
                                  chunk=8).minimize(loss, x0)
        early, late = int(np.argmin(full.iterations)), \
            int(np.argmax(full.iterations))
        assert full.iterations[early] < full.iterations[late]
        cut = StackedDeviceLBFGS(
            max_iter=int(full.iterations[early]), tol=1e-6,
            chunk=8).minimize(loss, x0)
        assert int(cut.iterations[early]) == int(full.iterations[early])
        np.testing.assert_array_equal(full.x[early], cut.x[early])
        np.testing.assert_allclose(full.loss_histories[early],
                                   cut.loss_histories[early], rtol=0)


    def test_one_model_stack_walks_the_serial_chunk(self, ctx):
        """The stacked chunk is ``vmap`` of the parts the serial chunk is
        built from: at K = 1, in float64, it takes the serial chunk's
        iterations and evaluations and sees its losses."""
        import jax.numpy as jnp

        from cycloneml_tpu.ml.optim import aggregators
        from cycloneml_tpu.ml.optim.device_lbfgs import (
            DeviceLBFGS, StackedDeviceLBFGS)
        from cycloneml_tpu.ml.optim.loss import (
            DistributedLossFunction, l2_regularization)

        stacked_f, d = self._stacked_loss(ctx, np.array([0.1]))
        stacked = StackedDeviceLBFGS(max_iter=100, tol=1e-6, chunk=8) \
            .minimize(stacked_f, np.zeros((1, d + 1)))

        ds = _binary_frame(ctx, seed=40).to_instance_dataset(
            "features", "label", None)
        serial_f = DistributedLossFunction(
            ds, aggregators.binary_logistic_scaled(d, True),
            l2_regularization(0.1, d, True, standardize=True),
            stacked_f.weight_sum,
            extra_args=tuple(stacked_f._agg_call.arrays()[-2:]))
        serial = DeviceLBFGS(max_iter=100, tol=1e-6, chunk=8).minimize(
            serial_f, np.zeros(d + 1))

        assert serial.iteration > 3
        assert int(stacked.iterations[0]) == serial.iteration
        assert int(stacked.evals[0]) == serial_f.n_evals
        assert stacked.converged_reasons[0] == serial.converged_reason
        np.testing.assert_allclose(stacked.loss_histories[0],
                                   serial.loss_history, rtol=1e-12)
        np.testing.assert_allclose(stacked.x[0], serial.x, rtol=1e-9,
                                   atol=1e-12)


class TestStackedGradientDescent:
    def test_matches_serial_per_model(self, ctx):
        from cycloneml_tpu.ml.optim import aggregators
        from cycloneml_tpu.ml.optim.gradient_descent import (
            GradientDescent, SquaredL2Updater, StackedGradientDescent)

        frame = _binary_frame(ctx, seed=50, n=320)
        ds = frame.to_instance_dataset("features", "label", None)
        y = np.asarray(ds.unpad(ds.y_host()))
        d = ds.n_features
        agg = aggregators.binary_logistic(d, fit_intercept=False)
        xdt = np.dtype(str(ds.x.dtype))
        # two models over the same X: the plain labels and their flip —
        # different objectives, different convergence iterations
        y2 = np.stack([y, 1.0 - y], axis=1).astype(xdt)
        y_pad = np.zeros((len(ds.y_host()), 2), dtype=xdt)
        y_pad[ds.valid_indices()] = y2
        ds_st = ds.derive(
            y=ctx.mesh_runtime.device_put_sharded_rows(y_pad))

        kw = dict(step_size=1.0, num_iterations=60, reg_param=0.01,
                  mini_batch_fraction=0.8, updater=SquaredL2Updater(),
                  convergence_tol=1e-3, seed=3)
        W, hists = StackedGradientDescent(**kw).optimize_stacked(
            ds_st, agg, np.zeros((2, d)))
        for kk, yk in enumerate((y, 1.0 - y)):
            y_pad1 = np.zeros(len(ds.y_host()), dtype=xdt)
            y_pad1[ds.valid_indices()] = yk.astype(xdt)
            ds_k = ds.derive(
                y=ctx.mesh_runtime.device_put_sharded_rows(y_pad1))
            w_ref, h_ref = GradientDescent(**kw).optimize(
                ds_k, agg, np.zeros(d))
            np.testing.assert_allclose(W[kk], w_ref, atol=1e-9)
            np.testing.assert_allclose(hists[kk], h_ref, atol=1e-9)


def test_safe_fit_parallelism_reports_stacked_width(ctx):
    from cycloneml_tpu.mesh import safe_fit_parallelism
    # thread pools stay capped on the shared 8-device mesh...
    assert safe_fit_parallelism(4) == 1
    # ...but a stacked fit IS the sanctioned parallel path at full width
    assert safe_fit_parallelism(4, stacked_width=7) == 7


@pytest.mark.parametrize("n_devices", [1])
def test_stacked_equivalence_on_one_device_mesh(n_devices, tmp_path):
    """The stacked engine must behave identically on a single-device mesh
    (no collectives to deadlock, but the same vmapped program); run in a
    subprocess so the device count differs from the session mesh."""
    script = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from cycloneml_tpu.conf import CycloneConf
        from cycloneml_tpu.context import CycloneContext
        from cycloneml_tpu.dataset.frame import MLFrame
        from cycloneml_tpu.ml.classification import (LogisticRegression,
                                                     OneVsRest)
        ctx = CycloneContext(CycloneConf().set(
            "cyclone.master", "local-mesh[{n_devices}]"))
        rng = np.random.RandomState(9)
        centers = rng.randn(4, 3) * 4.0
        y = rng.randint(0, 4, 240).astype(np.float64)
        x = centers[y.astype(int)] + 0.6 * rng.randn(240, 3)
        frame = MLFrame(ctx, {{"features": x, "label": y}})
        clf = LogisticRegression(maxIter=40, tol=0.0, regParam=0.01)
        st = OneVsRest(classifier=clf, parallelism=4).fit(frame)
        se = OneVsRest(classifier=clf, parallelism=1).fit(frame)
        assert all(m.summary.n_models == 4 for m in st.models)
        for ms, mr in zip(st.models, se.models):
            np.testing.assert_allclose(ms._coef, mr._coef, atol=1e-5)
            np.testing.assert_allclose(ms._icpt, mr._icpt, atol=1e-5)
        print("ONE_DEVICE_OK")
    """)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                          capture_output=True, text=True, timeout=420,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ONE_DEVICE_OK" in proc.stdout
