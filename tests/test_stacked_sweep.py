"""The stacked binomial sweep — K independent binary models from ONE read of
X, the K-class sweep's body under K sigmoids — and ``OneVsRest`` through it
from a device-resident dataset: the kernel in the Pallas interpreter and its
row-blocked XLA twin against K serial binomial evaluations in float64 numpy
and against the benchmark's plain reference
(``perfbench/reference/ovr_logistic_l2.py``), and the estimator's dataset
path against its frame path and K serial ``LogisticRegression`` fits. On the
chip the same kernel lowers to Mosaic (``tests/test_glm_layout_aot.py``
compiles it at the cell's shape; ``chip_smoke.py`` runs it)."""

import numpy as np
import pytest

from cycloneml_tpu.ops import kernels

ROW_AXES = ("replica", "data")


def _case(n, d, k, weights, seed, shared=False, scale=0.3):
    """bf16 X (its values are the float64 truth's), class-index labels (0/1
    labels where the models share them), weights that are 0/1 or ONE value,
    and a ``(k, d + 1)`` coefficient stack."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    y = rng.randint(0, 2 if shared else k, n).astype(np.float64)
    w = (rng.rand(n) > 0.2).astype(np.float64) if weights == "zero_one" \
        else np.full(n, 0.75)
    inv_std = rng.rand(d) + 0.5
    mu = rng.randn(d) * 0.3
    coef = rng.randn(k, d + 1) * scale
    return x, y, w, inv_std, mu, coef


def _serial_float64(x, y, w, inv_std, mu, coef, shared=False):
    """K serial binomial evaluations in float64 numpy: model j's
    ``(loss, grad)`` of the scaled binary aggregator on ``1[y == j]``."""
    import jax.numpy as jnp
    x = np.asarray(x.astype(jnp.float32), np.float64)
    d = x.shape[1]
    xh = x * inv_std - mu
    losses, grads = [], []
    for j, row in enumerate(coef):
        yj = y if shared else (y == j).astype(np.float64)
        m = xh @ row[:d] + row[d]
        losses.append(np.sum(w * (np.logaddexp(0.0, m) - yj * m)))
        mult = w * (1.0 / (1.0 + np.exp(-m)) - yj)
        grads.append(np.append(mult @ xh, mult.sum()))
    return np.array(losses), np.stack(grads), float(w.sum())


def _close(got, want_loss, want_grad, tol):
    np.testing.assert_allclose(np.asarray(got["loss"]), want_loss, rtol=tol)
    scale = np.abs(want_grad).max(axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got["grad"]) / scale,
                               want_grad / scale, rtol=0, atol=tol)


@pytest.mark.parametrize("weights", ["zero_one", "one_value"])
@pytest.mark.parametrize("k", [3, 10])
@pytest.mark.parametrize("feature_major,d", [(True, 16), (True, 48),
                                             (False, 128), (False, 256)])
def test_sweep_matches_k_serial_binomial_evaluations(ctx, feature_major, d,
                                                     k, weights):
    """Both tilings at d = 16·j and d = 128·j, a last tile of 188 rows
    (masked, not padded), 0/1 and one-value weights: every model's loss and
    gradient are its own serial evaluation's, f32-faithful."""
    case = _case(700, d, k, weights, seed=k + d)
    got = kernels.fused_stacked_binomial_scaled(
        *case, d, k, True, interpret=True, feature_major=feature_major,
        tile=256)
    loss, grad, count = _serial_float64(*case)
    assert got["loss"].shape == (k,) and got["grad"].shape == (k, d + 1)
    _close(got, loss, grad, 1e-6)
    assert float(got["count"]) == pytest.approx(count, rel=1e-6)


@pytest.mark.parametrize("weights", ["zero_one", "one_value"])
@pytest.mark.parametrize("k", [3, 10])
@pytest.mark.parametrize("d", [16, 128])
def test_twin_matches_k_serial_binomial_evaluations(ctx, d, k, weights):
    """The row-blocked XLA twin (what the CPU runs), chunks of 96 rows and
    a last chunk of 28: float64 here, so the sums agree to rounding."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    x, y, w, inv_std, mu, coef = _case(700, d, k, weights, seed=d - k)
    agg = aggregators.stacked_binary_logistic_scaled(d, k, True)
    real = aggregators.STACKED_CHUNK_BYTES
    aggregators.STACKED_CHUNK_BYTES = 96 * 4 * k
    try:
        got = agg(jnp.asarray(x, jnp.float64), *(jnp.asarray(a) for a in (
            y, w, inv_std, mu, coef)))
    finally:
        aggregators.STACKED_CHUNK_BYTES = real
    loss, grad, count = _serial_float64(x, y, w, inv_std, mu, coef)
    _close(got, loss, grad, 1e-11)
    assert float(got["count"]) == pytest.approx(count, rel=1e-12)


@pytest.mark.parametrize("path", ["kernel", "twin"])
def test_shared_labels_are_the_same_model_k_times(ctx, path):
    """``shared_labels``: every model fits ``y`` itself (a regParam grid's
    models differ in their penalty alone) — no intercept here."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    n, d, k = 640, 48, 4
    x, y, w, inv_std, mu, coef = _case(n, d, k, "zero_one", seed=9,
                                       shared=True)
    coef = coef[:, :d]
    if path == "kernel":
        got = kernels.fused_stacked_binomial_scaled(
            x, y, w, inv_std, mu, coef, d, k, False, shared_labels=True,
            interpret=True, feature_major=True, tile=256)
    else:
        got = aggregators.stacked_binary_logistic_scaled(d, k, False, True)(
            jnp.asarray(x, jnp.float64),
            *(jnp.asarray(a) for a in (y, w, inv_std, mu, coef)))
    loss, grad, _ = _serial_float64(
        x, y, w, inv_std, mu, np.hstack([coef, np.zeros((k, 1))]),
        shared=True)
    _close(got, loss, grad[:, :d], 1e-6 if path == "kernel" else 1e-11)


def test_a_tile_of_garbage_past_n_changes_nothing(ctx):
    d, k = 48, 10
    case = _case(640, d, k, "zero_one", seed=2)
    whole, tailed = (kernels.fused_stacked_binomial_scaled(
        *case, d, k, True, interpret=True, feature_major=True, tile=t)
        for t in (128, 512))
    np.testing.assert_allclose(np.asarray(whole["loss"]),
                               np.asarray(tailed["loss"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(whole["grad"]),
                               np.asarray(tailed["grad"]), rtol=0,
                               atol=1e-6 * np.abs(whole["grad"]).max())


def test_one_body_two_links(ctx):
    """The stacked sweep IS the K-class sweep: one kernel body
    (``_run_multinomial``) under a static link, one tile rule."""
    import inspect
    src = inspect.getsource(kernels)
    assert src.count("def _run_multinomial(") == 1
    assert src.count("pl.pallas_call(\n        glm_sweep_multinomial") == 1
    assert set(kernels._CLASS_LINKS) == {"softmax", "sigmoid",
                                         "shared_sigmoid"}
    # the softmax link's outputs are what they were: one loss row
    x, y, w, inv_std, mu, coef = _case(256, 16, 3, "zero_one", seed=1)
    out = kernels.fused_multinomial_logistic_scaled(
        x, y, w, inv_std, mu, coef.T.ravel()[:16 * 3 + 3], 16, 3, True,
        interpret=True, feature_major=True, tile=128)
    assert out["loss"].shape == () and out["grad"].shape == (16 * 3 + 3,)


# -- against the benchmark's plain reference ----------------------------------

def _dataset(ctx, n, d, k, seed):
    """A device-resident bf16 dataset and the benchmark's view of it:
    ``(InstanceDataset, (x, y, mesh, axes))`` with class labels from
    ``perfbench.class_labels``."""
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from perfbench import class_labels
    rt = ctx.mesh_runtime
    rng = np.random.RandomState(seed)
    x = rt.device_put_sharded_rows(
        np.asarray(jnp.asarray(rng.randn(n, d), jnp.bfloat16)))
    labels = class_labels.of(x, rt.mesh, ROW_AXES, classes=k, noise=0.3,
                             data_seed=seed)
    ds = InstanceDataset(
        ctx, x, labels,
        rt.device_put_sharded_rows(np.ones(n, np.float32)), n, d)
    return ds, (x, labels, rt.mesh, ROW_AXES)


@pytest.fixture
def labels_of(monkeypatch):
    """Point the reference's label model at a test's own (k, seed)."""
    from perfbench import class_labels

    def point(k, seed):
        monkeypatch.setattr(class_labels, "spec", lambda name: {
            "classes": k, "noise": 0.3, "data_seed": seed})
    return point


@pytest.mark.parametrize("path", ["kernel", "twin"])
@pytest.mark.parametrize("k", [3, 10])
def test_sweep_against_the_plain_references_loss_and_gradient(ctx, labels_of,
                                                              path, k):
    """Every model's objective and gradient at a common point, from the
    sweep's sums and the standardisation the estimator folds around them,
    are ``reference/ovr_logistic_l2.py``'s."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    from perfbench.reference import ovr_logistic_l2
    n, d, reg = 2048, 48, 0.01
    ds, data = _dataset(ctx, n, d, k, seed=21)
    labels_of(k, 21)
    prob = ovr_logistic_l2.Problem(data, {"regParam": reg})
    rng = np.random.RandomState(4)
    wmat, icpt = rng.randn(k, d) * 0.2, rng.randn(k) * 0.2
    want_f, want_g, want_g0 = prob.loss_grad(wmat, icpt)
    x, y = np.asarray(data[0]), np.asarray(data[1])
    args = (jnp.asarray(x), y, np.ones(n), prob.inv_std,
            prob.mean * prob.inv_std, np.hstack([wmat, icpt[:, None]]))
    if path == "kernel":
        got = kernels.fused_stacked_binomial_scaled(
            *args, d, k, True, interpret=True, feature_major=True, tile=512)
    else:
        got = aggregators.stacked_binary_logistic_scaled(d, k, True)(
            jnp.asarray(x, jnp.float64), *(jnp.asarray(a) for a in args[1:]))
    f = np.asarray(got["loss"]) / n + 0.5 * reg * np.sum(wmat * wmat, axis=1)
    g = np.asarray(got["grad"]) / n
    np.testing.assert_allclose(f, want_f, rtol=2e-6)
    np.testing.assert_allclose(g[:, :d] + reg * wmat, want_g, rtol=0,
                               atol=2e-6 * np.abs(want_g).max())
    np.testing.assert_allclose(g[:, d], want_g0, rtol=0,
                               atol=2e-6 * np.abs(want_g0).max())


# -- the estimator -------------------------------------------------------------

def _interpreted(monkeypatch, stored):
    """The package never interprets: the test makes ``pallas_call`` do so,
    and answers the layout question a CPU array cannot."""
    native_call = kernels.pl.pallas_call
    monkeypatch.setattr(
        kernels.pl, "pallas_call",
        lambda *a, **kw: native_call(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(kernels, "stored_feature_major",
                        lambda a: stored == "feature_major")


def _multiclass(seed, n, k, d=5):
    rng = np.random.RandomState(seed)
    centers = rng.randn(k, d) * 2.0
    y = rng.randint(0, k, n).astype(np.float64)
    return centers[y.astype(int)] + rng.randn(n, d), y


def _base(**kw):
    from cycloneml_tpu.ml.classification import LogisticRegression
    return LogisticRegression(**{"maxIter": 40, "tol": 0.0, "regParam": 0.01,
                                 **kw})


@pytest.mark.parametrize("k", [3, 10])
def test_dataset_fit_equals_frame_fit_equals_k_serial_fits(ctx, k):
    """``OneVsRest.fit(dataset)`` is ``OneVsRest.fit(frame)`` (the frame
    builds the dataset and takes the same path) and, model by model, the
    ``LogisticRegression`` fit of that class's 0/1 relabelling."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import OneVsRest
    x, y = _multiclass(seed=30 + k, n=640, k=k)
    frame = MLFrame(ctx, {"features": x, "label": y})
    ds = InstanceDataset.from_numpy(ctx, x, y)
    ovr = OneVsRest(classifier=_base(), parallelism=k)
    from_ds, from_frame = ovr.fit(ds), ovr.fit(frame)
    assert from_ds.num_classes == from_frame.num_classes == k
    for j, (a, b) in enumerate(zip(from_ds.models, from_frame.models)):
        np.testing.assert_array_equal(a._coef, b._coef)
        np.testing.assert_array_equal(a._icpt, b._icpt)
        one = _base().fit(MLFrame(ctx, {
            "features": x, "label": (y == j).astype(np.float64)}))
        np.testing.assert_allclose(a._coef, one._coef, atol=1e-6)
        np.testing.assert_allclose(a._icpt, one._icpt, atol=1e-6)
        assert a.summary.n_models == k and one.summary.n_models == 1
    assert from_ds.summary.total_evals == from_frame.summary.total_evals


def test_parallelism_one_stays_the_serial_loop(ctx):
    """A dataset through ``parallelism=1``: K binomial fits in a row, each
    the serial path's (its relabelled label vector derived on the device,
    X shared), equal to the frame's loop and close to the stacked fit."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import OneVsRest
    x, y = _multiclass(seed=41, n=480, k=3)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    serial = OneVsRest(classifier=_base(), parallelism=1)
    from_ds = serial.fit(ds)
    from_frame = serial.fit(MLFrame(ctx, {"features": x, "label": y}))
    stacked = OneVsRest(classifier=_base(), parallelism=3).fit(ds)
    for a, b, c in zip(from_ds.models, from_frame.models, stacked.models):
        assert a.summary.n_models == 1 and a.summary.stacked_evals is None
        np.testing.assert_allclose(a._coef, b._coef, atol=1e-12)
        np.testing.assert_allclose(a._coef, c._coef, atol=1e-6)
    s = from_ds.summary
    assert s.n_models == 1 and s.total_evals == sum(s.evals)
    assert s.total_dispatches == sum(
        m.summary.total_dispatches for m in from_ds.models)


def test_summary_counts_shared_sweeps_and_each_lanes_own(ctx):
    """``OneVsRestModel.summary``: per-model iterations and evaluations,
    the shared sweeps of X, and between them the lane-evaluations computed
    for models that had stopped."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.classification import OneVsRest
    x, y = _multiclass(seed=43, n=800, k=4)
    # one class far easier than the rest: its lane stops early
    x[y == 0] += 6.0
    ds = InstanceDataset.from_numpy(ctx, x, y)
    model = OneVsRest(classifier=_base(tol=1e-6, maxIter=100),
                      parallelism=4).fit(ds)
    s = model.summary
    assert s.num_classes == 4 and s.n_models == 4
    assert len(s.iterations) == len(s.evals) == len(s.objectives) == 4
    assert s.total_evals >= max(s.evals)
    assert sum(s.evals) <= 4 * s.total_evals
    assert len(set(s.iterations)) > 1, s.iterations
    assert s.total_dispatches < s.total_evals
    assert s.orientation is None and s.pieces is None     # the twin ran
    assert [m.summary.total_evals for m in model.models] == s.evals
    assert all(m.summary.stacked_evals == s.total_evals
               for m in model.models)


@pytest.mark.parametrize("stored,d,k", [("feature_major", 48, 3),
                                        ("feature_major", 48, 10),
                                        ("row_major", 128, 3)])
def test_estimator_through_the_kernel_against_the_plain_reference(
        ctx, monkeypatch, labels_of, stored, d, k):
    """``OneVsRest(parallelism=K)`` over a resident bf16 dataset takes the
    fused stacked sweep under the K-lane device optimiser and lands on the
    reference's K optima."""
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS
    from cycloneml_tpu.ml.classification import OneVsRest
    from perfbench import judge
    from perfbench.reference import ovr_logistic_l2
    ds, data = _dataset(ctx, 4096, d, k, seed=11)
    labels_of(k, 11)
    _interpreted(monkeypatch, stored)
    ctx.conf.set(USE_PALLAS_KERNELS, "true")
    try:
        model = OneVsRest(classifier=_base(tol=1e-6, maxIter=100),
                          parallelism=k).fit(ds)
    finally:
        ctx.conf.set(USE_PALLAS_KERNELS, "false")
    s = model.summary
    assert s.orientation == stored and s.pieces == 3 and s.num_classes == k
    assert s.total_dispatches < s.total_evals
    ref = ovr_logistic_l2.fit(data, {"regParam": 0.01})
    answer = {"coef": np.concatenate(
        [np.stack([m._coef[0] for m in model.models]).ravel(),
         [m._icpt[0] for m in model.models]]),
        "intercept": 0.0, "objective": sum(s.objectives)}
    got = judge.compare([answer], ref, {"coef_gap": 1e-3,
                                        "objective_gap": 1e-6})
    assert got["coef_gap"]["ok"] and got["objective_gap"]["ok"], got


def test_a_regparam_grid_through_the_kernel_equals_serial_fits(ctx,
                                                               monkeypatch):
    """``fit_stacked(reg_params=…)`` — the other caller of the stacked
    engine — takes the same kernel with every model's label the dataset's
    own 0/1 label."""
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    rt = ctx.mesh_runtime
    rng = np.random.RandomState(17)
    n, d = 2048, 32
    x = np.asarray(jnp.asarray(rng.randn(n, d), jnp.bfloat16))
    y = (x.astype(np.float64) @ rng.randn(d) + rng.randn(n) > 0).astype(
        np.float32)
    ds = InstanceDataset(ctx, rt.device_put_sharded_rows(x),
                         rt.device_put_sharded_rows(y),
                         rt.device_put_sharded_rows(np.ones(n, np.float32)),
                         n, d)
    regs = [0.01, 0.1, 1.0]
    _interpreted(monkeypatch, "feature_major")
    ctx.conf.set(USE_PALLAS_KERNELS, "true")
    try:
        stacked = _base(maxIter=100, tol=1e-6).fit_stacked(ds,
                                                           reg_params=regs)
        serial = [_base(maxIter=100, tol=1e-6, regParam=r).fit(ds)
                  for r in regs]
    finally:
        ctx.conf.set(USE_PALLAS_KERNELS, "false")
    for a, b in zip(stacked, serial):
        assert a.summary.orientation == "feature_major"
        assert a.summary.n_models == 3 and a.summary.pieces == 3
        # two float32 sweeps, each stopped at tol 1e-6 of its own path
        np.testing.assert_allclose(a._coef, b._coef, atol=5e-4)
        np.testing.assert_allclose(a._icpt, b._icpt, atol=5e-4)


def test_fits_name_their_sweep_and_a_warm_fit_launches_only_its_chunks(
        ctx, monkeypatch):
    """The normal path under the tracer: ``job.OneVsRest.fit`` ⊃
    ``fit.stats``, ``fit.prepare``, ``fit.optimize`` ⊃ ``optim.iteration``
    (with ``active_models``) ⊃ ``dispatch lbfgs.stacked_chunk`` ⊃ ``transfer
    lbfgs.readback``, ``fit.finish``; one ``kernel.glm_sweep`` instant a
    program built with what the stacked sweep is made of; the second fit of
    a dataset compiles nothing, adds no program, launches nothing but its
    chunks and moves nothing of one entry a (row, model) pair."""
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS
    from cycloneml_tpu.ml.classification import OneVsRest
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.parallel import collectives
    n, d, k = 4096 + 8 * 40, 48, 5
    ds, _ = _dataset(ctx, n, d, k, seed=13)
    _interpreted(monkeypatch, "feature_major")
    assert aggregators.stacked_binary_logistic_pallas_scaled(
        d, k, True, feature_major=True) is \
        aggregators.stacked_binary_logistic_pallas_scaled(
            d, k, True, feature_major=True)
    est = OneVsRest(classifier=_base(tol=1e-6, maxIter=100), parallelism=k)
    ctx.conf.set(USE_PALLAS_KERNELS, "true")
    tracing.disable()
    tracer = tracing.enable(max_spans=50_000)
    try:
        fits = []
        for _ in range(2):
            tracer.clear()
            model = est.fit(ds)
            fits.append((model, tracer.snapshot(),
                         len(collectives._program_cache)))
    finally:
        tracing.disable()
        ctx.conf.set(USE_PALLAS_KERNELS, "false")
    (cold, built, size), (warm, spans, size_again) = fits
    notes = [s for s in built if s.name == "kernel.glm_sweep"]
    assert notes and all(s.attrs == {
        "kind": "stacked_binomial", "orientation": "feature_major",
        "models": k, "model_pad": 16, "pieces": 3, "pad_cols": 0,
        "lane_tile": 512, "tail_rows": (n // 8) % 512} for s in notes)
    assert [s for s in built if s.kind == "compile"]    # the cold fit did
    assert size_again == size
    assert not [s for s in spans if s.kind in ("compile", "staging")]
    assert not [s for s in spans if s.name in ("kernel.glm_sweep",
                                               "cache.miss")]
    names = [(s.kind, s.name) for s in spans]
    for want in (("job", "OneVsRest.fit"), ("phase", "fit.stats"),
                 ("phase", "fit.prepare"), ("phase", "fit.optimize"),
                 ("phase", "optim.iteration"), ("phase", "fit.finish")):
        assert want in names, want
    launched = [s for s in spans
                if s.kind in ("dispatch", "transfer", "collective")]
    assert {(s.kind, s.name) for s in launched} == {
        ("dispatch", "lbfgs.stacked_chunk"), ("transfer", "lbfgs.readback")}
    # what a chunk sends home is counts and K loss rows: far under one
    # entry a (row, model) pair in any storage type
    assert all(s.attrs["bytes"] < n * k for s in launched
               if s.kind == "transfer")
    stats, = [s for s in spans if s.name == "fit.stats"]
    assert stats.attrs["cached"] is True
    turns = [s for s in spans if s.name == "optim.iteration"]
    assert turns[0].attrs["active_models"] == k == turns[0].attrs["n_models"]
    assert all(0 < s.attrs["active_models"] <= k for s in turns)
    for a, b in zip(warm.models, cold.models):
        np.testing.assert_array_equal(a._coef, b._coef)
    assert warm.summary.total_dispatches == len(
        [s for s in spans if s.name == "lbfgs.stacked_chunk"]) == len(turns)


def test_labels_are_checked_once_a_dataset_and_no_label_stack_is_taken(ctx):
    """The labels' check rides the cached histogram's one pass; a stacked
    fit has no argument for a ``(K, n)`` label stack (it makes its labels
    from the dataset's own) and refuses labels that are no class
    indices."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    x, y = _multiclass(seed=50, n=320, k=3)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    seen = []
    real = ds.y_host
    ds.y_host = lambda: seen.append(1) or real()
    assert ds.labels_are_class_indices() and len(seen) == 1
    _base(maxIter=5).fit_stacked(ds, num_classes=3)
    _base(maxIter=5).fit_stacked(ds, num_classes=3)
    assert len(seen) == 1                   # one pass a dataset, not a fit
    import inspect
    assert list(inspect.signature(_base().fit_stacked).parameters) == [
        "frame", "reg_params", "num_classes"]
    with pytest.raises(ValueError, match="num_classes or reg_params"):
        _base().fit_stacked(ds)
    with pytest.raises(ValueError, match="binary"):
        _base().fit_stacked(ds, reg_params=[0.0, 0.1])   # three classes
    halves = InstanceDataset.from_numpy(ctx, x, y + 0.5)
    assert not halves.labels_are_class_indices()
    with pytest.raises(ValueError, match="class-index labels"):
        _base().fit_stacked(halves, num_classes=4)
