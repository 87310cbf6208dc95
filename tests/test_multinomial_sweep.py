"""The fused multinomial (softmax) sweep: the kernel in the Pallas
interpreter against float64 numpy, the precision its bf16 pieces buy, and
the estimator through it against the benchmark's plain reference
(``perfbench/reference/softmax_l2.py``). On the chip the same kernel lowers
to Mosaic (``tests/test_glm_layout_aot.py`` compiles it at the cell's shape;
``chip_smoke.py`` runs it)."""

import numpy as np
import pytest

from cycloneml_tpu.ops import kernels


def _case(n, d, k, fit_intercept, centred, seed, scale=0.3):
    """bf16 X (its values are the float64 truth's), integer labels, weights
    with zeros, and a coefficient vector in MLlib's flat layout."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    y = rng.randint(0, k, n).astype(np.float64)
    w = rng.rand(n) + 0.5
    w[::7] = 0.0
    inv_std = rng.rand(d) + 0.5
    mu = rng.randn(d) * 0.3 if centred else np.zeros(d)
    coef = rng.randn(d * k + (k if fit_intercept else 0)) * scale
    return x, y, w, inv_std, mu, coef


def _float64(x, y, w, inv_std, mu, coef, k, fit_intercept):
    """(margins, loss, grad, count) of the scaled multinomial aggregator in
    float64 numpy."""
    import jax.numpy as jnp
    x = np.asarray(x.astype(jnp.float32), np.float64)
    n, d = x.shape
    wmat = coef[:d * k].reshape(k, d)
    b = coef[d * k:] if fit_intercept else np.zeros(k)
    xh = x * inv_std - mu
    m = xh @ wmat.T + b
    top = m.max(axis=1, keepdims=True)
    lse = top[:, 0] + np.log(np.exp(m - top).sum(axis=1))
    rows, yi = np.arange(n), y.astype(int)
    loss = float(np.sum(w * (lse - m[rows, yi])))
    r = np.exp(m - lse[:, None])
    r[rows, yi] -= 1.0
    mult = w[:, None] * r
    g = mult.T @ xh
    grad = np.concatenate([g.ravel(), mult.sum(axis=0)]) if fit_intercept \
        else g.ravel()
    return m, loss, grad, float(w.sum())


@pytest.mark.parametrize("centred", [True, False])
@pytest.mark.parametrize("fit_intercept", [True, False])
@pytest.mark.parametrize("k", [3, 10])
@pytest.mark.parametrize("feature_major", [True, False])
def test_sweep_matches_float64(ctx, feature_major, k, fit_intercept, centred):
    """Both tilings, a last tile of 188 rows (masked, not padded), weights
    with zeros, with and without the intercepts and ``scaled_mean``."""
    n, d = 700, (48 if feature_major else 128)
    case = _case(n, d, k, fit_intercept, centred, seed=k)
    got = kernels.fused_multinomial_logistic_scaled(
        *case, d, k, fit_intercept, interpret=True,
        feature_major=feature_major, tile=256)
    _, loss, grad, count = _float64(*case, k, fit_intercept)
    assert abs(float(got["loss"]) - loss) <= 5e-7 * abs(loss)
    assert float(got["count"]) == pytest.approx(count, rel=1e-6)
    np.testing.assert_allclose(np.asarray(got["grad"]), grad,
                               atol=1e-6 * np.abs(grad).max(), rtol=0)


def test_a_tile_of_garbage_past_n_changes_nothing(ctx):
    """The same rows with and without a tail tile: the rows the last grid
    step reads past n are selected out, so the sums are those of the full
    tiles plus the live rows."""
    d, k = 48, 10
    x, y, w, inv_std, mu, coef = _case(640, d, k, True, True, seed=2)
    whole = kernels.fused_multinomial_logistic_scaled(
        x, y, w, inv_std, mu, coef, d, k, True, interpret=True,
        feature_major=True, tile=128)
    tailed = kernels.fused_multinomial_logistic_scaled(
        x, y, w, inv_std, mu, coef, d, k, True, interpret=True,
        feature_major=True, tile=512)
    assert float(whole["loss"]) == pytest.approx(float(tailed["loss"]),
                                                 rel=1e-6)
    np.testing.assert_allclose(np.asarray(whole["grad"]),
                               np.asarray(tailed["grad"]), rtol=0,
                               atol=1e-6 * np.abs(whole["grad"]).max())


def _drop(which):
    """``split`` with the named pieces zeroed: what a sweep does that hands
    the MXU fewer than three pieces of an f32 operand."""
    def patched(split):
        def fn(v):
            pieces = split(v)
            return tuple(p * 0 if i in which else p
                         for i, p in enumerate(pieces))
        return fn
    return patched


@pytest.mark.parametrize("feature_major", [True, False])
def test_precision_guard_every_piece_counts(ctx, monkeypatch, feature_major):
    """Coefficients that carry bits below bfloat16 (every entry perturbed at
    2^-12 of its size): loss and gradient stay within 3e-7 of float64
    (1.3e-7 as written: the f32 sums), the gradient is 1e-4 and more off as
    soon as either product keeps ONE piece, and the lowest piece of either
    operand alone is worth 4x the sound gap — so a piece dropped from the
    coefficient matrix going in or from the multipliers coming out fails
    this test."""
    n, d, k = 1024, (48 if feature_major else 128), 10
    x, y, w, inv_std, mu, coef = _case(n, d, k, True, True, seed=5, scale=1.0)
    rng = np.random.RandomState(6)
    coef = coef * (1.0 + 2.0 ** -12 * rng.choice([-1.0, 1.0], coef.shape))
    _, loss, grad, _ = _float64(x, y, w, inv_std, mu, coef, k, True)
    scale = np.abs(grad).max()

    def gaps():
        got = kernels.fused_multinomial_logistic_scaled(
            x, y, w, inv_std, mu, coef, d, k, True, interpret=True,
            feature_major=feature_major, tile=256)
        return (abs(float(got["loss"]) - loss) / abs(loss),
                np.abs(np.asarray(got["grad"]) - grad).max() / scale)

    sound = gaps()
    assert max(sound) < 3e-7, sound
    # the coefficient matrix as ONE bf16 piece (what the XLA twin's
    # _tier_dot does): margins, and with them loss and gradient, are off
    monkeypatch.setattr(kernels, "_split3_rounded",
                        _drop({1, 2})(kernels._split3_rounded))
    one_piece = gaps()
    assert one_piece[0] > 2e-5 and one_piece[1] > 1e-4, one_piece
    monkeypatch.undo()
    # any single piece missing on either side is seen, the lowest too
    for name, which in (("_split3_rounded", {2}), ("_split3_rounded", {1}),
                        ("_split3", {2}), ("_split3", {1})):
        monkeypatch.setattr(kernels, name, _drop(which)(getattr(kernels,
                                                                name)))
        assert gaps()[1] > max(4e-7, 3 * sound[1]), (name, which)
        monkeypatch.undo()
    monkeypatch.setattr(kernels, "_split3", _drop({1, 2})(kernels._split3))
    assert gaps()[1] > 1e-4           # the multipliers as one piece
    monkeypatch.undo()
    assert gaps() == sound


def test_which_shapes_the_kernel_takes():
    """bf16 storage only; the width ends on a packed sublane group
    (feature-major) or on a lane (row-major); 128 rows at least; ``k d``
    inside the VMEM budget. The cell's shape takes 1,024-row tiles."""
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    tile = kernels.multinomial_sweep_tile
    assert tile(8_100_000, 784, 10, bf16, True) == 1024
    assert tile(8_100_000, 784, 10, bf16, False) is None    # 784 % 128
    assert tile(2_000_000, 1280, 10, bf16, False) == 1024
    assert tile(2_000_000, 1280, 10, np.float32, False) is None
    assert tile(100, 784, 10, bf16, True) is None
    assert tile(300, 784, 10, bf16, True) == 256
    assert tile(10 ** 6, 790, 10, bf16, True) is None       # 790 % 16
    assert tile(10 ** 6, 784, 100, bf16, True) is not None
    assert tile(10 ** 6, 784, 1000, bf16, True) is None     # VMEM
    assert tile(10 ** 6, 2000, 40, bf16, True) is not None
    assert tile(10 ** 6, 784, 1, bf16, True) is None


# -- the estimator through the kernel -----------------------------------------

def _interpreted(monkeypatch, stored):
    """The package never interprets: the test makes ``pallas_call`` do so,
    and answers the layout question a CPU array cannot."""
    native_call = kernels.pl.pallas_call
    monkeypatch.setattr(
        kernels.pl, "pallas_call",
        lambda *a, **kw: native_call(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(kernels, "stored_feature_major",
                        lambda a: stored == "feature_major")


def _dataset(ctx, n, d, k, seed):
    """A device-resident bf16 dataset and the benchmark's view of it:
    ``(InstanceDataset, (x, y, mesh, axes))`` with class labels from
    ``perfbench.class_labels``."""
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from perfbench import class_labels
    from perfbench.entries.logistic_regression_multinomial import ROW_AXES
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


@pytest.mark.parametrize("stored,d,k", [("feature_major", 48, 3),
                                        ("feature_major", 48, 10),
                                        ("row_major", 128, 3)])
def test_estimator_against_the_plain_reference(ctx, monkeypatch, stored, d,
                                               k):
    """``family="auto"`` with more than two labels takes the fused sweep
    under the device-resident L-BFGS and lands on the reference's optimum:
    the unique penalised ``W`` and the centred intercepts."""
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS
    from cycloneml_tpu.ml.classification import LogisticRegression
    from perfbench import class_labels, judge
    from perfbench.reference import softmax_l2
    n = 4096
    ds, data = _dataset(ctx, n, d, k, seed=11)
    monkeypatch.setattr(class_labels, "spec", lambda name: {
        "classes": k, "noise": 0.3, "data_seed": 11})
    _interpreted(monkeypatch, stored)
    ctx.conf.set(USE_PALLAS_KERNELS, "true")
    try:
        model = LogisticRegression(maxIter=100, regParam=0.01).fit(ds)
    finally:
        ctx.conf.set(USE_PALLAS_KERNELS, "false")
    s = model.summary
    assert s.orientation == stored and s.num_classes == k
    assert model.num_classes == k
    assert s.total_dispatches < s.total_evals <= 2 * s.total_iterations
    icpt = model.intercept_vector.to_array()
    assert abs(icpt.sum()) < 1e-9 and np.abs(icpt).max() > 0
    ref = softmax_l2.fit(data, {"regParam": 0.01})
    answer = {"coef": np.concatenate(
        [model.coefficient_matrix.to_array().ravel(), icpt]),
        "intercept": 0.0, "objective": s.objective_history[-1]}
    got = judge.compare([answer], ref, {"coef_gap": 1e-3,
                                        "objective_gap": 1e-6})
    assert got["coef_gap"]["ok"] and got["objective_gap"]["ok"], got


def test_fits_name_their_sweep_and_a_warm_fit_builds_nothing(ctx,
                                                              monkeypatch):
    """One ``kernel.glm_sweep`` instant per program built, with what the
    K-class sweep is made of; the aggregator factory is cached by value, so
    the second fit of a dataset compiles nothing, adds no program and
    launches nothing but its chunk."""
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.parallel import collectives
    n, d, k = 4096 + 8 * 40, 48, 5
    ds, _ = _dataset(ctx, n, d, k, seed=13)
    _interpreted(monkeypatch, "feature_major")
    assert aggregators.multinomial_logistic_pallas_scaled(
        d, k, True, feature_major=True) is \
        aggregators.multinomial_logistic_pallas_scaled(
            d, k, True, feature_major=True)
    est = LogisticRegression(maxIter=100, regParam=0.01)
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
        "kind": "multinomial", "orientation": "feature_major", "classes": k,
        "class_pad": 16, "pieces": 3, "pad_cols": 0, "lane_tile": 512,
        "tail_rows": (n // 8) % 512} for s in notes)
    assert [s for s in built if s.kind == "compile"]    # the cold fit did
    assert size_again == size
    assert not [s for s in spans if s.kind == "compile"]
    assert not [s for s in spans if s.name in ("kernel.glm_sweep",
                                               "cache.miss")]
    launched = [(s.kind, s.name) for s in spans
                if s.kind in ("dispatch", "transfer", "collective")]
    assert set(launched) == {("dispatch", "lbfgs.chunk"),
                             ("transfer", "lbfgs.readback")}
    turns = [s for s in spans if s.name == "optim.iteration"]
    assert turns and all(
        set(s.attrs) == {a for t in built if t.name == "optim.iteration"
                         for a in t.attrs} for s in turns)
    np.testing.assert_array_equal(warm.coefficient_matrix.to_array(),
                                  cold.coefficient_matrix.to_array())
    assert warm.summary.num_classes == cold.summary.num_classes == k
    assert warm.summary.total_dispatches == len(
        [s for s in spans if s.name == "lbfgs.chunk"])


def test_label_histogram_is_read_once_a_dataset(ctx):
    """The weighted class histogram a fit's ``fit.prepare`` reads is a
    property of the immutable dataset: one host pass, cached; labels
    attached anew drop it; what comes back is the caller's own copy."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    rng = np.random.RandomState(3)
    y = rng.randint(0, 4, 512).astype(np.float64)
    w = rng.rand(512) + 0.5
    ds = InstanceDataset.from_numpy(ctx, rng.randn(512, 6), y, w)
    want = np.bincount(y.astype(int), weights=w, minlength=4)
    first = ds.label_histogram()
    np.testing.assert_allclose(first, want)
    first[:] = 0.0                      # the cache is not the caller's
    seen = []
    real = ds.y_host
    ds.y_host = lambda: seen.append(1) or real()
    np.testing.assert_allclose(ds.label_histogram(), want)
    assert seen == []                   # no second pass over the labels
    ds.attach_host_labels(np.where(y == 3, 0.0, y), w)
    assert len(ds.label_histogram()) == 3 and seen == [1]
