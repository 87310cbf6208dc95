"""``GeneralizedLinearRegression`` on the normal aggregation path: whole fits
through ``fit(InstanceDataset)`` against the benchmark's plain reference
(``perfbench/reference/glr_binomial.py``: Newton on the binomial deviance,
which imports nothing of the program), against the frame path, and the
path's own shape — one aggregation program an iteration, spans, counters, a
warm fit that builds nothing.

Tolerances. X is stored at the tier under test and both sides read the
stored values; the program's sums are at the accumulator's width (float64
under these tests' x64 for f32/f64 storage, f32 for bf16 storage and on the
Pallas kernel) and the reference's are f32 ``highest`` blocks summed in
float64. IRLS stops where the largest coefficient change is under ``tol``
1e-6 and Newton converges quadratically, so the stopping point sits far
under the f32 sums: a model of 4,096 rows agrees to a few 1e-6 of its norm.
2e-5 (``COEF_RTOL``) leaves room for the order of the f32 sums and still
fails a bf16 accumulator or a Gramian of rounded X (1e-3 and up). The
deviance is a sum of 4,096 f32 terms on the reference's side: 1e-6. The
standard errors are ``sqrt(diag(H^-1))`` with ``H`` at the LAST pass's
working weights on the program's side (MLlib's ``diagInvAtWA``) and at the
optimum on the reference's: they differ by the last step (< 1e-6) and by
the reference's f32 ``highest`` information matrix: 1e-4.
"""

import numpy as np
import pytest

from cycloneml_tpu.ops import kernels

COEF_RTOL = 2e-5
DEVIANCE_RTOL = 1e-6
SE_RTOL = 1e-4
ROW_AXES = ("replica", "data")
BINOMIAL = {"family": "binomial"}


def _case(seed, n, d):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d)
    beta = rng.randn(d) / np.sqrt(d)
    y = (x @ beta + 0.2 + 0.7 * rng.logistic(size=n) > 0).astype(np.float64)
    return x, y


def _stored(x, dtype):
    """X rounded to its storage type, as float64: what both sides see."""
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(x, jnp.dtype(dtype)), np.float64)


def _data(ctx, x, y):
    rt = ctx.mesh_runtime
    return (rt.device_put_sharded_rows(x.astype(np.float32)),
            rt.device_put_sharded_rows(y.astype(np.float32)),
            rt.mesh, ROW_AXES)


def _reference(ctx, x, y, params=BINOMIAL):
    from perfbench.reference import glr_binomial
    return glr_binomial.fit(_data(ctx, x, y), params)


def _fit(ctx, x, y, dtype, w=None, **params):
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.regression import GeneralizedLinearRegression
    ds = InstanceDataset.from_numpy(ctx, x, y, w, dtype=jnp.dtype(dtype))
    return GeneralizedLinearRegression(**params).fit(ds), ds


def _gap(model, ref):
    got = np.append(np.asarray(model.coefficients, np.float64),
                    model.intercept)
    want = np.append(ref["coef"], ref["intercept"])
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _assert_equals_reference(model, ref):
    s = model.summary
    assert _gap(model, ref) <= COEF_RTOL
    ours = ref["problem"].objective_of(
        np.asarray(model.coefficients)[None], np.array([model.intercept]))[0]
    assert s.deviance == pytest.approx(ours, rel=DEVIANCE_RTOL)
    assert s.deviance == pytest.approx(ref["objective"], rel=DEVIANCE_RTOL)
    np.testing.assert_allclose(s.coefficient_standard_errors,
                               ref["problem"].standard_errors(),
                               rtol=SE_RTOL)


import contextlib


@contextlib.contextmanager
def _moments_on_the_interpreter(monkeypatch, feature_major, tile=384):
    """The moment pass on the Pallas kernel: the test routes its
    pallas_call through the interpreter and says what the chip would
    (Mosaic lowers, X is stored this way, a tile of ``tile`` rows)."""
    from cycloneml_tpu.parallel import collectives
    native_call = kernels.pl.pallas_call
    monkeypatch.setattr(
        kernels.pl, "pallas_call",
        lambda *a, **kw: native_call(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(kernels, "pallas_available", lambda: True)
    monkeypatch.setattr(kernels, "stored_feature_major",
                        lambda a: feature_major)
    monkeypatch.setattr(kernels, "moment_gramian_tile", lambda *a: tile)
    collectives.clear_program_cache()
    try:
        yield
    finally:
        collectives.clear_program_cache()


# -- (a) whole fits against the plain reference, and the frame path -----------

@pytest.mark.parametrize("dtype,d", [("float32", 200), ("bfloat16", 200),
                                     ("bfloat16", 28)])
def test_binomial_fit_equals_the_plain_reference(ctx, dtype, d):
    """``GeneralizedLinearRegression(family="binomial").fit(ds)`` lands on
    the point where the reference's Newton iteration on the deviance stops:
    coefficients, intercept, the returned model's deviance, and the
    standard errors of the coefficient table."""
    x, y = _case(33, 4096, d)
    xs = _stored(x, dtype)
    model, _ = _fit(ctx, xs, y, dtype, **BINOMIAL)
    s = model.summary
    assert s.num_iterations == 5        # as before PR 38's first-pass mu0
    assert s.total_passes == s.num_iterations
    assert s.total_dispatches == s.num_iterations + 1
    assert len(s.deviance_history) == s.num_iterations
    # the passes report the PREVIOUS model's deviance (the first, the
    # starting mu0's, which is no model's): it falls to the returned one's
    assert s.deviance_history[-1] == pytest.approx(s.deviance, rel=1e-6)
    assert s.deviance_history[1] > s.deviance_history[-1]
    _assert_equals_reference(model, _reference(ctx, xs, y))


@pytest.mark.parametrize("path", ["feature_major", "row_major"])
def test_binomial_fit_on_the_kernel_equals_the_plain_reference(
        ctx, monkeypatch, path):
    """The same fit with the reweighted moments on the Pallas kernel (the
    test routes its pallas_call through the interpreter and says what the
    chip would: Mosaic lowers, X is stored this way): the working weights
    past the first pass are no one-value mask, so those passes take the
    kernel's three-piece form — both tilings, rows that do not fill the
    last tile."""
    d = 48 if path == "feature_major" else 128
    x, y = _case(34, 4096, d)
    xs = _stored(x, "bfloat16")
    with _moments_on_the_interpreter(monkeypatch, path == "feature_major"):
        model, _ = _fit(ctx, xs, y, "bfloat16", **BINOMIAL)
    assert model.summary.num_iterations == 5
    _assert_equals_reference(model, _reference(ctx, xs, y))


def test_frame_and_dataset_take_the_same_loop(ctx):
    """A frame builds the dataset and runs the same program: bit for bit
    the same model, the same counters, the same summary."""
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.regression import GeneralizedLinearRegression
    x, y = _case(35, 1000, 12)
    est = GeneralizedLinearRegression(**BINOMIAL)
    by_frame = est.fit(MLFrame(ctx, {"features": x, "label": y}))
    by_ds, _ = _fit(ctx, x, y, "float64", **BINOMIAL)
    np.testing.assert_array_equal(by_frame.coefficients.to_array(),
                                  by_ds.coefficients.to_array())
    assert by_frame.intercept == by_ds.intercept
    a, b = by_frame.summary, by_ds.summary
    assert a.deviance == b.deviance and a.num_iterations == b.num_iterations == 5
    np.testing.assert_array_equal(a.coefficient_standard_errors,
                                  b.coefficient_standard_errors)
    assert a.null_deviance == b.null_deviance and a.aic == b.aic
    np.testing.assert_array_equal(a.residuals("deviance"),
                                  b.residuals("deviance"))
    assert a.residuals("deviance").shape == (1000,)
    # the fitted means stay on the devices, in the padded row space
    import jax
    assert isinstance(a.prediction_mean, jax.Array)
    assert a.prediction_mean.shape == by_ds.summary._ds.y.shape


def test_the_two_references_agree_without_a_penalty(ctx):
    """``lr_epsilon``'s reference (Newton on the standardised mean logistic
    loss) at ``regParam`` 0 and this cell's (Newton on the deviance over
    ``[X | 1]``) state the same problem: one optimum, and the deviance is
    2n times the mean loss."""
    from perfbench.reference import logistic_l2
    x, y = _case(36, 4096, 40)
    xs = _stored(x, "bfloat16")
    glr = _reference(ctx, xs, y)
    lr = logistic_l2.fit(_data(ctx, xs, y), {"regParam": 0.0})
    want = np.append(glr["coef"], glr["intercept"])
    got = np.append(lr["coef"], lr["intercept"])
    assert np.linalg.norm(got - want) / np.linalg.norm(want) <= COEF_RTOL
    assert 2 * len(y) * lr["objective"] == pytest.approx(glr["objective"],
                                                         rel=DEVIANCE_RTOL)


# -- (c), (d) the path's shape: programs, spans, counters ------------------------

def _traced_fits(ctx, est, ds, n_fits):
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.parallel import collectives
    tracing.disable()
    tracer = tracing.enable(max_spans=50_000)
    try:
        fits = []
        for _ in range(n_fits):
            tracer.clear()
            model = est.fit(ds)
            fits.append((model, tracer.snapshot(),
                         len(collectives._program_cache)))
    finally:
        tracing.disable()
    return fits


def test_warm_fit_builds_and_launches_nothing_outside_its_passes(ctx):
    """The aggregators are cached by the value of family and link, so a
    second fit asks ``tree_aggregate`` for the same functions: no
    ``compile`` span, no new program, and every launch and readback is a
    pass's own."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.regression import GeneralizedLinearRegression
    from cycloneml_tpu.parallel import collectives
    x, y = _case(37, 2048, 16)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    collectives.clear_program_cache()       # the first fit builds its own
    fits = _traced_fits(ctx, GeneralizedLinearRegression(**BINOMIAL), ds, 3)
    first, built, size = fits[0]
    assert first.summary.num_iterations == 4
    assert [s for s in built if s.kind == "compile"]   # the cold fit did
    for model, spans, cache_size in fits[1:]:
        assert cache_size == size
        assert not [s for s in spans if s.kind == "compile"]
        assert not [s for s in spans if s.name == "cache.miss"]
        dispatches = {s.span_id for s in spans if s.kind == "dispatch"}
        for s in spans:
            if s.kind in ("collective", "transfer"):
                assert s.parent_id in dispatches, (s.kind, s.name)
        prepare, = [s for s in spans if s.name == "fit.prepare"]
        assert [s.name for s in spans
                if s.parent_id == prepare.span_id] == ["cache.hit"]
        np.testing.assert_array_equal(model.coefficients.to_array(),
                                      first.coefficients.to_array())


def test_spans_and_counters_of_a_fit(ctx):
    """One ``irls.iteration`` a pass, each around its ``dispatch irls.pass``
    ⊃ ``transfer irls.readback`` and its ``fit.solve``, which says that it
    factored the moment block as delivered; the summary's counters are the
    spans counted."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.regression import GeneralizedLinearRegression
    x, y = _case(38, 2048, 16)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    (model, spans, _), = _traced_fits(
        ctx, GeneralizedLinearRegression(**BINOMIAL), ds, 1)
    s = model.summary
    iterations = [sp for sp in spans if sp.name == "irls.iteration"]
    assert len(iterations) == s.num_iterations == s.total_passes == 5
    assert [sp.attrs["iteration"] for sp in iterations] == \
        list(range(s.num_iterations))
    assert [sp.attrs["deviance"] for sp in iterations] == s.deviance_history
    assert iterations[-1].attrs["delta"] < 1e-6 <= iterations[-2].attrs["delta"]
    dispatches = [sp for sp in spans if sp.kind == "dispatch"]
    assert len(dispatches) == s.total_dispatches == s.num_iterations + 1
    assert [sp.name for sp in dispatches] == \
        ["irls.pass"] * s.num_iterations + ["irls.deviance"]
    for it, dsp in zip(iterations, dispatches):
        assert dsp.parent_id == it.span_id
        readback, = [sp for sp in spans if sp.kind == "transfer"
                     and sp.parent_id == dsp.span_id]
        assert readback.name == "irls.readback" and readback.attrs["bytes"] > 0
        solve, = [sp for sp in spans if sp.name == "fit.solve"
                  and sp.parent_id == it.span_id]
        assert solve.t0 >= dsp.t1
        assert solve.attrs["system"] == "moments"
    finish, = [sp for sp in spans if sp.name == "fit.finish"]
    assert dispatches[-1].parent_id == finish.span_id
    assert [sp.name for sp in spans if sp.kind == "phase"
            and sp.name.startswith("fit.")][0] == "fit.prepare"


# -- (d') the pass count the working weights ask for --------------------------

def test_first_pass_has_one_working_weight(ctx):
    """Binomial / logit, 0/1 labels, unit weights: ``mu0`` is 0.75 or 0.25
    and the working weight ``mu0 (1 - mu0)`` 0.1875 for every row — IF the
    working point is taken at ``mu0`` itself. Through ``unlink(link(mu0))``
    (a ``log`` and a ``sigmoid``) float32 gives the two labels weights an
    ulp apart, and the moment pass sees two live values."""
    import jax.numpy as jnp
    from unittest.mock import patch
    from cycloneml_tpu.ml.regression import glm
    rng = np.random.RandomState(43)
    n, d = 512, 8
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    y = jnp.asarray(rng.rand(n) > 0.4, jnp.float32)
    w = jnp.ones(n, jnp.float32).at[-40:].set(0.0)     # a shard's padding
    params = jnp.asarray(np.append(np.zeros(d + 1), 1.0), jnp.float32)
    seen, moment_sums = {}, kernels.moment_sums

    def spy(x_, z, omega, **kw):
        seen["omega"], seen["z"] = np.asarray(omega), np.asarray(z)
        return moment_sums(x_, z, omega, **kw)

    with patch.object(kernels, "moment_sums", spy):
        glm.irls_aggregator(glm.Binomial(), glm.Logit())(x, y, w, params)
    live = np.unique(seen["omega"][seen["omega"] != 0])
    assert len(live) == 1, [float(v).hex() for v in live]
    assert live[0] == pytest.approx(0.1875, rel=1e-6)
    assert np.all(seen["omega"][-40:] == 0) and np.all(seen["z"][-40:] == 0)
    # the working response is eta0 + (y - mu0) g: one value a label
    assert len(np.unique(seen["z"][:-40])) == 2


def _pass_spans(tracer):
    return [s for s in tracer.snapshot()
            if s.kind == "dispatch" and s.name == "irls.pass"]


@pytest.fixture
def ring():
    """The default tracer of a context: the flight ring."""
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.observe.flight import FlightTracer
    tracing.disable()
    t = tracing.install_if_absent(FlightTracer(max_spans=50_000))
    yield t
    tracing.disable()


@pytest.mark.parametrize("path", ["feature_major", "row_major"])
def test_dispatch_spans_say_which_form_of_the_gramian_ran(
        ctx, monkeypatch, ring, path):
    """The form is chosen on the device, so the host learns it with the
    moments: every ``irls.pass`` dispatch span carries ``mxu_passes`` and
    the summary the same list — a binomial-logit fit of 0/1 labels and
    unit weights takes ONE pass for its first Gramian (one working weight
    on every shard of the mesh) and three for each later one."""
    d = 48 if path == "feature_major" else 128
    x, y = _case(44, 4096, d)
    xs = _stored(x, "bfloat16")
    with _moments_on_the_interpreter(monkeypatch, path == "feature_major"):
        model, _ = _fit(ctx, xs, y, "bfloat16", **BINOMIAL)
    s = model.summary
    assert s.num_iterations == 5
    assert s.mxu_passes == [1, 3, 3, 3, 3]
    assert [sp.attrs["mxu_passes"] for sp in _pass_spans(ring)] == \
        s.mxu_passes
    _assert_equals_reference(model, _reference(ctx, xs, y))


def test_poisson_log_takes_three_passes_from_the_first(ctx, monkeypatch,
                                                       ring):
    """poisson / log: ``mu0`` follows the label, so the first pass's
    working weights already hold many values — the rule observes, it is
    told nothing."""
    rng = np.random.RandomState(45)
    x = rng.randn(4096, 48) * 0.2
    xs = _stored(x, "bfloat16")
    y = rng.poisson(np.exp(xs @ (rng.randn(48) * 0.5) + 0.3)).astype(
        np.float64)
    with _moments_on_the_interpreter(monkeypatch, True):
        model, _ = _fit(ctx, xs, y, "bfloat16", family="poisson")
    s = model.summary
    assert s.mxu_passes == [3] * s.num_iterations and s.num_iterations >= 3
    assert [sp.attrs["mxu_passes"] for sp in _pass_spans(ring)] == \
        s.mxu_passes
    mu = np.exp(xs @ model.coefficients.to_array() + model.intercept)
    score = np.append(xs.T @ (y - mu), np.sum(y - mu))
    assert np.max(np.abs(score)) < 1e-4 * np.sum(y)


def test_xla_moments_carry_no_pass_count(ctx, ring):
    """Where XLA's contraction is the Gramian (the host platform here)
    there is no such count: spans and summary say ``None``."""
    x, y = _case(46, 1024, 8)
    model, _ = _fit(ctx, x, y, "float64", **BINOMIAL)
    assert model.summary.mxu_passes == [None] * model.summary.num_iterations
    assert [sp.attrs["mxu_passes"] for sp in _pass_spans(ring)] == \
        model.summary.mxu_passes


# -- (e) the other families through the same program -------------------------

def test_poisson_log_fit_through_the_dataset_path(ctx):
    """poisson / log: the same loop and program factory; the optimum is
    where the score ``[X | 1]'(y - mu)`` vanishes (float64 here)."""
    rng = np.random.RandomState(39)
    x = rng.randn(3000, 6) * 0.4
    beta = rng.randn(6) * 0.5
    y = rng.poisson(np.exp(x @ beta + 0.3)).astype(np.float64)
    w = rng.uniform(0.5, 2.0, 3000)
    model, _ = _fit(ctx, x, y, "float64", w, family="poisson", tol=1e-10)
    mu = np.exp(x @ model.coefficients.to_array() + model.intercept)
    score = np.append(x.T @ (w * (y - mu)), np.sum(w * (y - mu)))
    assert np.max(np.abs(score)) < 1e-6 * np.sum(w * y)
    s = model.summary
    assert s.family == "poisson" and s.link == "log"
    assert s.total_passes == s.num_iterations == 6
    assert s.mxu_passes == [None] * 6       # float64 X: XLA's contraction
    dev = 2 * np.sum(w * (np.where(y > 0, y * np.log(np.maximum(y, 1e-300)
                                                     / mu), 0.0) - (y - mu)))
    assert s.deviance == pytest.approx(dev, rel=1e-10)
    # standard errors: diag((X'WX)^-1) at the working weights w·mu
    xa = np.hstack([x, np.ones((3000, 1))])
    want = np.sqrt(np.diag(np.linalg.inv(xa.T @ (xa * (w * mu)[:, None]))))
    np.testing.assert_allclose(s.coefficient_standard_errors, want,
                               rtol=1e-6)


def test_gaussian_identity_in_one_pass_is_the_normal_solve(ctx):
    """gaussian / identity: the first pass's working response is the label
    and its working weight the prior weight, so ONE pass is
    ``LinearRegression``'s normal-equation solve — the same moments
    through the same kernel into the same solver."""
    from cycloneml_tpu.ml.regression import LinearRegression
    rng = np.random.RandomState(40)
    x = rng.randn(2048, 10) * rng.uniform(0.5, 3.0, 10)
    y = x @ rng.randn(10) + 1.5 + 0.3 * rng.randn(2048)
    w = rng.uniform(0.5, 2.0, 2048)
    model, ds = _fit(ctx, x, y, "float64", w, family="gaussian", maxIter=1)
    normal = LinearRegression(solver="normal").fit(ds)
    assert model.summary.total_passes == 1
    np.testing.assert_allclose(model.coefficients.to_array(),
                               normal.coefficients.to_array(), rtol=1e-12)
    assert model.intercept == pytest.approx(normal.intercept, rel=1e-12)
    full, _ = _fit(ctx, x, y, "float64", w, family="gaussian")
    assert full.summary.num_iterations == 2
    np.testing.assert_allclose(full.coefficients.to_array(),
                               normal.coefficients.to_array(), rtol=1e-9)


def test_offset_rides_as_a_fourth_row_vector(ctx):
    """The frame path's offset column: the same loop, one more row-sharded
    argument; a dataset has no column to name."""
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.regression import GeneralizedLinearRegression
    rng = np.random.RandomState(41)
    x = rng.randn(1500, 4) * 0.4
    off = rng.uniform(-0.5, 0.5, 1500)
    y = rng.poisson(np.exp(x @ np.array([0.3, -0.2, 0.5, 0.1]) + 0.2
                           + off)).astype(np.float64)
    frame = MLFrame(ctx, {"features": x, "label": y, "off": off})
    est = GeneralizedLinearRegression(family="poisson", offsetCol="off",
                                      tol=1e-10)
    model = est.fit(frame)
    assert model.summary.num_iterations == 6
    mu = np.exp(x @ model.coefficients.to_array() + model.intercept + off)
    assert np.max(np.abs(x.T @ (y - mu))) < 1e-6 * np.sum(y)
    assert np.isfinite(model.summary.null_deviance)
    with pytest.raises(ValueError, match="offsetCol"):
        est.fit(frame.to_instance_dataset("features", "label"))


def test_reg_param_is_the_plain_ridge_of_the_reweighted_problem(ctx):
    """``regParam``: every reweighted problem goes to WeightedLeastSquares
    with nothing standardised (what the reference's IRLS passes), i.e.
    ``1/(2 Σω) Σ ω (z - x.b - b0)^2 + regParam/2 |b|^2``. For gaussian /
    identity that is ONE closed form: ridge with an unpenalised intercept."""
    rng = np.random.RandomState(42)
    x = rng.randn(2048, 6) * rng.uniform(0.5, 3.0, 6)
    y = x @ rng.randn(6) + 0.7 + 0.3 * rng.randn(2048)
    w = rng.uniform(0.5, 2.0, 2048)
    reg = 0.3
    model, _ = _fit(ctx, x, y, "float64", w, family="gaussian", regParam=reg)
    assert model.summary.num_iterations == 2
    xm, ym = np.average(x, axis=0, weights=w), np.average(y, weights=w)
    xc, yc = x - xm, y - ym
    a = xc.T @ (xc * w[:, None]) / w.sum() + reg * np.eye(6)
    want = np.linalg.solve(a, xc.T @ (w * yc) / w.sum())
    np.testing.assert_allclose(model.coefficients.to_array(), want,
                               rtol=1e-9)
    assert model.intercept == pytest.approx(ym - xm @ want, rel=1e-9)
