"""LinearRegression parity tests (BASELINE config 2 family).

Mapping to sklearn (derived from the doubly-standardized glmnet objective the
reference uses — see module docstring of linear_regression.py):
  standardization=True  ⇔ sklearn ElasticNet(alpha=regParam, l1_ratio=α) on
                          (X/σx, y/σy), mapped back β = ŵ·σy/σx, b = b̂·σy
  OLS (reg=0)           ⇔ plain least squares, any solver
"""

import numpy as np
import pytest

from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.ml.regression import LinearRegression, LinearRegressionModel


def _frame(ctx, n=400, d=5, seed=21, noise=0.1):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d) * rng.uniform(0.5, 4.0, d)[None, :]
    true = rng.randn(d)
    y = x @ true + 3.0 + noise * rng.randn(n)
    return MLFrame(ctx, {"features": x, "label": y}), x, y


def test_ols_both_solvers_match_lstsq(ctx):
    frame, x, y = _frame(ctx)
    xa = np.hstack([x, np.ones((len(y), 1))])
    ref = np.linalg.lstsq(xa, y, rcond=None)[0]
    for solver in ("normal", "l-bfgs"):
        m = LinearRegression(regParam=0.0, solver=solver, tol=1e-12,
                             maxIter=500).fit(frame)
        np.testing.assert_allclose(m.coefficients.to_array(), ref[:-1], atol=1e-6)
        np.testing.assert_allclose(m.intercept, ref[-1], atol=1e-6)


def test_ridge_standardized_vs_sklearn(ctx):
    from sklearn.linear_model import ElasticNet
    frame, x, y = _frame(ctx, seed=22)
    reg = 0.3
    m = LinearRegression(regParam=reg, elasticNetParam=0.0, solver="l-bfgs",
                         tol=1e-12, maxIter=1000).fit(frame)
    sx = x.std(axis=0, ddof=1)
    sy = y.std(ddof=1)
    # glmnet semantics (proven by tests/test_ref_golden_parity.py): the
    # user's regParam is divided by the label std before penalizing the
    # y-standardized problem — so sklearn's alpha here is reg/sy
    sk = ElasticNet(alpha=reg / sy, l1_ratio=0.0, tol=1e-12,
                    max_iter=100000).fit(x / sx, y / sy)
    np.testing.assert_allclose(m.coefficients.to_array(), sk.coef_ * sy / sx,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(m.intercept, sk.intercept_ * sy, rtol=1e-4)


def test_normal_solver_equals_lbfgs_with_l2(ctx):
    """The two solvers agree to ~1e-4 relative under L2 — not exactly:
    since r5 the normal path IS the WLS component (population-weighted
    moments, glmnet's convention, as the reference's WeightedLeastSquares
    uses) while the l-bfgs path standardizes with the Summarizer's
    UNBIASED std (as the reference's l-bfgs path does, LinearRegression
    .scala:396) — the reference's own two paths carry the same n/(n−1)
    penalty-scale gap."""
    frame, _, _ = _frame(ctx, seed=23)
    reg = 0.2
    m1 = LinearRegression(regParam=reg, solver="normal").fit(frame)
    m2 = LinearRegression(regParam=reg, solver="l-bfgs", tol=1e-13,
                          maxIter=2000).fit(frame)
    np.testing.assert_allclose(m1.coefficients.to_array(),
                               m2.coefficients.to_array(), rtol=3e-4,
                               atol=1e-8)
    np.testing.assert_allclose(m1.intercept, m2.intercept, rtol=3e-4)


def test_elasticnet_lasso_vs_sklearn(ctx):
    from sklearn.linear_model import ElasticNet
    frame, x, y = _frame(ctx, seed=24, noise=0.5)
    reg, a = 0.2, 1.0
    m = LinearRegression(regParam=reg, elasticNetParam=a, tol=1e-12,
                         maxIter=2000).fit(frame)
    sx = x.std(axis=0, ddof=1)
    sy = y.std(ddof=1)
    # alpha = reg/sy: glmnet label-std scaling (see ridge test note)
    sk = ElasticNet(alpha=reg / sy, l1_ratio=a, tol=1e-14,
                    max_iter=200000).fit(x / sx, y / sy)
    np.testing.assert_allclose(m.coefficients.to_array(), sk.coef_ * sy / sx,
                               atol=1e-4)
    ours_nz = set(np.nonzero(np.abs(m.coefficients.to_array()) > 1e-10)[0])
    sk_nz = set(np.nonzero(np.abs(sk.coef_) > 1e-10)[0])
    assert ours_nz == sk_nz


def test_no_intercept(ctx):
    frame, x, y = _frame(ctx, seed=25)
    m = LinearRegression(regParam=0.0, fitIntercept=False, solver="l-bfgs",
                         tol=1e-12, maxIter=500).fit(frame)
    ref = np.linalg.lstsq(x, y, rcond=None)[0]
    np.testing.assert_allclose(m.coefficients.to_array(), ref, atol=1e-5)
    assert m.intercept == 0.0


def test_constant_label(ctx):
    n = 64
    frame = MLFrame(ctx, {"features": np.random.RandomState(0).randn(n, 3),
                          "label": np.full(n, 7.5)})
    m = LinearRegression().fit(frame)
    np.testing.assert_allclose(m.coefficients.to_array(), 0.0)
    assert m.intercept == pytest.approx(7.5)


def test_evaluate_metrics(ctx):
    frame, x, y = _frame(ctx, seed=26, noise=0.0)
    m = LinearRegression(regParam=0.0, solver="normal").fit(frame)
    ev = m.evaluate(frame)
    assert ev["rmse"] < 1e-6 and abs(ev["r2"] - 1.0) < 1e-10
    out = m.transform(frame)
    np.testing.assert_allclose(out["prediction"], y, atol=1e-5)


def test_save_load(ctx, tmp_path):
    frame, _, _ = _frame(ctx, seed=27)
    m = LinearRegression(regParam=0.1).fit(frame)
    p = str(tmp_path / "lin")
    m.save(p)
    back = LinearRegressionModel.load(p)
    np.testing.assert_allclose(back.coefficients.to_array(),
                               m.coefficients.to_array())
    assert back.intercept == m.intercept


def test_warm_fit_prepare_builds_and_launches_nothing(ctx):
    """The label moments ride the Summarizer's cached pass: once a dataset
    has its summary, ``fit.prepare`` traces, compiles, launches and reads
    back nothing, and a re-fit adds no program to the cache."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.stat import Summarizer
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.parallel import collectives
    rng = np.random.RandomState(28)
    n, d = 4096, 24
    x = rng.randn(n, d) * rng.uniform(0.5, 4.0, d)[None, :]
    y = x @ rng.randn(d) + 3.0 + 0.5 * rng.randn(n)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    est = LinearRegression(maxIter=40, regParam=0.05, elasticNetParam=0.5)
    tracing.disable()
    tracer = tracing.enable(max_spans=50_000)
    try:
        fits = []
        for _ in range(3):
            tracer.clear()
            model = est.fit(ds)
            fits.append((model, tracer.snapshot(),
                         len(collectives._program_cache)))
    finally:
        tracing.disable()

    first, built, size = fits[0]
    assert [s for s in built if s.kind == "compile"]   # the cold fit did
    for model, spans, cache_size in fits[1:]:
        assert cache_size == size
        assert not [s for s in spans if s.kind == "compile"]
        stats, = [s for s in spans if s.name == "fit.stats"]
        assert stats.attrs == {"cached": True}
        prepares = {s.span_id for s in spans if s.name == "fit.prepare"}
        assert len(prepares) == 2
        by_id = {s.span_id: s for s in spans}

        def in_prepare(s):
            while s is not None and s.span_id not in prepares:
                s = by_id.get(s.parent_id)
            return s is not None
        assert not [(s.kind, s.name) for s in spans if in_prepare(s)
                    and s.kind in ("compile", "dispatch", "transfer",
                                   "collective")]
        assert not [s for s in spans if s.name == "cache.miss"]
        np.testing.assert_array_equal(model.coefficients.to_array(),
                                      first.coefficients.to_array())
        assert model.intercept == first.intercept
        assert model.summary.objective_history == \
            first.summary.objective_history

    # the same fit with the label's mean and std from float64 numpy
    y_mean, y_std = float(y.mean()), float(y.std(ddof=1))
    coef, icpt, state, _, _ = est._solve_quasi_newton(
        ds, Summarizer.summarize(ds), y_mean, y_std, 0.05 / y_std, 0.5)
    np.testing.assert_allclose(first.coefficients.to_array(), coef,
                               rtol=1e-9, atol=1e-12)
    assert first.intercept == pytest.approx(icpt, rel=1e-9)
    np.testing.assert_allclose(first.summary.objective_history,
                               state.loss_history, rtol=1e-9)
