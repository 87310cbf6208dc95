"""Estimator-level sparse training: LogisticRegression.fit on the sparse
tier must match the dense estimator on identical data (the reference trains
on sparse vectors transparently; here fit() accepts a SparseInstanceDataset
directly)."""

import numpy as np
import pytest

from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.dataset.sparse import SparseInstanceDataset
from cycloneml_tpu.ml.classification import LogisticRegression
from tests.test_sparse import _random_sparse, _random_varlen_sparse  # noqa: E501


def _both(ctx, seed=0, n=300, d=30, hybrid=False):
    if hybrid:
        rows, dense, y, w = _random_varlen_sparse(n=n, d=d, seed=seed)
        sds = SparseInstanceDataset.from_rows_hybrid(
            ctx, rows, y=y, w=w, n_features=d, k_ell=8)
    else:
        rows, dense, y, w = _random_sparse(n=n, d=d, k=5, seed=seed)
        sds = SparseInstanceDataset.from_rows(ctx, rows, y=y, w=w,
                                              n_features=d)
    frame = MLFrame(ctx, {"features": dense, "label": y, "w": w})
    return sds, frame


@pytest.mark.parametrize("hybrid", [False, True])
def test_sparse_fit_matches_dense_fit(ctx, hybrid):
    sds, frame = _both(ctx, seed=3, d=30, hybrid=hybrid)
    lr = LogisticRegression(maxIter=60, regParam=0.05, tol=1e-10,
                            weightCol="w")
    dense_model = lr.fit(frame)
    sparse_model = lr.fit(sds)  # weights ride inside the dataset
    # the two tiers compute features_std through different f32 reduction
    # orders; the standardized-space penalty therefore differs in the last
    # few ulps, legitimately shifting the regularized optimum ~1e-3
    np.testing.assert_allclose(sparse_model.coefficients.to_array(),
                               dense_model.coefficients.to_array(),
                               rtol=1e-2, atol=1e-3)
    np.testing.assert_allclose(sparse_model.intercept, dense_model.intercept,
                               rtol=1e-2, atol=1e-3)
    # sparse fits are tracked jobs too
    assert sparse_model.summary.total_iterations > 0


def test_sparse_fit_elastic_net_and_bounds(ctx):
    sds, frame = _both(ctx, seed=7, d=24)
    # OWL-QN path: L1 drives coefficients to exact zeros on both tiers
    lr = LogisticRegression(maxIter=80, regParam=0.1, elasticNetParam=0.6,
                            weightCol="w", tol=1e-9)
    sm, dm = lr.fit(sds), lr.fit(frame)
    s_zero = sm.coefficients.to_array() == 0.0
    d_zero = dm.coefficients.to_array() == 0.0
    assert s_zero.any() and (s_zero == d_zero).mean() > 0.9
    # LBFGS-B path: nonnegative coefficients
    nn = LogisticRegression(maxIter=80, regParam=0.05, weightCol="w",
                            lowerBoundsOnCoefficients=np.zeros((1, 24)))
    m = nn.fit(sds)
    assert np.all(m.coefficients.to_array() >= -1e-9)


def test_sparse_fit_no_standardization(ctx):
    sds, frame = _both(ctx, seed=11, d=20)
    lr = LogisticRegression(maxIter=60, regParam=0.05, weightCol="w",
                            standardization=False, tol=1e-10)
    np.testing.assert_allclose(lr.fit(sds).coefficients.to_array(),
                               lr.fit(frame).coefficients.to_array(),
                               rtol=1e-2, atol=1e-4)


def test_sparse_fit_rejects_multinomial(ctx):
    rows, dense, y, w = _random_sparse(n=60, d=10, k=3, seed=1)
    y3 = (np.arange(60) % 3).astype(float)
    sds = SparseInstanceDataset.from_rows(ctx, rows, y=y3, n_features=10)
    with pytest.raises(NotImplementedError, match="binomial"):
        LogisticRegression(maxIter=5).fit(sds)


def test_sparse_fit_checkpoints_and_resumes(ctx, tmp_path):
    """checkpointDir works on the sparse path too (shared optimize tail)."""
    sds, _ = _both(ctx, seed=13, d=16)
    ck = str(tmp_path / "ck")
    full = LogisticRegression(maxIter=30, regParam=0.02, tol=1e-11,
                              weightCol="w").fit(sds)
    LogisticRegression(maxIter=4, regParam=0.02, tol=1e-11, weightCol="w",
                       checkpointDir=ck, checkpointInterval=1).fit(sds)
    resumed = LogisticRegression(maxIter=30, regParam=0.02, tol=1e-11,
                                 weightCol="w", checkpointDir=ck,
                                 checkpointInterval=1).fit(sds)
    np.testing.assert_allclose(resumed.coefficients.to_array(),
                               full.coefficients.to_array(),
                               rtol=1e-6, atol=1e-8)


def test_sparse_fit_binomial_family_rejects_multiclass(ctx):
    rows, dense, y, w = _random_sparse(n=60, d=10, k=3, seed=2)
    y3 = (np.arange(60) % 3).astype(float)
    sds = SparseInstanceDataset.from_rows(ctx, rows, y=y3, n_features=10)
    with pytest.raises(ValueError, match="Binomial family"):
        LogisticRegression(maxIter=5, family="binomial").fit(sds)


@pytest.mark.slow
def test_criteo_class_end_to_end(tmp_path, monkeypatch):
    """BASELINE config-1 analog at committed-test scale: synthetic
    hashed-sparse libsvm (~0.25 GB) -> streamed bounded-memory ELL ingest
    -> sparse-tier LR fit -> AUC, with the driver's ingest staging bounded
    (the full-size 2 GB run was a builder run of round 3; its record was
    deleted in PR 21).
    Runs examples/criteo_class_demo.py verbatim — the demo IS the test."""
    import io
    import runpy
    import sys
    monkeypatch.setenv("CRITEO_DEMO_PATH", str(tmp_path / "criteo.svm"))
    monkeypatch.setattr(sys, "argv", ["criteo_class_demo", "0.25", "19"])
    out = io.StringIO()
    from contextlib import redirect_stdout
    with redirect_stdout(out):
        runpy.run_path("examples/criteo_class_demo.py", run_name="__main__")
    text = out.getvalue()
    assert "AUC=" in text, text
    auc = float(text.split("AUC=")[1].split()[0].rstrip(","))
    assert auc > 0.65, text
