"""Pallas kernel parity tests (interpret mode on the CPU mesh; the same
kernels lower to Mosaic on TPU — chip_smoke.py exercises that path on hardware).

Parity targets are the pure-jnp aggregator/Gramian implementations, which are
themselves tested against sklearn/scipy golden numbers elsewhere.
"""

import numpy as np
import pytest

from cycloneml_tpu.ops import (fused_binary_logistic,
                               fused_binary_logistic_scaled, fused_gramian,
                               fused_kmeans_assign,
                               fused_least_squares_scaled)
from cycloneml_tpu.ml.optim import aggregators


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(42)
    n, d = 300, 37  # deliberately unaligned with tiles/lanes
    x = rng.randn(n, d)
    y = (rng.rand(n) > 0.4).astype(np.float64)
    w = rng.rand(n) + 0.5
    return x, y, w


@pytest.mark.parametrize("fit_intercept", [True, False])
def test_fused_logistic_matches_aggregator(data, fit_intercept, ctx):
    x, y, w = data
    d = x.shape[1]
    rng = np.random.RandomState(0)
    coef = rng.randn(d + (1 if fit_intercept else 0))

    ref = aggregators.binary_logistic(d, fit_intercept)(
        np.asarray(x, np.float32), np.asarray(y, np.float32),
        np.asarray(w, np.float32), np.asarray(coef, np.float32))
    got = fused_binary_logistic(x, y, w, coef, d, fit_intercept,
                                interpret=True, row_tile=128)

    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got["grad"]),
                               np.asarray(ref["grad"]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(got["count"]), float(ref["count"]),
                               rtol=1e-6)


def test_fused_logistic_padding_rows_inert(ctx):
    """Rows added by tile padding (w=0) must not change any output."""
    rng = np.random.RandomState(1)
    d = 17
    coef = rng.randn(d + 1)
    x, y, w = rng.randn(100, d), (rng.rand(100) > 0.5).astype(float), np.ones(100)
    small = fused_binary_logistic(x, y, w, coef, d, True,
                                  interpret=True, row_tile=128)
    # same data with explicit zero-weight junk rows appended
    x2 = np.vstack([x, rng.randn(60, d) * 100])
    y2 = np.concatenate([y, np.ones(60)])
    w2 = np.concatenate([w, np.zeros(60)])
    big = fused_binary_logistic(x2, y2, w2, coef, d, True,
                                interpret=True, row_tile=128)
    np.testing.assert_allclose(float(big["loss"]), float(small["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(big["grad"]),
                               np.asarray(small["grad"]), rtol=1e-5, atol=1e-5)


def test_fused_kmeans_assign(ctx):
    rng = np.random.RandomState(7)
    x = rng.randn(500, 23)
    centers = rng.randn(11, 23)
    best, dist = fused_kmeans_assign(x, centers, interpret=True, row_tile=128)
    d2 = ((x[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(best), d2.argmin(1))
    np.testing.assert_allclose(np.asarray(dist), d2.min(1), rtol=1e-4,
                               atol=1e-4)


def test_fused_kmeans_padded_centers_never_win(ctx):
    rng = np.random.RandomState(8)
    x = rng.randn(50, 5) * 1000  # huge distances; padded centers are at 0
    centers = rng.randn(3, 5) * 1000
    best, _ = fused_kmeans_assign(x, centers, interpret=True, row_tile=128)
    assert np.asarray(best).max() < 3


def test_fused_gramian(ctx):
    rng = np.random.RandomState(3)
    x = rng.randn(400, 19)
    g = fused_gramian(x, interpret=True, row_tile=128)
    np.testing.assert_allclose(np.asarray(g), x.T @ x, rtol=1e-4, atol=1e-3)
    # symmetry is exact, not approximate
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g).T)


def test_fused_gramian_weight_mask(ctx):
    """w masks rows by presence INSIDE the kernel — the jnp path's
    x * (w > 0) row mask without the masked X copy."""
    rng = np.random.RandomState(4)
    x = rng.randn(120, 11)
    w = np.ones(120)
    w[60:] = 0.0  # masked rows must contribute nothing
    g = fused_gramian(x, w=w, interpret=True, row_tile=64)
    ref = x[:60].T @ x[:60]
    np.testing.assert_allclose(np.asarray(g), ref, rtol=1e-4, atol=1e-3)


# -- bf16 data tier: storage-width reads, fp32 in-kernel accumulation --------

def _bf16(a):
    import ml_dtypes
    return np.asarray(a, dtype=ml_dtypes.bfloat16)


def test_fused_logistic_bf16_inputs(data, ctx):
    """bf16 X stays at storage width through the kernel (no fp32 X
    materialization); accumulation is f32, so parity with the f32
    aggregator over the SAME bf16-rounded values is kernel-tight."""
    x, y, w, = data
    d = x.shape[1]
    rng = np.random.RandomState(0)
    coef = rng.randn(d + 1)
    xbf = _bf16(x)
    ref = aggregators.binary_logistic(d, True)(
        np.asarray(xbf, np.float32), np.asarray(y, np.float32),
        np.asarray(w, np.float32), np.asarray(coef, np.float32))
    got = fused_binary_logistic(xbf, y, w, coef, d, True,
                                interpret=True, row_tile=128)
    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got["grad"]),
                               np.asarray(ref["grad"]), rtol=5e-3, atol=5e-3)


def test_fused_logistic_scaled_bf16_inputs(data, ctx):
    x, y, w = data
    d = x.shape[1]
    rng = np.random.RandomState(2)
    coef = rng.randn(d + 1)
    inv_std = rng.rand(d) + 0.5
    mu = rng.randn(d)
    xbf = _bf16(x)
    ref = aggregators.binary_logistic_scaled(d, True)(
        np.asarray(xbf, np.float32), np.asarray(y, np.float32),
        np.asarray(w, np.float32), np.asarray(inv_std, np.float32),
        np.asarray(mu, np.float32), np.asarray(coef, np.float32))
    got = fused_binary_logistic_scaled(xbf, y, w, inv_std, mu, coef, d, True,
                                       interpret=True, row_tile=128)
    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got["grad"]),
                               np.asarray(ref["grad"]), rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("narrow", [False, True])
def test_fused_least_squares_scaled_matches_aggregator(data, narrow, ctx):
    x, y, w = data
    d = x.shape[1]
    rng = np.random.RandomState(6)
    coef = rng.randn(d)
    inv_std = rng.rand(d) + 0.5
    mu = rng.randn(d)
    y_pars = np.array([1.7, 0.3])  # [1/sigma_y, scaled y mean]
    xin = _bf16(x) if narrow else x
    xref = np.asarray(xin, np.float32)
    ref = aggregators.least_squares_scaled(d)(
        xref, np.asarray(y, np.float32), np.asarray(w, np.float32),
        np.asarray(inv_std, np.float32), np.asarray(mu, np.float32),
        np.asarray(y_pars, np.float32), np.asarray(coef, np.float32))
    got = fused_least_squares_scaled(xin, y, w, inv_std, mu, y_pars, coef, d,
                                     interpret=True, row_tile=128)
    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got["grad"]),
                               np.asarray(ref["grad"]), rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(float(got["count"]), float(ref["count"]),
                               rtol=1e-6)


def test_fused_kmeans_assign_bf16_points(ctx):
    """bf16 points with f32 distance accumulation: assignments match the
    f64 reference computed over the SAME bf16-rounded values (the tier
    rounds the data once; the kernel must not round the accumulation)."""
    rng = np.random.RandomState(9)
    xbf = _bf16(rng.randn(300, 17))
    centers = rng.randn(5, 17)
    best, dist = fused_kmeans_assign(xbf, centers, interpret=True,
                                     row_tile=128)
    xf = np.asarray(xbf, np.float64)
    d2 = ((xf[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(best), d2.argmin(1))
    np.testing.assert_allclose(np.asarray(dist), d2.min(1), rtol=1e-2,
                               atol=1e-2)


def test_fused_gramian_bf16(ctx):
    rng = np.random.RandomState(10)
    xbf = _bf16(rng.randn(256, 13))
    g = fused_gramian(xbf, interpret=True, row_tile=128)
    xf = np.asarray(xbf, np.float64)
    np.testing.assert_allclose(np.asarray(g), xf.T @ xf, rtol=1e-3,
                               atol=1e-2)


def test_estimators_run_on_pallas_kernels(ctx, monkeypatch):
    """cyclone.ml.usePallasKernels routes LR's aggregator and KMeans
    assignment through ops/kernels.py; results match the XLA-fused default
    path to f32-kernel tolerance (VERDICT r2 item 6 — the kernels must be
    wired, not ornamental)."""
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.ml.clustering import KMeans

    rng = np.random.RandomState(7)
    x = rng.randn(600, 12)
    y = (x[:, 0] - x[:, 1] > 0).astype(float)
    ds = InstanceDataset.from_numpy(ctx, x, y)

    # the package never interprets: on the CPU mesh the TEST routes the
    # kernels' pallas_calls through the interpreter for its own duration
    from cycloneml_tpu.ops import kernels
    native_call = kernels.pl.pallas_call
    monkeypatch.setattr(
        kernels.pl, "pallas_call",
        lambda *a, **kw: native_call(*a, **{**kw, "interpret": True}))

    def both(fit):
        ctx.conf.set(USE_PALLAS_KERNELS, "false")
        ref = fit()
        ctx.conf.set(USE_PALLAS_KERNELS, "true")
        try:
            pal = fit()
        finally:
            ctx.conf.set(USE_PALLAS_KERNELS, "false")
        return ref, pal

    ref, pal = both(lambda: LogisticRegression(
        maxIter=30, regParam=0.01, tol=1e-8).fit(ds))
    np.testing.assert_allclose(pal.coefficients, ref.coefficients,
                               rtol=5e-3, atol=5e-4)

    refk, palk = both(lambda: KMeans(k=4, maxIter=10, seed=5).fit(ds))
    c_ref = np.asarray(sorted(refk.cluster_centers, key=lambda c: tuple(c)))
    c_pal = np.asarray(sorted(palk.cluster_centers, key=lambda c: tuple(c)))
    np.testing.assert_allclose(c_pal, c_ref, rtol=1e-4, atol=1e-5)


# -- fp8 data tier: 1-byte codes + per-VMEM-block dequant scales --------------

def _fp8_cols(x):
    """Quantize columns the way the dataset tier does: per-column scales
    into e4m3's finite range."""
    from cycloneml_tpu.dataset.instance import quantize_fp8
    return quantize_fp8(x)[:2]


def test_fused_logistic_fp8_scale_operand(data, ctx):
    """fp8 codes + the in-kernel per-column scale reproduce the f32
    aggregator over the SAME dequantized values, kernel-tight: the scale
    multiply runs per VMEM block, after the tile upcast."""
    x, y, w = data
    d = x.shape[1]
    rng = np.random.RandomState(8)
    coef = rng.randn(d + 1)
    x8, scale = _fp8_cols(x)
    deq = np.asarray(x8, np.float32) * scale[None, :].astype(np.float32)
    ref = aggregators.binary_logistic(d, True)(
        deq, np.asarray(y, np.float32), np.asarray(w, np.float32),
        np.asarray(coef, np.float32))
    got = fused_binary_logistic(x8, y, w, coef, d, True,
                                interpret=True, row_tile=128,
                                x_scale=scale)
    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got["grad"]),
                               np.asarray(ref["grad"]), rtol=5e-3, atol=5e-3)


def test_fused_least_squares_fp8_scale_operand(data, ctx):
    x, y, w = data
    d = x.shape[1]
    rng = np.random.RandomState(9)
    coef = rng.randn(d)
    inv_std = rng.rand(d) + 0.5
    mu = rng.randn(d)
    y_pars = np.array([1.7, 0.3])
    x8, scale = _fp8_cols(x)
    deq = np.asarray(x8, np.float32) * scale[None, :].astype(np.float32)
    ref = aggregators.least_squares_scaled(d)(
        deq, np.asarray(y, np.float32), np.asarray(w, np.float32),
        np.asarray(inv_std, np.float32), np.asarray(mu, np.float32),
        np.asarray(y_pars, np.float32), np.asarray(coef, np.float32))
    got = fused_least_squares_scaled(x8, y, w, inv_std, mu, y_pars, coef,
                                     d, interpret=True, row_tile=128,
                                     x_scale=scale)
    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got["grad"]),
                               np.asarray(ref["grad"]), rtol=5e-3, atol=5e-3)


def test_fused_gramian_fp8(ctx):
    rng = np.random.RandomState(10)
    x = rng.randn(96, 9) * np.array([1.0, 4.0, 0.5, 2.0, 1.0, 3.0, 1.0,
                                     0.25, 1.0])
    x8, scale = _fp8_cols(x)
    deq = np.asarray(x8, np.float64) * scale[None, :]
    g = fused_gramian(x8, interpret=True, row_tile=32, x_scale=scale)
    np.testing.assert_allclose(np.asarray(g), deq.T @ deq,
                               rtol=1e-4, atol=1e-3)


def test_fused_kmeans_assign_fp8(ctx):
    rng = np.random.RandomState(11)
    centers = rng.randn(5, 8) * 2.0
    x = centers[rng.randint(0, 5, 200)] + 0.05 * rng.randn(200, 8)
    x8, scale = _fp8_cols(x)
    deq = np.asarray(x8, np.float64) * scale[None, :]
    best, dist = fused_kmeans_assign(x8, centers, interpret=True,
                                     row_tile=64, x_scale=scale)
    # reference assignment on the dequantized points
    d2 = ((deq[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(best), d2.argmin(1))
    np.testing.assert_allclose(np.asarray(dist), d2.min(1),
                               rtol=1e-4, atol=1e-4)
