"""bf16 data tier with fp32 accumulation (ISSUE 6 acceptance suite).

Three contracts pinned here:

1. **Byte reduction is real and measured** — the bf16 logistic sweep
   accesses < 60% of the fp32 sweep's bytes by XLA's own accounting
   (``observe/costs.sweep_cost``, lower-only — nothing executes), not by
   dtype-width arithmetic.
2. **Accuracy survives the tier** — seeded logreg/linreg coefficient
   parity between the bf16 and fp32 tiers within the documented tolerance
   (docs/mixed-precision.md: ~2% relative for well-scaled problems), and
   stacked == serial stays tight *within* a tier.
3. **The opt-out is exact** — ``cyclone.data.dtype=float32`` takes the
   pre-tier code path: full-width aggregator math is bit-identical to the
   pre-PR formula (no ``preferred_element_type``, no downcasts anywhere).

Tests run under the x64 CPU config like the rest of tier-1; the bf16 tier
is forced per-test via conf and restored afterwards (auto resolves to
float64 under x64, which is what keeps every OTHER suite byte-identical).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.dataset.instance import (compute_dtype, data_dtype,
                                            is_narrow_dtype)
from cycloneml_tpu.ml.optim import aggregators


@pytest.fixture
def tier(ctx):
    """Set cyclone.data.dtype for one test, always restoring 'auto'."""
    def set_tier(name):
        ctx.conf.set("cyclone.data.dtype", name)
    yield set_tier
    ctx.conf.set("cyclone.data.dtype", "auto")


def _fresh_frame(ctx, x, y):
    # a new MLFrame per tier: the frame's dataset cache is keyed by dtype,
    # but distinct frames make each test's placement explicit
    return MLFrame(ctx, {"features": x, "label": y})


# -- tier resolution ---------------------------------------------------------

def test_data_dtype_auto_is_float64_under_x64(ctx):
    assert jax.config.jax_enable_x64
    assert np.dtype(data_dtype(ctx.conf)) == np.float64
    assert np.dtype(compute_dtype()) == np.float64


def test_data_dtype_overrides(ctx, tier):
    tier("bfloat16")
    assert str(np.dtype(data_dtype(ctx.conf))) == "bfloat16"
    assert is_narrow_dtype(data_dtype(ctx.conf))
    tier("float32")
    assert np.dtype(data_dtype(ctx.conf)) == np.float32
    assert not is_narrow_dtype(np.float32)


def test_data_dtype_validator_rejects_junk(ctx, tier):
    tier("int8")
    with pytest.raises(ValueError):
        data_dtype(ctx.conf)


# -- dataset plumbing --------------------------------------------------------

def test_bf16_dataset_stores_x_narrow_yw_wide(ctx, tier):
    tier("bfloat16")
    rng = np.random.RandomState(0)
    x = rng.randn(100, 8)
    y = (rng.rand(100) > 0.5).astype(np.float64)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    assert str(ds.x.dtype) == "bfloat16"
    # labels/weights stay in the accumulator tier: weight sums, label
    # moments and optimizer state must not round at storage width
    assert np.dtype(str(ds.y.dtype)) == np.dtype(compute_dtype())
    assert np.dtype(str(ds.w.dtype)) == np.dtype(compute_dtype())
    # storage accounting reflects the split tiers
    n_pad = int(ds.x.shape[0])
    assert ds.padded_bytes() == n_pad * (8 * 2 + 2 * 8)


def test_bf16_npz_spill_and_checkpoint_roundtrip(ctx, tier, tmp_path):
    tier("bfloat16")
    rng = np.random.RandomState(1)
    x = rng.randn(64, 5)
    ds = InstanceDataset.from_numpy(ctx, x)
    x_before = np.asarray(ds.x)
    # DISK tier spill: npz drops extension dtypes unless packed
    ds.persist_disk(str(tmp_path / "spill.npz"))
    assert str(ds.x.dtype) == "bfloat16"  # transparent restore
    np.testing.assert_array_equal(np.asarray(ds.x), x_before)
    # checkpoint/restore round trip
    ds2 = InstanceDataset.from_numpy(ctx, x)
    path = ds2.checkpoint(str(tmp_path / "ckpt.npz"))
    ds3 = InstanceDataset.restore(ctx, path)
    assert str(ds3.x.dtype) == "bfloat16"
    np.testing.assert_array_equal(np.asarray(ds3.x), x_before)
    # y can ride the data tier too (a derived bf16 label matrix) — the
    # pack must cover it, not just x
    import ml_dtypes
    rt = ctx.mesh_runtime
    y_stackish = rng.rand(64, 2) > 0.5
    ds4 = InstanceDataset.from_numpy(ctx, x).derive(
        y=rt.device_put_sharded_rows(
            y_stackish.astype(ml_dtypes.bfloat16)))
    y_before = np.asarray(ds4.y)
    path4 = ds4.checkpoint(str(tmp_path / "ckpt_y.npz"))
    ds5 = InstanceDataset.restore(ctx, path4)
    assert str(ds5.y.dtype) == "bfloat16"
    np.testing.assert_array_equal(np.asarray(ds5.y), y_before)


def test_summarizer_counts_exact_over_bf16(ctx, tier):
    from cycloneml_tpu.ml.stat import Summarizer
    tier("bfloat16")
    rng = np.random.RandomState(2)
    n = 2000  # far past bf16's 256-integer exactness limit
    x = rng.randn(n, 3)
    x[:, 2] = 0.0
    ds = InstanceDataset.from_numpy(ctx, x)
    s = Summarizer.summarize(ds)
    assert s.count == n
    assert s.num_nonzeros[2] == 0
    assert s.num_nonzeros[0] == np.count_nonzero(
        np.asarray(ds.unpad(np.asarray(ds.x))[:, 0]))
    # means/stds at bf16 input resolution
    np.testing.assert_allclose(s.mean[:2], x[:, :2].mean(0), atol=2e-2)


# -- seeded parity: bf16 vs fp32 tier ---------------------------------------

# documented accuracy expectation (docs/mixed-precision.md): coefficient
# agreement for well-scaled dense problems within ~2% relative; the
# tolerance here is the contract the docs quote
BF16_COEF_RTOL = 5e-2


def test_logreg_bf16_vs_fp32_coef_parity(ctx, tier):
    from cycloneml_tpu.ml.classification import LogisticRegression
    rng = np.random.RandomState(7)
    n, d = 2000, 16
    x = rng.randn(n, d) * (1.0 + np.arange(d) / 4.0) + 0.3
    beta = rng.randn(d)
    y = (x @ beta + rng.randn(n) > 0).astype(np.float64)

    def fit(t):
        tier(t)
        return LogisticRegression(maxIter=80, regParam=0.01, tol=1e-10).fit(
            _fresh_frame(ctx, x, y))

    m32, mbf = fit("float32"), fit("bfloat16")
    c32 = np.asarray(m32.coefficients.to_array())
    cbf = np.asarray(mbf.coefficients.to_array())
    rel = np.abs(cbf - c32) / np.maximum(np.abs(c32), 1e-2)
    assert rel.max() < BF16_COEF_RTOL, rel.max()
    # and the tier is genuinely narrow, not silently promoted
    dsbf = _fresh_frame(ctx, x, y).to_instance_dataset("features", "label")
    assert str(dsbf.x.dtype) == "bfloat16"


def test_linreg_bf16_vs_fp32_coef_parity(ctx, tier):
    from cycloneml_tpu.ml.regression import LinearRegression
    rng = np.random.RandomState(11)
    n, d = 2000, 12
    x = rng.randn(n, d) * 2.0 + 1.0
    beta = rng.randn(d)
    y = x @ beta + 0.05 * rng.randn(n)

    def fit(t):
        tier(t)
        return LinearRegression(maxIter=80, solver="l-bfgs",
                                regParam=0.001, tol=1e-10).fit(
            _fresh_frame(ctx, x, y))

    m32, mbf = fit("float32"), fit("bfloat16")
    c32 = np.asarray(m32.coefficients.to_array())
    cbf = np.asarray(mbf.coefficients.to_array())
    rel = np.abs(cbf - c32) / np.maximum(np.abs(c32), 1e-2)
    assert rel.max() < BF16_COEF_RTOL, rel.max()


def test_stacked_equals_serial_within_bf16_tier(ctx, tier):
    """The stacked engine's equivalence contract holds INSIDE the narrow
    tier too: both paths read the same bf16 X with the same fp32/f64
    accumulation, so their fixed points agree far tighter than either
    agrees with the fp32 tier."""
    from cycloneml_tpu.ml.classification import LogisticRegression, OneVsRest
    tier("bfloat16")
    rng = np.random.RandomState(5)
    n, d, k = 900, 10, 3
    centers = rng.randn(k, d) * 3.0
    y = rng.randint(0, k, n).astype(np.float64)
    x = centers[y.astype(int)] + rng.randn(n, d)
    frame = _fresh_frame(ctx, x, y)
    clf = LogisticRegression(maxIter=150, regParam=0.01, tol=1e-10)
    stacked = OneVsRest(classifier=clf, parallelism=k).fit(frame)
    serial = OneVsRest(classifier=clf, parallelism=1).fit(frame)
    diff = max(float(np.abs(a._coef - b._coef).max())
               for a, b in zip(stacked.models, serial.models))
    assert diff < 1e-5, diff
    # the OvR label stack rides the data tier
    from cycloneml_tpu.dataset.instance import data_dtype as _dd
    assert str(np.dtype(_dd(ctx.conf))) == "bfloat16"


# -- the acceptance pin: measured byte reduction -----------------------------

def test_bf16_sweep_accesses_under_60_percent_of_fp32_bytes(ctx, tier):
    """ISSUE-6 acceptance: bytes-accessed per logreg optimizer sweep
    (observe/costs registry, XLA cost analysis on CPU — lower-only, no
    execution) drops >= 40% at equal n×d when the data tier narrows to
    bf16. d is wide enough that X dominates the (n,)-vector temporaries,
    as in every shape the roofline motivation is about."""
    from cycloneml_tpu.observe import costs
    rng = np.random.RandomState(3)
    n, d = 4096, 256
    x = rng.randn(n, d)
    y = (rng.rand(n) > 0.5).astype(np.float64)

    def measure(t):
        tier(t)
        ds = InstanceDataset.from_numpy(ctx, x, y)
        # extras/coef in f32 regardless of the x64 test config: the
        # measurement must mirror the production (non-x64) program, where
        # the accumulator tier is f32 — f64 extras under x64 would inflate
        # the fp32 sweep via operand promotion and flatter the ratio
        f32 = np.float32
        cost = costs.sweep_cost(
            ds.tree_aggregate_fn(aggregators.binary_logistic_scaled(d, True)),
            jnp.ones(d, f32), jnp.zeros(d, f32), jnp.zeros(d + 1, f32),
            name=f"sweep.{t}")
        return cost.bytes_accessed_total

    fp32_bytes = measure("float32")
    bf16_bytes = measure("bfloat16")
    assert fp32_bytes and bf16_bytes  # CPU reports cost analysis
    ratio = bf16_bytes / fp32_bytes
    assert ratio < 0.60, (bf16_bytes, fp32_bytes, ratio)


# -- the opt-out guard: float32 tier is bit-identical pre-PR math ------------

def test_float32_tier_aggregator_is_bitwise_pre_tier(ctx, tier):
    """cyclone.data.dtype=float32 restores the pre-PR sweep exactly: the
    full-width branch of the tier-aware dot IS the pre-tier jnp.dot — no
    preferred_element_type, no casts — pinned bitwise against a local
    reimplementation of the pre-PR formula."""
    tier("float32")
    rng = np.random.RandomState(4)
    n, d = 256, 9
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    y = jnp.asarray((rng.rand(n) > 0.5), jnp.float32)
    w = jnp.asarray(rng.rand(n) + 0.5, jnp.float32)
    inv_std = jnp.asarray(rng.rand(d) + 0.5, jnp.float32)
    mu = jnp.asarray(rng.randn(d), jnp.float32)
    coef = jnp.asarray(rng.randn(d + 1), jnp.float32)

    got = aggregators.binary_logistic_scaled(d, True)(
        x, y, w, inv_std, mu, coef)

    prec = jax.lax.Precision.HIGHEST
    beta, b0 = coef[:d], coef[d]
    sb = inv_std * beta
    margin = (jnp.dot(x, sb, precision=prec)
              - jnp.dot(mu, beta, precision=prec) + b0)
    loss = jnp.sum(w * (jax.nn.softplus(margin) - y * margin))
    mult = w * (jax.nn.sigmoid(margin) - y)
    msum = jnp.sum(mult)
    g = inv_std * jnp.dot(x.T, mult, precision=prec) - mu * msum
    grad = jnp.concatenate([g, msum[None]])

    assert float(got["loss"]) == float(loss)
    np.testing.assert_array_equal(np.asarray(got["grad"]),
                                  np.asarray(grad))


def test_float32_tier_fit_is_deterministic(ctx, tier):
    from cycloneml_tpu.ml.classification import LogisticRegression
    tier("float32")
    rng = np.random.RandomState(9)
    x = rng.randn(500, 7)
    y = (x[:, 0] > 0).astype(np.float64)
    fits = [LogisticRegression(maxIter=30, regParam=0.01).fit(
        _fresh_frame(ctx, x, y)) for _ in range(2)]
    np.testing.assert_array_equal(
        np.asarray(fits[0].coefficients.to_array()),
        np.asarray(fits[1].coefficients.to_array()))


# -- narrow labels stay exact ------------------------------------------------

def test_bf16_label_stack_is_exact(ctx, tier):
    """{0, 1} is exactly representable in bf16 — the stacked label matrix
    rides the data tier without any label distortion."""
    import ml_dtypes
    y = np.array([0.0, 1.0, 2.0, 1.0])
    stack = (np.arange(3)[:, None] == y[None, :]).astype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(
        stack.astype(np.float64),
        (np.arange(3)[:, None] == y[None, :]).astype(np.float64))


# -- the second rung: fp8 (e4m3) storage with per-column scales ---------------

# documented fp8 accuracy envelope (docs/mixed-precision.md): coefficient
# agreement with the fp32 tier within 20% of the coefficient scale for
# probe-passing problems (observed ~6-17% across seeds); the envelope
# probe falls back to bf16 for anything wilder
FP8_COEF_NORMREL = 0.20


def _norm_rel(a, b):
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-9))


def test_fp8_tier_resolution(ctx, tier):
    from cycloneml_tpu.dataset.instance import is_fp8_dtype
    tier("float8")
    # forced form: e4m3 for capable callers even under the x64 parity
    # config; NON-capable callers land on the bf16 rung — raw codes must
    # never reach an estimator that would read them as values
    assert str(np.dtype(data_dtype(ctx.conf, fp8_capable=True))) \
        == "float8_e4m3fn"
    assert str(np.dtype(data_dtype(ctx.conf))) == "bfloat16"
    assert is_fp8_dtype(data_dtype(ctx.conf, fp8_capable=True))
    assert not is_fp8_dtype(np.float32)
    tier("auto8")
    # auto8 keeps the x64 parity tier full-width, like auto
    assert jax.config.jax_enable_x64
    assert np.dtype(data_dtype(ctx.conf, fp8_capable=True)) == np.float64
    assert np.dtype(data_dtype(ctx.conf)) == np.float64


def test_fp8_dataset_quantizes_with_scales(ctx, tier):
    tier("float8")
    rng = np.random.RandomState(21)
    x = rng.randn(200, 6) * np.array([1.0, 10.0, 0.1, 5.0, 2.0, 1.0])
    y = (rng.rand(200) > 0.5).astype(np.float64)
    ds = InstanceDataset.from_numpy(
        ctx, x, y, dtype=data_dtype(ctx.conf, fp8_capable=True))
    assert str(ds.x.dtype) == "float8_e4m3fn"
    assert ds.x_scale is not None and ds.x_scale.shape == (6,)
    # y/w stay at accumulator width
    assert np.dtype(str(ds.y.dtype)) == np.dtype(compute_dtype())
    # storage accounting sees the 1-byte itemsize
    n_pad = int(ds.x.shape[0])
    assert ds.padded_bytes() == n_pad * (6 * 1 + 2 * 8)
    # every stored code is finite (e4m3fn overflow is NaN, not saturate)
    codes = np.asarray(ds.x).astype(np.float32)
    assert np.isfinite(codes).all()
    # dequantized values match the raw data at e4m3 resolution (2^-4
    # relative half-ulp), column scales included
    deq, _, _ = ds.to_numpy()
    col_scale = np.abs(x).max(axis=0)
    assert np.abs(deq - x).max(axis=0).max() < 0.07 * col_scale.max()
    np.testing.assert_allclose(np.abs(deq - x).max(axis=0),
                               np.zeros(6), atol=(0.07 * col_scale).max())


def test_fp8_npz_spill_and_checkpoint_roundtrip(ctx, tier, tmp_path):
    tier("float8")
    rng = np.random.RandomState(22)
    x = rng.randn(64, 5)
    dt = data_dtype(ctx.conf, fp8_capable=True)
    ds = InstanceDataset.from_numpy(ctx, x, dtype=dt)
    x_before = np.asarray(ds.x)
    scale_before = ds.x_scale.copy()
    # DISK spill: fp8 packs as a uint8 bit-view + dtype tag + scales
    ds.persist_disk(str(tmp_path / "spill8.npz"))
    assert str(ds.x.dtype) == "float8_e4m3fn"  # transparent restore
    np.testing.assert_array_equal(np.asarray(ds.x), x_before)
    np.testing.assert_array_equal(ds.x_scale, scale_before)
    # checkpoint/restore round trip keeps codes AND scales
    ds2 = InstanceDataset.from_numpy(ctx, x, dtype=dt)
    path = ds2.checkpoint(str(tmp_path / "ckpt8.npz"))
    ds3 = InstanceDataset.restore(ctx, path)
    assert str(ds3.x.dtype) == "float8_e4m3fn"
    np.testing.assert_array_equal(np.asarray(ds3.x), x_before)
    np.testing.assert_array_equal(ds3.x_scale, scale_before)


def test_fp8_npz_torn_tag_is_a_loud_error(ctx, tier, tmp_path):
    """A corrupt dtype tag must fail the LOAD with a clear error — never
    silently reinterpret packed bytes as a different tier."""
    tier("float8")
    rng = np.random.RandomState(23)
    ds = InstanceDataset.from_numpy(
        ctx, rng.randn(32, 4), dtype=data_dtype(ctx.conf, fp8_capable=True))
    path = ds.checkpoint(str(tmp_path / "torn.npz"))
    z = dict(np.load(path, allow_pickle=False))
    # torn tag case 1: tag names a WIDER dtype than the packed payload
    z1 = dict(z)
    z1["x_dtype"] = "bfloat16"
    np.savez(str(tmp_path / "torn1.npz"), **z1)
    with pytest.raises(ValueError, match="corrupt npz dtype tag"):
        InstanceDataset.restore(ctx, str(tmp_path / "torn1.npz"))
    # torn tag case 2: tag is garbage
    z2 = dict(z)
    z2["x_dtype"] = "float8_e4m3fnX"
    np.savez(str(tmp_path / "torn2.npz"), **z2)
    with pytest.raises(ValueError, match="corrupt npz dtype tag"):
        InstanceDataset.restore(ctx, str(tmp_path / "torn2.npz"))


def test_summarizer_dequantizes_fp8_moments(ctx, tier):
    from cycloneml_tpu.ml.stat import Summarizer
    tier("float8")
    rng = np.random.RandomState(24)
    x = rng.randn(1500, 4) * np.array([1.0, 8.0, 0.25, 3.0]) + 0.5
    ds = InstanceDataset.from_numpy(
        ctx, x, dtype=data_dtype(ctx.conf, fp8_capable=True))
    s = Summarizer.summarize(ds)
    assert s.count == 1500
    # moments are in VALUE space (scales folded in _finalize), at e4m3
    # resolution
    np.testing.assert_allclose(s.mean, x.mean(0), atol=0.1)
    np.testing.assert_allclose(s.std, x.std(0, ddof=0), rtol=0.1)
    np.testing.assert_allclose(s.max, x.max(0), rtol=0.08)
    np.testing.assert_allclose(s.min, x.min(0), rtol=0.08)


def test_logreg_fp8_vs_fp32_coef_parity(ctx, tier):
    from cycloneml_tpu.ml.classification import LogisticRegression
    rng = np.random.RandomState(25)
    n, d = 2000, 16
    x = rng.randn(n, d) * (1.0 + np.arange(d) / 4.0) + 0.3
    beta = rng.randn(d)
    y = (x @ beta + rng.randn(n) > 0).astype(np.float64)

    def fit(t):
        tier(t)
        return LogisticRegression(maxIter=80, regParam=0.01, tol=1e-10).fit(
            _fresh_frame(ctx, x, y))

    m32, m8 = fit("float32"), fit("float8")
    c32 = np.asarray(m32.coefficients.to_array())
    c8 = np.asarray(m8.coefficients.to_array())
    assert _norm_rel(c8, c32) < FP8_COEF_NORMREL, _norm_rel(c8, c32)
    # and the tier is genuinely 1-byte, not silently promoted
    ds8 = _fresh_frame(ctx, x, y).to_instance_dataset(
        "features", "label", fp8_capable=True)
    assert str(ds8.x.dtype) == "float8_e4m3fn"
    assert ds8.x_scale is not None


def test_linreg_fp8_vs_fp32_coef_parity(ctx, tier):
    from cycloneml_tpu.ml.regression import LinearRegression
    rng = np.random.RandomState(26)
    n, d = 2000, 12
    x = rng.randn(n, d) * 2.0 + 1.0
    beta = rng.randn(d)
    y = x @ beta + 0.05 * rng.randn(n)

    def fit(t):
        tier(t)
        return LinearRegression(maxIter=80, solver="l-bfgs",
                                regParam=0.001, tol=1e-10).fit(
            _fresh_frame(ctx, x, y))

    m32, m8 = fit("float32"), fit("float8")
    c32 = np.asarray(m32.coefficients.to_array())
    c8 = np.asarray(m8.coefficients.to_array())
    assert _norm_rel(c8, c32) < FP8_COEF_NORMREL, _norm_rel(c8, c32)


def test_fp8_sweep_accesses_under_45_percent_of_fp32_bytes(ctx, tier):
    """ISSUE-14 acceptance: the fp8 logistic sweep's bytes-accessed
    (XLA cost analysis, lower-only) lands under 0.45x the fp32 sweep at
    n=4096 d=256 — `make bench-bytes` gates the same ratio off-x64
    (measured ~0.35 there; the x64 config's f64 y/w overheads make the
    fp32 baseline heavier, so the measured ratio here is lower still)."""
    from cycloneml_tpu.observe import costs
    rng = np.random.RandomState(27)
    n, d = 4096, 256
    x = rng.randn(n, d)
    y = (rng.rand(n) > 0.5).astype(np.float64)

    def measure(t):
        tier(t)
        ds = InstanceDataset.from_numpy(
            ctx, x, y, dtype=data_dtype(ctx.conf, fp8_capable=True))
        f32 = np.float32
        cost = costs.sweep_cost(
            ds.tree_aggregate_fn(aggregators.binary_logistic_scaled(d, True)),
            jnp.ones(d, f32), jnp.zeros(d, f32), jnp.zeros(d + 1, f32),
            name=f"sweep8.{t}")
        return cost.bytes_accessed_total

    fp32_bytes = measure("float32")
    fp8_bytes = measure("float8")
    assert fp32_bytes and fp8_bytes
    ratio = fp8_bytes / fp32_bytes
    assert ratio < 0.45, (fp8_bytes, fp32_bytes, ratio)


def test_fp8_envelope_probe_triggers_bf16_fallback(ctx, tier):
    """The safety rail, end to end: an ill-conditioned feature (absmax
    >> std) makes the pre-fit probe decline e4m3; the fit falls back to
    bf16 storage, trains fine, and the decision surfaces as BOTH a
    PrecisionFallback event and the FitProfile.fp8_fallbacks field."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.observe.profile import FitProfile
    from cycloneml_tpu.util.events import PrecisionFallback
    tier("float8")
    rng = np.random.RandomState(28)
    n, d = 800, 8
    x = rng.randn(n, d)
    x[:, 2] = 1000.0 + 0.01 * rng.randn(n)  # absmax/std ~ 1e5
    y = (x[:, 0] > 0).astype(np.float64)

    events = []
    ctx.listener_bus.add_listener(events.append)
    tracer = tracing.enable(max_spans=50_000)
    try:
        model = LogisticRegression(maxIter=25, regParam=0.01).fit(
            _fresh_frame(ctx, x, y))
        ctx.listener_bus.wait_until_empty()
        spans = tracer.snapshot()
    finally:
        tracing.disable()
        ctx.listener_bus.remove_listener(events.append)
    assert np.all(np.isfinite(np.asarray(model.coefficients.to_array())))
    fallbacks = [e for e in events if isinstance(e, PrecisionFallback)]
    assert len(fallbacks) == 1
    assert fallbacks[0].estimator == "LogisticRegression"
    assert fallbacks[0].from_dtype == "float8_e4m3fn"
    assert fallbacks[0].to_dtype == "bfloat16"
    assert "absmax/std" in fallbacks[0].reason
    profile = FitProfile.from_spans(spans)
    assert profile.fp8_fallbacks == 1
    # a well-scaled fit under the same tier does NOT fall back
    events2 = []
    ctx.listener_bus.add_listener(events2.append)
    try:
        x2 = rng.randn(n, d)
        y2 = (x2[:, 0] > 0).astype(np.float64)
        LogisticRegression(maxIter=25, regParam=0.01).fit(
            _fresh_frame(ctx, x2, y2))
        ctx.listener_bus.wait_until_empty()
    finally:
        ctx.listener_bus.remove_listener(events2.append)
    assert not [e for e in events2 if isinstance(e, PrecisionFallback)]


def test_fp8_probe_heuristics(ctx):
    from types import SimpleNamespace
    from cycloneml_tpu.dataset.instance import fp8_probe_ok
    good = SimpleNamespace(std=np.ones(3), max=np.full(3, 3.0),
                           min=np.full(3, -3.0))
    assert fp8_probe_ok(good) is None
    # constant columns are exempt (standardization drops them)
    const = SimpleNamespace(std=np.array([1.0, 0.0]),
                            max=np.array([3.0, 500.0]),
                            min=np.array([-3.0, 500.0]))
    assert fp8_probe_ok(const) is None
    bad = SimpleNamespace(std=np.array([1.0, 0.01]),
                          max=np.array([3.0, 100.0]),
                          min=np.array([-3.0, 99.0]))
    assert "absmax/std" in fp8_probe_ok(bad)
    # weight overflow: |w * residual| past e4m3's finite range
    assert "weight" in fp8_probe_ok(good, w_max=1000.0)


def test_fp8_generic_consumers_get_bf16(ctx, tier):
    """Structural safety: under the fp8 tiers, every consumer that has
    NOT declared fp8 capability materializes at the bf16 rung — raw
    e4m3 codes never reach an estimator that would read them as
    values — and a quantized dataset handed to a non-capable bridge
    dequantizes."""
    tier("float8")
    rng = np.random.RandomState(29)
    x = rng.randn(100, 4)
    ds = InstanceDataset.from_numpy(ctx, x)  # no explicit dtype
    assert str(ds.x.dtype) == "bfloat16"
    frame = _fresh_frame(ctx, x, (x[:, 0] > 0).astype(np.float64))
    assert str(frame.to_instance_dataset("features", "label").x.dtype) \
        == "bfloat16"
    # a quantized dataset through the non-capable bridge dequantizes
    ds8 = InstanceDataset.from_numpy(
        ctx, x, dtype=data_dtype(ctx.conf, fp8_capable=True))
    assert str(ds8.x.dtype) == "float8_e4m3fn"
    ds_view = ds8.to_instance_dataset()
    assert str(ds_view.x.dtype) == "bfloat16"
    assert ds_view.x_scale is None


def test_ovr_stacked_rides_fp8(ctx, tier):
    """OneVsRest under the fp8 tier: X stays e4m3 codes (shared via
    derive), the label stack rides the bf16 rung ({0,1} exact; fp8
    refuses implicit promotion by design), and the stacked fixed points
    stay within the fp8 envelope of the serial ones."""
    from cycloneml_tpu.ml.classification import LogisticRegression, OneVsRest
    tier("float8")
    rng = np.random.RandomState(30)
    n, d, k = 900, 10, 3
    centers = rng.randn(k, d) * 3.0
    y = rng.randint(0, k, n).astype(np.float64)
    x = centers[y.astype(int)] + rng.randn(n, d)
    frame = _fresh_frame(ctx, x, y)
    clf = LogisticRegression(maxIter=120, regParam=0.01, tol=1e-10)
    stacked = OneVsRest(classifier=clf, parallelism=k).fit(frame)
    serial = OneVsRest(classifier=clf, parallelism=1).fit(frame)
    for a, b in zip(stacked.models, serial.models):
        assert np.all(np.isfinite(a._coef))
        assert _norm_rel(a._coef, b._coef) < FP8_COEF_NORMREL


def test_fp8_streamed_fit_streams_codes(ctx, tier):
    """A quantized dataset routed to the streaming engine (oocore force
    mode / budget-guard degradation) keeps its e4m3 CODES on the shard
    set — the in-core envelope probe already admitted this data to the
    fp8 rung, the stream stages 1-byte codes, and the per-column dequant
    scale folds into the aggregator read exactly like the in-core fp8
    fit — so the streamed coefficients land ulp-close to the in-core fp8
    ones and the host→device byte bill stays halved. Only a
    ``streamDtype=bfloat16`` pin forces the codes back up, visibly
    (PrecisionFallback)."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.oocore import shard_set_cache
    from cycloneml_tpu.util.events import PrecisionFallback
    tier("float8")
    shard_set_cache().clear()
    rng = np.random.RandomState(31)
    n, d = 900, 6
    x = rng.randn(n, d) * np.array([1.0, 8.0, 0.5, 2.0, 1.0, 4.0])
    y = (x[:, 1] - x[:, 2] > 0).astype(np.float64)
    est = LogisticRegression(maxIter=40, regParam=0.01, tol=1e-10)
    m_incore = est.fit(_fresh_frame(ctx, x, y))
    events = []
    ctx.listener_bus.add_listener(events.append)
    ctx.conf.set("cyclone.oocore.mode", "force")
    try:
        m_streamed = est.fit(_fresh_frame(ctx, x, y))
        ctx.listener_bus.wait_until_empty()
        # the codes spilled AS codes: no precision fallback fired
        assert not [e for e in events if isinstance(e, PrecisionFallback)]
        assert m_streamed.summary.streamed
        # same codes, same set-level scale, same stats → the streamed
        # fit agrees with the in-core fp8 fit far inside the envelope
        c_in = np.asarray(m_incore.coefficients.to_array())
        c_st = np.asarray(m_streamed.coefficients.to_array())
        assert _norm_rel(c_st, c_in) < 1e-6, _norm_rel(c_st, c_in)
        # pinning the stream to the bf16 rung forces the codes up — the
        # dequant leaves the fp8 tier visibly, never silently
        ctx.conf.set("cyclone.oocore.streamDtype", "bfloat16")
        m_pinned = est.fit(_fresh_frame(ctx, x, y))
        ctx.listener_bus.wait_until_empty()
        assert any(isinstance(e, PrecisionFallback)
                   and e.estimator == "StreamingDataset.from_dataset"
                   for e in events)
        c_pin = np.asarray(m_pinned.coefficients.to_array())
        assert _norm_rel(c_pin, c_in) < FP8_COEF_NORMREL
    finally:
        ctx.conf.set("cyclone.oocore.mode", "auto")
        ctx.conf.remove("cyclone.oocore.streamDtype")
        ctx.listener_bus.remove_listener(events.append)
        shard_set_cache().clear()
