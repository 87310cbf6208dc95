"""Chip smoke: dense ``LogisticRegression.fit`` end to end on the attached TPU.

Run from the repository root on a machine with a TPU: ``python chip_smoke.py``.
One process, every attached TPU device, no arguments, nothing read from the
environment. It drives the main path through the public entry points —
``CycloneContext`` (default ``master="tpu"``), ``generate_classification``,
``LogisticRegression.fit`` — at full width, then a host-fed numpy → ``MLFrame``
→ ``fit`` leg, a small binomial ``GeneralizedLinearRegression`` fit (IRLS over
both forms of the moment Gramian: one MXU pass, then three), a small ten-class
``LogisticRegression`` fit (the fused multinomial sweep under the
device-resident L-BFGS), a ``KMeans`` fit from a stated starting set (the fused
Lloyd step), the stacked binomial sweep (K binary models a read of X) against K
serial binomial sweeps at mnist8m's width and at a lane-aligned one, then compiles and checks every
Pallas kernel natively at small n — the one-model GLM sweep in the form each
stored X selects (its MXU form for bf16 at widths of 16 / 128, the VPU body
for f32, fp8 and d = 28). Every leg asserts WHICH path ran (platform, data dtype, Mosaic custom call,
one in-core dispatch) and that what came out is right (finite non-increasing
objective, agreement with the XLA twin and with a float64 reference).

Any failed check raises; no TPU exits 1 with a one-line reason and prints no
result. Standard output is two lines of JSON. The first is the summary of what
ran (shapes, paths, objectives, agreement, compile cache); its seconds are
observations of a smoke run (one trial, compile included where labelled), NOT
benchmark results. The LAST line is the verdict the driver parses, and holds
exactly ``{"ok": true, "device": {"platform", "kind", "count"}}`` with the
device as jax reports it.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

N_ROWS = 2_000_000      # the r05 shape: 5.12 GB of bf16 X on one chip
N_COLS = 1_280
N_HOST_ROWS = 100_000   # host-fed leg: numpy -> MLFrame -> fit
N_GLR_ROWS = 131_072    # binomial GLR leg: IRLS from a device-resident dataset
N_GLR_COLS = 256
N_SOFTMAX_ROWS = 65_536  # multinomial leg: mnist8m's width and classes
N_SOFTMAX_COLS = 784
N_CLASSES = 10
N_KMEANS_ROWS = 2_000_000  # k-means leg: the first row-major width, k = 1,000
N_KMEANS_COLS = 128
N_CENTRES = 1_000
MAX_ITER = 25
REG = 0.01

# Pallas-vs-XLA agreement of two fits over the SAME bf16 X, both with f32
# accumulators. The twins differ in summation order only — Kahan-compensated
# sequential row tiles against XLA's MXU/tree reduction, ~1e-7 relative per
# evaluation — and ten L-BFGS iterations can amplify a last-ulp difference in
# one line-search decision. Observed on the v5e (PR 21, one chip and four):
# loss <= 6.4e-8, coefficients <= 1.5e-5. Two orders of margin.
LOSS_RTOL = 1e-5
COEF_RTOL = 1e-3        # ||b_pallas - b_xla|| / ||b_xla||

# kernel output vs a float64 reference over the same stored values: f32
# accumulation over <= 4k rows (HIGHEST-precision MXU passes in the matmul
# kernels). Observed on the v5e (PR 21): <= 2.7e-6.
KERNEL_RTOL = 2e-5


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileWatch:
    """Counts jax's own compile events: persistent-cache hits/misses and
    backend-compile seconds (cache retrieval included)."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))


def in_use(devices) -> list:
    return [int(d.memory_stats()["bytes_in_use"]) for d in devices]


def assert_on_all_devices(ds, before: list, devices) -> None:
    import jax
    jax.block_until_ready(ds.x)
    check(str(ds.x.dtype) == "bfloat16",
          f"data tier is {ds.x.dtype}, expected bfloat16")
    check(len(ds.x.sharding.device_set) == len(devices),
          f"X sits on {len(ds.x.sharding.device_set)} of "
          f"{len(devices)} devices")
    grew = [a > b for a, b in zip(in_use(devices), before)]
    check(all(grew), f"bytes_in_use did not grow on every device: {grew}")


def assert_fit_in_core(model, what: str) -> list:
    s = model.summary
    hist = [float(v) for v in s.objective_history]
    check(not s.streamed, f"{what}: fit was re-routed out of core")
    check(s.total_dispatches == 1,
          f"{what}: {s.total_dispatches} dispatches — the device chunk was "
          f"degraded or the fit left DeviceLBFGS")
    # tol=0 still stops early when an iteration no longer moves the f32
    # objective (|f - f_new| <= 0): fewer than MAX_ITER is legitimate
    check(1 <= s.total_iterations <= MAX_ITER,
          f"{what}: {s.total_iterations} iterations of {MAX_ITER}")
    check(np.all(np.isfinite(hist)), f"{what}: non-finite objective {hist}")
    check(all(b <= a for a, b in zip(hist, hist[1:])),
          f"{what}: objective history increases: {hist}")
    check(np.all(np.isfinite(np.asarray(model.coefficients))),
          f"{what}: non-finite coefficients")
    # d = 1,280 is stored row-major ({1,0}): the fused sweep keeps the
    # row-major tiling, and the XLA sweep names none
    check(s.orientation == ("row_major" if "pallas" in what else None),
          f"{what}: the fit ran the {s.orientation} sweep at d = {N_COLS}")
    # a bf16 X at a width of 128: the sweep's MXU form, three pieces
    check(s.pieces == (3 if "pallas" in what else None),
          f"{what}: the fit's sweep took {s.pieces} pieces at d = {N_COLS}")
    return hist


def aggregation_program_text(ds, d: int) -> str:
    """Lowered text of the tree_aggregate program a default-conf binomial
    fit of ``ds`` inlines. The aggregator factory and the program cache
    are both identity-keyed, so asking again after the fit returns the
    very program the fit built — asserted by the cache not growing."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.parallel import collectives

    size = len(collectives._program_cache)
    call = ds.tree_aggregate_fn(
        aggregators.binary_logistic_pallas_scaled(d, True))
    check(len(collectives._program_cache) == size,
          "the fit did not build the Pallas aggregation program")
    v = jnp.zeros(d, jnp.float32)
    return call.compiled.__wrapped__.lower(
        *call.arrays(), v, v, jnp.zeros(d + 1, jnp.float32)).as_text()


def fit_pair(ctx, lr, data, what: str):
    """The default (fused Pallas) fit cold and warm, then the same fit
    under usePallasKernels=false on the same data; returns
    (model, history, seconds, agreement)."""
    from cycloneml_tpu.conf import USE_PALLAS_KERNELS

    t0 = time.perf_counter()
    model = lr.fit(data)
    cold_s = time.perf_counter() - t0
    hist = assert_fit_in_core(model, f"{what} (pallas, cold)")
    t0 = time.perf_counter()
    warm = lr.fit(data)
    warm_s = time.perf_counter() - t0
    assert_fit_in_core(warm, f"{what} (pallas, warm)")
    check(np.array_equal(np.asarray(model.coefficients),
                         np.asarray(warm.coefficients)),
          f"{what}: the warm fit is not bit-identical to the cold one")

    ctx.conf.set(USE_PALLAS_KERNELS, "false")
    try:
        ref = lr.fit(data)
    finally:
        ctx.conf.set(USE_PALLAS_KERNELS, "auto")
    ref_hist = assert_fit_in_core(ref, f"{what} (xla)")
    b, b_ref = (np.asarray(m.coefficients, np.float64) for m in (model, ref))
    loss_rel = abs(hist[-1] - ref_hist[-1]) / abs(ref_hist[-1])
    coef_rel = float(np.linalg.norm(b - b_ref) / np.linalg.norm(b_ref))
    check(loss_rel <= LOSS_RTOL,
          f"{what}: pallas/xla final loss differ by {loss_rel:.3e}")
    check(coef_rel <= COEF_RTOL,
          f"{what}: pallas/xla coefficients differ by {coef_rel:.3e}")
    return model, hist, (cold_s, warm_s), {
        "loss_rel": loss_rel, "coef_rel": coef_rel,
        "xla_objective_last": ref_hist[-1]}


def device_leg(ctx, n: int, d: int, devices) -> dict:
    """Leg 1: device-generated data, the full-width in-core fit."""
    import jax
    from cycloneml_tpu.dataset.random import generate_classification
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.observe import costs

    before = in_use(devices)
    t0 = time.perf_counter()
    ds = generate_classification(ctx, n, d, seed=0)
    assert_on_all_devices(ds, before, devices)
    gen_s = time.perf_counter() - t0

    lr = LogisticRegression(maxIter=MAX_ITER, regParam=REG, tol=0.0)
    model, hist, (cold_s, warm_s), agree = fit_pair(ctx, lr, ds, "device leg")

    text = aggregation_program_text(ds, d)
    check("tpu_custom_call" in text,
          "no Mosaic custom call in the aggregation program: the kernel "
          "was interpreted or replaced by the XLA aggregator")
    check(ds.x_scale is None
          and (ctx.fit_profile() or {}).get("fp8_fallbacks", 0) == 0,
          "an fp8 tier or fallback on a default-conf (bf16) fit")

    # the generator's shards draw from their own streams, so the dataset
    # (and the model) depends on the device count; what is shared is the
    # ground truth every shard labels with — recover it
    beta = np.asarray(jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(0), 2 ** 31 - 1), (d,),
        dtype="float32"), np.float64)
    b = np.asarray(model.coefficients, np.float64)
    cosine = float(b @ beta / np.linalg.norm(b) / np.linalg.norm(beta))
    check(cosine > 0.95, f"fitted direction misses the ground truth: "
                         f"cosine {cosine:.4f}")
    s = model.summary
    return {
        "n": n, "d": d, "data_dtype": str(ds.x.dtype),
        "kernel_path": "pallas:tpu_custom_call",
        "iterations": s.total_iterations, "evals": s.total_evals,
        "dispatches": s.total_dispatches, "pieces": s.pieces,
        "objective_first": hist[0], "objective_last": hist[-1],
        "pallas_vs_xla": agree, "cosine_to_truth": cosine,
        "generate_s": round(gen_s, 3),
        "cold_fit_s": round(cold_s, 3), "warm_fit_s": round(warm_s, 3),
        "device_bytes_limit": costs.device_memory_limit(ctx.conf),
        "bytes_in_use": in_use(devices),
    }


def host_leg(ctx, n: int, d: int, devices) -> dict:
    """Leg 2: numpy -> MLFrame -> fit (MeshRuntime.device_put_sharded_rows).
    The data comes from a numpy seed, so it is the same on any device
    count: the coefficients are the cross-machine parity probe."""
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import LogisticRegression

    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, d), dtype=np.float32)
    beta = rng.standard_normal(d).astype(np.float32)
    y = (x @ beta + rng.standard_normal(n, dtype=np.float32) > 0)
    frame = MLFrame(ctx, {"features": x, "label": y.astype(np.float64)})

    lr = LogisticRegression(maxIter=MAX_ITER, regParam=REG, tol=0.0)
    before = in_use(devices)
    t0 = time.perf_counter()
    # the frame caches the dataset it places: this is the fit's own X
    ds = frame.to_instance_dataset(lr.get("featuresCol"), lr.get("labelCol"),
                                   None, fp8_capable=True)
    assert_on_all_devices(ds, before, devices)
    put_s = time.perf_counter() - t0
    model, hist, (cold_s, warm_s), agree = fit_pair(ctx, lr, frame, "host leg")

    b = np.asarray(model.coefficients, np.float64)
    acc = float(np.mean((x @ b.astype(np.float32) + model.intercept > 0) == y))
    check(acc > 0.9, f"host leg: training accuracy {acc:.3f}")
    os.makedirs("chiprun_out", exist_ok=True)
    np.save(os.path.join("chiprun_out",
                         f"chip_smoke_host_coef_{len(devices)}dev.npy"),
            np.append(b, model.intercept))
    s = model.summary
    return {
        "n": n, "d": d, "data_dtype": str(ds.x.dtype),
        "iterations": s.total_iterations, "evals": s.total_evals,
        "dispatches": s.total_dispatches,
        "objective_first": hist[0], "objective_last": hist[-1],
        "pallas_vs_xla": agree, "train_accuracy": acc,
        "coef_l2": float(np.linalg.norm(b)),
        "coef_head": [float(v) for v in b[:4]],
        "intercept": float(model.intercept),
        "device_put_s": round(put_s, 3),
        "cold_fit_s": round(cold_s, 3), "warm_fit_s": round(warm_s, 3),
    }


def glr_leg(ctx, n: int, d: int, devices) -> dict:
    """Leg 3: a binomial ``GeneralizedLinearRegression`` fit from a
    device-resident dataset — IRLS: the first pass's working weights hold
    one value (``mu0 (1 - mu0)``) and take ONE MXU pass of the moment
    Gramian, every later pass its three-piece form, and the fit's
    ``mxu_passes`` must say so — against a float64 Newton iteration over
    the same stored values."""
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.random import generate_classification
    from cycloneml_tpu.ml.regression import GeneralizedLinearRegression
    from cycloneml_tpu.ml.regression import glm
    from cycloneml_tpu.ops import kernels

    # noise at half the signal's deviation (beta ~ N(0, 1): |x.beta| ~
    # sqrt(d)): coefficients of a few tenths, as a table's are. At the
    # generator's default the classes all but separate, the coefficients
    # pass 1 and float32 cannot resolve IRLS's ABSOLUTE tol of 1e-6
    ds = generate_classification(ctx, n, d, seed=1, noise=0.5 * d ** 0.5)
    t0 = time.perf_counter()
    model = GeneralizedLinearRegression(family="binomial").fit(ds)
    cold_s = time.perf_counter() - t0
    s = model.summary
    se = np.asarray(s.coefficient_standard_errors, np.float64)
    check(str(ds.x.dtype) == "bfloat16", f"glr leg: data tier {ds.x.dtype}")
    check(2 <= s.num_iterations < 25 and s.total_passes == s.num_iterations
          and s.total_dispatches == s.num_iterations + 1,
          f"glr leg: {s.num_iterations} iterations, {s.total_passes} passes, "
          f"{s.total_dispatches} dispatches")
    check(s.mxu_passes == [1] + [3] * (s.num_iterations - 1),
          f"glr leg: MXU passes of the Gramians {s.mxu_passes}")
    call = ds.tree_aggregate_fn(glm.irls_aggregator(
        glm.Binomial(), glm.Logit(), kernels.stored_feature_major(ds.x),
        False))
    text = call.compiled.__wrapped__.lower(
        *call.arrays(), jnp.zeros(d + 2, jnp.float32)).as_text()
    check("tpu_custom_call" in text,
          "glr leg: no Mosaic custom call in the IRLS program")

    x, y, _ = ds.to_numpy()
    xa = np.hstack([np.asarray(x, np.float64), np.ones((len(y), 1))])
    y = np.asarray(y, np.float64)
    b = np.zeros(d + 1)
    for _ in range(25):
        mu = 1.0 / (1.0 + np.exp(-(xa @ b)))
        h = xa.T @ (xa * (mu * (1.0 - mu))[:, None])
        step = np.linalg.solve(h, xa.T @ (y - mu))
        b += step
        if np.max(np.abs(step)) < 1e-12:
            break
    got = np.append(np.asarray(model.coefficients, np.float64),
                    model.intercept)
    gap = float(np.linalg.norm(got - b) / np.linalg.norm(b))
    se_gap = rel_err(se, np.sqrt(np.diag(np.linalg.inv(h))))
    mu = 1.0 / (1.0 + np.exp(-(xa @ got)))
    dev = -2.0 * float(np.sum(y * np.log(mu) + (1 - y) * np.log1p(-mu)))
    check(gap < 1e-4, f"glr leg: coefficients {gap:.3e} off float64 Newton")
    check(se_gap < 1e-3, f"glr leg: standard errors {se_gap:.3e} off")
    check(abs(s.deviance - dev) < 1e-5 * dev,
          f"glr leg: deviance {s.deviance} against {dev}")
    return {"n": n, "d": d, "orientation":
            "feature_major" if kernels.stored_feature_major(ds.x)
            else "row_major",
            "iterations": s.num_iterations, "passes": s.total_passes,
            "dispatches": s.total_dispatches, "mxu_passes": s.mxu_passes,
            "deviance": s.deviance,
            "coef_gap_vs_f64": gap, "se_gap_vs_f64": se_gap,
            "cold_fit_s": round(cold_s, 3)}


def multinomial_leg(ctx, n: int, d: int, k: int, devices) -> dict:
    """Leg 4: ``LogisticRegression`` with ``k`` label classes from a
    device-resident bf16 dataset: ``family="auto"`` turns multinomial and
    takes the fused K-class sweep (Mosaic, both products on the MXU) under
    ``DeviceLBFGS`` — checked against the float64 objective and gradient
    at the model it returns, over the same stored values."""
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.dataset.random import generate_classification
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ops import kernels

    base = generate_classification(ctx, n, d, seed=2)
    rng = np.random.RandomState(2)
    # a class a row by a noisy linear score: learnable, not separable
    score = jnp.asarray(rng.randn(d, k) / np.sqrt(d), jnp.bfloat16)
    labels = jax.jit(lambda x, e: jnp.argmax(
        jnp.dot(x, score, preferred_element_type=jnp.float32) + e,
        axis=1).astype(jnp.float32))(
        base.x, ctx.mesh_runtime.device_put_sharded_rows(
            (0.5 * rng.randn(n, k)).astype(np.float32)))
    ds = InstanceDataset(ctx, base.x, labels, base.w, n, d)
    t0 = time.perf_counter()
    model = LogisticRegression(maxIter=100, regParam=REG).fit(ds)
    cold_s = time.perf_counter() - t0
    s = model.summary
    stored = "feature_major" if kernels.stored_feature_major(ds.x) \
        else "row_major"
    check(str(ds.x.dtype) == "bfloat16", f"softmax leg: tier {ds.x.dtype}")
    check(s.num_classes == model.num_classes == k,
          f"softmax leg: {s.num_classes} classes of {k}")
    check(s.orientation == stored,
          f"softmax leg: sweep {s.orientation!r}, X is stored {stored}")
    check(not s.streamed and s.total_dispatches < s.total_evals
          <= 2 * s.total_iterations,
          f"softmax leg: {s.total_evals} evaluations, {s.total_iterations} "
          f"iterations, {s.total_dispatches} dispatches")
    call = ds.tree_aggregate_fn(aggregators.multinomial_logistic_pallas_scaled(
        d, k, True, feature_major=stored == "feature_major"))
    v = jnp.zeros(d, jnp.float32)
    text = call.compiled.__wrapped__.lower(
        *call.arrays(), v, v, jnp.zeros(d * k + k, jnp.float32)).as_text()
    check("tpu_custom_call" in text and "glm_sweep_multinomial" in text,
          "softmax leg: no Mosaic K-class sweep in the aggregation program")

    x, y, _ = ds.to_numpy()
    x, yi = np.asarray(x, np.float64), np.asarray(y).astype(int)
    mean, std = x.mean(axis=0), x.std(axis=0, ddof=1)
    wmat = model.coefficient_matrix.to_array() * std[None, :]
    icpt = model.intercept_vector.to_array()
    check(abs(icpt.sum()) < 1e-6, f"softmax leg: intercepts sum {icpt.sum()}")
    xh = (x - mean) / std
    m = xh @ wmat.T + (icpt + (wmat / std) @ mean)
    top = m.max(axis=1, keepdims=True)
    lse = top[:, 0] + np.log(np.exp(m - top).sum(axis=1))
    objective = float(np.mean(lse - m[np.arange(n), yi])
                      + 0.5 * REG * np.sum(wmat * wmat))
    r = np.exp(m - lse[:, None])
    r[np.arange(n), yi] -= 1.0
    grad = r.T @ xh / n + REG * wmat
    at_zero = np.zeros((n, k))
    at_zero[np.arange(n), yi] = -1.0
    first = np.linalg.norm((at_zero + 1.0 / k).T @ xh / n)
    gap = abs(s.objective_history[-1] - objective) / objective
    shrink = float(np.linalg.norm(grad) / first)
    check(gap < 1e-5, f"softmax leg: objective {s.objective_history[-1]} "
                      f"against float64 {objective}")
    check(shrink < 1e-2, f"softmax leg: the float64 gradient at the model "
                         f"is {shrink:.3e} of the one at zero")
    return {"n": n, "d": d, "classes": k, "orientation": s.orientation,
            "iterations": s.total_iterations, "evals": s.total_evals,
            "dispatches": s.total_dispatches,
            "objective": s.objective_history[-1],
            "objective_gap_vs_f64": gap, "gradient_left_vs_f64": shrink,
            "accuracy": float(np.mean(np.argmax(m, axis=1) == yi)),
            "cold_fit_s": round(cold_s, 3)}


def kmeans_leg(ctx, n: int, d: int, k: int, devices) -> dict:
    """Leg 5: ``KMeans(k, initialModel=...)`` on a device-resident bf16
    mixture at a row-major width: every step is the Mosaic kernel
    ``kmeans_lloyd``; one step's assignments equal float32 nearest centres
    (XLA, ``highest``, row chunks) and its sums a float64 host reduction
    over the same stored values."""
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.dataset.random import generate_classification
    from cycloneml_tpu.ml.clustering import KMeans, kmeans

    base = generate_classification(ctx, n, d, seed=3)
    rng = np.random.RandomState(3)
    mu = rng.randn(k, d).astype(np.float32)
    pts = jax.jit(lambda x, m: (
        x.astype(jnp.float32)
        + jnp.take(m, (jnp.arange(n) * 7919) % k, axis=0)
    ).astype(jnp.bfloat16), out_shardings=base.x.sharding)(base.x, mu)
    ds = InstanceDataset(ctx, pts, base.y, base.w, n, d)
    start = (mu + 0.05 * rng.randn(k, d)).astype(np.float64)
    t0 = time.perf_counter()
    model = KMeans(k=k, maxIter=3, initialModel=start).fit(ds)
    cold_s = time.perf_counter() - t0
    s = model.summary
    check(str(ds.x.dtype) == "bfloat16", f"kmeans leg: tier {ds.x.dtype}")
    check(s.orientation == "row_major" and s.pieces == 3,
          f"kmeans leg: step {s.orientation!r}, {s.pieces} pieces")
    check(s.total_dispatches <= s.total_steps + 1,
          f"kmeans leg: {s.total_dispatches} dispatches, {s.total_steps} steps")
    call = ds.tree_aggregate_fn(kmeans.lloyd_aggregator(True, True))
    c32 = jnp.asarray(start, jnp.float32)
    text = call.compiled.__wrapped__.lower(*call.arrays(), c32).as_text()
    check("tpu_custom_call" in text and "kmeans_lloyd" in text,
          "kmeans leg: no Mosaic Lloyd step in the aggregation program")
    out = jax.device_get(call(c32))
    check(float(out["kernel_shards"]) == len(devices),
          f"kmeans leg: {out['kernel_shards']} shards took the kernel")

    chunk = 50_000

    def nearest(x, c):
        with jax.default_matmul_precision("highest"):
            def block(xb):
                xf = xb.astype(jnp.float32)
                return jnp.argmin(
                    jnp.sum(xf * xf, 1)[:, None] - 2.0 * xf @ c.T
                    + jnp.sum(c * c, 1)[None], axis=1)
            return jax.lax.map(block, x.reshape(n // chunk, chunk, d))

    assign = np.asarray(jax.jit(nearest)(ds.x, c32)).ravel()
    x64 = np.asarray(ds.to_numpy()[0], np.float64)
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=k)
    check(counts.min() > 0, "kmeans leg: an empty cluster in the check")
    sums = np.add.reduceat(x64[order], np.cumsum(counts) - counts, axis=0)
    check(np.array_equal(np.asarray(out["counts"]), counts),
          "kmeans leg: assignments differ from float32 nearest centres")
    gap = rel_err(out["sums"], sums)
    check(gap < 1e-6, f"kmeans leg: sums {gap:.3e} off the float64 reduction")

    # the step's two-piece screen against the step without it, at the
    # fitted centres (three Lloyd steps on: their mid and lo pieces are
    # non-zero): the same sums and counts to the bit, shard by shard. Both
    # run in two stages (tile g's scores beside tile g − 1's update, since
    # PR 42), so this also holds the re-check's 128-row column of the MXU
    # product and the pipelined tile order to the unscreened bits
    def screen_against_none(x, y, w, c):
        from cycloneml_tpu.ops.kmeans_lloyd import fused_lloyd_step
        a, b = (fused_lloyd_step(x, w, c, screen=on) for on in (True, False))
        return {"differing": jnp.sum(a["sums"] != b["sums"])
                + jnp.sum(a["counts"] != b["counts"]),
                "rechecked_groups": a["rechecked_groups"],
                "screened_groups": a["screened_groups"],
                "cost_gap": jnp.abs(a["cost"] - b["cost"]) / b["cost"]}

    fitted = jnp.asarray(model.cluster_centers_matrix().to_array(),
                         jnp.float32)
    screened = jax.device_get(
        ds.tree_aggregate_fn(screen_against_none)(fitted))
    check(int(screened["differing"]) == 0,
          f"kmeans leg: the screened step differs from the unscreened one "
          f"in {int(screened['differing'])} sums / counts")
    check(float(screened["cost_gap"]) < 1e-6 * len(devices),
          f"kmeans leg: screened cost {float(screened['cost_gap']):.3e} off")
    print(f"chip_smoke: kmeans leg rechecked_groups "
          f"{float(screened['rechecked_groups']):.0f} of the step's "
          f"{float(screened['screened_groups']):.0f}, recheck_share of the "
          f"fit {s.recheck_share}", file=sys.stderr)
    return {"n": n, "d": d, "k": k, "orientation": s.orientation,
            "steps": s.total_steps, "dispatches": s.total_dispatches,
            "training_cost": s.training_cost, "sums_vs_f64": gap,
            "recheck_share": s.recheck_share,
            "rechecked_groups": float(screened["rechecked_groups"]),
            "cold_fit_s": round(cold_s, 3)}


def stacked_leg(ctx, devices) -> dict:
    """Leg 6: the stacked binomial sweep — K binary models from ONE read of
    a bf16 X, the K-class sweep's body under K sigmoids — against K serial
    binomial sweeps of the same stored rows (model j on ``1[y == j]``), in
    the tiling each array's layout dictates: the largest loss and gradient
    gap over the models, relative to the serial sweep's. Then one stacked
    fit twice, in core and streamed (``cyclone.oocore.mode=force``: the
    same sweep a staged shard at a time, no label stack staged)."""
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.ops import kernels

    out = {"pieces": kernels.SOFTMAX_PIECES}
    rng = np.random.default_rng(41)
    for n, d, k in ((131_072, 784, 10), (131_072, 784, 3), (4_096, 1_280, 10)):
        x = jnp.asarray(rng.standard_normal((n, d), dtype=np.float32),
                        jnp.bfloat16)
        y = jnp.asarray(rng.integers(0, k, n), jnp.float32)
        w = jnp.ones(n, jnp.float32)
        inv_std = jnp.asarray(1.0 + rng.random(d), jnp.float32)
        mean = jnp.asarray(0.1 * rng.standard_normal(d), jnp.float32)
        coef = jnp.asarray(0.05 * rng.standard_normal((k, d + 1)),
                           jnp.float32)
        feature_major = kernels.stored_feature_major(x)
        check(feature_major == (d % kernels.LANE != 0),
              f"stacked leg: a {n} x {d} bf16 array is stored "
              f"{'feature' if feature_major else 'row'}-major")
        stacked = jax.jit(lambda x, y, c, fm=feature_major, d=d, k=k:
                          kernels.fused_stacked_binomial_scaled(
                              x, y, w, inv_std, mean, c, d, k, True,
                              feature_major=fm))
        text = stacked.lower(x, y, coef).compile().as_text()
        check(text.count("tpu_custom_call") == 1
              and "glm_sweep_stacked_binomial" in text,
              f"stacked leg: {text.count('tpu_custom_call')} Mosaic calls "
              f"for {k} models at d={d}")
        got = jax.device_get(stacked(x, y, coef))
        serial = jax.jit(lambda x, yj, c, fm=feature_major, d=d:
                         kernels.fused_binary_logistic_scaled(
                             x, yj, w, inv_std, mean, c, d, True,
                             feature_major=fm))
        loss_gap = grad_gap = 0.0
        for j in range(k):
            one = jax.device_get(serial(x, (y == j).astype(jnp.float32),
                                        coef[j]))
            loss_gap = max(loss_gap, abs(got["loss"][j] - one["loss"])
                           / abs(one["loss"]))
            grad_gap = max(grad_gap, rel_err(got["grad"][j], one["grad"]))
        check(loss_gap < 2e-6 and grad_gap < 2e-5,
              f"stacked leg: n={n}, d={d}, K={k}: loss gap {loss_gap:.3e}, "
              f"gradient gap {grad_gap:.3e} against {k} serial sweeps")
        out[f"n={n},d={d},K={k}"] = {
            "orientation": "feature_major" if feature_major else "row_major",
            "loss_gap": float(loss_gap), "grad_gap": float(grad_gap)}

    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.dataset.random import generate_classification
    from cycloneml_tpu.ml.classification import LogisticRegression
    n, d, k = N_SOFTMAX_ROWS, N_SOFTMAX_COLS, 3
    base = generate_classification(ctx, n, d, seed=6)
    score = jnp.asarray(rng.standard_normal((d, k)) / np.sqrt(d),
                        jnp.bfloat16)
    labels = jax.jit(lambda x, e: jnp.argmax(
        jnp.dot(x, score, preferred_element_type=jnp.float32) + e,
        axis=1).astype(jnp.float32))(
        base.x, ctx.mesh_runtime.device_put_sharded_rows(
            (0.5 * rng.standard_normal((n, k))).astype(np.float32)))
    ds = InstanceDataset(ctx, base.x, labels, base.w, n, d)
    lr = LogisticRegression(maxIter=100, regParam=REG)
    incore = lr.fit_stacked(ds, num_classes=k)
    ctx.conf.set("cyclone.oocore.mode", "force")
    try:
        streamed = lr.fit_stacked(ds, num_classes=k)
    finally:
        ctx.conf.remove("cyclone.oocore.mode")
    si, ss = incore[0].summary, streamed[0].summary
    check(not si.streamed and ss.streamed
          and si.orientation == ss.orientation == "feature_major"
          and si.pieces == ss.pieces == kernels.SOFTMAX_PIECES,
          f"stacked leg: in core {si.orientation!r}, {si.pieces} pieces "
          f"(streamed {si.streamed}), forced {ss.orientation!r}, "
          f"{ss.pieces} pieces (streamed {ss.streamed})")
    coef_gap = max(rel_err(a._coef, b._coef)
                   for a, b in zip(streamed, incore))
    obj_gap = max(abs(a.summary.objective_history[-1]
                      - b.summary.objective_history[-1])
                  for a, b in zip(streamed, incore))
    check(coef_gap < 10 * COEF_RTOL and obj_gap < LOSS_RTOL,
          f"stacked leg: streamed against in-core fit: coefficients "
          f"{coef_gap:.3e}, objective {obj_gap:.3e}")
    out["streamed_vs_incore"] = {
        "orientation": ss.orientation, "coef_gap": float(coef_gap),
        "objective_gap": float(obj_gap),
        "epochs": int(ss.stacked_evals), "sweeps": int(si.stacked_evals)}
    return out


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def moment_reference(xv, y, w) -> dict:
    """float64 weighted moments of the stored values ``xv``."""
    y, w = np.asarray(y, np.float64), np.asarray(w, np.float64)
    return {"w_sum": w.sum(), "b_sum": (w * y).sum(),
            "bb_sum": (w * y * y).sum(), "a_sum": w @ xv,
            "ab_sum": (w * y) @ xv, "aa_sum": (xv * w[:, None]).T @ xv}


def kernel_matrix() -> dict:
    """Every kernel in ops/kernels.py, compiled natively (never
    interpreted) for every storage tier it claims, at published widths and
    small n — including row counts no aligned tile divides — and checked
    against a float64 reference over the same stored values."""
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.instance import quantize_fp8
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.observe import tracing
    from cycloneml_tpu.ops import kernels

    rng = np.random.default_rng(1)
    out = {}

    def stored(x32, tier):
        """(device array at the tier's storage dtype, per-column scale or
        None, the float64 values the kernel is meant to see)"""
        if tier == "float8":
            codes, scale = quantize_fp8(x32)[:2]
            scale = np.asarray(scale, np.float32)
            return (jnp.asarray(codes), scale,
                    np.asarray(codes, np.float64) * scale)
        xs = jnp.asarray(x32, jnp.dtype(tier))
        return xs, None, np.asarray(xs, np.float64)

    def record(name, fn, args, want: dict, pieces=None):
        """``pieces``: the one-model GLM sweep's form the stored X selects
        (3: the MXU form, 0: the VPU body), read off its instant"""
        jitted = jax.jit(fn)
        check("tpu_custom_call" in jitted.lower(*args).as_text(),
              f"{name}: not lowered to Mosaic")
        if pieces is not None:
            notes = [s.attrs for s in tracing.active().snapshot()
                     if s.name == "kernel.glm_sweep"]
            check(notes[-1]["pieces"] == pieces,
                  f"{name}: the sweep took {notes[-1]['pieces']} pieces")
            out[f"{name}/pieces"] = pieces
        got = jitted(*args)
        errs = {k: rel_err(got[k], v) for k, v in want.items()}
        check(all(e <= KERNEL_RTOL for e in errs.values()),
              f"{name}: off the float64 reference: {errs}")
        out[name] = max(errs.values())

    d = N_COLS
    for tier in ("float32", "bfloat16", "float8"):
        # the one-model sweep's form: a bf16 X at a width of 128 the MXU
        # form, every other tier the VPU body
        pieces = kernels.SOFTMAX_PIECES if tier == "bfloat16" else 0
        # 4096: tile 256; 4104 = 8*513 and 1000 = 8*125: no 16/32-row
        # tile divides, the narrow tiers run (8, d) blocks or pad
        for n in (4096, 4104, 1000):
            x32 = rng.standard_normal((n, d), dtype=np.float32)
            xs, scale, xv = stored(x32, tier)
            y = (rng.random(n) > 0.5).astype(np.float32)
            w = np.ones(n, np.float32)
            inv_std = (1.0 + rng.random(d)).astype(np.float32)
            mean = (0.1 * rng.standard_normal(d)).astype(np.float32)
            coef = (0.05 * rng.standard_normal(d + 1)).astype(np.float32)
            # float64 reference of the scaled aggregator's contract
            xh = xv * inv_std - mean
            m = xh @ coef[:d].astype(np.float64) + coef[d]
            mult = w * (1.0 / (1.0 + np.exp(-m)) - y)
            want = {"loss": np.sum(w * (np.logaddexp(0.0, m) - y * m)),
                    "grad": np.append(xh.T @ mult, mult.sum())}
            record(f"logistic/{tier}/n={n}",
                   lambda x, s=scale: kernels.fused_binary_logistic_scaled(
                       x, y, w, inv_std, mean, coef, d, True, x_scale=s),
                   (xs,), want, pieces)
            if n != 4096:
                continue
            yr = (xv @ rng.standard_normal(d) / 30.0).astype(np.float32)
            y_pars = np.asarray([0.5, 0.2], np.float32)
            c = coef[:d]
            err = xh @ c.astype(np.float64) + y_pars[1] - y_pars[0] * yr
            record(f"least_squares/{tier}/n={n}",
                   lambda x, s=scale: kernels.fused_least_squares_scaled(
                       x, yr, w, inv_std, mean, y_pars, c, d, x_scale=s),
                   (xs,), {"loss": 0.5 * np.sum(w * err * err),
                           "grad": xh.T @ (w * err)}, pieces)
            if tier == "bfloat16":
                # the moment Gramian claims the bf16 tier only (every
                # other storage takes XLA's contraction): row-major tile
                # at this width, one MXU pass under a presence mask or
                # any ONE live value, three under weights that hold more
                mask = rng.random(n) > 0.2
                for kind, wm, passes in (
                        ("mask", mask, 1.0), ("one_value", 0.1875 * mask, 1.0),
                        ("weights", 0.5 + rng.random(n), 3.0)):
                    wm = wm.astype(np.float32)
                    record(f"moment_gramian/{kind}/n={n}",
                           lambda x, wm=wm: kernels.moment_sums(
                               x, yr, wm, feature_major=False),
                           (xs,), dict(moment_reference(xv, yr, wm),
                                       mxu_passes=passes))

    # the feature-major tiling: arrays XLA:TPU stores with the rows on the
    # lanes (a width that is no multiple of 128, and enough rows that
    # padding THEM to 128 wastes less: a (4104, 2000) array is still stored
    # row-major), read as they lie — d whole on the sublanes, 16392 rows =
    # full lane tiles + a masked tail of 8
    n = 16392
    for d_odd, tiers in ((2000, ("float32", "bfloat16", "float8")),
                         (28, ("bfloat16",))):
        for tier in tiers:
            # bf16 at a width of 16 (2,000): the MXU form; d = 28, f32 and
            # fp8 the VPU body
            pieces = kernels.SOFTMAX_PIECES \
                if tier == "bfloat16" and d_odd % 16 == 0 else 0
            x32 = rng.standard_normal((n, d_odd), dtype=np.float32)
            xs, scale, xv = stored(x32, tier)
            check(kernels.stored_feature_major(xs),
                  f"a ({n}, {d_odd}) {tier} array is not stored "
                  f"feature-major: {xs.format}")
            y = (rng.random(n) > 0.5).astype(np.float32)
            w = (0.5 + rng.random(n)).astype(np.float32)
            inv_std = (1.0 + rng.random(d_odd)).astype(np.float32)
            mean = (0.1 * rng.standard_normal(d_odd)).astype(np.float32)
            coef = (0.05 * rng.standard_normal(d_odd + 1)).astype(np.float32)
            xh = xv * inv_std - mean
            m = xh @ coef[:d_odd].astype(np.float64) + coef[d_odd]
            mult = w * (1.0 / (1.0 + np.exp(-m)) - y)
            record(f"logistic_feature_major/{tier}/n={n},d={d_odd}",
                   lambda x, s=scale: kernels.fused_binary_logistic_scaled(
                       x, y, w, inv_std, mean, coef, d_odd, True, x_scale=s,
                       feature_major=True),
                   (xs,), {"loss": np.sum(w * (np.logaddexp(0.0, m) - y * m)),
                           "grad": np.append(xh.T @ mult, mult.sum()),
                           "count": w.sum()}, pieces)
            yr = (xv @ rng.standard_normal(d_odd) / 30.0).astype(np.float32)
            y_pars = np.asarray([0.5, 0.2], np.float32)
            c = coef[:d_odd]
            err = xh @ c.astype(np.float64) + y_pars[1] - y_pars[0] * yr
            record(f"least_squares_feature_major/{tier}/n={n},d={d_odd}",
                   lambda x, s=scale: kernels.fused_least_squares_scaled(
                       x, yr, w, inv_std, mean, y_pars, c, d_odd, x_scale=s,
                       feature_major=True),
                   (xs,), {"loss": 0.5 * np.sum(w * err * err),
                           "grad": xh.T @ (w * err)}, pieces)
            if tier == "bfloat16" and d_odd % kernels.MOMENT_ROWS == 0:
                # the moment Gramian on the array as it lies: (d, T)
                # tiles of x.T, the tail of 8 rows masked in the kernel
                record(f"moment_gramian_feature_major/n={n},d={d_odd}",
                       lambda x: kernels.moment_sums(
                           x, yr, w, feature_major=True),
                       (xs,), dict(moment_reference(xv, yr, w),
                                   mxu_passes=3.0))

    return out


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU attached — jax's default backend is "
              f"{devices[0].platform!r} ({len(devices)} device(s)); this "
              f"script only runs on the chip", file=sys.stderr)
        return 1
    watch = CompileWatch()

    from importlib.metadata import version

    from cycloneml_tpu import CycloneConf, CycloneContext
    from cycloneml_tpu import mesh as mesh_mod

    t_start = time.perf_counter()
    cache_dir = mesh_mod.compilation_cache_dir()
    entries_before = cache_entries(cache_dir)
    ctx = CycloneContext(
        CycloneConf().set("cyclone.app.name", "chip-smoke")
        # the whole iteration budget in ONE device dispatch
        .set("cyclone.ml.lbfgs.deviceChunk", str(MAX_ITER + 8)))
    rt = ctx.mesh_runtime
    check(rt.platform == "tpu", f"mesh platform is {rt.platform}")
    check(rt.n_devices == len(devices),
          f"mesh has {rt.n_devices} of {len(devices)} devices")
    mesh_shape = dict(zip(rt.mesh.axis_names, rt.mesh.devices.shape))
    check(mesh_shape == {"replica": 1, "data": len(devices), "model": 1},
          f"unexpected mesh shape {mesh_shape}")
    check(jax.config.jax_compilation_cache_dir == cache_dir,
          f"compile cache is at {jax.config.jax_compilation_cache_dir}, "
          f"expected {cache_dir}")
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind} "
          f"({devices[0].platform}), mesh {mesh_shape}, jax {jax.__version__}",
          file=sys.stderr)

    device = device_leg(ctx, N_ROWS, N_COLS, devices)
    print(f"chip_smoke: device leg ok {device}", file=sys.stderr)
    host = host_leg(ctx, N_HOST_ROWS, N_COLS, devices)
    print(f"chip_smoke: host leg ok {host}", file=sys.stderr)
    glr = glr_leg(ctx, N_GLR_ROWS, N_GLR_COLS, devices)
    print(f"chip_smoke: glr leg ok {glr}", file=sys.stderr)
    softmax = multinomial_leg(ctx, N_SOFTMAX_ROWS, N_SOFTMAX_COLS, N_CLASSES,
                              devices)
    print(f"chip_smoke: softmax leg ok {softmax}", file=sys.stderr)
    lloyd = kmeans_leg(ctx, N_KMEANS_ROWS, N_KMEANS_COLS, N_CENTRES, devices)
    print(f"chip_smoke: kmeans leg ok {lloyd}", file=sys.stderr)
    stacked = stacked_leg(ctx, devices)
    print(f"chip_smoke: stacked leg ok {stacked}", file=sys.stderr)
    kernels_ok = kernel_matrix()
    print(f"chip_smoke: kernel matrix ok {kernels_ok}", file=sys.stderr)
    ctx.stop()

    verdict = {"ok": True,
               "device": {"platform": devices[0].platform,
                          "kind": devices[0].device_kind,
                          "count": len(devices)}}
    print(json.dumps({
        **verdict,
        "mesh": mesh_shape,
        "versions": {"jax": jax.__version__, "jaxlib": version("jaxlib"),
                     "libtpu": version("libtpu")},
        "fit": device,
        "host_fit": host,
        "glr_fit": glr,
        "softmax_fit": softmax,
        "kmeans_fit": lloyd,
        "stacked_sweep_vs_serial": stacked,
        "kernel_matrix_max_rel_err": kernels_ok,
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": cache_entries(cache_dir),
            "hits": watch.hits, "misses": watch.misses,
            "compile_s": round(watch.seconds, 3)},
        "wall_s": round(time.perf_counter() - t_start, 3),
        "note": "seconds are smoke observations, not benchmark results",
        "claim": None,
    }))
    # the last line carries the verdict and the device, and nothing else
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
