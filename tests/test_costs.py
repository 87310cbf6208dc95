"""XLA cost & HBM accounting tests: harvest at the program-cache waist,
roofline-aware FitProfile rollup, counter-event export, the disabled-path
no-op pin, and the compile-time memory budget guard (deviceChunk
degradation, warn-only contract)."""

import json

import numpy as np
import pytest

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.ml.optim import aggregators
from cycloneml_tpu.ml.optim.device_lbfgs import DeviceLBFGS
from cycloneml_tpu.ml.optim.loss import DistributedLossFunction
from cycloneml_tpu.observe import (FitProfile, costs, export_chrome_trace,
                                   span_kinds, tracing,
                                   validate_chrome_trace)


@pytest.fixture
def tracer():
    tracing.disable()
    t = tracing.enable(max_spans=50_000)
    yield t
    tracing.disable()


def _fit(ctx, seed=0, n=128, d=6, max_iter=6, **lr_kwargs):
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import LogisticRegression
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d)
    y = (x @ rng.randn(d) > 0).astype(float)
    frame = MLFrame(ctx, {"features": x, "label": y})
    model = LogisticRegression(maxIter=max_iter, regParam=0.01, tol=0.0,
                               **lr_kwargs).fit(frame)
    assert ctx.listener_bus.wait_until_empty()
    return model


def _last_lr_profile(ctx):
    jobs = [j for j in ctx.status_store.job_list()
            if "LogisticRegression.fit" in j["description"]]
    return FitProfile.from_dict(ctx.status_store.profile(jobs[-1]["jobId"]))


# -- harvest + rollup ------------------------------------------------------------

def test_traced_fit_profile_has_cost_rollup(ctx, tracer):
    """The ISSUE acceptance: a traced LR fit on the 8-device CPU mesh
    yields non-null total FLOPs, per-program cost entries keyed by
    program-cache identity, and memory fields populated (CPU has
    cost_analysis + memory_analysis) while live memory_stats is
    explicitly unavailable."""
    _fit(ctx, seed=1)
    prof = _last_lr_profile(ctx)
    assert prof.total_flops is not None and prof.total_flops > 0
    assert prof.total_bytes_accessed and prof.total_bytes_accessed > 0
    assert prof.arithmetic_intensity and prof.arithmetic_intensity > 0
    assert prof.achieved_flops and prof.achieved_flops > 0
    assert prof.n_devices == 8
    # CPU backend matrix: static analyses report, live telemetry does not
    assert prof.cost_availability == "full"
    assert prof.hbm_peak_bytes is not None and prof.hbm_peak_bytes > 0
    assert prof.hbm_argument_bytes is not None
    assert prof.memory_stats_available is False
    # per-program entries keyed by program-cache identity, with executions
    assert prof.programs
    for pid, entry in prof.programs.items():
        assert isinstance(pid, str) and "#" in pid
        assert entry["executions"] >= 1
    # totals really are executions x per-program mesh-wide cost
    expect = sum(e["flops_total"] * e["executions"]
                 for e in prof.programs.values() if e.get("flops_total"))
    assert prof.total_flops == pytest.approx(expect)
    # the profile survives the event/JSON round trip with costs intact
    again = FitProfile.from_dict(json.loads(json.dumps(prof.to_dict())))
    assert again.total_flops == prof.total_flops
    assert again.programs == prof.programs


def test_cost_entries_shared_across_fits_by_cache_identity(ctx, tracer):
    """Program-cache identity IS the cost key: a second fit at the same
    shapes reuses the cached programs, so the registry analyzes nothing
    new and both profiles cite the same program ids."""
    _fit(ctx, seed=2)
    p1 = _last_lr_profile(ctx)
    before = costs.analyze_call_count()
    _fit(ctx, seed=3)  # same shapes/config -> same program identities
    p2 = _last_lr_profile(ctx)
    assert costs.analyze_call_count() == before
    assert set(p2.programs) == set(p1.programs)


def test_no_cost_analysis_when_tracing_disabled(ctx):
    """The no-op pin: with tracing off and no explicit memory budget the
    harvest path is one global read — lower()/cost_analysis() never run."""
    tracing.disable()
    before = costs.analyze_call_count()
    _fit(ctx, seed=4)
    assert costs.analyze_call_count() == before


def test_counter_events_export_and_validate(tracer, tmp_path):
    """Counter samples become Chrome-trace "C" events that pass the schema
    validator — the Perfetto HBM/FLOPs timeline contract."""
    tracer.counter("hbm.bytes_in_use", 4096)
    tracer.counter("flops.cumulative", 1.5e9)
    with tracer.span("dispatch", "x"):
        pass
    path = str(tmp_path / "c.trace.json")
    export_chrome_trace(tracer, path)
    assert validate_chrome_trace(path) == []
    kinds = span_kinds(path)
    assert kinds.get("counter") == 2 and kinds.get("dispatch") == 1
    evs = [e for e in json.load(open(path))["traceEvents"]
           if e.get("ph") == "C"]
    assert {e["name"] for e in evs} == {"hbm.bytes_in_use",
                                        "flops.cumulative"}
    assert all(isinstance(e["args"]["value"], (int, float)) for e in evs)


def test_traced_fit_emits_counter_events(ctx, tracer, tmp_path):
    _fit(ctx, seed=5)
    path = str(tmp_path / "fit.trace.json")
    ctx.export_trace(path)
    assert validate_chrome_trace(path) == []
    assert span_kinds(path).get("counter", 0) >= 1


def test_memory_stats_unavailable_on_cpu(ctx):
    """Backend availability matrix: CPU devices report no memory_stats —
    the availability gauge says so and no per-device gauges exist."""
    assert costs.memory_stats_available() is False
    vals = ctx.metrics.registry.values()
    assert vals["device.memoryStats.available"] == 0.0
    assert not any(k.startswith("device.0.memory.") for k in vals)


def test_program_id_stable_and_distinct():
    key_a = ("lbfgs_chunk", test_program_id_stable_and_distinct, 10, 8)
    key_b = ("lbfgs_chunk", test_program_id_stable_and_distinct, 10, 4)
    assert costs.program_id("x", key_a) == costs.program_id("x", key_a)
    assert costs.program_id("x", key_a) != costs.program_id("x", key_b)
    anon = costs.program_id("x", None, jitted=object())
    assert anon.startswith("x#anon")


# -- memory budget guard ---------------------------------------------------------

def _loss(ctx, n=400, d=12, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d)
    y = (x @ rng.randn(d) > 0).astype(np.float64)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    return DistributedLossFunction(
        ds, aggregators.binary_logistic(d, fit_intercept=True)), d


@pytest.fixture
def budget_conf(ctx):
    """Arm the guard with an impossible budget; always restore."""
    def arm(fraction="1e-12", action=None):
        ctx.conf.set("cyclone.memory.budgetFraction", fraction)
        if action:
            ctx.conf.set("cyclone.memory.budgetAction", action)
    yield arm
    ctx.conf.remove("cyclone.memory.budgetFraction")
    ctx.conf.remove("cyclone.memory.budgetAction")


def test_budget_guard_degrades_chunk_and_stays_equivalent(ctx, budget_conf):
    """The ISSUE acceptance: an artificially low budgetFraction produces a
    MemoryBudgetExceeded event and a reduced deviceChunk, never an
    exception in warn-only mode — and the seeded result matches the
    unguarded run (chunk size never changes the trajectory)."""
    f1, d = _loss(ctx, seed=21)
    base = DeviceLBFGS(max_iter=20, tol=1e-10, chunk=8)
    ref = base.minimize(f1, np.zeros(d + 1))
    assert base.effective_chunk == 8  # unguarded: configured chunk kept

    warnings_before = len(ctx.status_store.memory_warnings)
    budget_conf("1e-12")
    f2, _ = _loss(ctx, seed=21)
    opt = DeviceLBFGS(max_iter=20, tol=1e-10, chunk=8)
    out = opt.minimize(f2, np.zeros(d + 1))
    assert ctx.listener_bus.wait_until_empty()

    assert opt.effective_chunk < 8  # degraded, not OOM'd, not raised
    warns = ctx.status_store.memory_warnings[warnings_before:]
    assert warns and warns[-1]["predictedBytes"] > warns[-1]["budgetBytes"]
    assert warns[-1]["action"] == "warn"
    np.testing.assert_allclose(out.x, ref.x, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(out.value, ref.value, rtol=1e-12)
    # smaller chunks = more dispatches for the same trajectory
    assert f2.n_dispatches > f1.n_dispatches


def test_budget_guard_raise_action(ctx, budget_conf):
    budget_conf("1e-12", action="raise")
    f, d = _loss(ctx, seed=22)
    with pytest.raises(costs.MemoryBudgetError):
        DeviceLBFGS(max_iter=5, tol=0.0, chunk=8).minimize(
            f, np.zeros(d + 1))


def test_budget_guard_degrades_stacked_chunk(ctx, budget_conf):
    """The stacked (model-axis) chunk path takes the same degradation:
    OneVsRest's stacked fit under an impossible budget still matches the
    unguarded fit and runs with a reduced chunk."""
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import LogisticRegression, OneVsRest
    rng = np.random.RandomState(31)
    k, d, n = 3, 4, 90
    centers = rng.randn(k, d) * 3.0
    y = rng.randint(0, k, n).astype(np.float64)
    x = centers[y.astype(int)] + rng.randn(n, d)
    frame = MLFrame(ctx, {"features": x, "label": y})
    est = lambda: OneVsRest(  # noqa: E731 — two identical estimators
        classifier=LogisticRegression(maxIter=10, regParam=0.1, tol=0.0),
        parallelism=k)
    ref = est().fit(frame)
    warnings_before = len(ctx.status_store.memory_warnings)
    budget_conf("1e-12")
    out = est().fit(frame)
    assert ctx.listener_bus.wait_until_empty()
    assert any("stacked" in (w["program"] or "")
               for w in ctx.status_store.memory_warnings[warnings_before:])
    for mr, mo in zip(ref.models, out.models):
        np.testing.assert_allclose(mo._coef, mr._coef, rtol=1e-9, atol=1e-9)


def test_registry_bounded_and_reset_with_program_caches():
    """The cost registry must not leak: ids embed program/mesh object
    identities, so it is LRU-bounded and cleared alongside the program
    caches on mesh teardown/rebuild."""
    from cycloneml_tpu.parallel.collectives import clear_program_cache

    class NoLower:  # analyze degrades to an all-None entry, still registered
        pass

    first_pid = costs.ensure("fake", ("bound", -1), NoLower(), ())
    for i in range(costs.MAX_REGISTRY_ENTRIES + 20):
        costs.ensure("fake", ("bound", i), NoLower(), ())
    snap = costs.snapshot()
    assert len(snap) == costs.MAX_REGISTRY_ENTRIES
    assert first_pid not in snap  # oldest evicted first
    clear_program_cache()
    assert costs.snapshot() == {}


def test_budget_guard_rechecks_rebuilt_program(ctx, budget_conf):
    """The degradation loop re-analyzes each rebuilt candidate instead of
    trusting the proportional guess: with an impossible budget every
    candidate stays over, so the guard walks down to chunk 1 and proceeds
    warn-only (footprint is chunk-independent-dominated)."""
    budget_conf("1e-12")
    before = costs.analyze_call_count()
    f, d = _loss(ctx, seed=23)
    opt = DeviceLBFGS(max_iter=6, tol=0.0, chunk=8)
    opt.minimize(f, np.zeros(d + 1))
    # initial chunk-8 analysis + at least the rebuilt chunk-1 analysis
    assert costs.analyze_call_count() - before >= 2
    assert opt.effective_chunk == 1


def test_select_chunk_policy():
    assert costs.select_chunk(8, predicted_bytes=100, budget_bytes=200) == 8
    assert costs.select_chunk(8, predicted_bytes=400, budget_bytes=200) == 4
    assert costs.select_chunk(8, predicted_bytes=10**9, budget_bytes=1) == 1
    assert costs.select_chunk(1, predicted_bytes=10**9, budget_bytes=1) == 1
    # always strictly smaller when over budget (never returns the chunk
    # that was just predicted not to fit)
    assert costs.select_chunk(8, predicted_bytes=201, budget_bytes=200) == 7


# -- buffer donation at the chunk dispatches (JX009-proven) -------------------

def test_chunk_program_donates_state_buffers(ctx):
    """The serial L-BFGS chunk program donates the S/Y ring buffers —
    the driver rebinds both from the outputs every chunk and only ever
    exposes slices of them (the discipline graftlint JX009 checks
    statically), so XLA aliases them in place. coef/grad stay undonated:
    yielded OptimStates carry them and the resilience retry path retains
    those states across dispatches. Pinned via the program's own
    memory_analysis: the alias covers the ring buffers (2·m·n
    accumulator-width elements)."""
    import jax.numpy as jnp

    from cycloneml_tpu.dataset.instance import compute_dtype
    from cycloneml_tpu.ml.optim.device_lbfgs import _build_chunk
    f, d = _loss(ctx, seed=41)
    cdt = np.dtype(compute_dtype())
    arrays = f._agg_call.arrays()
    m, chunk, n = 10, 8, d + 1
    args = (*arrays, jnp.zeros(n, cdt), jnp.zeros((m, n), cdt),
            jnp.zeros((m, n), cdt), jnp.int32(0), cdt.type(0.0),
            jnp.zeros(n, cdt), np.bool_(True), cdt.type(f.weight_sum),
            cdt.type(1e-6), cdt.type(1e-6), np.int32(chunk),
            np.bool_(True))
    donated = _build_chunk(f._agg_call.compiled, None, m, chunk,
                           1e-4, 0.9, 30, cdt, n_arrays=len(arrays))
    ma = donated.lower(*args).compile().memory_analysis()
    state_bytes = 2 * m * n * cdt.itemsize
    assert int(ma.alias_size_in_bytes) >= state_bytes


def test_traced_chunk_fit_peak_reflects_donation(ctx, tracer):
    """End-to-end: a traced DeviceLBFGS fit's cost rollup reports the
    chunk program's peak NET of the donated state — predicted peak
    (args+out+temp+gen-alias) sits below the gross sum by at least the
    donated state bytes. This is the measurable HBM win the donation
    buys, read through the same observe/costs.py waist bench.py and
    obs-demo report."""
    from cycloneml_tpu.dataset.instance import compute_dtype
    f, d = _loss(ctx, seed=42)
    opt = DeviceLBFGS(max_iter=8, tol=0.0, chunk=4)
    opt.minimize(f, np.zeros(d + 1))
    snap = costs.snapshot()
    chunk_entries = [e for pid, e in snap.items()
                     if pid.startswith("lbfgs.chunk")]
    assert chunk_entries, "chunk program missing from the cost registry"
    e = chunk_entries[-1]
    cdt = np.dtype(compute_dtype())
    m, n = 10, d + 1
    state_bytes = 2 * m * n * cdt.itemsize
    gross = (e["argument_bytes"] + e["output_bytes"] + e["temp_bytes"]
             + (e["generated_code_bytes"] or 0))
    assert e["peak_bytes"] <= gross - state_bytes


def test_yielded_state_survives_later_dispatches(ctx):
    """The resilience retry path retains a yielded OptimState and may
    resume from it AFTER the generator has dispatched further chunks
    (parallel/resilience.py's transient-failure loop). Every retained
    state's arrays must therefore stay readable — donation of coef/grad
    would delete them behind the caller's back."""
    f, d = _loss(ctx, seed=43)
    opt = DeviceLBFGS(max_iter=12, tol=0.0, chunk=2)
    states = []
    for s in opt.iterations(f, np.zeros(d + 1)):
        states.append(s)
        if len(states) >= 3:
            break
    assert len(states) >= 2
    for s in states:
        np.asarray(s.x)       # raises "Array has been deleted" if donated
        np.asarray(s.grad)
        for h in (*s.hist_s, *s.hist_y):
            np.asarray(h)
    # and the retained (non-latest) state actually resumes
    resumed = next(iter(opt.iterations(f, np.zeros(d + 1),
                                       resume=states[0])))
    assert resumed.iteration == states[0].iteration
