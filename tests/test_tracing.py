"""Step-level tracing tests: span API, FitProfile, Chrome export, and the
end-to-end acceptance contract (a traced LogisticRegression.fit exports a
valid Chrome trace with >= 4 span kinds whose FitProfile counts agree with
the model summary's dispatch/eval ledger).

The tracer is process-global state like faults._active: every test that
enables it disables it in a finally block so the rest of the suite keeps
the zero-overhead disabled path.
"""

import json
import threading

import numpy as np
import pytest

from cycloneml_tpu.observe import (FitProfile, chrome_trace,
                                   export_chrome_trace, span_kinds, tracing,
                                   validate_chrome_trace)


@pytest.fixture
def tracer():
    tracing.disable()  # defend against a leak from a dirty test
    t = tracing.enable(max_spans=10_000)
    yield t
    tracing.disable()


# -- disabled path ---------------------------------------------------------------

def test_span_api_is_noop_when_disabled():
    tracing.disable()
    assert tracing.active() is None
    s1 = tracing.span("dispatch", "a")
    s2 = tracing.span("collective", "b", attr=1)
    # one shared object, no allocation per call — the zero-overhead contract
    assert s1 is s2 is tracing.NOOP_SPAN
    with s1 as s:
        s.annotate(evals=1)
        s.annotate_bytes({"x": np.zeros(8)})  # must not walk the tree
        assert s.span_id == ""
    tracing.instant("fault", point="collectives.step")
    assert tracing.current_span_id() == ""


def test_instrumented_sites_record_nothing_when_disabled(ctx):
    """A tree_aggregate dispatch with tracing off must leave no trace state
    behind — then the same program dispatched under a tracer records a
    collective span (cache already warm: no compile span)."""
    import jax.numpy as jnp
    from cycloneml_tpu.parallel.collectives import tree_aggregate

    def agg(x):
        return {"s": jnp.sum(x)}

    rt = ctx.mesh_runtime
    data = rt.device_put_sharded_rows(np.ones((64, 2), dtype=np.float32))
    prog = tree_aggregate(agg, rt, data)
    prog(data)  # disabled: nothing recorded anywhere
    t = tracing.enable(max_spans=100)
    try:
        prog(data)
        kinds = {s.kind for s in t.snapshot()}
        assert "collective" in kinds
    finally:
        tracing.disable()


# -- span recording --------------------------------------------------------------

def test_spans_nest_and_annotate(tracer):
    with tracer.span("job", "fit") as job:
        with tracer.span("dispatch", "loss.eval", evals=1) as d:
            tracer.instant("cache.miss")
            d.annotate(extra=7)
    spans = tracer.snapshot()
    by_kind = {s.kind: s for s in spans}
    assert by_kind["dispatch"].parent_id == job.span_id
    assert by_kind["instant"].parent_id == by_kind["dispatch"].span_id
    assert by_kind["dispatch"].attrs == {"evals": 1, "extra": 7}
    assert by_kind["job"].t1 >= by_kind["dispatch"].t1 >= \
        by_kind["dispatch"].t0
    assert tracing.current_span_id() == ""  # stack fully unwound


def test_span_buffer_bound():
    t = tracing.Tracer(max_spans=3)
    for i in range(5):
        t.instant("x", i=i)
    assert len(t.snapshot()) == 3 and t.dropped == 2


def test_span_ring_drops_oldest_and_keeps_monotonic_marks():
    """Overflow semantics pin (ISSUE 12 satellite): the ring drops the
    OLDEST spans, counts them, and sequence positions survive the wrap —
    a mark taken before the wrap still reads exactly the survivors past
    it, never a replay and never a skip."""
    t = tracing.Tracer(max_spans=4)
    for i in range(4):
        t.instant("x", i=i)
    mark = t.mark()
    assert mark == 4
    for i in range(4, 10):
        t.instant("x", i=i)
    # the RECENT window survives; the oldest 6 were dropped and counted
    assert [s.attrs["i"] for s in t.snapshot()] == [6, 7, 8, 9]
    assert t.dropped == 6
    assert t.mark() == 10
    # the pre-wrap mark: positions 4..5 fell off the ring floor, so the
    # read returns the SURVIVING suffix (6..9), not a stale replay
    assert [s.attrs["i"] for s in t.snapshot(since=mark)] == [6, 7, 8, 9]
    assert [s.attrs["i"] for s in t.snapshot(since=8)] == [8, 9]
    assert t.snapshot(since=10) == []
    # clear keeps positions monotonic: an old cursor yields only new spans
    t.clear()
    assert t.dropped == 0
    t.instant("x", i=99)
    assert [s.attrs["i"] for s in t.snapshot(since=mark)] == [99]


def test_concurrent_producers_and_drain_lose_and_duplicate_nothing():
    """ISSUE 12 satellite: N threads record while a collector drains via
    the atomic ``drain(since)`` cursor — every span delivered exactly
    once. (A separate mark()-then-snapshot() pair would double-deliver
    spans recorded between the two calls.)"""
    t = tracing.Tracer(max_spans=100_000)
    n_threads, per_thread = 4, 500
    done = threading.Event()
    collected = []

    def producer(k):
        for i in range(per_thread):
            t.instant("p", k=k, i=i)

    def collector():
        since = 0
        while True:
            spans, since = t.drain(since)
            collected.extend(spans)
            if done.is_set():
                spans, since = t.drain(since)  # final sweep
                collected.extend(spans)
                return

    col = threading.Thread(target=collector)
    col.start()
    producers = [threading.Thread(target=producer, args=(k,))
                 for k in range(n_threads)]
    for p in producers:
        p.start()
    for p in producers:
        p.join()
    done.set()
    col.join()
    keys = [(s.attrs["k"], s.attrs["i"]) for s in collected]
    assert len(keys) == n_threads * per_thread      # none lost...
    assert len(set(keys)) == len(keys)              # ...none double-shipped
    assert t.dropped == 0


def test_export_emits_process_and_thread_metadata(tracer, tmp_path):
    """ISSUE 12 satellite: the Chrome export labels lanes with M-phase
    process_name/thread_name events (Perfetto shows names, not bare
    pids/tids) and the validator accepts them."""
    with tracer.span("job", "fit"):
        pass
    obj = chrome_trace(tracer)
    assert validate_chrome_trace(obj) == []
    meta = [e for e in obj["traceEvents"] if e["ph"] == "M"]
    names = {e["name"] for e in meta}
    assert names == {"process_name", "thread_name"}
    threads = [e for e in meta if e["name"] == "thread_name"]
    assert any(e["args"]["name"] == threading.current_thread().name
               for e in threads)
    # validator rejects a malformed metadata event
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "process_name", "ph": "M", "pid": 1,
                          "args": {}}]})


def test_export_header_and_profile_carry_spans_dropped(tmp_path):
    t = tracing.Tracer(max_spans=2)
    for i in range(5):
        t.instant("x", i=i)
    obj = chrome_trace(t)
    assert obj["otherData"]["spans_dropped"] == 3
    assert obj["otherData"]["trace_id"] == t.trace_id
    prof = t.profile_for(None)
    assert prof.spans_dropped == 3
    # the count survives the dict round trip (status store / journal)
    assert FitProfile.from_dict(prof.to_dict()).spans_dropped == 3


def test_threads_get_independent_context(tracer):
    seen = {}

    def worker(name):
        with tracer.span("job", name) as sp:
            seen[name] = sp.span_id

    ts = [threading.Thread(target=worker, args=(f"j{i}",)) for i in range(4)]
    for x in ts:
        x.start()
    for x in ts:
        x.join()
    roots = [s for s in tracer.snapshot() if s.kind == "job"]
    assert len(roots) == 4
    assert all(not s.parent_id for s in roots)  # no cross-thread bleed


# -- FitProfile ------------------------------------------------------------------

def test_fit_profile_scopes_to_root(tracer):
    with tracer.span("job", "fit-A") as a:
        with tracer.span("dispatch", "loss.eval", evals=3):
            pass
        with tracer.span("transfer", "rb") as t:
            t.annotate(bytes=128)
        tracer.instant("fault", point="collectives.step")
    with tracer.span("job", "fit-B"):
        with tracer.span("dispatch", "loss.eval", evals=5):
            pass
    prof = tracer.profile_for(a.span_id)
    assert prof.dispatch_count == 1 and prof.eval_count == 3
    assert prof.transfer_count == 1 and prof.transfer_bytes == 128
    assert prof.faults_injected == 1
    assert prof.description == "fit-A" and prof.wall_seconds > 0
    everything = tracer.profile_for(None)
    assert everything.dispatch_count == 2 and everything.eval_count == 8


def test_fit_profile_compile_vs_steady(tracer):
    import time
    with tracer.span("dispatch", "lbfgs.chunk", evals=2):
        with tracer.span("compile", "lbfgs.chunk"):
            pass
    with tracer.span("dispatch", "lbfgs.chunk", evals=2) as steady:
        pass
    prof = tracer.profile_for(None)
    assert prof.compile_count == 1
    assert prof.dispatch_count == 2
    # steady excludes the dispatch that paid the compile
    assert prof.steady_seconds == pytest.approx(steady.span.duration_s)


def test_fit_profile_excludes_deeply_nested_compiles_from_steady(tracer):
    """The host L-BFGS shape: dispatch → collective → compile. The compile
    is TWO levels below the dispatch, whose wall time includes the staging
    — it must not count as steady state."""
    import time
    with tracer.span("dispatch", "loss.eval", evals=1):
        with tracer.span("collective", "tree_aggregate"):
            with tracer.span("compile", "tree_aggregate"):
                time.sleep(0.01)
    with tracer.span("dispatch", "loss.eval", evals=1) as steady:
        pass
    prof = tracer.profile_for(None)
    assert prof.compile_count == 1 and prof.dispatch_count == 2
    assert prof.steady_seconds == pytest.approx(steady.span.duration_s)
    assert prof.steady_seconds < 0.01  # staging time fully excluded


def test_fit_profile_roundtrips_dict(tracer):
    with tracer.span("dispatch", "x", evals=1):
        pass
    prof = tracer.profile_for(None)
    again = FitProfile.from_dict(prof.to_dict())
    assert again == prof


# -- Chrome export ---------------------------------------------------------------

def test_chrome_trace_exports_and_validates(tracer, tmp_path):
    with tracer.span("job", "fit"):
        with tracer.span("dispatch", "loss.eval", evals=1):
            tracer.instant("cache.hit")
    path = str(tmp_path / "t.trace.json")
    export_chrome_trace(tracer, path)
    assert validate_chrome_trace(path) == []
    obj = json.load(open(path))
    kinds = span_kinds(obj)
    assert kinds == {"job": 1, "dispatch": 1, "instant": 1}
    evs = {e["name"]: e for e in obj["traceEvents"] if e["ph"] != "M"}
    assert evs["loss.eval"]["args"]["evals"] == 1
    assert evs["loss.eval"]["args"]["parent_id"] == \
        evs["fit"]["args"]["span_id"]
    assert evs["cache.hit"]["ph"] == "i"


def test_validator_rejects_malformed_traces():
    assert validate_chrome_trace({"nope": []})
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "ts": 0.0}]}
    )  # X without dur
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "ts": 0.0,
                          "dur": 1.0}]}) == []


# -- end-to-end acceptance -------------------------------------------------------

def _fit_traced(ctx, tmp_path, **lr_kwargs):
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import LogisticRegression

    rng = np.random.RandomState(0)
    x = rng.randn(128, 6)
    y = (x @ rng.randn(6) > 0).astype(float)
    frame = MLFrame(ctx, {"features": x, "label": y})
    model = LogisticRegression(maxIter=6, regParam=0.01, tol=0.0,
                               **lr_kwargs).fit(frame)
    assert ctx.listener_bus.wait_until_empty()
    return model


def test_traced_fit_exports_chrome_trace_with_4_kinds(ctx, tmp_path):
    """The ISSUE acceptance: one traced LogisticRegression.fit ->
    Chrome-trace JSON with >= 4 distinct span kinds that validates, and a
    FitProfile whose dispatch/eval counts agree with the ledger bench.py
    logs (summary.total_dispatches / total_evals)."""
    tracing.disable()
    tracer = tracing.enable(max_spans=50_000)
    try:
        model = _fit_traced(
            ctx, tmp_path,
            checkpointDir=str(tmp_path / "ckpt"), checkpointInterval=2)
        jobs = [j for j in ctx.status_store.job_list()
                if "LogisticRegression.fit" in j["description"]]
        jid = jobs[-1]["jobId"]
        prof = FitProfile.from_dict(ctx.status_store.profile(jid))

        path = str(tmp_path / "fit.trace.json")
        ctx.export_trace(path)
        assert validate_chrome_trace(path) == []
        kinds = set(span_kinds(path))
        want = {"compile", "dispatch", "collective", "transfer",
                "checkpoint", "job"}
        assert len(kinds & want) >= 4, f"only {sorted(kinds & want)}"
        # the per-fit profile agrees with the counts the summary logs
        assert prof.dispatch_count == model.summary.total_dispatches
        assert prof.eval_count == model.summary.total_evals
        assert prof.checkpoint_saves >= 1
        assert prof.transfer_count >= prof.dispatch_count
        assert prof.wall_seconds > 0
        # events carry span ids joinable onto the trace
        steps = ctx.status_store.steps(jid)
        assert steps and all(st["spanId"] for st in steps)
    finally:
        tracing.disable()


def test_traced_fit_profile_via_webui(ctx, tmp_path):
    """The per-fit profile is served by the REST/web UI surface."""
    import urllib.request
    tracing.disable()
    tracing.enable(max_spans=50_000)
    try:
        _fit_traced(ctx, tmp_path)
        jobs = [j for j in ctx.status_store.job_list()
                if "LogisticRegression.fit" in j["description"]]
        jid = jobs[-1]["jobId"]
        from cycloneml_tpu.util.webui import StatusWebUI
        ui = StatusWebUI(ctx.status_store)
        try:
            body = urllib.request.urlopen(
                f"{ui.url}api/v1/jobs/{jid}/profile", timeout=5).read()
            prof = json.loads(body)
            assert prof["dispatch_count"] >= 1
            assert prof["eval_count"] >= 1
        finally:
            ui.stop()
    finally:
        tracing.disable()


def test_chaos_fault_lands_in_trace(ctx, tmp_path):
    """A chaos run's injected fault + retry become annotations inside the
    training timeline (the readable-chaos-trace contract)."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ml.optim.lbfgs import LBFGS
    from cycloneml_tpu.ml.optim.loss import DistributedLossFunction
    from cycloneml_tpu.parallel.faults import (FaultInjector, FaultSchedule,
                                               TransientCollectiveError)
    from cycloneml_tpu.parallel.resilience import train_with_checkpoints
    from cycloneml_tpu.util.checkpoint import TrainingCheckpointer

    rng = np.random.RandomState(0)
    d = 6
    x = rng.randn(256, d)
    y = (x @ rng.randn(d) > 0).astype(np.float64)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    tracing.disable()
    tracer = tracing.enable(max_spans=50_000)
    try:
        sched = FaultSchedule(seed=7)
        sched.at("collectives.step", [4],
                 TransientCollectiveError("injected DCN flake"))
        ck = TrainingCheckpointer(str(tmp_path / "ck"))
        loss = DistributedLossFunction(
            ds, aggregators.binary_logistic(d, fit_intercept=False))
        with FaultInjector(sched) as inj:
            train_with_checkpoints(
                LBFGS(max_iter=20, tol=1e-9), loss, np.zeros(d), ck,
                interval=5, max_step_failures=3, backoff_base_s=0.001,
                seed=7)
        assert inj.log  # the fault fired
        names = {s.name for s in tracer.snapshot() if s.kind == "instant"}
        assert "fault" in names and "retry" in names
        prof = tracer.profile_for(None)
        assert prof.faults_injected >= 1 and prof.retries >= 1
        assert prof.checkpoint_saves >= 1
    finally:
        tracing.disable()


# -- one clock: spans mirrored into the profiler's capture -----------------------

class _RecordingAnnotation:
    """Stand-in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""

    def __init__(self, log):
        self.log = log

    def __call__(self, name):
        log = self.log

        class _Region:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name, exc[0]))
                return False
        return _Region()


def test_annotation_factory_sees_spans_in_nesting_order(tracer):
    log = []
    tracer.annotation = _RecordingAnnotation(log)
    with tracing.span("job", "LogisticRegression.fit"):
        with tracing.span("phase", "fit.optimize"):
            with tracing.span("dispatch", "lbfgs.chunk"):
                pass
        with tracing.span("phase", "fit.finish"):
            pass
    names = ["cyclone.job.LogisticRegression.fit",
             "cyclone.phase.fit.optimize", "cyclone.dispatch.lbfgs.chunk",
             "cyclone.phase.fit.finish"]
    assert [e[:2] for e in log] == [
        ("enter", names[0]), ("enter", names[1]), ("enter", names[2]),
        ("exit", names[2]), ("exit", names[1]), ("enter", names[3]),
        ("exit", names[3]), ("exit", names[0])]
    # a benchmark finds ITS window by its own prefix: never ours
    assert all(n.startswith(tracing.ANNOTATION_PREFIX)
               and not n.startswith("perfbench.") for _, n, *_ in log)
    # the recorded spans are the same four, on the tracer's own clock
    assert [s.name for s in tracer.snapshot()] == [
        "lbfgs.chunk", "fit.optimize", "fit.finish",
        "LogisticRegression.fit"]


@pytest.mark.parametrize("emit", ["instant", "record_span", "counter"])
def test_points_and_retroactive_spans_emit_no_annotation(tracer, emit):
    """An annotation brackets a live region on one thread; an instant, a
    counter sample and a span recorded after the fact are none."""
    log = []
    tracer.annotation = _RecordingAnnotation(log)
    if emit == "instant":
        tracing.instant("cache.miss", cache="program")
    elif emit == "counter":
        tracing.counter("hbm.bytes_in_use", 1.0)
    else:
        tracer.record_span("request", "predict", t0=1.0, t1=2.0)
    assert len(tracer.snapshot()) == 1 and log == []


def test_no_tracer_means_no_annotation():
    """With no tracer the path is the unchanged shared no-op: a factory
    left on a tracer that was uninstalled is never called."""
    tracing.disable()
    t = tracing.enable(max_spans=10)
    log = []
    t.annotation = _RecordingAnnotation(log)
    tracing.disable()
    with tracing.span("phase", "fit.prepare") as s:
        assert s is tracing.NOOP_SPAN
    assert log == []
    # and a tracer nobody gave a factory records spans and calls nothing
    t2 = tracing.enable(max_spans=10)
    try:
        assert t2.annotation is None
        with tracing.span("phase", "fit.prepare"):
            pass
        assert len(t2.snapshot()) == 1
    finally:
        tracing.disable()


def test_exception_inside_a_span_still_exits_its_annotation(tracer):
    log = []
    tracer.annotation = _RecordingAnnotation(log)
    with pytest.raises(KeyError):
        with tracing.span("phase", "fit.prepare"):
            with tracing.span("dispatch", "loss.eval"):
                raise KeyError("boom")
    assert [e[0] for e in log] == ["enter", "enter", "exit", "exit"]
    assert log[2][1] == "cyclone.dispatch.loss.eval" and \
        log[3][1] == "cyclone.phase.fit.prepare"
    assert log[2][2] is KeyError and log[3][2] is KeyError
    assert tracing.current_span_id() == ""
    assert [s.name for s in tracer.snapshot()] == ["loss.eval",
                                                   "fit.prepare"]


def test_spans_land_in_a_profiler_capture(ctx, tmp_path):
    """The real factory: under ``ctx.profile`` a fit's spans are events of
    the capture's host plane, the phases inside the job's window."""
    import glob

    import jax
    from jax.profiler import ProfileData
    ds, est = _phase_case(ctx, "logistic")
    est.fit(ds)                                   # compile outside
    tracing.disable()
    tracer = tracing.enable(max_spans=10_000)
    tracer.annotation = jax.profiler.TraceAnnotation
    try:
        with ctx.profile(str(tmp_path)):
            est.fit(ds)
    finally:
        tracing.disable()
    capture, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))
    data = ProfileData.from_file(capture)
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("cyclone."):
                        events.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    (j0, j1), = events["cyclone.job.LogisticRegression.fit"]
    for name in ("cyclone.phase.fit.stats", "cyclone.phase.fit.prepare",
                 "cyclone.phase.fit.optimize", "cyclone.phase.fit.finish",
                 "cyclone.phase.optim.iteration",
                 "cyclone.dispatch.lbfgs.chunk",
                 "cyclone.transfer.lbfgs.readback"):
        assert name in events, sorted(events)
        assert all(j0 <= s and e <= j1 for s, e in events[name]), name


# -- phase spans over the fit path -----------------------------------------------

def _phase_case(ctx, which):
    """A dense fit of each estimator the benchmark's cells run: logistic
    (device-resident L-BFGS: one chunk a dispatch) and elastic-net linear
    regression (host OWL-QN: one dispatch an evaluation)."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    rng = np.random.RandomState(5)
    x = rng.randn(4096, 24)
    beta = rng.randn(24)
    if which == "logistic":
        from cycloneml_tpu.ml.classification import LogisticRegression
        y = (x @ beta + 0.5 * rng.randn(4096) > 0).astype(np.float64)
        est = LogisticRegression(maxIter=40, regParam=0.01)
    else:
        from cycloneml_tpu.ml.regression import LinearRegression
        y = x @ beta + 0.5 * rng.randn(4096)
        est = LinearRegression(maxIter=40, regParam=0.05,
                               elasticNetParam=0.5)
    return InstanceDataset.from_numpy(ctx, x, y), est


def _traced_fit(ctx, which):
    """``(model, job span, the job's spans)`` of a warm traced fit."""
    ds, est = _phase_case(ctx, which)
    est.fit(ds)            # compiles, and caches the dataset's moments
    tracing.disable()
    tracer = tracing.enable(max_spans=50_000)
    try:
        model = est.fit(ds)
        spans = tracer.snapshot()
    finally:
        tracing.disable()
    job = [s for s in spans if s.kind == "job"][-1]
    by_id = {s.span_id: s for s in spans}

    def under_job(s):
        while s is not None and s is not job:
            s = by_id.get(s.parent_id)
        return s is job
    return model, job, [s for s in spans if under_job(s)], by_id


def _ancestors(s, by_id):
    s = by_id.get(s.parent_id)
    while s is not None:
        yield s
        s = by_id.get(s.parent_id)


@pytest.mark.parametrize("which", ["logistic", "linreg_enet"])
def test_phase_spans_cover_the_fit(ctx, which):
    model, job, spans, by_id = _traced_fit(ctx, which)
    phases = [s for s in spans if s.kind == "phase"]
    names = {s.name for s in phases}
    assert names == {"fit.stats", "fit.prepare", "fit.optimize",
                     "fit.finish", "optim.iteration"}
    # every phase hangs off the job span run_job opened
    assert all(job in _ancestors(s, by_id) for s in phases)
    stats, = [s for s in phases if s.name == "fit.stats"]
    assert stats.attrs == {"cached": True}        # second fit of one ds
    opt, = [s for s in phases if s.name == "fit.optimize"]
    assert opt.attrs["optimizer"] == ("DeviceLBFGS" if which == "logistic"
                                      else "OWLQN")
    # the fit.* phases tile the job: their union covers >= 95 % of it
    covered, at = 0.0, job.t0
    for s in sorted((s for s in phases if s.name.startswith("fit.")),
                    key=lambda s: s.t0):
        assert s.t0 >= at - 1e-9, "fit.* phases overlap"
        covered += s.t1 - s.t0
        at = s.t1
    assert covered >= 0.95 * job.duration_s, (covered, job.duration_s)
    # one optim.iteration per turn: a chunk (device) / the initial
    # evaluation plus every iteration (host)
    turns = [s for s in phases if s.name == "optim.iteration"]
    summary = model.summary
    want = summary.total_dispatches if which == "logistic" \
        else summary.total_iterations + 1
    assert len(turns) == want and want >= 2 - (which == "logistic")
    assert [s.attrs["iteration"] for s in turns] == sorted(
        s.attrs["iteration"] for s in turns)
    assert all(opt in _ancestors(s, by_id) for s in turns)
    if which == "linreg_enet":
        # OWL-QN's turns say how their search went, and the summary
        # carries the same counts without a trace
        searched = [s.attrs for s in turns if s.attrs["iteration"] > 0]
        assert [1] + [a["search_evals"] for a in searched] \
            == summary.search_evals
        assert sum(summary.search_evals) == summary.total_evals
        assert all(a["search"] in ("first_trial", "backtracked", "unresolved")
                   and a["alpha"] >= 0 for a in searched)
    else:
        assert summary.search_evals is None
    # and every dispatch of the optimiser happens inside a turn
    dispatches = [s for s in spans if s.kind == "dispatch"]
    assert len(dispatches) == summary.total_dispatches
    for dsp in dispatches:
        assert any(a in turns for a in _ancestors(dsp, by_id)), dsp


@pytest.mark.parametrize("which", ["logistic", "linreg_enet"])
def test_fit_profile_phase_seconds_are_the_hosts_side(ctx, which):
    """Held by the spans' own arithmetic, so that a loaded machine cannot
    fail it (a wall-clock tolerance of 1 ms did, with six workers on the
    cores): every instant of the job outside its dispatch spans lies in a
    phase, but for the estimator's entry code — the part of the job span
    that no ``fit.*`` phase covers."""
    _, job, spans, by_id = _traced_fit(ctx, which)
    prof = FitProfile.from_spans(spans, root_id=job.span_id)
    assert set(prof.phase_seconds) == {
        "fit.stats", "fit.prepare", "fit.optimize", "fit.finish",
        "optim.iteration"}
    assert all(v >= 0.0 for v in prof.phase_seconds.values())
    # the fit.* phases are the job's children, in order and disjoint ...
    top = sorted((s for s in spans if s.kind == "phase"
                  and by_id.get(s.parent_id) is job), key=lambda s: s.t0)
    assert {s.name for s in top} == {"fit.stats", "fit.prepare",
                                     "fit.optimize", "fit.finish"}
    # (and the job's ONLY children: nothing the tracer times runs in the
    # gaps between them, so the entry code is untimed host code alone)
    assert [s for s in spans if by_id.get(s.parent_id) is job
            and s.kind != "phase"] == []
    at = job.t0
    for s in top:
        assert at <= s.t0 <= s.t1 <= job.t1, (s, at)
        at = s.t1
    # ... every dispatch lies in a phase, none inside another dispatch ...
    dispatches = [s for s in spans if s.kind == "dispatch"]
    assert len(dispatches) == prof.dispatch_count >= 1
    for d in dispatches:
        above = [a.kind for a in _ancestors(d, by_id)]
        assert "phase" in above and "dispatch" not in above, (d, above)
    # ... so the host's side of the job is the phases' self time plus what
    # the job span holds before, between and after them: the entry code
    entry_code = job.duration_s - sum(s.duration_s for s in top)
    assert entry_code >= 0.0
    host = prof.wall_seconds - prof.dispatch_seconds
    assert sum(prof.phase_seconds.values()) == pytest.approx(
        host - entry_code, rel=1e-9, abs=1e-9)
    # fit.optimize's own time is what is left outside its turns
    assert prof.phase_seconds["fit.optimize"] < \
        0.5 * sum(s.duration_s for s in spans if s.name == "fit.optimize")
    assert FitProfile.from_dict(prof.to_dict()).phase_seconds == \
        prof.phase_seconds


def test_phase_self_time_takes_nested_spans_off_once(tracer):
    """Hand-built tree: a dispatch nested in a dispatch is its parent's
    time already; a phase in a phase comes off the outer one."""
    def rec(kind, name, t0, t1, parent=""):
        return tracer.record_span(kind, name, t0=t0, t1=t1,
                                  parent=parent).span_id
    job = rec("job", "fit", 0.0, 10.0)
    outer = rec("phase", "fit.optimize", 1.0, 9.0, job)
    turn = rec("phase", "optim.iteration", 2.0, 8.0, outer)
    d = rec("dispatch", "lbfgs.stacked_host", 3.0, 7.0, turn)
    rec("dispatch", "loss.eval", 4.0, 5.0, d)
    coll = rec("collective", "tree_aggregate", 2.0, 2.5, turn)
    rec("dispatch", "loss.eval", 2.1, 2.4, coll)     # through a non-phase
    prof = FitProfile.from_spans(tracer.snapshot(), root_id=job)
    assert prof.phase_seconds == pytest.approx(
        {"fit.optimize": 2.0, "optim.iteration": 6.0 - 4.0 - 0.3})


def _abandon_cases():
    from cycloneml_tpu.ml.optim.lbfgs import LBFGS, LBFGSB, OWLQN
    return {"LBFGS": lambda: LBFGS(max_iter=20),
            "OWLQN": lambda: OWLQN(max_iter=20, l1_reg=0.01),
            "LBFGSB": lambda: LBFGSB(-np.ones(3), np.ones(3), max_iter=20)}


@pytest.mark.parametrize("name", ["LBFGS", "OWLQN", "LBFGSB", "DeviceLBFGS"])
def test_abandoned_iterations_leave_the_span_stack_empty(ctx, tracer, name):
    """A consumer that stops reading ``iterations()`` mid-run (or raises
    while holding a yielded state) must find the thread's span stack as it
    left it: no turn's span is open across a yield."""
    if name == "DeviceLBFGS":
        from cycloneml_tpu.dataset.dataset import InstanceDataset
        from cycloneml_tpu.ml.optim import aggregators
        from cycloneml_tpu.ml.optim.device_lbfgs import DeviceLBFGS
        from cycloneml_tpu.ml.optim.loss import DistributedLossFunction
        rng = np.random.RandomState(1)
        x = rng.randn(256, 3)
        y = (x @ np.array([1.0, -2.0, 0.5]) > 0).astype(np.float64)
        f = DistributedLossFunction(
            InstanceDataset.from_numpy(ctx, x, y),
            aggregators.binary_logistic(3, fit_intercept=False))
        opt = DeviceLBFGS(max_iter=20, chunk=2)
    else:
        def f(v):
            return float(np.sum((v - 0.3) ** 4 + (v - 0.3) ** 2)), \
                4 * (v - 0.3) ** 3 + 2 * (v - 0.3)
        opt = _abandon_cases()[name]()
    with tracing.span("job", "abandoned") as job:
        gen = opt.iterations(f, np.zeros(3))
        seen = []
        for state in gen:
            # between yields only the caller's own span is open
            assert tracing.current_span_id() == job.span_id
            seen.append(state.iteration)
            if len(seen) == 3:
                break
        gen.close()
        assert tracing.current_span_id() == job.span_id
    assert tracing.current_span_id() == ""
    turns = [s for s in tracer.snapshot() if s.name == "optim.iteration"]
    assert turns and all(s.parent_id == job.span_id and s.t1 >= s.t0 > 0
                         for s in turns)
    # OWL-QN's own search annotates its turn (not the initial evaluation);
    # the strong-Wolfe optimisers' turns carry the iteration alone
    for s in turns:
        searched = name == "OWLQN" and s.attrs["iteration"] > 0
        assert ({"search_evals", "alpha", "search"} <= set(s.attrs)) \
            == searched, s.attrs
        if searched:
            assert s.attrs["search_evals"] >= 1 and s.attrs["alpha"] > 0
            assert s.attrs["search"] in ("first_trial", "backtracked")


# -- names on the device ---------------------------------------------------------

def test_aggregation_program_is_named_after_its_aggregator(ctx):
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ml.stat.summarizer import _get_moments_fn
    rng = np.random.RandomState(2)
    ds = InstanceDataset.from_numpy(ctx, rng.randn(64, 5),
                                    (rng.rand(64) > 0.5).astype(float))
    call = ds.tree_aggregate_fn(aggregators.binary_logistic_scaled(5, True))
    extras = (jnp.ones(5), jnp.zeros(5), jnp.zeros(6))
    text = call.compiled.__wrapped__.lower(
        *call.arrays(), *extras).as_text(debug_info=True)
    assert "jit_tree_aggregate__binary_logistic_scaled" in text
    assert "jit_sharded" not in text and ".sharded" not in text
    moments = ds.tree_aggregate_fn(_get_moments_fn(), auto_psum=False)
    text = moments.compiled.__wrapped__.lower(
        *moments.arrays()).as_text(debug_info=True)
    assert "jit_tree_aggregate__summarizer_moments" in text


@pytest.mark.parametrize("kind,name", [
    ("logistic", "glm_sweep_logistic"),
    ("squared", "glm_sweep_least_squares")])
def test_glm_kernel_and_scopes_are_named_in_the_tpu_lowering(kind, name):
    """Lowered FOR the TPU from here (no chip): the Mosaic call carries the
    kernel's name. (The ``jax.named_scope``s this test once looked for are
    gone: the v5e profiler keeps no stat that carries one, PERF.md §3.)"""
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    n, d = 512, 200
    x = jnp.zeros((n, d), jnp.bfloat16)
    v, z = jnp.zeros(n, jnp.float32), jnp.zeros(d, jnp.float32)
    if kind == "logistic":
        agg = aggregators.binary_logistic_pallas_scaled(d, True)
        args = (x, v, v, z, z, jnp.zeros(d + 1, jnp.float32))
    else:
        agg = aggregators.least_squares_pallas_scaled(d)
        args = (x, v, v, z, z, jnp.zeros(2, jnp.float32), z)
    text = jax.jit(agg).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text and name in text
    assert "kern" not in text.replace("kernel", "")


def test_chunk_and_line_search_programs_are_named(ctx):
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.dataset.instance import compute_dtype
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ml.optim.device_lbfgs import _build_chunk
    from cycloneml_tpu.ml.optim.loss import _build_line_search
    rng = np.random.RandomState(3)
    d = 4
    ds = InstanceDataset.from_numpy(ctx, rng.randn(64, d),
                                    (rng.rand(64) > 0.5).astype(float))
    call = ds.tree_aggregate_fn(aggregators.binary_logistic(d, False))
    cdt = np.dtype(compute_dtype())
    arrays = call.arrays()
    chunk = _build_chunk(call.compiled, None, 3, 2, 1e-4, 0.9, 5, cdt,
                         n_arrays=len(arrays))
    c = jnp.zeros(d, cdt)
    hist = jnp.zeros((3, d), cdt)
    one = cdt.type(1.0)
    text = chunk.lower(*arrays, c, hist, hist, jnp.int32(0), one, c,
                       np.bool_(True), one, one, one, np.int32(2),
                       np.bool_(True)).as_text(debug_info=True)
    assert "jit_lbfgs_chunk" in text
    search = _build_line_search(call.compiled, None, 1e-4, 0.9, 5, cdt)
    # x0, direction, f(x0), ∇f(x0), the slope, the first step, Σw
    text = search.lower(*arrays, c, c, one, c, one, one, one).as_text()
    assert "jit_lbfgs_line_search" in text


# -- counters at the boundary ----------------------------------------------------

def test_linear_regression_summary_counts_what_the_step_counter_counts(ctx):
    """The benchmark's entry reads the delta of the context's
    ``steps.completed`` counter today; the summary now carries the same
    counts itself."""
    ds, est = _phase_case(ctx, "linreg_enet")
    counter = ctx.metrics.registry.counter("steps.completed")
    before = counter.count
    model = est.fit(ds)
    steps = counter.count - before
    s = model.summary
    assert s.total_evals == s.total_dispatches == steps
    assert s.total_evals > s.total_iterations >= 1
    assert s.solver == "l-bfgs" and s.total_passes == s.total_evals
    # the normal equations evaluate no loss function and say so; since
    # PR 29 they state what they did instead: one pass over X, one dispatch
    from cycloneml_tpu.ml.regression import LinearRegression
    before = counter.count
    normal = LinearRegression(solver="normal").fit(ds).summary
    assert normal.total_evals is None
    assert (normal.solver, normal.total_passes, normal.total_dispatches,
            counter.count - before) == ("normal", 1, 1, 1)


def test_normal_solver_fit_is_a_traced_counted_fit(ctx):
    """A normal-equation fit under the tracer: ``fit.prepare``, the moment
    program's dispatch with its collective and its readback inside,
    ``fit.solve`` (of the moment block as delivered: ``system``),
    ``fit.finish`` — all under the job span, the phases
    disjoint from each other and from the dispatch (what the idle readers
    partition by), together covering the job — and one ``kernel.
    wls_moments`` instant for the program the first fit built."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.regression import LinearRegression
    from cycloneml_tpu.parallel import collectives
    rng = np.random.RandomState(9)
    x = rng.randn(4096, 24)
    y = x @ rng.randn(24) + 0.5 * rng.randn(4096)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    est = LinearRegression(regParam=0.01)
    collectives.clear_program_cache()
    tracing.disable()
    tracer = tracing.enable(max_spans=50_000)
    try:
        est.fit(ds)                       # builds the program
        built = tracer.snapshot()
        tracer.clear()
        est.fit(ds)
        spans = tracer.snapshot()
    finally:
        tracing.disable()
    notes = [s for s in built if s.name == "kernel.wls_moments"]
    assert len(notes) == 1
    assert notes[0].attrs["pad_cols"] == 0
    assert notes[0].attrs["orientation"] == "xla"     # the host platform
    assert not [s for s in spans if s.name == "kernel.wls_moments"]

    job, = [s for s in spans if s.kind == "job"]
    by_id = {s.span_id: s for s in spans}
    named = {(s.kind, s.name): s for s in spans}
    order = [("phase", "fit.prepare"), ("dispatch", "wls.moments"),
             ("phase", "fit.solve"), ("phase", "fit.finish")]
    assert [k for k in order if k in named] == order
    assert {s.name for s in spans if s.kind == "phase"} == {
        "fit.prepare", "fit.solve", "fit.finish"}
    assert len([s for s in spans if s.kind == "dispatch"]) == 1
    assert named[("phase", "fit.solve")].attrs["system"] == "moments"
    at, covered = job.t0, 0.0
    for key in order:
        s = named[key]
        assert by_id[s.parent_id] is job
        assert s.t0 >= at - 1e-9, f"{key} overlaps what came before"
        at, covered = s.t1, covered + s.t1 - s.t0
    # a 3 ms fit: the estimator's entry code (0.3 ms) is a tenth of it
    assert covered >= 0.75 * job.duration_s, (covered, job.duration_s)
    dispatch = named[("dispatch", "wls.moments")]
    assert dispatch.attrs["passes"] == 1
    readback = named[("transfer", "wls.readback")]
    collective, = [s for s in spans if s.kind == "collective"]
    assert by_id[readback.parent_id] is dispatch
    assert by_id[collective.parent_id] is dispatch
    assert collective.t1 <= readback.t0 + 1e-9
    assert readback.attrs["bytes"] >= 4 * 24 * 24


# -- staging: what jax stages, under the program's spans -------------------------
# All counts and structure: a CPU time says nothing of the chip's.

@pytest.fixture
def ring():
    """The default tracer of a context: the flight ring. It harvests no
    costs, so nothing is staged ahead of a program's first dispatch (a FULL
    tracer's AOT analysis traces, lowers and compiles each new program once
    more, before its ``compile`` span opens)."""
    from cycloneml_tpu.observe.flight import FlightTracer
    tracing.disable()
    t = tracing.install_if_absent(FlightTracer(max_spans=50_000))
    yield t
    tracing.disable()


def _staging(spans):
    return [s for s in spans if s.kind == "staging"]


def _under(s, root, by_id):
    return s is root or root in _ancestors(s, by_id)


def test_fresh_jit_under_a_span_yields_one_span_a_step(ctx, tracer):
    """Also the alarm if a later jax stops emitting the events: the ``ctx``
    fixture registered the listeners, the rest is jax's."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.sin(x) + 1.0

    def staged_here(x):
        return inner(x) * 2.0 + jnp.cos(x).sum()

    f = jax.jit(staged_here)
    x = jnp.ones(8)
    outside = tracer.totals()["staging.outside"]["n"]
    jax.jit(lambda v: v * 3.5)(x)          # no span open: not the program's
    assert tracer.totals()["staging.outside"]["n"] == outside + 3
    assert not _staging(tracer.snapshot())

    with tracer.span("dispatch", "fresh") as sp:
        f(x).block_until_ready()
    staged = _staging(tracer.snapshot())
    assert [s.name for s in staged] == ["trace", "lower", "compile"]
    assert all(s.parent_id == sp.span_id for s in staged)
    trace, lower, compiled = staged
    assert trace.attrs["fun"] == "staged_here"
    assert "staged_here" in lower.attrs["fun"]
    assert "staged_here" in compiled.attrs["fun"]
    # inner, sin, add, multiply, cos, the sum, add: folded in, not recorded
    assert trace.attrs["nested"] >= 6
    assert lower.attrs["nested"] == compiled.attrs["nested"] == 0
    assert compiled.attrs["cache"] in ("off", "hit", "miss")
    assert trace.t0 <= trace.t1 <= lower.t0 <= lower.t1 <= compiled.t0
    totals = tracer.totals()
    for name in ("trace", "lower", "compile"):
        assert totals[f"staging.{name}"]["n"] == 1
    assert totals["staging.trace"]["seconds"] == trace.duration_s
    with tracer.span("dispatch", "warm"):
        f(x).block_until_ready()            # a warm call fires no listener
    assert len(_staging(tracer.snapshot())) == 3


def test_a_trace_that_raises_leaves_the_span_stack_as_it_was(ctx, tracer):
    import jax
    import jax.numpy as jnp

    def refuses(x):
        jnp.sin(x)
        raise ValueError("not traceable")

    x = jnp.ones(3)              # staged here, outside the program's spans
    with tracer.span("job", "raises") as job:
        before = list(tracer._stack())
        with pytest.raises(ValueError, match="not traceable"):
            jax.jit(refuses)(x)
        assert tracer._stack() == before
        assert not tracer._staging().open and tracer._staging().live is None
        assert tracing.current_span_id() == job.span_id
    assert tracer._stack() == []
    failed, = _staging(tracer.snapshot())
    assert (failed.name, failed.attrs["fun"]) == ("trace", "refuses")
    assert failed.parent_id == job.span_id and failed.t1 >= failed.t0 > 0


def test_an_exit_without_its_entry_is_ignored(tracer):
    """A tracer installed mid-step sees the exit alone: nothing was opened,
    so nothing is recorded, inside a span or outside, and the thread's
    stack is untouched either way."""
    import time
    event = "/jax/core/compile/backend_compile_duration"
    now = time.time()
    tracing.on_staging_span(event, now - 0.5, now, fun_name="jit(late)")
    with tracer.span("dispatch", "mid") as sp:
        before = list(tracer._stack())
        tracing.on_staging_span(event, now - 0.25, now, fun_name="jit(late)")
        assert tracer._stack() == before
        assert tracing.current_span_id() == sp.span_id
        # under an announced step of another kind it is that step's already
        tracing.on_staging_start("/jax/core/compile/jaxpr_trace_duration",
                                 0.0, fun_name="outer")
        tracing.on_staging_span(event, now - 0.25, now, fun_name="jit(late)")
        assert tracer._staging().open == ["trace"]
        tracing.on_staging_span("/jax/core/compile/jaxpr_trace_duration",
                                now - 0.25, now, fun_name="outer")
    outer, = _staging(tracer.snapshot())
    assert (outer.name, outer.attrs) == ("trace", {"fun": "outer",
                                                   "nested": 0})
    assert tracer._stack() == [] and not tracer._staging().open
    totals = tracer.totals()
    assert totals["staging.outside"]["n"] == 0
    assert "staging.compile" not in totals
    # other events of jax's are not staging
    tracing.on_staging_start("/jax/some/other_scalar", 1.0)
    tracing.on_cache_event("/jax/compilation_cache/tasks_using_cache")
    assert not tracer._staging().open


def test_persistent_cache_answers_ride_the_compile_span(tracer):
    """The listeners by hand, as jax calls them on a hit and on a miss."""
    step = "/jax/core/compile/backend_compile_duration"

    def compile_step(*cache_events, retrieval=None):
        tracing.on_staging_start(step, 0.0, fun_name="jit(f)")
        for e in cache_events:
            tracing.on_cache_event("/jax/compilation_cache/" + e)
        if retrieval is not None:
            tracing.on_cache_duration(
                "/jax/compilation_cache/cache_retrieval_time_sec", retrieval)
        tracing.on_staging_span(step, 0.0, 1.0, fun_name="jit(f)")

    with tracer.span("job", "fit"):
        acc = tracer.open_staging_account()
        compile_step("compile_requests_use_cache", "cache_hits",
                     retrieval=0.125)
        compile_step("compile_requests_use_cache", "cache_misses")
        compile_step()
        tracer.close_staging_account(acc)
        compile_step("cache_misses")            # after the account closed
    hit, miss, off, _ = _staging(tracer.snapshot())
    assert hit.attrs == {"fun": "jit(f)", "nested": 0, "cache": "hit",
                         "retrieval_s": 0.125}
    assert miss.attrs == {"fun": "jit(f)", "nested": 0, "cache": "miss"}
    assert off.attrs["cache"] == "off"
    totals = tracer.totals()
    assert totals["staging.cache_hit"]["n"] == 1
    assert totals["staging.cache_miss"]["n"] == 2
    assert totals["staging.compile"]["n"] == 4
    assert (acc["programs"], acc["cache_hit"], acc["cache_miss"]) == (3, 1, 1)
    assert acc["compile"] == sum(s.duration_s for s in (hit, miss, off))
    assert acc["slowest_fun"] == "jit(f)"
    # the account's seconds are the job's entry among the totals
    assert totals["staging.job"] == {
        "n": 1, "seconds": acc["compile"], "first_s": acc["compile"],
        "max_s": acc["compile"]}
    tracer.clear()
    assert tracer.totals() == {
        k: {"n": 0, "seconds": 0.0, "first_s": 0.0, "max_s": 0.0}
        for k in ("staging.cache_hit", "staging.cache_miss",
                  "staging.outside")}


def test_totals_outlive_the_flight_ring(ctx):
    """A private context beside the session's, started under a ring of eight
    spans: after two fits the ring holds neither the context's start nor the
    first fit; the totals hold both."""
    from cycloneml_tpu import context as ctx_mod
    from cycloneml_tpu.context import CycloneContext
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.regression import LinearRegression
    from cycloneml_tpu.observe.flight import FlightTracer
    tracing.disable()
    ring = tracing.install_if_absent(FlightTracer(max_spans=8))
    with ctx_mod._active_lock:
        old = ctx_mod._active_context
        ctx_mod._active_context = None
    try:
        private = CycloneContext(master="local-mesh[8]", app_name="ring")
        try:
            assert tracing.active() is ring
            rng = np.random.RandomState(4)
            x = rng.randn(512, 6)
            ds = InstanceDataset.from_numpy(private, x, x @ rng.randn(6))
            est = LinearRegression(regParam=0.01)
            est.fit(ds)
            est.fit(ds)
        finally:
            private.stop()
    finally:
        with ctx_mod._active_lock:
            ctx_mod._active_context = old
        tracing.disable()
    assert ring.dropped > 0 and len(ring.snapshot()) == 8
    assert not [s for s in ring.snapshot() if s.name == "context.start"]
    totals = ring.totals()
    start = totals["phase.context.start"]
    assert start["n"] == 1 and start["seconds"] > 0.0
    assert totals["phase.context.mesh"]["seconds"] \
        + totals["phase.context.services"]["seconds"] <= start["seconds"]
    job = totals["job.LinearRegression.fit"]
    assert job["n"] == 2
    # the first fit of the process: it paid the staging, the second did not
    assert job["first_s"] == job["max_s"] > job["seconds"] - job["first_s"]
    assert totals["staging.compile"]["n"] >= 1
    # all of it beneath the first job: what the jobs staged is what the
    # process staged, and the second job's share of it is nothing
    staged = totals["staging.job"]
    assert staged["n"] == 2 and staged["first_s"] == staged["seconds"] > 0.0
    assert staged["seconds"] == pytest.approx(sum(
        totals[f"staging.{step}"]["seconds"]
        for step in ("trace", "lower", "compile")), rel=1e-9)
    assert sum(t["n"] for t in totals.values()) > 8


def _cell_case(ctx, which):
    """The five cells' estimators (``BENCHMARK.json``) at a tiny shape."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.ml.regression import (GeneralizedLinearRegression,
                                             LinearRegression)
    rng = np.random.RandomState(11)
    # a shape of its own each: jax keeps lowered and compiled programs by
    # their jaxpr, and a shape another test has used would find them there
    n, d = 1048, 13 + ["lr_epsilon", "linreg_enet", "linreg_ridge_normal",
                       "glr_binomial", "lr_multinomial",
                       "capture"].index(which)
    x = rng.randn(n, d)
    margin = x @ rng.randn(d)
    if which == "lr_epsilon":
        y, est = (margin + 0.5 * rng.randn(n) > 0).astype(np.float64), \
            LogisticRegression(maxIter=100, regParam=0.01)
    elif which == "linreg_enet":
        y, est = margin + 0.5 * rng.randn(n), \
            LinearRegression(regParam=0.01, elasticNetParam=0.5)
    elif which in ("linreg_ridge_normal", "capture"):
        y, est = margin + 0.5 * rng.randn(n), LinearRegression(regParam=0.01)
    elif which == "glr_binomial":
        y, est = (margin + 0.5 * rng.randn(n) > 0).astype(np.float64), \
            GeneralizedLinearRegression(family="binomial")
    else:
        scores = x @ rng.randn(d, 4) + 0.5 * rng.randn(n, 4)
        y, est = np.argmax(scores, axis=1).astype(np.float64), \
            LogisticRegression(maxIter=100, regParam=0.01)
    return InstanceDataset.from_numpy(ctx, x, y), est


@pytest.mark.parametrize("which", [
    "lr_epsilon", "linreg_enet", "linreg_ridge_normal", "glr_binomial",
    "lr_multinomial"])
def test_first_fit_stages_beneath_its_compile_spans_second_fit_nothing(
        ctx, ring, which):
    from cycloneml_tpu.parallel import collectives
    ds, est = _cell_case(ctx, which)
    collectives.clear_program_cache()
    est.fit(ds)
    first = ring.snapshot()
    ring.clear()
    est.fit(ds)
    second = ring.snapshot()
    totals = ring.totals()
    by_id = {s.span_id: s for s in first}
    job, = [s for s in first if s.kind == "job"]
    compiles = [s for s in first if s.kind == "compile"]
    assert compiles
    for c in compiles:
        steps = [s.name for s in _staging(first) if c in _ancestors(s, by_id)]
        # the first dispatch of a new program object traces, lowers and
        # compiles it (and whatever eager operation it meets first)
        assert {"trace", "lower", "compile"} <= set(steps), (c, steps)
    # staging is the program's only beneath the program's spans
    assert all(_under(s, job, by_id) and s.attrs["fun"]
               for s in _staging(first))
    # a warm fit stages nothing: a staging span here would be a re-trace,
    # named by its function
    assert [(s.name, s.attrs["fun"]) for s in _staging(second)] == []
    assert not [s for s in second if s.kind == "compile"]
    assert not [k for k in totals if k.startswith("staging.")
                and k != "staging.job" and totals[k]["n"]]
    assert (totals["staging.job"]["n"], totals["staging.job"]["seconds"]) \
        == (1, 0.0)


def test_staging_spans_lie_inside_their_compile_event_in_a_capture(
        ctx, ring, tmp_path):
    """Live spans: a cold fit's ``cyclone.staging.*`` events sit inside a
    ``cyclone.compile.*`` event on the profiler's clock; a warm fit's
    capture holds none."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from cycloneml_tpu.parallel import collectives
    ds, est = _cell_case(ctx, "capture")
    collectives.clear_program_cache()
    ring.annotation = jax.profiler.TraceAnnotation

    def captured(where):
        with ctx.profile(str(where)):
            est.fit(ds)
        capture, = glob.glob(str(where / "plugins" / "profile" / "*"
                                 / "*.xplane.pb"))
        return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for plane in ProfileData.from_file(capture).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name.startswith("cyclone.")]

    cold = captured(tmp_path / "cold")
    compiles = [(s, e) for name, s, e in cold
                if name.startswith("cyclone.compile.")]
    staged = [(name, s, e) for name, s, e in cold
              if name.startswith("cyclone.staging.")]
    assert compiles and {name for name, _, _ in staged} == {
        "cyclone.staging.trace", "cyclone.staging.lower",
        "cyclone.staging.compile"}
    inside = [ev for ev in staged
              if any(s <= ev[1] and ev[2] <= e for s, e in compiles)]
    assert {name for name, _, _ in inside} == {name for name, _, _ in staged}
    warm = captured(tmp_path / "warm")
    assert warm and not [name for name, _, _ in warm
                         if name.startswith(("cyclone.staging.",
                                             "cyclone.compile."))]


def test_a_fresh_lambda_per_fit_shows_as_staging_under_fit_prepare(ctx, ring):
    """PR 32's bug in its direct form: the program cache keys on the
    aggregator's function object, so a ``lambda`` built per fit is traced,
    lowered and compiled per fit — and the spans name it. A module-level
    aggregator is staged once."""
    import jax.numpy as jnp
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    rng = np.random.RandomState(6)
    ds = InstanceDataset.from_numpy(ctx, rng.randn(128, 3), rng.randn(128))

    def fit(aggregator):
        with tracing.span("job", "Sketch.fit") as job:
            with tracing.span("phase", "fit.prepare"):
                ds.tree_aggregate_fn(aggregator or (
                    lambda x, y, w: {"wy": jnp.sum(w * y)}))()
        return job.span_id

    def staged_under_prepare(job_id):
        spans = ring.snapshot()
        by_id = {s.span_id: s for s in spans}
        prepare, = [s for s in spans if s.name == "fit.prepare"
                    and s.parent_id == job_id]
        return [(s.name, s.attrs["fun"]) for s in _staging(spans)
                if prepare in _ancestors(s, by_id)]

    fit(None)
    second = staged_under_prepare(fit(None))
    assert [name for name, _ in second] == ["trace", "lower", "compile"]
    assert all("lambda" in fun for _, fun in second), second

    def label_moment(x, y, w):
        return {"wy": jnp.sum(w * y)}
    assert staged_under_prepare(fit(label_moment))
    assert staged_under_prepare(fit(label_moment)) == []


def test_a_two_chunk_device_lbfgs_fit_compiles_its_chunk_twice(ctx, ring):
    """PERF.md §7, found by hand with ``jax_log_compiles`` in PR 34: the
    first turn's operands are uncommitted host values, later turns pass the
    program's own committed outputs, so jit stages ``lbfgs_chunk`` again
    beneath the SAME program object (one ``compile`` span, the program's
    bracket, sees none of it). Pinned as found: when this reads 1 the
    second compile is gone — correct PERF.md §7."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ml.optim.device_lbfgs import DeviceLBFGS
    from cycloneml_tpu.ml.optim.loss import DistributedLossFunction
    rng = np.random.RandomState(8)
    x = rng.randn(512, 5)
    y = (x @ rng.randn(5) + rng.randn(512) > 0).astype(np.float64)
    f = DistributedLossFunction(
        InstanceDataset.from_numpy(ctx, x, y),
        aggregators.binary_logistic(5, fit_intercept=False))
    with tracing.span("job", "two_chunks"):
        states = list(DeviceLBFGS(max_iter=4, chunk=2, tol=0.0)
                      .iterations(f, np.zeros(5)))
    assert states[-1].iteration == 4
    spans = ring.snapshot()
    chunks = [s for s in spans if (s.kind, s.name) == ("dispatch",
                                                       "lbfgs.chunk")]
    assert len(chunks) == 2
    assert len([s for s in spans if (s.kind, s.name) == (
        "compile", "lbfgs.chunk")]) == 1
    compiled = [s for s in _staging(spans) if s.name == "compile"
                and "lbfgs_chunk" in s.attrs["fun"]]
    assert len(compiled) == 2
    by_id = {s.span_id: s for s in spans}
    assert [chunks.index(next(a for a in _ancestors(s, by_id)
                              if a in chunks)) for s in compiled] == [0, 1]


def test_run_job_logs_one_line_and_the_profile_carries_its_numbers(
        ctx, caplog):
    import logging
    from cycloneml_tpu.parallel import collectives
    ds, est = _cell_case(ctx, "linreg_ridge_normal")
    collectives.clear_program_cache()
    profiles = []
    listener = (lambda e: profiles.append(e.profile)
                if type(e).__name__ == "FitProfileCompleted" else None)
    ctx.listener_bus.add_listener(listener)
    tracing.disable()
    tracing.enable(max_spans=50_000)
    try:
        with caplog.at_level(logging.INFO, logger="cycloneml_tpu.context"):
            est.fit(ds)
            est.fit(ds)
        ctx.listener_bus.wait_until_empty()
    finally:
        tracing.disable()
        ctx.listener_bus.remove_listener(listener)
    lines = [r.getMessage() for r in caplog.records if " staged " in
             r.getMessage()]
    assert len(lines) == 1, lines           # the warm fit staged nothing
    cold, warm = [FitProfile.from_dict(p) for p in profiles[-2:]]
    assert cold.staged_programs >= 1
    assert f"staged {cold.staged_programs} programs" in lines[0]
    assert cold.staging_slowest_fun and cold.staging_slowest_fun in lines[0]
    assert set(cold.staging_seconds) == {"trace", "lower", "compile"}
    assert all(v > 0.0 for v in cold.staging_seconds.values())
    assert cold.staging_cache_hits + cold.staging_cache_misses \
        <= cold.staged_programs
    assert sum(cold.staging_seconds.values()) <= cold.wall_seconds
    assert (warm.staged_programs, warm.staging_slowest_fun) == (0, "")
    assert not any(warm.staging_seconds.values())
    assert FitProfile.from_dict(cold.to_dict()) == cold
