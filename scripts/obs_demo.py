"""obs-demo: run a small traced fit, export + validate its Chrome trace.

The executable form of the observability acceptance contract
(docs/observability.md):

1. a ``LogisticRegression.fit`` with tracing enabled exports a
   Chrome-trace JSON that passes ``validate_chrome_trace`` (loads in
   Perfetto),
2. the trace contains >= 4 distinct span kinds out of
   {compile, dispatch, collective, transfer, checkpoint, job}, plus
   counter ("C"-phase) events — the HBM/FLOPs timeline tracks,
3. the fit's ``FitProfile`` dispatch/eval counts agree with the ledger the
   model summary (and bench.py) already reports,
4. the profile carries the XLA cost rollup: non-null total FLOPs,
   per-program cost entries keyed by program-cache identity, and memory
   fields either populated or explicitly marked unavailable
   (``cost_availability`` / ``memory_stats_available`` record the
   backend matrix — CPU has cost+memory analysis but no live
   ``memory_stats``).

Run via ``make obs-demo``. Exits non-zero on any violation.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402


def main() -> int:
    from cycloneml_tpu.conf import CycloneConf
    from cycloneml_tpu.context import CycloneContext
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.observe import (FitProfile, span_kinds, tracing,
                                       validate_chrome_trace)

    work = tempfile.mkdtemp(prefix="cyclone-obs-demo-")
    conf = (CycloneConf()
            .set("cyclone.master", "local-mesh[8]")
            .set("cyclone.app.name", "obs-demo")
            .set("cyclone.trace.enabled", "true"))
    ctx = CycloneContext(conf)
    try:
        rng = np.random.RandomState(0)
        x = rng.randn(256, 8)
        y = (x @ rng.randn(8) > 0).astype(float)
        frame = MLFrame(ctx, {"features": x, "label": y})
        # checkpointDir adds the checkpoint span family to the trace
        lr = LogisticRegression(maxIter=8, regParam=0.01, tol=0.0,
                                checkpointDir=os.path.join(work, "ckpt"),
                                checkpointInterval=2)
        model = lr.fit(frame)
        ctx.listener_bus.wait_until_empty()

        trace_path = os.path.join(work, "fit.trace.json")
        ctx.export_trace(trace_path)
        profile = FitProfile.from_dict(ctx.fit_profile())

        errors = validate_chrome_trace(trace_path)
        if errors:
            print("FAIL: trace schema violations:", file=sys.stderr)
            for e in errors[:20]:
                print(f"  - {e}", file=sys.stderr)
            return 1
        kinds = span_kinds(trace_path)
        print(f"trace: {trace_path}")
        print(f"span kinds: { {k: v for k, v in sorted(kinds.items())} }")
        want = {"compile", "dispatch", "collective", "transfer",
                "checkpoint", "job"}
        got = want & set(kinds)
        if len(got) < 4:
            print(f"FAIL: only {len(got)} of the span kinds {sorted(want)} "
                  f"present: {sorted(got)}", file=sys.stderr)
            return 1

        summary = model.summary
        print(f"FitProfile: dispatches={profile.dispatch_count} "
              f"evals={profile.eval_count} compiles={profile.compile_count} "
              f"({profile.compile_seconds:.3f}s) "
              f"transfers={profile.transfer_count} "
              f"({profile.transfer_bytes} B) "
              f"checkpoints={profile.checkpoint_saves} "
              f"steady={profile.steady_seconds:.3f}s "
              f"wall={profile.wall_seconds:.3f}s")
        print(f"summary:    dispatches={summary.total_dispatches} "
              f"evals={summary.total_evals}")
        if profile.dispatch_count != summary.total_dispatches:
            print(f"FAIL: profile dispatch_count {profile.dispatch_count} "
                  f"!= summary total_dispatches {summary.total_dispatches}",
                  file=sys.stderr)
            return 1
        if profile.eval_count != summary.total_evals:
            print(f"FAIL: profile eval_count {profile.eval_count} "
                  f"!= summary total_evals {summary.total_evals}",
                  file=sys.stderr)
            return 1
        if profile.checkpoint_saves < 1:
            print("FAIL: no checkpoint spans recorded", file=sys.stderr)
            return 1

        # -- XLA cost & HBM accounting acceptance --
        if kinds.get("counter", 0) < 1:
            print("FAIL: no counter ('C'-phase) events in the trace",
                  file=sys.stderr)
            return 1
        print(f"cost:       availability={profile.cost_availability} "
              f"flops={profile.total_flops} "
              f"hbm_peak_bytes={profile.hbm_peak_bytes} "
              f"achieved_flops={profile.achieved_flops} "
              f"intensity={profile.arithmetic_intensity} "
              f"memory_stats="
              f"{'live' if profile.memory_stats_available else 'unavailable'}")
        if profile.total_flops is None or profile.total_flops <= 0:
            print("FAIL: FitProfile.total_flops is null — the compile-span "
                  "harvest did not run", file=sys.stderr)
            return 1
        if not profile.programs:
            print("FAIL: no per-program cost entries in the profile",
                  file=sys.stderr)
            return 1
        for pid, entry in profile.programs.items():
            print(f"  program {pid}: execs={entry.get('executions')} "
                  f"flops={entry.get('flops')} "
                  f"peak_bytes={entry.get('peak_bytes')}")
            if entry.get("executions", 0) < 1:
                print(f"FAIL: program {pid} has no executions",
                      file=sys.stderr)
                return 1
        # memory fields: populated, or EXPLICITLY marked unavailable
        d = profile.to_dict()
        for key in ("hbm_peak_bytes", "hbm_argument_bytes", "hbm_temp_bytes"):
            if key not in d:
                print(f"FAIL: profile lacks the {key} field", file=sys.stderr)
                return 1
        if d["hbm_peak_bytes"] is None and profile.cost_availability == "full":
            print("FAIL: cost_availability=full but hbm_peak_bytes is null",
                  file=sys.stderr)
            return 1
        print("OK: trace validates (incl. counter events), >=4 span kinds, "
              "profile counts agree with the model summary, cost rollup "
              "present (FLOPs + memory fields or explicit unavailable "
              "markers)")

        # -- distributed telemetry: merged 2-process trace --------------
        # a child process runs its own traced fit and ships spans back to
        # a collector here; the merged export must validate and hold BOTH
        # process lanes (the ISSUE-12 obs-demo acceptance)
        rc = _merged_trace_demo(work)
        if rc != 0:
            return rc
        return 0
    finally:
        ctx.stop()
        tracing.disable()


_CHILD = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from cycloneml_tpu.conf import CycloneConf
from cycloneml_tpu.context import CycloneContext
from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.ml.classification import LogisticRegression

# collector address + trace context arrive via the environment (the same
# channel the deploy harness injects for launched apps)
conf = (CycloneConf().set("cyclone.master", "local-mesh[2]")
        .set("cyclone.worker.id", "demo-worker")
        .set("cyclone.telemetry.collect.intervalMs", "100"))
ctx = CycloneContext(conf)
rng = np.random.RandomState(1)
x = rng.randn(96, 4)
y = (x @ rng.randn(4) > 0).astype(float)
LogisticRegression(maxIter=3, regParam=0.01, tol=0.0).fit(
    MLFrame(ctx, {"features": x, "label": y}))
ctx.stop()   # flushes the span shipper
"""


def _merged_trace_demo(work: str) -> int:
    import subprocess
    import time

    from cycloneml_tpu.observe import (process_lanes, tracing,
                                       validate_chrome_trace)
    from cycloneml_tpu.observe.collect import TraceCollector

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tracer = tracing.active()
    col = TraceCollector(host_label="demo-master", tracer=tracer)
    child_py = os.path.join(work, "child_fit.py")
    with open(child_py, "w", encoding="utf-8") as fh:
        fh.write(_CHILD)
    try:
        span = tracer.span("deploy", "submit child_fit.py")
        with span:
            env = dict(os.environ)
            env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
            env.update(col.launch_env(parent_span_id=span.span_id))
            r = subprocess.run(
                [sys.executable, child_py], env=env, timeout=240,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        if r.returncode != 0:
            print("FAIL: child fit process failed:\n"
                  + r.stdout.decode()[-2000:], file=sys.stderr)
            return 1
        deadline = time.time() + 30
        while not any(rec["spans"] for rec in col.hosts().values()):
            if time.time() > deadline:
                print("FAIL: no span batches arrived from the child",
                      file=sys.stderr)
                return 1
            time.sleep(0.2)
        merged_path = os.path.join(work, "merged.trace.json")
        col.export(merged_path)
        errors = validate_chrome_trace(merged_path)
        if errors:
            print("FAIL: merged trace schema violations:", file=sys.stderr)
            for e in errors[:20]:
                print(f"  - {e}", file=sys.stderr)
            return 1
        lanes = process_lanes(merged_path)
        if len(lanes) < 2:
            print(f"FAIL: merged trace has {len(lanes)} process lane(s), "
                  f"need >= 2: {lanes}", file=sys.stderr)
            return 1
        hosts = col.hosts()
        child = hosts.get("demo-worker", {})
        if child.get("trace_id") != tracer.trace_id:
            print(f"FAIL: child trace_id {child.get('trace_id')!r} != "
                  f"master {tracer.trace_id!r}", file=sys.stderr)
            return 1
        print(f"merged trace: {merged_path}")
        print(f"process lanes: { {k: v for k, v in sorted(lanes.items())} }")
        print("OK: merged 2-process trace validates, >=2 labeled process "
              "lanes, one shared trace id")
        return 0
    finally:
        col.stop()


if __name__ == "__main__":
    sys.exit(main())
