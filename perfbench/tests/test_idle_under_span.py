"""The idle-time split, on the CPU: the ``idle_under_span`` reader on a trace
written by hand (a gap split across two spans by overlap, ``exclude``, no
program span at all), the six metric files against that trace, and a traced
rehearsal of each cell whose result line holds the split. Nothing timed."""

import pytest

from perfbench import manifest, trace
from perfbench.readers import device_ms_per_fit, idle_under_span
from perfbench.tests.test_run import result_of, run_cell

MS = 1_000_000  # ns

# one chip, one traced fit of 100 ms; the device runs 10-30 (pad of X, copy
# of X, sweep) and 50-70 (sweep), so it idles 0-10, 30-50 and 70-100
PAD = ('%pad.0 = bf16[64,2048]{1,0:T(8,128)(2,1)} pad(bf16[64,2000]{1,0} '
       '%copy.1, bf16[] %c), padding=0_0x0_48, metadata={op_name='
       '"jit(tree_aggregate__x)/glm.prepare_x/jit(_pad)/pad"}')
COPY = ("%copy.1 = bf16[64,2000]{1,0:T(8,128)(2,1)} copy(bf16[64,2000]{0,1} "
        "%x.1)")
RESHAPE = "%copy.2 = f32[64,1]{1,0:T(8,128)} copy(f32[64]{0} %bitcast.11)"
SWEEP = ('%glm_sweep_logistic.1 = (f32[1,1]{1,0}) custom-call(bf16[64,2048] '
         '%pad.0), custom_call_target="tpu_custom_call"')
CHIP = [(COPY, 10 * MS, 4 * MS), (PAD, 14 * MS, 6 * MS),
        (RESHAPE, 20 * MS, 2 * MS), (SWEEP, 22 * MS, 8 * MS),
        (SWEEP, 50 * MS, 20 * MS)]
# the host: 0-8 stats, 8-40 prepare (so the 30-50 gap straddles prepare and
# the optimiser's turn), 40-90 one turn holding a dispatch 45-85 with its
# readback 70-85, 90-96 finish, 96-100 the benchmark reading the model
HOST = [("perfbench.fit.0", 0, 100 * MS),
        ("cyclone.job.LogisticRegression.fit", 0, 96 * MS),
        ("cyclone.phase.fit.stats", 0, 8 * MS),
        ("cyclone.phase.fit.prepare", 8 * MS, 32 * MS),
        ("cyclone.phase.fit.optimize", 40 * MS, 50 * MS),
        ("cyclone.phase.optim.iteration", 40 * MS, 50 * MS),
        ("cyclone.dispatch.lbfgs.chunk", 45 * MS, 40 * MS),
        ("cyclone.transfer.lbfgs.readback", 70 * MS, 15 * MS),
        ("cyclone.phase.fit.finish", 90 * MS, 6 * MS),
        ("_vectors.py:61 apply", 97 * MS, 2 * MS)]


def run_of(chips, host, n_fits=1):
    return {"trace": trace.Trace(chips, host),
            "traced_fits": [{"evals": 2}] * n_fits}


def metric(name, run):
    read, args = manifest.reader_of(name)
    return read(run, **args)


def test_overlap_of_interval_lists():
    a = [(0, 10), (20, 30), (40, 50)]
    assert idle_under_span.overlap(a, [(5, 25), (45, 60)]) \
        == [(5, 10), (20, 25), (45, 50)]
    assert idle_under_span.overlap(a, []) == []
    assert idle_under_span.overlap(a, [(10, 20)]) == []


def test_a_gap_is_split_between_the_spans_it_straddles():
    run = run_of({0: CHIP}, HOST)
    read = idle_under_span.read
    # idle 0-10 and 30-50; prepare covers 8-10 and 30-40 of them: by the
    # gap's midpoint (40) all 20 ms of 30-50 would have gone to one span
    assert read(run, spans=r"^cyclone\.phase\.fit\.prepare$") \
        == pytest.approx(12.0)
    assert read(run, spans=r"^cyclone\.phase\.optim\.iteration$") \
        == pytest.approx(10.0 + 20.0)          # 40-50 and 70-90
    # ... less what lies inside its dispatch (45-50, 70-85)
    assert read(run, spans=r"^cyclone\.phase\.optim\.iteration$",
                exclude=r"^cyclone\.dispatch\.") == pytest.approx(5.0 + 5.0)
    # clipped to the benchmark's fit spans: 'inside' names another prefix
    assert read(run, spans=r"^cyclone\.", inside="perfbench.warmup") == 0.0
    # per traced fit
    assert read({**run, "traced_fits": [{}] * 4},
                spans=r"^cyclone\.phase\.fit\.prepare$") \
        == pytest.approx(3.0)


def test_no_program_span_reads_none_and_no_fit_reads_none():
    bare = [ev for ev in HOST if not ev[0].startswith("cyclone.")]
    assert idle_under_span.read(run_of({0: CHIP}, bare),
                                spans=r"^perfbench\.fit") is None
    for name in ("idle_fit_edges_ms", "idle_host_optim_ms",
                 "idle_dispatch_ms", "idle_readback_ms",
                 "idle_unattributed_ms"):
        assert metric(name, run_of({0: CHIP}, bare)) is None
    assert idle_under_span.read(run_of({0: CHIP}, HOST, n_fits=0),
                                spans=r"^cyclone\.") is None


def test_the_five_idle_metrics_partition_the_idle_time():
    run = run_of({0: CHIP}, HOST)
    want = {"idle_fit_edges_ms": 8 + 2 + 10 + 6,   # 0-10, 30-40, 90-96
            "idle_host_optim_ms": 5 + 5,           # 40-45, 85-90
            "idle_dispatch_ms": 5,                 # 45-50
            "idle_readback_ms": 15,                # 70-85
            "idle_unattributed_ms": 4}             # 96-100: after the job
    got = {name: metric(name, run) for name in want}
    assert got == pytest.approx(want)
    tr = run["trace"]
    idle_ms = 1e3 * (tr.window_s - tr.busy_s(0))
    assert sum(got.values()) == pytest.approx(idle_ms) == pytest.approx(60.0)


def test_a_platform_without_a_device_plane_was_idle_throughout():
    run = run_of({}, HOST)
    assert metric("idle_readback_ms", run) == pytest.approx(15.0)
    assert sum(metric(m, run) for m in (
        "idle_fit_edges_ms", "idle_host_optim_ms", "idle_dispatch_ms",
        "idle_readback_ms", "idle_unattributed_ms")) == pytest.approx(100.0)


def test_x_prepare_reads_the_pad_and_the_layout_copy_of_x():
    run = run_of({0: CHIP}, HOST)
    assert metric("x_prepare_device_ms", run) == pytest.approx(10.0)
    # by the scope where the event's name carries it, by X's shape where
    # it does not: either alone finds the pad, only the shape the copy
    _, args = manifest.reader_of("x_prepare_device_ms")
    scope, shape = args["include"]
    assert device_ms_per_fit.read(run, include=[scope]) == pytest.approx(6.0)
    assert device_ms_per_fit.read(run, include=[shape]) \
        == pytest.approx(10.0)
    # the (n, 1) relayout of y and w and the sweep are not X's preparation
    assert not trace.matching([(RESHAPE, 0, 1), (SWEEP, 0, 1)],
                              args["include"])


def test_the_new_metrics_are_additions_with_both_cells():
    bench = manifest.benchmark()
    new = {m["name"]: m for m in bench["per_layer"][-6:]}
    assert list(new) == ["x_prepare_device_ms", "idle_fit_edges_ms",
                         "idle_host_optim_ms", "idle_dispatch_ms",
                         "idle_readback_ms", "idle_unattributed_ms"]
    for m in new.values():
        assert m["workloads"] == ["lr_epsilon_fit", "linreg_enet_fit"]
        assert (m["unit"], m["better"], m["moves"]) == ("ms", "lower",
                                                        "fit_s")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    layers = {m["layer"] for m in bench["per_layer"][:-6]}
    assert {m["layer"] for m in new.values()} - layers == {"aggregation"}


@pytest.mark.parametrize("cell", ["lr_epsilon_fit", "linreg_enet_fit"])
def test_a_traced_rehearsal_reports_the_split(cell):
    """The context puts the profiler's annotation on the always-on ring, so
    a default-conf run under ``--trace 1`` holds the program's spans. The
    host platform has no device plane: the five idle metrics then split the
    whole of the traced fits, and ``x_prepare_device_ms`` — device time —
    has nothing to read and is left out."""
    r = result_of(run_cell(cell, 1))
    assert r["correct"] is True, r["compared"]
    idle = ["idle_fit_edges_ms", "idle_host_optim_ms", "idle_dispatch_ms",
            "idle_readback_ms", "idle_unattributed_ms"]
    assert set(idle) <= set(r["metrics"]), sorted(r["metrics"])
    assert "x_prepare_device_ms" not in r["metrics"]
    assert all(r["metrics"][m]["unit"] == "ms" for m in idle)
    total = sum(r["metrics"][m]["value"] for m in idle)
    n_fits = manifest.Cell(cell).traffic["traced_fits"]
    # the fits' spans tile the window but for the marks between them
    assert total * n_fits / 1e3 == pytest.approx(r["device"]["window_s"],
                                                 rel=0.02)
    assert r["metrics"]["idle_dispatch_ms"]["value"] > 0.0
    assert r["metrics"]["idle_readback_ms"]["value"] > 0.0
    # what no span of the program covers is the edge of the job, not a hole
    assert r["metrics"]["idle_unattributed_ms"]["value"] < 0.1 * total
