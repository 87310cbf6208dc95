"""The benchmark's yardstick, on the CPU: the trace reduction on a trace
written by hand, the work counts against hand counts, and BENCHMARK.json
against the files it names. No chip, no topology, nothing timed."""

import importlib
import json
import os
import re

import pytest

from perfbench import manifest, trace
from perfbench.entries import glm
from perfbench.readers import (device_idle, device_ms_per_fit,
                               fit_share_of_peak, kernel_roofline)

MS = 1_000_000  # ns

# one chip: a 100 ms while loop holding two 30 ms sweeps, a 10 ms copy and a
# 5 ms all-reduce, then 50 ms of nothing, then a 20 ms fusion
# (events are named as the profiler names them: by the whole HLO instruction)
WHILE = "%while.1 = (s32[]{:T(128)}, f32[8]{0:T(128)}) while((s32[]) %tuple.3), body=%b"
SWEEP = ('%sharded.2 = (f32[1,1]{1,0:T(1,128)}) custom-call(bf16[64,2048]{1,0:T(8,128)(2,1)}'
         ' %pad.0), custom_call_target="tpu_custom_call"')
COPY = "%copy.3 = bf16[64,2000]{1,0:T(8,128)(2,1)} copy(bf16[64,2000]{0,1} %args_0_.1)"
ALLREDUCE = "%all-reduce.4 = f32[2001]{0:T(1024)} all-reduce(f32[2001]{0} %x), to_apply=%add"
CHIP0 = [(WHILE, 0, 100 * MS), (SWEEP, 5 * MS, 30 * MS),
         (COPY, 40 * MS, 10 * MS), (SWEEP, 55 * MS, 30 * MS),
         (ALLREDUCE, 90 * MS, 5 * MS), ("fusion.5", 150 * MS, 20 * MS)]
KERNEL = ["tpu_custom_call"]
HOST = [("perfbench.fit.0", 0, 120 * MS), ("device_get", 100 * MS, 20 * MS),
        ("perfbench.between_fits", 120 * MS, 10 * MS),
        ("perfbench.fit.1", 130 * MS, 40 * MS), ("PjitFunction(f)", 131 * MS, 2 * MS)]


@pytest.fixture
def tr():
    return trace.Trace({0: CHIP0, 1: CHIP0[:1]}, HOST)


def test_busy_union_and_window(tr):
    assert tr.window == (0, 170 * MS)
    assert tr.n_fits == 2
    assert tr.busy_s(0) == pytest.approx(0.120)      # while + fusion
    assert tr.busy_s(1) == pytest.approx(0.100)
    assert tr.mean_busy_s() == pytest.approx(0.110)
    assert tr.fullest_chip() == 0


def test_pattern_time_and_allreduce(tr):
    assert tr.matching_s(0, KERNEL) == pytest.approx(0.060)
    assert tr.matching_s(0, ["^%all-reduce"]) == pytest.approx(0.005)
    assert tr.matching_s(0, ["^nothing"]) == 0.0
    # overlapping matches count once
    assert tr.matching_s(0, ["^%while", "^%copy"]) == pytest.approx(0.100)


def test_self_times_take_children_off_their_parent():
    own = trace.self_times(CHIP0)
    assert own[WHILE] == pytest.approx(0.025)         # 100 - 30 - 10 - 30 - 5
    assert own[SWEEP] == pytest.approx(0.060)
    assert own["fusion.5"] == pytest.approx(0.020)


def test_idle_gaps_are_labelled_by_the_host(tr):
    b = tr.breakdown()
    assert [n for n, _ in b["device_ops"]][:2] == [
        "custom-call:tpu_custom_call %sharded.2", "fusion fusion.5"]
    assert not any(n.startswith("while") for n, _ in b["device_ops"])
    assert trace.op_kind(COPY) == "copy" and trace.op_kind(WHILE) == "while"
    assert trace.short_name(ALLREDUCE) == "all-reduce %all-reduce.4"
    gaps = dict(b["idle_gaps"])
    assert gaps["perfbench.between_fits/no_host_span"] == pytest.approx(0.050)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_clip_cuts_events_to_the_window():
    assert trace.clip([("a", -5, 10), ("b", 8, 10), ("c", 30, 5)], (0, 12)) \
        == [("a", 0, 5), ("b", 8, 4)]


def test_readers_on_the_hand_trace(tr):
    fits = [{"evals": 2, "dispatches": 1}, {"evals": 2, "dispatches": 1}]
    run = {"trace": tr, "traced_fits": fits, "fits": fits, "fit_s": 0.085,
           "chips": 2, "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "work": {"bytes": 2e9, "flops": 4e9}}
    assert device_idle.read(run) == pytest.approx(100 * 50 / 170)
    assert device_ms_per_fit.read(run, exclude=KERNEL) \
        == pytest.approx(30.0)                         # (120 - 60) / 2 fits
    assert device_ms_per_fit.read(run, include=["^%all-reduce"]) \
        == pytest.approx(2.5)
    assert device_ms_per_fit.read(run, include=["^nothing"]) is None
    # a chip's share of one evaluation: 1e9 B at 1e11 B/s = 10 ms (bytes bind:
    # 2e9 FLOP at 1e12 = 2 ms); the sweeps took 60 ms / 4 evaluations = 15 ms
    assert kernel_roofline.read(run, patterns=KERNEL) \
        == pytest.approx(100 * 10 / 15)
    assert kernel_roofline.read(run, patterns=["^nothing"]) is None
    # 2 evaluations x 2e9 B over 0.085 s x 2 chips x 1e11 B/s
    assert fit_share_of_peak.read(run, work="bytes", peak="hbm_bytes_per_s") \
        == pytest.approx(100 * 4e9 / (0.085 * 2 * 1e11))


def test_work_of_one_evaluation_against_hand_counts():
    w = glm.work_per_eval(1_000_000, 2_000, 2)
    assert w["bytes"] == 4e9          # the stored bf16 X, read once
    assert w["flops"] == 8e9          # X b and X' r: 2 x 2 n d


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_names_files_that_exist():
    bench = manifest.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for c in configs.values():
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        cfg = manifest.load_json(manifest.ROOT, c["file"])
        importlib.import_module("perfbench.entries." + cfg["entry"])
        importlib.import_module("perfbench.reference." + cfg["reference"])
        assert set(cfg["correct"]["limits"]) == {"coef_gap", "objective_gap"}
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    for w in cells.values():
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.exists(os.path.join(
            manifest.HERE, "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]
        read, args = manifest.reader_of(m["name"])
        assert callable(read) and isinstance(args, dict)
    for name, w in cells.items():
        cell = manifest.Cell(name)
        assert len(cell.end_to_end()) >= 2 and cell.per_layer()
    assert len(json.dumps(bench)) < 64 * 1024


def test_unknown_device_kind_is_an_error():
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        manifest.peaks("TPU v9")
