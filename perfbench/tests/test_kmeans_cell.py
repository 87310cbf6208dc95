"""The cell ``kmeans_k1000_lloyd_fit`` rehearsed on the CPU in a process of
its own: its result line comes out ``correct`` and holds the cell's metrics,
the planted faults and the reference fed one-piece centres (the centres
rounded to bfloat16 in the product) do not pass the rehearsal's own limits,
and a point is a function of its row. Tiny sizes; nothing here is a
measurement."""

import json

from perfbench import manifest
from perfbench.tests.test_run import python, result_of

CELL = "kmeans_k1000_lloyd_fit"
SIZE = "4096x128"
#: what only a chip's trace and peak table can give
NEEDS_A_CHIP = {"kmeans_step_roofline", "nonkmeans_device_ms", "fit_mfu_pct",
                "fit_hbm_pct", "device_idle_pct"}


def run_cell(trace, seed=3):
    return python(["perfbench.run", "--workload", CELL, "--seed", str(seed),
                   "--seconds", "0.3", "--trace", str(trace),
                   "--rehearse", SIZE])


def test_traced_result_line_holds_every_metric_of_the_cell():
    r = result_of(run_cell(1))
    cell = manifest.Cell(CELL)
    listed = {m["name"] for m in cell.per_layer()}
    assert NEEDS_A_CHIP < listed and "idle_host_lloyd_ms" in listed
    assert set(r["metrics"]) == listed - NEEDS_A_CHIP
    assert r["correct"] is True, r["compared"]
    steps = r["metrics"]["evals_per_fit"]["value"]
    assert 2 <= steps <= 20
    assert steps <= r["metrics"]["dispatches_per_fit"]["value"] <= steps + 1
    assert {"glm_sweep_roofline", "nonsweep_device_ms", "idle_host_optim_ms",
            "gramian_roofline", "x_prepare_device_ms"}.isdisjoint(listed)


def test_untraced_result_line_reports_fit_s_and_setup_s_only():
    r = result_of(run_cell(0, seed=2 ** 31 + 39))
    assert set(r["metrics"]) == {"fit_s", "setup_s"}
    assert r["correct"] is True, r["compared"]


READINGS = f"""
import json
import numpy as np
from perfbench import control, judge, kmeans_points, manifest, run
cell = manifest.Cell({CELL!r})
ctx = run.make_context(cell, True)
mesh = ctx.mesh_runtime.mesh
params = cell.config["estimator"]["params"]
spec = kmeans_points.spec(cell.config["name"])
out = {{}}
x, y, _ = run.make_data(cell, ctx, 5, run.rehearsal_size({SIZE!r}))
pts = np.asarray(kmeans_points.points(x, mesh, run.ROW_AXES, **spec))
order = np.random.RandomState(6).permutation(len(pts))
moved = np.asarray(kmeans_points.points(x[order], mesh, run.ROW_AXES, **spec))
out["moved_rows_keep_their_points"] = bool(np.all(moved == pts[order]))
which = np.asarray(kmeans_points.cluster_of(x, spec["k"]))
out["clusters_seen"] = len(set(which.tolist()))
data = (x, y, mesh, run.ROW_AXES)
ref = cell.reference.fit(data, params)
faults = control.planted(cell, data, ref)
faults["one_piece"] = cell.reference.fit(data, params, centre_bits=7)
out["faults"] = {{
    name: {{k: [v["value"], v["limit"], v["ok"]] for k, v in
           judge.compare([ans], ref, {{"coef_gap": 1e-6,
                                       "objective_gap": 1e-6}}).items()}}
    for name, ans in faults.items()}}
print(json.dumps(out))
"""


def test_faults_and_one_piece_centres_are_not_correct():
    proc = python(READINGS)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # a point is a function of its row: another order moves it with the row
    assert out["moved_rows_keep_their_points"]
    assert out["clusters_seen"] > 900
    assert set(out["faults"]) == {"control", "half_batch", "altered",
                                  "unchanged", "one_piece"}
    for name, got in out["faults"].items():
        assert not all(ok for _, _, ok in got.values()), (name, got)
