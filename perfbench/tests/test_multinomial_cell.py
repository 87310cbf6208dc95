"""The cell ``lr_multinomial_mnist8m_fit`` rehearsed on the CPU in a process
of its own: its result line comes out ``correct``, the reference fed
one-piece margins (the coefficient matrix rounded to bfloat16 in the
margins, what the XLA twin of the sweep does on a bf16 X) does not, and the
label function gives the same rows the same labels in any order. Tiny
sizes; nothing here is a measurement."""

import json

from perfbench import manifest
from perfbench.tests.test_run import SIZE, python, result_of, run_cell

CELL = "lr_multinomial_mnist8m_fit"
#: what only a chip's trace and peak table can give
NEEDS_A_CHIP = {"glm_sweep_roofline", "nonsweep_device_ms", "fit_mfu_pct",
                "fit_hbm_pct", "device_idle_pct"}


def test_traced_result_line_holds_every_metric_of_the_cell():
    r = result_of(run_cell(CELL, 1))
    cell = manifest.Cell(CELL)
    listed = {m["name"] for m in cell.per_layer()}
    assert NEEDS_A_CHIP < listed and "evals_per_iteration" in listed
    assert set(r["metrics"]) == listed - NEEDS_A_CHIP
    assert r["correct"] is True, r["compared"]
    evals = r["metrics"]["evals_per_fit"]["value"]
    assert r["metrics"]["dispatches_per_fit"]["value"] < evals
    assert 1.0 <= r["metrics"]["evals_per_iteration"]["value"] < 2.0
    assert {"gramian_roofline", "idle_host_solve_ms", "idle_host_irls_ms",
            "x_prepare_device_ms"}.isdisjoint(listed)


def test_untraced_result_line_reports_fit_s_and_setup_s_only():
    r = result_of(run_cell(CELL, 0, seed=2 ** 31 + 35))
    assert set(r["metrics"]) == {"fit_s", "setup_s"}
    assert r["correct"] is True, r["compared"]


READINGS = f"""
import json
import numpy as np
from perfbench import class_labels, judge, manifest, run
cell = manifest.Cell({CELL!r})
ctx = run.make_context(cell, True)
mesh = ctx.mesh_runtime.mesh
params = cell.config["estimator"]["params"]
spec = class_labels.spec(cell.config["name"])
out = {{}}
x, y, _ = run.make_data(cell, ctx, 5, run.rehearsal_size({SIZE!r}))
labels = np.asarray(class_labels.of(x, mesh, run.ROW_AXES, **spec))
order = np.random.RandomState(6).permutation(len(labels))
moved = np.asarray(class_labels.of(x[order], mesh, run.ROW_AXES, **spec))
out["moved_rows_keep_their_labels"] = bool(np.all(moved == labels[order]))
out["classes_seen"] = len(set(labels.tolist()))
data = (x, y, mesh, run.ROW_AXES)
ref = cell.reference.fit(data, params)
one = cell.reference.fit(data, params, margin_bits=7)
got = judge.compare([one], ref, cell.limits)
out["one_piece"] = {{k: [v["value"], v["limit"], v["ok"]]
                    for k, v in got.items()}}
print(json.dumps(out))
"""


def test_one_piece_margins_are_not_correct_and_labels_follow_the_rows():
    proc = python(READINGS)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # a label is a function of its row: another order moves it with the row
    assert out["moved_rows_keep_their_labels"] and out["classes_seen"] == 10
    assert not all(ok for _, _, ok in out["one_piece"].values()), out
