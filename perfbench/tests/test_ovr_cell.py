"""The cell ``ovr_lr_mnist8m_fit`` rehearsed on the CPU in a process of its
own: its result line comes out ``correct`` with every metric a CPU can
read, ``frozen_lane_evals_pct`` is the arithmetic the entry states, the
reference agrees with ``reference/logistic_l2.py`` class by class, and fed
one-piece margins (the coefficient stack rounded to bfloat16 in the margins,
what the XLA twin of the sweep does on a bf16 X) it does not come out
correct. Tiny sizes; nothing here is a measurement."""

import json

import pytest

from perfbench import manifest
from perfbench.tests.test_run import SIZE, python, result_of, run_cell

CELL = "ovr_lr_mnist8m_fit"
#: what only a chip's trace and peak table can give
NEEDS_A_CHIP = {"glm_sweep_roofline", "nonsweep_device_ms", "fit_mfu_pct",
                "fit_hbm_pct", "device_idle_pct"}


def test_traced_result_line_holds_every_metric_of_the_cell():
    r = result_of(run_cell(CELL, 1))
    cell = manifest.Cell(CELL)
    listed = {m["name"] for m in cell.per_layer()}
    assert NEEDS_A_CHIP < listed
    assert {"frozen_lane_evals_pct", "evals_per_iteration",
            "idle_host_optim_ms", "stagings_per_fit"} < listed
    assert set(r["metrics"]) == listed - NEEDS_A_CHIP
    assert r["correct"] is True, r["compared"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["dispatches_per_fit"] < m["evals_per_fit"]
    assert m["stagings_per_fit"] == 0
    assert 0.0 <= m["frozen_lane_evals_pct"] < 100.0
    assert {"gramian_roofline", "idle_host_solve_ms", "idle_host_irls_ms",
            "kmeans_step_roofline", "x_prepare_device_ms"}.isdisjoint(listed)


def test_untraced_result_line_reports_fit_s_and_setup_s_only():
    r = result_of(run_cell(CELL, 0, seed=2 ** 31 + 41))
    assert set(r["metrics"]) == {"fit_s", "setup_s"}
    assert r["correct"] is True, r["compared"]


@pytest.mark.parametrize("classes,evals,lane_evals,want", [
    (10, 14, 140, 0.0),        # every lane asked for every sweep
    (10, 14, 119, 15.0),       # 21 of 140 computed for lanes that had stopped
    (3, 12, 18, 50.0),
    (10, 0, 0, 0.0)])          # no sweep, nothing frozen
def test_frozen_lane_share_is_computed_over_wanted(classes, evals,
                                                   lane_evals, want):
    from perfbench.entries import one_vs_rest
    assert one_vs_rest.frozen_lane_evals_pct(classes, evals, lane_evals) \
        == pytest.approx(want)


def test_the_metric_file_reads_the_entrys_counter():
    from perfbench.readers import count_per_fit
    read, args = manifest.reader_of("frozen_lane_evals_pct")
    assert read is count_per_fit.read
    fits = [{"frozen_lane_evals_pct": 10.0}, {"frozen_lane_evals_pct": 20.0}]
    assert read({"fits": fits}, **args) == pytest.approx(15.0)
    # a program without the counter: nothing to read, nothing raised
    assert read({"fits": [{"evals": 3}]}, **args) is None
    spec, = [m for m in manifest.benchmark()["per_layer"]
             if m["name"] == "frozen_lane_evals_pct"]
    assert spec["workloads"] == [CELL] and spec["moves"] == "fit_s"


def test_the_work_of_an_evaluation_is_one_read_of_x_for_all_models():
    from perfbench.entries import one_vs_rest
    work = one_vs_rest.work_per_eval(8_100_000, 784, 2)
    assert work["bytes"] == 8_100_000 * 784 * 2           # ONE read, not K
    assert work["flops"] == 4.0 * 8_100_000 * 784 * 10


READINGS = f"""
import json
import numpy as np
import jax.numpy as jnp
from perfbench import class_labels, judge, manifest, run
from perfbench.reference import logistic_l2
cell = manifest.Cell({CELL!r})
ctx = run.make_context(cell, True)
mesh = ctx.mesh_runtime.mesh
params = cell.config["estimator"]["params"]
spec = class_labels.spec(cell.config["name"])
out = {{}}
x, y, _ = run.make_data(cell, ctx, 5, run.rehearsal_size({SIZE!r}))
data = (x, y, mesh, run.ROW_AXES)
ref = cell.reference.fit(data, params)
prob = ref["problem"]
k, d = prob.k, prob.d
labels = class_labels.of(x, mesh, run.ROW_AXES, **spec)
gaps, total = [], 0.0
for j in (0, k - 1):
    one = logistic_l2.fit((x, (labels == j).astype(jnp.float32), mesh,
                           run.ROW_AXES), params)
    want = np.append(one["coef"], one["intercept"])
    got = np.append(ref["coef"][j * d:(j + 1) * d], ref["coef"][k * d + j])
    gaps.append(float(np.linalg.norm(got - want) / np.linalg.norm(want)))
out["class_by_class_gap"] = max(gaps)
out["objective_is_the_sum"] = [ref["objective"],
                               float(prob.objective_of(ref["coef"][None])[0])]
one = cell.reference.fit(data, params, margin_bits=7)
got = judge.compare([one], ref, cell.limits)
out["one_piece"] = {{k: [v["value"], v["limit"], v["ok"]]
                    for k, v in got.items()}}
print(json.dumps(out))
"""


def test_reference_is_logistic_l2_class_by_class_and_one_piece_is_not_correct():
    proc = python(READINGS)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # float32 sums at `highest` on both sides, in two orders
    assert out["class_by_class_gap"] < 5e-6, out
    a, b = out["objective_is_the_sum"]
    assert abs(a - b) <= 1e-6 * abs(a)
    assert not all(ok for _, _, ok in out["one_piece"].values()), out
