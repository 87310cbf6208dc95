"""The cell ``glr_binomial_irls_fit`` rehearsed on the CPU in a process of
its own: its traced result line holds every metric the cell lists that a
platform with no device can give, ``correct`` comes out false when half
the rows are left out, and the control fails. Tiny sizes; nothing here is
a measurement."""

import json

from perfbench import manifest
from perfbench.tests.test_run import SIZE, python, result_of, run_cell

CELL = "glr_binomial_irls_fit"
#: what only a chip's trace and peak table can give
NEEDS_A_CHIP = {"gramian_roofline", "nongramian_device_ms", "fit_mfu_pct",
                "fit_hbm_pct", "device_idle_pct"}


def test_traced_result_line_holds_every_metric_of_the_cell():
    r = result_of(run_cell(CELL, 1))
    cell = manifest.Cell(CELL)
    listed = {m["name"] for m in cell.per_layer()}
    assert NEEDS_A_CHIP < listed
    assert set(r["metrics"]) == listed - NEEDS_A_CHIP
    assert r["correct"] is True, r["compared"]
    passes = r["metrics"]["evals_per_fit"]["value"]
    assert 4 <= passes < 25 and passes == int(passes)
    assert r["metrics"]["dispatches_per_fit"]["value"] == passes + 1
    assert {"idle_host_irls_ms", "idle_host_solve_ms", "idle_fit_edges_ms",
            "idle_dispatch_ms", "idle_readback_ms",
            "idle_unattributed_ms"} <= set(r["metrics"])
    assert {"idle_host_optim_ms", "glm_sweep_roofline", "nonsweep_device_ms",
            "x_prepare_device_ms"}.isdisjoint(listed)


def test_untraced_result_line_reports_fit_s_and_setup_s_only():
    r = result_of(run_cell(CELL, 0, seed=2 ** 31 + 33))
    assert set(r["metrics"]) == {"fit_s", "setup_s"}
    assert r["correct"] is True, r["compared"]


def test_half_the_rows_left_out_is_not_correct():
    code = f"""
import sys
import numpy as np
from perfbench import run
from perfbench.entries import glm
def half(ctx, x, y, host_labels):
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    n, d = x.shape
    w = np.ones(n, np.float32)
    w[n // 2:] = 0.0
    return InstanceDataset(ctx, x, y,
                           ctx.mesh_runtime.device_put_sharded_rows(w), n, d)
glm.instance_dataset = half
sys.exit(run.main(["--workload", {CELL!r}, "--seed", "5", "--seconds", "0.3",
                   "--trace", "0", "--rehearse", {SIZE!r}]))
"""
    r = result_of(python(code))
    assert r["correct"] is False, r["compared"]
    for name in ("coef_gap", "objective_gap"):
        assert r["compared"][name]["value"] > r["compared"][name]["limit"]


def test_the_control_and_every_planted_fault_fail_coef_gap():
    proc = python(["perfbench.control", "--workload", CELL, "--seeds", "7",
                   "--rehearse", SIZE])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    limit = manifest.Cell(CELL).limits["coef_gap"]
    for fault in ("control", "half_batch", "altered", "unchanged"):
        assert line[fault]["coef_gap"] > limit, (fault, line[fault])
