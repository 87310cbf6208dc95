"""The benchmark's command, rehearsed on the CPU in a process of its own (a
context is one per process): the result line's keys, the refusal without a
chip, and ``correct`` coming out false with the timed path broken underneath
and for the control. Tiny sizes; nothing here is a measurement."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import manifest

ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
       "JAX_ENABLE_X64": "0"}
SIZE = "4096x64"


def python(code_or_args, timeout=300):
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str)
            else [sys.executable, "-m", *code_or_args])
    return subprocess.run(args, cwd=manifest.ROOT, env=ENV, text=True,
                          capture_output=True, timeout=timeout)


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_cell(cell, trace, seed=3):
    return python(["perfbench.run", "--workload", cell, "--seed", str(seed),
                   "--seconds", "0.3", "--trace", str(trace),
                   "--rehearse", SIZE])


def test_result_line_of_an_untraced_run():
    proc = run_cell("lr_epsilon_fit", 0, seed=2 ** 31 + 11)
    r = result_of(proc)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared" and "rehearsal" in r
    assert set(r["metrics"]) == {"fit_s", "fit_p95_s", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in r["metrics"].values())
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert r["attempted"] >= 1 and r["failed"] == 0 and r["correct"] is True
    assert set(r["compared"]) == {"coef_gap", "objective_gap"}
    # each number compared stands beside its limit at the end of stderr
    tail = proc.stderr.strip().splitlines()[-2:]
    assert all("compared" in line and "limit" in line for line in tail)


def test_result_line_of_a_traced_run():
    r = result_of(run_cell("linreg_enet_fit", 1))
    bench = manifest.benchmark()
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) <= {m["name"] for m in bench["per_layer"]}
    assert r["metrics"]["evals_per_fit"]["value"] \
        == r["metrics"]["dispatches_per_fit"]["value"] > 1
    # no device, no peak table: no share of a peak is made up
    assert not {"fit_mfu_pct", "fit_hbm_pct", "glm_sweep_roofline"} \
        & set(r["metrics"])
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_without_a_chip_there_is_no_result():
    proc = python(["perfbench.run", "--workload", "lr_epsilon_fit", "--seed",
                   "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0 and proc.stdout.strip() == ""


BROKEN = {
    # a step that returns its state unchanged: no iteration is made
    "unchanged": ("lr_epsilon_fit", """
real = entry.estimator
entry.estimator = lambda params: real({**params, "maxIter": 0})
entry.assert_path = lambda *a, **k: None
"""),
    # half of every shard's rows left out, the mean taken over the rest
    "half_batch": ("linreg_enet_fit", """
import numpy as np
from perfbench.entries import glm
def half(ctx, x, y, host_labels):
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    n, d = x.shape
    w = np.ones(n, np.float32)
    w[n // 2:] = 0.0
    ds = InstanceDataset(ctx, x, y,
                         ctx.mesh_runtime.device_put_sharded_rows(w), n, d)
    return ds.attach_host_labels(np.asarray(y).astype(np.float64),
                                 w.astype(np.float64))
glm.instance_dataset = half
"""),
    # an answer altered where it is produced
    "altered": ("lr_epsilon_fit", """
import numpy as np
real = entry.fit
def fit(est, ds, ctx):
    a = real(est, ds, ctx)
    j = int(np.argmax(np.abs(a["coef"])))
    a["coef"][j] = -a["coef"][j]
    return a
entry.fit = fit
"""),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_timed_path_is_not_correct(fault):
    cell, patch = BROKEN[fault]
    code = f"""
import sys
from perfbench import manifest, run
entry = manifest.Cell({cell!r}).entry
{patch}
sys.exit(run.main(["--workload", {cell!r}, "--seed", "5", "--seconds", "0.3",
                   "--trace", "0", "--rehearse", {SIZE!r}]))
"""
    r = result_of(python(code))
    assert r["correct"] is False, r["compared"]
    assert any(c["value"] > c["limit"] for c in r["compared"].values())


@pytest.mark.parametrize("cell", ["lr_epsilon_fit", "linreg_enet_fit"])
def test_the_control_is_not_correct(cell):
    """The reference computed from float8 X, in the program's place."""
    proc = python(["perfbench.control", "--workload", cell, "--seeds", "7",
                   "--rehearse", SIZE])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    limits = manifest.Cell(cell).limits
    assert any(line["control"][k] > limits[k] for k in limits), line
