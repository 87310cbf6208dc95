"""Readings for the limits of ``correct``, many seeds in one process:

    python3 -m perfbench.control --workload <cell> --seeds 1,2,3 [--sound 1] [--rehearse ROWS[xCOLS]]

For every seed it makes the cell's data and solves the plain reference, then
puts in the program's place, and judges exactly as a run would:

- ``control``: the reference computed from X rounded to 8-bit e4m3 (the
  nearest precision below the bfloat16 the configurations state);
- the faults a fit can have, planted in the reference: ``half_batch`` (half
  of every shard's rows left out, the mean taken over the rest),
  ``no_exchange`` (only the first chip's partial sums; cells on several chips),
  ``altered`` (one coefficient's sign flipped where the answer is produced)
  and ``unchanged`` (the fit returns its starting point);
- with ``--sound 1``, the program itself: one warm fit per seed.

One JSON line per seed on standard output. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from perfbench import judge, manifest, run as runner


def planted(cell, data, ref: dict) -> dict:
    """``{name: answer}`` of the control and of every fault of this cell."""
    params = cell.config["estimator"]["params"]
    x, y, mesh, axes = data
    rows = x.shape[0] // cell.chips
    out = {"control": cell.reference.fit(data, params, quant="fp8"),
           "half_batch": cell.reference.fit(data, params,
                                            rows_used=rows // 2)}
    if cell.chips > 1:
        out["no_exchange"] = cell.reference.fit(data, params, shards_used=1)
    prob = ref["problem"]
    flipped = np.array(ref["coef"], np.float64)
    j = int(np.argmax(np.abs(flipped)))
    flipped[j] = -flipped[j]
    out["altered"] = {"coef": flipped, "intercept": ref["intercept"],
                      "objective": ref["objective"]}
    y_mean = float(np.mean(np.asarray(y, np.float64)))
    start = (np.log(y_mean / (1.0 - y_mean))
             if cell.config["data"]["task"] == "classification" else y_mean)
    zero = np.zeros_like(flipped)
    out["unchanged"] = {
        "coef": zero, "intercept": start,
        "objective": float(prob.objective_of(zero[None], np.array([start]))[0])}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="perfbench.control")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--sound", type=int, default=0)
    p.add_argument("--planted", type=int, default=1 << 30,
                   help="how many of the leading seeds get the control's "
                        "and the faults' readings")
    p.add_argument("--rehearse", type=runner.rehearsal_size, default=None)
    args = p.parse_args(argv)
    cell = manifest.Cell(args.workload)
    rehearse = args.rehearse is not None
    runner.devices_or_exit(cell, rehearse)
    ctx = runner.make_context(cell, rehearse)
    mesh = ctx.mesh_runtime.mesh
    cfg = cell.config
    limits = {k: float("inf") for k in cell.limits}
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        x, y, _ = runner.make_data(cell, ctx, seed, args.rehearse)
        data = (x, y, mesh, runner.ROW_AXES)
        answers = {}
        if args.sound:
            ds = cell.entry.dataset(ctx, x, y)
            est = cell.entry.estimator(cfg["estimator"]["params"])
            cell.entry.fit(est, ds, ctx)
            answers["sound"] = cell.entry.fit(est, ds, ctx)
            del ds, est
        ref = cell.reference.fit(data, cfg["estimator"]["params"])
        if k < args.planted:
            answers.update(planted(cell, data, ref))
        line = {"workload": cell.name, "seed": seed}
        for name, ans in answers.items():
            got = judge.compare([ans], ref, limits)
            line[name] = {k: v["value"] for k, v in got.items()}
            if name == "sound":
                line[name].update(evals=ans["evals"],
                                  iterations=ans["iterations"])
        print(json.dumps(line), flush=True)
        del x, y, data, ref, answers
    ctx.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
