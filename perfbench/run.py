"""One run of one cell:

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (context, data from the seed, two warm-up fits, the proof of the
path), then the window of whole fits back to back, then — with ``--trace 1``
— a short traced window, then the comparison with the plain reference. The
last line of standard output is the result. ``--rehearse ROWS`` runs the same
code on the host platform at a tiny size and marks its line as no
measurement; there is no fallback to it.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, as near as Python gets

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import statistics                  # noqa: E402
import sys                         # noqa: E402

from perfbench import judge, manifest  # noqa: E402

#: the host clock is off by half a millisecond: nothing shorter is timed
MIN_TIMED_S = 0.25
TRACE_DIR = os.path.join(manifest.HERE, ".trace")
REHEARSAL_DTYPE = "float32"
#: the mesh axes the program shards rows over
ROW_AXES = ("replica", "data")


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    p = argparse.ArgumentParser(prog="perfbench.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", type=rehearsal_size, default=None,
                   metavar="ROWS[xCOLS]",
                   help="rows a chip (and a width) on the HOST platform: a "
                        "rehearsal of the control flow, never a measurement")
    return p.parse_args(argv)


def rehearsal_size(text: str):
    rows, _, cols = text.partition("x")
    return int(rows), (int(cols) if cols else None)


def sizes(cell, rehearse):
    """``(rows a chip, columns, stored type)``: the cell's own, or the
    rehearsal's."""
    rows, cols = rehearse or (None, None)
    return (rows or int(cell.traffic["rows_per_chip"]),
            cols or int(cell.config["n_features"]),
            REHEARSAL_DTYPE if rehearse else cell.config["data_dtype"])


def make_data(cell, ctx, seed: int, rehearse):
    """``(x, y, stored type)`` of the cell on ``ctx``'s mesh: the traffic's
    rows, in the seed's order."""
    from perfbench import datagen
    rows, n_cols, x_dtype = sizes(cell, rehearse)
    data = cell.config["data"]
    x, y = datagen.generate(ctx.mesh_runtime.mesh, ROW_AXES,
                            int(cell.traffic["data_seed"]), seed, rows,
                            n_cols, data["task"], data["label_noise"],
                            x_dtype)
    return x, y, x_dtype


def devices_or_exit(cell, rehearse: bool):
    """The cell's devices, or exit without a result: a cell measures a chip
    count, so fewer chips — or another platform — is no run of it."""
    import jax
    devices = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want or len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} x {want}; jax reports "
            f"{len(devices)} x {devices[0].platform}")
        raise SystemExit(3)
    if not rehearse and len(devices) != cell.chips:
        log(f"{cell.name} is sized for {cell.chips} chip(s); this machine "
            f"has {len(devices)} and the program takes them all")
        raise SystemExit(3)
    return devices[:cell.chips]


def make_context(cell, rehearse: bool):
    import jax
    from cycloneml_tpu import CycloneConf, CycloneContext
    conf = CycloneConf().set("cyclone.app.name", "perfbench-" + cell.name)
    for k, v in cell.config.get("cyclone_conf", {}).items():
        conf = conf.set(k, str(v))
    if rehearse:
        # the host platform has no default master, and its sweep is the XLA
        # twin, which rounds the coefficients to a narrow data tier (the
        # Pallas kernel on the chip does not): a rehearsal stores float32,
        # so that its comparison means what a run's does
        conf = conf.set("cyclone.master", f"local-mesh[{cell.chips}]") \
                   .set("cyclone.data.dtype", REHEARSAL_DTYPE)
    ctx = CycloneContext(conf)
    rt = ctx.mesh_runtime
    if rt.n_devices != cell.chips:
        raise AssertionError(f"mesh has {rt.n_devices} devices, the cell "
                             f"{cell.chips}")
    if not rehearse:
        from cycloneml_tpu import mesh as mesh_mod
        want = mesh_mod.compilation_cache_dir()
        if jax.config.jax_compilation_cache_dir != want:
            raise AssertionError(
                f"compile cache at {jax.config.jax_compilation_cache_dir}, "
                f"expected {want}")
        # every program, the reference's too, is found again by the next run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return ctx


def timed_fit(cell, est, ds, ctx, label: str) -> dict:
    import jax
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(label):
        answer = cell.entry.fit(est, ds, ctx)
    answer["wall_s"] = time.perf_counter() - t0
    return answer


def window(cell, est, ds, ctx, seconds: float):
    """Whole fits back to back from one caller until ``seconds`` have
    passed; every fit that starts is finished and counted."""
    fits, failed = [], 0
    start = time.perf_counter()
    while True:
        try:
            fits.append(timed_fit(cell, est, ds, ctx, "perfbench.fit"))
        except Exception as e:          # counted, and fails the run
            failed += 1
            log(f"fit failed: {type(e).__name__}: {e}")
            if failed >= 3:
                break
        end = time.perf_counter()
        if end - start >= seconds:
            break
    return fits, failed, end - start


def traced_window(cell, est, ds, ctx, n_fits: int):
    """``n_fits`` more fits under the profiler, reduced to a ``Trace``."""
    import jax
    from perfbench import trace
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    try:
        fits = []
        for k in range(n_fits):
            fits.append(timed_fit(cell, est, ds, ctx, f"perfbench.fit.{k}"))
            if k + 1 < n_fits:
                with jax.profiler.TraceAnnotation("perfbench.between_fits"):
                    pass
    finally:
        jax.profiler.stop_trace()
    path = trace.newest_xplane(TRACE_DIR)
    if os.environ.get("PERFBENCH_TRACE_NAMES"):
        os.makedirs(os.path.dirname(os.environ["PERFBENCH_TRACE_NAMES"])
                    or ".", exist_ok=True)
        with open(os.environ["PERFBENCH_TRACE_NAMES"], "w") as f:
            f.write(trace.name_table(path))
    tr = trace.load(path)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return fits, tr


def end_to_end(cell, fits, elapsed: float, setup_s: float) -> dict:
    walls = [f["wall_s"] for f in fits]
    values = {"setup_s": setup_s}
    if fits:
        values["fit_s"] = elapsed / len(fits)
        values["fit_p95_s"] = (statistics.quantiles(walls, n=20)[18]
                               if len(walls) >= 2 else walls[0])
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end() if m["name"] in values}


def per_layer(cell, run: dict) -> dict:
    out = {}
    for m in cell.per_layer():
        read, args = manifest.reader_of(m["name"])
        value = read(run, **args)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = manifest.Cell(args.workload)
    rehearse = args.rehearse is not None
    devices = devices_or_exit(cell, rehearse)
    import jax

    ctx = make_context(cell, rehearse)
    rt = ctx.mesh_runtime
    cfg, traffic = cell.config, cell.traffic
    log(f"context up at {time.perf_counter() - _T0:.3f} s")
    x, y, x_dtype = make_data(cell, ctx, args.seed, args.rehearse)
    ds = cell.entry.dataset(ctx, x, y)
    log(f"data on the devices at {time.perf_counter() - _T0:.3f} s")
    est = cell.entry.estimator(cfg["estimator"]["params"])
    for k in range(int(traffic["warmup_fits"])):
        warm = timed_fit(cell, est, ds, ctx, "perfbench.warmup")
        log(f"warm-up fit {k}: {warm['wall_s']:.3f} s, {warm['evals']} "
            f"evaluations, {warm['dispatches']} dispatches")
    cell.entry.assert_path(ctx, ds, warm, x_dtype, native=not rehearse)
    setup_s = time.perf_counter() - _T0

    fits, failed, elapsed = window(cell, est, ds, ctx, args.seconds)
    if fits and elapsed < MIN_TIMED_S and not rehearse:
        raise AssertionError(f"the window spans {elapsed:.3f} s: too short "
                             f"for the host clock")
    walls = sorted(f["wall_s"] for f in fits)
    log(f"set-up {setup_s:.3f} s; window: {len(fits)} fits in {elapsed:.3f} s"
        f", {failed} failed; fit wall median {walls[len(walls) // 2]:.4f} s"
        f", longest {walls[-1]:.4f} s" if fits else "window: no fit completed")
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}

    result_metrics = end_to_end(cell, fits, elapsed, setup_s)
    breakdown = None
    if args.trace:
        traced, tr = traced_window(cell, est, ds, ctx,
                                   int(traffic["traced_fits"]))
        device["busy_s"] = tr.mean_busy_s()
        if not rehearse and not device["busy_s"] > 0.0:
            raise AssertionError("no device operation in the traced window")
        device["window_s"] = tr.window_s
        run = {"cell": cell, "fits": fits, "traced_fits": traced,
               "trace": tr, "fit_s": elapsed / max(len(fits), 1),
               "work": cell.entry.work_per_eval(*x.shape, x.dtype.itemsize),
               "chips": cell.chips,
               "peaks": None if rehearse else manifest.peaks(
                   devices[0].device_kind)}
        result_metrics = per_layer(cell, run)
        breakdown = tr.breakdown()

    # the program's state goes before the reference runs: only the
    # benchmark's own X and y stay on the devices
    del ds, est
    ctx.stop()
    t_ref = time.perf_counter()
    ref = cell.reference.fit((x, y, rt.mesh, ROW_AXES),
                             cfg["estimator"]["params"])
    compared = judge.compare(fits, ref, cell.limits)
    log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s")
    correct = failed == 0 and all(c["ok"] for c in compared.values())

    result = {"correct": correct, "attempted": len(fits) + failed,
              "failed": failed, "metrics": result_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if rehearse:
        result["rehearsal"] = ("host platform, tiny size: no number here is "
                               "a measurement")
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in compared.items()}
    sys.stdout.flush()
    for name, c in compared.items():
        log(f"compared {name} = {c['value']:.6g} (limit {c['limit']:.6g}) "
            f"{'ok' if c['ok'] else 'OVER'}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
