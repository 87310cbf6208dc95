"""A sweep kernel's share of its roofline: the least time one chip could
take for its share of one evaluation's required work (the larger of bytes
over peak bandwidth and FLOP over peak rate), over the device time the
matching operations took per evaluation, on the busiest chip."""


def read(run, patterns):
    tr, peaks = run["trace"], run["peaks"]
    evals = sum(f["evals"] for f in run["traced_fits"])
    if not evals or not tr.chips or peaks is None:
        return None
    took = tr.matching_s(tr.fullest_chip(), patterns) / evals
    if took == 0.0:
        return None
    least = max(run["work"]["bytes"] / peaks["hbm_bytes_per_s"],
                run["work"]["flops"] / peaks["flops_per_s"]) / run["chips"]
    return 100.0 * least / took
