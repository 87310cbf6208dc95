"""The whole fit's share of a peak of the chips it holds: the work its
evaluations require (``work`` names bytes or flops) over the window's
``fit_s``, the chips and the peak."""


def read(run, work: str, peak: str):
    fits, peaks = run["fits"], run["peaks"]
    if not fits or peaks is None or not run["fit_s"]:
        return None
    evals = sum(f["evals"] for f in fits) / len(fits)
    return 100.0 * evals * run["work"][work] / (
        run["fit_s"] * run["chips"] * peaks[peak])
