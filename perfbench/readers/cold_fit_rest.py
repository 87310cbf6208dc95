"""What the first fit of the process cost that is neither staging nor
fitting: ``first_s`` of the ``job`` total, less ``first_s`` of the per-job
staging total (the seconds of the ``staging`` spans beneath that same first
job), less the window's ``fit_s``. A later fit that re-stages does not move
it. The staging metrics are whole-process totals, so they, this and ``fit_s``
sum to the cold fit exactly when nothing was staged after the first fit; the
shortfall is what later jobs re-staged (``stagings_per_fit`` names it).
``None`` where the program's tracer keeps no per-job total."""

from perfbench.readers import span_total


def read(run, job: str, staged: str):
    cold = span_total.read(run, job, "first_s")
    first_staged = span_total.read(run, staged, "first_s")
    if cold is None or first_staged is None or not run["fit_s"]:
        return None
    return cold - first_staged - run["fit_s"]
