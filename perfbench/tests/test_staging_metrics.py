"""The set-up metrics, on the CPU: the three readers of the program's tracer
(``span_total``, ``cold_fit_rest``, ``ring_spans_per_job``) against a tracer
filled by hand, what they return where the program has no tracer or one that
keeps no totals (the parent of the PR that added them), and the eight metric
files by name. Nothing timed."""

import pytest

from cycloneml_tpu.observe import tracing
from perfbench import manifest
from perfbench.readers import cold_fit_rest, ring_spans_per_job, span_total

NEW = ["context_start_s", "cold_fit_s", "staging_trace_s", "staging_lower_s",
       "staging_compile_s", "persistent_cache_misses", "cold_fit_unstaged_s",
       "stagings_per_fit"]


@pytest.fixture
def tracer():
    tracing.disable()
    yield tracing.enable(max_spans=64)
    tracing.disable()


def fit(tracer, t0, staged):
    """One job span from ``t0`` lasting 1 s + what it staged; each staged
    step is a span of its own beneath a phase, and the job's account holds
    its seconds as ``run_job``'s does."""
    at = t0
    job = tracer.reserve_span_id()
    prepare = tracer.reserve_span_id()
    account = tracer.open_staging_account()
    for step, seconds in staged:
        tracer.record_span("staging", step, t0=at, t1=at + seconds,
                           parent=prepare, fun="f")
        account[step] += seconds
        at += seconds
    tracer.close_staging_account(account)
    tracer.record_span("phase", "fit.prepare", t0=t0, t1=at, parent=job,
                       span_id=prepare)
    tracer.record_span("job", "LinearRegression.fit", t0=t0, t1=at + 1.0,
                       span_id=job)
    return at + 1.0


def value(name, run):
    read, args = manifest.reader_of(name)
    return read(run, **args)


def test_readers_on_a_tracer_filled_by_hand(tracer):
    tracer.record_span("phase", "context.start", t0=0.0, t1=0.25)
    at = fit(tracer, 1.0, [("trace", 0.5), ("lower", 0.25), ("compile", 2.0)])
    for _ in range(3):
        at = fit(tracer, at, [])
    run = {"fit_s": 1.0, "traced_fits": [{}, {}, {}]}
    assert value("context_start_s", run) == 0.25
    assert value("cold_fit_s", run) == 3.75
    assert (value("staging_trace_s", run), value("staging_lower_s", run),
            value("staging_compile_s", run)) == (0.5, 0.25, 2.0)
    # 0 is a reading: the total is there from the start
    assert value("persistent_cache_misses", run) == 0
    assert value("cold_fit_unstaged_s", run) == pytest.approx(0.0)
    assert value("stagings_per_fit", run) == 0.0
    # nothing staged after the first fit: the four parts and fit_s make
    # the cold fit
    parts = ("staging_trace_s", "staging_lower_s", "staging_compile_s",
             "cold_fit_unstaged_s")
    assert sum(value(n, run) for n in parts) + run["fit_s"] \
        == pytest.approx(value("cold_fit_s", run))
    # a re-trace in one of the last three fits is a third of a span a fit;
    # it joins the whole-process staging and leaves the cold fit's rest
    # where it was, so the sum now overshoots by just that re-trace
    fit(tracer, at, [("trace", 0.125)])
    assert value("stagings_per_fit", run) == pytest.approx(1 / 3)
    assert value("staging_trace_s", run) == 0.625
    assert value("cold_fit_unstaged_s", run) == pytest.approx(0.0)
    assert sum(value(n, run) for n in parts) + run["fit_s"] \
        == pytest.approx(value("cold_fit_s", run) + 0.125)


def test_no_name_no_tracer_and_a_tracer_without_totals_read_nothing(tracer):
    run = {"fit_s": 1.0, "traced_fits": [{}]}
    assert span_total.read(run, r"job\..*\.fit", "first_s") is None
    assert cold_fit_rest.read(run, r"job\..*\.fit", r"staging\.job") is None
    assert ring_spans_per_job.read(run, "staging") is None    # no job span
    fit(tracer, 0.0, [])
    assert ring_spans_per_job.read({"traced_fits": []}, "staging") is None

    class Older:
        """The tracer of a program without totals or staging spans."""
        def snapshot(self):
            return tracer.snapshot()
    tracing.disable()
    for installed in (None, Older()):
        if installed is not None:
            tracing.install_if_absent(installed)
        for name in NEW:
            assert value(name, run) is None, (name, installed)


def test_a_ring_that_lost_part_of_the_first_job_reads_nothing():
    from cycloneml_tpu.observe.flight import FlightTracer
    tracing.disable()
    ring = tracing.install_if_absent(FlightTracer(max_spans=4))
    try:
        fit(ring, 0.0, [("trace", 0.5), ("lower", 0.5), ("compile", 0.5)])
        run = {"traced_fits": [{}]}
        assert ring.spans_dropped == 1
        assert ring_spans_per_job.read(run, "staging") is None
        fit(ring, 10.0, [("trace", 0.5)])
        assert ring_spans_per_job.read(run, "staging") == 1.0
        assert span_total.read(run, r"staging\.trace", "n") == 2
    finally:
        tracing.disable()


def test_the_metric_files_and_their_entries():
    bench = manifest.benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert list(new) == NEW
    for name, m in new.items():
        assert m["workloads"] == cells and m["better"] == "lower"
        assert m["moves"] == ("fit_s" if name == "stagings_per_fit"
                              else "setup_s")
        manifest.reader_of(name)
