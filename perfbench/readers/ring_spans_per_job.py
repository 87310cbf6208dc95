"""Spans of one ``kind`` beneath the last ``job`` spans the program's ring
still holds, one job a traced fit, per fit. ``None`` where the program runs
no tracer, its tracer is one that records no such kind (it keeps no totals
either), or the ring no longer holds those fits whole."""


def read(run, kind: str):
    from cycloneml_tpu.observe import tracing
    tracer = tracing.active()
    n_fits = len(run["traced_fits"])
    if tracer is None or not n_fits or not hasattr(tracer, "totals"):
        return None
    spans = tracer.snapshot()
    jobs = [s for s in spans if s.kind == "job"][-n_fits:]
    if len(jobs) < n_fits or (tracer.spans_dropped
                              and spans[0].t0 >= jobs[0].t0):
        return None
    parent = {s.span_id: s.parent_id for s in spans}
    roots = {s.span_id for s in jobs}

    def beneath(span_id: str) -> bool:
        while span_id and span_id not in roots:
            span_id = parent.get(span_id, "")
        return bool(span_id)

    return sum(1 for s in spans
               if s.kind == kind and beneath(s.parent_id)) / n_fits
