"""A total the program's tracer keeps by span name, which outlives its ring:
``field`` (``n``, ``seconds``, ``first_s``, ``max_s``) of the
``<kind>.<name>`` totals whose key matches ``name`` whole, summed (the cells
open one ``job.<Estimator>.fit`` name, and every other pattern names one
key). ``None`` where the program runs no tracer, its tracer keeps no totals,
or no name matches: a reading of 0 is a total the tracer holds at 0."""

import re


def program_totals():
    """``Tracer.totals()`` of the program's active tracer, or ``None``."""
    from cycloneml_tpu.observe import tracing
    totals = getattr(tracing.active(), "totals", None)
    return totals() if totals is not None else None


def read(run, name: str, field: str):
    totals = program_totals()
    if totals is None:
        return None
    rx = re.compile(name)
    found = [t[field] for key, t in totals.items() if rx.fullmatch(key)]
    return sum(found) if found else None
