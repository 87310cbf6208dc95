"""One module per kind of per-layer reader. ``read(run, **args)`` returns the
metric's value, or ``None`` where it finds nothing to read (the harness then
leaves the metric out: a share of a peak is never reported as 0).

``run`` holds: ``fits`` (the window's fits with their counters), ``fit_s``,
``traced_fits`` and ``trace`` (a ``perfbench.trace.Trace``), ``work`` (bytes
and FLOP one evaluation requires, from the shapes), ``chips`` and ``peaks``.
"""
