"""Always-on flight recorder: a bounded ring of recent spans that runs
even when full tracing is off, dumped on trouble.

The observability gap this closes: the first mesh rebuild (or shed burst,
or injected fault) in a LONG job is exactly the event nobody paid full
tracing for — by the time an operator re-runs with ``cyclone.trace.enabled``
the failure is gone. The flight recorder keeps the last
``cyclone.telemetry.flight.ringSpans`` spans in memory at all times and,
when a trigger fires, freezes that window and (when ``cyclone.trace.dir``
is set) writes it as a normal Chrome trace — the minutes *before* the
event, loadable in Perfetto after the fact.

Mechanics: :class:`FlightTracer` is a :class:`~cycloneml_tpu.observe.
tracing.Tracer` with ``full = False``, installed as THE process-global
tracer when no full tracer is active. Every instrumentation site therefore
keeps its one-global-read disabled discipline — a site sees "a tracer" and
records spans into the ring; the ``full`` flag gates everything that costs
real money (XLA cost harvest, budget analysis, per-job profile rollups,
metrics bridging), which is what keeps flight-only overhead small (the
``trace_overhead`` BENCH field pins the number). ``tracing.enable()``
upgrades a flight ring to a full tracer; full tracing never loses to the
ring.

Triggers (each a one-global-read no-op when nothing is installed):

=======================  =====================================================
reason                   fired from
=======================  =====================================================
``fault``                every chaos injection (``faults.FaultInjector.fire``)
``mesh.rebuild``         ``MeshSupervisor.recover`` entry — the window shows
                         what the mesh was doing when it degraded
``serving.shed``         a ServingOverloaded shed (queue backpressure or
                         admission-control shed burst)
``slo.breach``           the skew detector's SLO latch (observe/skew.py)
=======================  =====================================================

Dumps are throttled (``minIntervalMs``) so a burst freezes one window, not
one per shed request. The last few dumps stay readable in memory
(:func:`dumps`) whether or not a dump directory is configured.
"""

from __future__ import annotations

import os
import re
import threading
import time
from typing import Any, Dict, List, Optional

from cycloneml_tpu.observe import tracing
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

DEFAULT_RING_SPANS = 2048
MAX_KEPT_DUMPS = 16


class FlightTracer(tracing.Tracer):
    """The always-on ring: a Tracer that records spans and nothing else
    (``full = False`` — no metrics bridge, no cost harvest, no rollups).
    It keeps ``Tracer.totals()`` like any tracer: they are what a ring of
    2,048 spans forgets (the context's start, the first fit)."""

    full = False

    def __init__(self, max_spans: int = DEFAULT_RING_SPANS):
        super().__init__(max_spans=max_spans, registry=None)


_lock = threading.Lock()
_dump_dir: Optional[str] = None
_min_interval_s = 1.0
_diagnose_dumps = True
_last_trigger = 0.0
_trigger_count = 0
_dumps: List[Dict[str, Any]] = []


def enable(ring_spans: int = DEFAULT_RING_SPANS) -> tracing.Tracer:
    """Install the flight ring unless a tracer (full or flight) is already
    active; returns whichever tracer ends up installed."""
    return tracing.install_if_absent(FlightTracer(max_spans=ring_spans))


def disable() -> None:
    """Uninstall the flight ring. A FULL tracer is left untouched — only
    the owner of full tracing (context/tests) may disable it."""
    t = tracing.active()
    if t is not None and not t.full:
        tracing.disable()


def active() -> Optional[tracing.Tracer]:
    """The installed FLIGHT ring, or None (a full tracer is not it)."""
    t = tracing.active()
    if t is not None and not t.full:
        return t
    return None


_KEEP = object()


def configure(dump_dir=_KEEP, min_interval_s: Optional[float] = None,
              diagnose: Optional[bool] = None) -> None:
    """Set where triggered dumps are written (``None``/empty = in-memory
    records only; omit the argument to keep the current directory), the
    trigger throttle, and whether dumps auto-attach a doctor report
    (``cyclone.doctor.flightDiagnosis``)."""
    global _dump_dir, _min_interval_s, _diagnose_dumps
    with _lock:
        if dump_dir is not _KEEP:
            _dump_dir = dump_dir or None
        if min_interval_s is not None:
            _min_interval_s = max(float(min_interval_s), 0.0)
        if diagnose is not None:
            _diagnose_dumps = bool(diagnose)


def trigger(reason: str, **attrs) -> Optional[Dict[str, Any]]:
    """Freeze the recent-span window and dump it.

    Works against whichever tracer is active (the flight ring, or a full
    tracer — then the dump is the last ``DEFAULT_RING_SPANS`` spans of the
    full buffer); a no-op when tracing is entirely off. Throttled: within
    ``minIntervalMs`` of the previous trigger only the counter moves.
    Returns the dump record (``reason``/``n_spans``/``path``) or None."""
    tr = tracing.active()
    if tr is None:
        return None
    global _last_trigger, _trigger_count
    now = time.monotonic()
    with _lock:
        _trigger_count += 1
        count = _trigger_count
        if _last_trigger and now - _last_trigger < _min_interval_s:
            return None
        _last_trigger = now
        dump_dir = _dump_dir
        diagnose_dump = _diagnose_dumps
    window = DEFAULT_RING_SPANS if tr.full else tr.max_spans
    # tail-limited read: under a FULL 100k-span tracer a whole-buffer
    # snapshot would copy everything under the tracer lock on the
    # triggering (step) thread — ask for the window's positions instead
    spans = tr.snapshot(since=max(0, tr.mark() - window))
    dump: Dict[str, Any] = {
        "reason": reason, "attrs": dict(attrs), "n_spans": len(spans),
        "trigger": count, "time": time.time(), "path": None,
        "spans": spans,
    }
    if diagnose_dump:
        # the dump arrives pre-triaged: the doctor runs over the frozen
        # ring (spans only, no live sources — deterministic for a given
        # window) and a doctor failure must never break the dump itself
        try:
            from cycloneml_tpu.observe.diagnose import diagnose
            dump["diagnosis"] = diagnose(
                spans=spans, skew=None, cache_stats=None,
                source="flight").to_dict()
        except Exception:
            logger.exception("flight recorder: dump diagnosis failed")
    if dump_dir:
        from cycloneml_tpu.observe import export
        slug = re.sub(r"[^A-Za-z0-9_.-]+", "_", reason)[:48] or "trigger"
        path = os.path.join(dump_dir, f"flight-{count:04d}-{slug}.trace.json")
        try:
            os.makedirs(dump_dir, exist_ok=True)
            other = {"flight_reason": reason, "flight_trigger": count,
                     **{f"flight_{k}": v for k, v in attrs.items()}}
            if "diagnosis" in dump:
                # the on-disk post-mortem carries its own triage
                other["diagnosis"] = dump["diagnosis"]
            obj = export.chrome_trace(tr, spans=spans, other=other)
            export.write_chrome_trace(obj, path)
            dump["path"] = path
            logger.warning("flight recorder: dumped %d spans to %s (%s)",
                           len(spans), path, reason)
        except OSError:
            logger.exception("flight recorder: dump to %s failed", dump_dir)
    with _lock:
        _dumps.append(dump)
        while len(_dumps) > MAX_KEPT_DUMPS:
            _dumps.pop(0)
    return dump


def dumps() -> List[Dict[str, Any]]:
    """The recent dump records (bounded), newest last."""
    with _lock:
        return list(_dumps)


def trigger_count() -> int:
    with _lock:
        return _trigger_count


def reset() -> None:
    """Clear dump records and the throttle (tests)."""
    global _last_trigger, _trigger_count
    with _lock:
        _dumps.clear()
        _last_trigger = 0.0
        _trigger_count = 0
