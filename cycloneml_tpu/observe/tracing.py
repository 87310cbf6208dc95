"""Step-level tracing: hierarchical spans over the trace→compile→dispatch→
collective path.

The framework's performance story lives in one narrow boundary (estimator →
block aggregator → ``tree_aggregate`` → psum), yet tracing-JIT systems hide
exactly where a fit's wall clock goes: staging costs (trace + XLA compile)
happen once, silently, and dominate small fits (Frostig et al., SysML 2018),
while steady-state cost is per-dispatch latency plus device→host readbacks.
This module makes those phases visible the way Dapper makes RPC trees
visible (Sigelman et al. 2010, PAPERS.md): every instrumented boundary opens
a :class:`Span` (kind + name + wall window + attrs) nested under the
current thread's open span, and the process-global :class:`Tracer` collects
them for per-fit :class:`~cycloneml_tpu.observe.profile.FitProfile`
aggregation and Chrome-trace export
(:mod:`cycloneml_tpu.observe.export` — loads in Perfetto / chrome://tracing).

Span kind taxonomy (docs/observability.md has the full catalogue):

=============  ==============================================================
kind           opened around
=============  ==============================================================
``job``        a ``ctx.run_job`` bracket (one estimator ``fit``)
``dispatch``   one optimizer-level device dispatch (loss eval, fused line
               search, L-BFGS chunk, GD step); ``evals`` attr carries the
               loss/grad evaluations the dispatch performed
``collective`` one dispatch of a ``tree_aggregate`` psum program
``compile``    the FIRST dispatch of a freshly built program — the call that
               pays tracing + XLA compilation (program-cache misses)
``transfer``   a blocking ``jax.device_get`` readback; ``bytes`` attr
``phase``      host work of a fit between its dispatches: ``fit.stats`` /
               ``fit.prepare`` / ``fit.optimize`` / ``fit.finish`` in the
               estimator, ``optim.iteration`` per turn of an optimizer's
               host loop (its self time — duration less its ``dispatch``
               children — is the host optimizer's own work)
``checkpoint`` ``TrainingCheckpointer`` save / commit / restore
``rebuild``    a ``MeshSupervisor.recover`` mesh rebuild
``instant``    zero-duration annotations: injected faults, step retries,
               program-cache hits/misses
``counter``    a Perfetto counter sample (Chrome-trace ``"C"`` phase):
               ``hbm.bytes_in_use`` / ``hbm.predicted_peak_bytes`` /
               ``flops.cumulative`` timelines from ``observe.costs``
=============  ==============================================================

One clock with the device: when the tracer carries an ``annotation``
factory (``CycloneContext`` installs ``jax.profiler.TraceAnnotation``; this
module never imports jax), every live span also enters
``annotation("cyclone.<kind>.<name>")``, so a ``jax.profiler`` capture
(``with ctx.profile(dir):``) holds the program's spans on the profiler's own
clock, above the device operations. The annotation records only while a
profiler session is open. :func:`instant`, :func:`counter` and
:meth:`Tracer.record_span` emit nothing there: an annotation brackets a live
region on one thread, and those are points or regions that already ended
(possibly on another thread).

Off by default with near-zero disabled cost: every instrumentation site
performs ONE module-global read (the same pattern ``faults.inject`` uses)
and :func:`span` returns a shared no-op context manager — no allocation, no
clock read. Enabled via :func:`enable` (``CycloneContext`` does this when
``cyclone.trace.enabled`` / ``CYCLONE_TRACE`` is set).

Tracer-awareness contract: instrumentation sites that can be reached at
JAX trace time (a program inlined into a larger jitted program) must NOT
open spans there — a span records host wall clock, which is meaningless
inside tracing and would bake host work into the program (see
``collectives._instrument_dispatch`` and the graftlint JX001 fixture
``tests/fixtures/graftlint/jx001_tracing_pass.py``).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "Span", "Tracer", "enable", "disable", "active", "full_active",
    "install_if_absent", "span", "instant", "counter", "current_span_id",
    "nbytes",
]


class Span:
    """One closed (or instant) trace span. ``t0``/``t1`` are
    ``time.perf_counter`` readings; the owning tracer anchors them to wall
    time for export."""

    __slots__ = ("span_id", "parent_id", "kind", "name", "t0", "t1", "tid",
                 "attrs")

    def __init__(self, span_id: str, parent_id: str, kind: str, name: str,
                 tid: int, attrs: Dict[str, Any]):
        self.span_id = span_id
        self.parent_id = parent_id
        self.kind = kind
        self.name = name
        self.t0 = 0.0
        self.t1 = 0.0
        self.tid = tid
        self.attrs = attrs

    @property
    def duration_s(self) -> float:
        return max(self.t1 - self.t0, 0.0)

    def __repr__(self) -> str:  # debugging/test readability only
        return (f"Span({self.kind}:{self.name} id={self.span_id} "
                f"parent={self.parent_id or '-'} dur={self.duration_s:.6f})")


class _NoopSpan:
    """Shared do-nothing span: the entire disabled-tracing API surface."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def annotate(self, **attrs) -> None:
        pass

    def annotate_bytes(self, tree) -> None:
        # no nbytes walk on the disabled path
        pass

    @property
    def span_id(self) -> str:
        return ""


NOOP_SPAN = _NoopSpan()

#: what every span's event in a ``jax.profiler`` capture starts with (a
#: benchmark finds ITS spans by its own prefix: this one is the program's)
ANNOTATION_PREFIX = "cyclone."


class _LiveSpan:
    """Context manager recording one span into its tracer."""

    __slots__ = ("_tracer", "span", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self.span = span
        self._annotation = None

    def __enter__(self) -> "_LiveSpan":
        factory = self._tracer.annotation
        if factory is not None:
            self._annotation = factory(
                f"{ANNOTATION_PREFIX}{self.span.kind}.{self.span.name}")
            self._annotation.__enter__()
        stack = self._tracer._stack()
        if stack and not self.span.parent_id:
            self.span.parent_id = stack[-1].span_id
        elif not stack and not self.span.parent_id:
            # root span in a process that adopted a distributed trace
            # context: parent to the submitting process's span (a
            # host-qualified id, or "" when no context was adopted)
            self.span.parent_id = self._tracer.parent_span_id
        stack.append(self.span)
        self.span.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.span.t1 = time.perf_counter()
        stack = self._tracer._stack()
        if stack and stack[-1] is self.span:
            stack.pop()
        self._tracer._record(self.span)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False

    def annotate(self, **attrs) -> None:
        """Attach attributes (usable during AND after the ``with`` block —
        the recorded span holds the same attrs dict)."""
        self.span.attrs.update(attrs)

    def annotate_bytes(self, tree) -> None:
        self.span.attrs["bytes"] = nbytes(tree)

    @property
    def span_id(self) -> str:
        return self.span.span_id


class Tracer:
    """Collects spans process-wide; thread-safe.

    Context propagation is per-thread (a thread-local span stack), so
    nested fits and concurrent fits in different threads each get a correct
    parent chain. Cross-thread propagation is explicit: capture
    :meth:`current_span_id` in the submitting thread and pass it as
    ``parent`` to :meth:`span` in the worker. Cross-PROCESS propagation is
    the trace context (:meth:`set_trace_context`): ``trace_id`` names the
    distributed trace this process participates in and ``parent_span_id``
    (a host-qualified id from the submitting process) becomes the parent
    of every root span recorded here — the Dapper join
    (``observe/collect.py`` merges the per-process traces).

    The buffer is a RING: past ``max_spans`` the OLDEST span is dropped
    (and counted in ``dropped``), so a long job always retains its most
    recent window — the flight-recorder semantics. Buffer positions are
    monotonic sequence numbers (``mark``/``snapshot(since)``/``drain``
    speak seq, not list index), so readers see exact once-each delivery
    across wrap-arounds.

    ``registry`` (a :class:`~cycloneml_tpu.util.metrics.MetricsRegistry`)
    bridges spans into the metrics system: every closed span updates
    ``span.<kind>`` (a Timer) and every instant bumps ``trace.<name>`` (a
    Counter) — visible through the Prometheus endpoint.
    """

    #: False on the flight-recorder tracer (observe/flight.py): sites that
    #: pay real money when traced (XLA cost harvest, budget analysis,
    #: per-job profile rollups) run only under a FULL tracer — the flight
    #: ring records spans and nothing else.
    full = True

    #: ``name -> context manager`` entered and exited with every live span
    #: (None: spans stay on this tracer's clock only). The context sets
    #: ``jax.profiler.TraceAnnotation``; tests set a recording fake.
    annotation = None

    def __init__(self, max_spans: int = 100_000, registry=None):
        self.max_spans = max(1, int(max_spans))
        self.registry = registry
        # wall anchor: perf_counter offsets map onto real time for export
        self.epoch_wall = time.time()
        self.epoch_perf = time.perf_counter()
        self._spans: "collections.deque[Span]" = collections.deque()
        self._base = 0          # seq of the oldest span still in the ring
        self.dropped = 0        # ring overflow: oldest-dropped count
        self.trace_id = uuid.uuid4().hex[:16]
        self.parent_span_id = ""   # remote parent for root spans ("" = none)
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._tid_names: Dict[int, str] = {}

    @property
    def wall_base(self) -> float:
        """Offset mapping a span's ``perf_counter`` reading onto wall
        time: ``wall = wall_base + t``."""
        return self.epoch_wall - self.epoch_perf

    def set_trace_context(self, trace_id: str, parent_span_id: str = ""
                          ) -> None:
        """Adopt a distributed trace context (the deploy launch env's
        ``CYCLONE_TRACE_ID`` / ``CYCLONE_TRACE_PARENT``): subsequent ROOT
        spans parent to ``parent_span_id`` — a host-qualified id
        (``label/sN``) minted by the submitting process."""
        if trace_id:
            self.trace_id = str(trace_id)
        self.parent_span_id = str(parent_span_id or "")

    def thread_names(self) -> Dict[int, str]:
        """tid -> thread name for every thread that recorded a span (the
        Chrome-trace ``thread_name`` metadata source)."""
        with self._lock:
            return dict(self._tid_names)

    @property
    def spans_dropped(self) -> int:
        """Ring-overflow drop count as a first-class telemetry reading
        (the drop-counter rollup in ``TelemetryStatsUpdated`` and
        ``/api/v1/telemetry`` reads this; previously visible only in the
        trace export header)."""
        with self._lock:
            return self.dropped

    # -- context ---------------------------------------------------------------
    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current_span_id(self) -> str:
        stack = self._stack()
        return stack[-1].span_id if stack else ""

    # -- recording -------------------------------------------------------------
    def span(self, kind: str, name: str = "", parent: str = "",
             **attrs) -> _LiveSpan:
        s = Span(f"s{next(self._ids)}", parent, kind, name or kind,
                 threading.get_ident(), attrs)
        return _LiveSpan(self, s)

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration annotation under the current span (faults,
        retries, cache hits/misses). Not mirrored into a profiler capture
        (see the module docstring)."""
        s = Span(f"s{next(self._ids)}", self.current_span_id(), "instant",
                 name, threading.get_ident(), attrs)
        s.t0 = s.t1 = time.perf_counter()
        self._record(s)

    def record_span(self, kind: str, name: str = "", t0: float = 0.0,
                    t1: float = 0.0, parent: str = "", **attrs) -> Span:
        """Record an already-timed span retroactively (``t0``/``t1`` are
        ``perf_counter`` readings). For producers whose phases span
        threads — the serving batcher times a request's queue phase on
        the submitting thread and its dispatch on the worker, then
        records one request span after the fact; a context-manager span
        could not bracket that lifetime. For the same reason it is not
        mirrored into a profiler capture: the region is over, and not this
        thread's."""
        s = Span(f"s{next(self._ids)}", parent, kind, name or kind,
                 threading.get_ident(), attrs)
        s.t0, s.t1 = t0, t1
        self._record(s)
        return s

    def counter(self, name: str, value: float) -> None:
        """One sample of a Perfetto counter track (exported as a
        Chrome-trace ``"C"``-phase event): device-memory / cumulative-FLOP
        timelines render as graphs next to the spans."""
        s = Span(f"s{next(self._ids)}", "", "counter", name,
                 threading.get_ident(), {"value": float(value)})
        s.t0 = s.t1 = time.perf_counter()
        self._record(s)

    def _record(self, s: Span) -> None:
        with self._lock:
            self._spans.append(s)
            while len(self._spans) > self.max_spans:
                # oldest-dropped: a bounded job keeps its RECENT window
                # (the flight-recorder contract); the count is surfaced in
                # the export header and FitProfile.spans_dropped
                self._spans.popleft()
                self._base += 1
                self.dropped += 1
            if s.tid not in self._tid_names:
                # _record always runs on the thread whose ident stamps the
                # span (context-manager exit / instant / retroactive
                # record_span all execute on the recording thread)
                self._tid_names[s.tid] = threading.current_thread().name
        reg = self.registry
        if reg is not None:
            try:
                if s.kind == "instant":
                    reg.counter(f"trace.{s.name}").inc()
                elif s.kind != "counter":
                    # counter samples have live gauges on the metrics side
                    # already (costs.register_memory_gauges) — a zero-
                    # duration timer entry would only skew span.* stats
                    reg.timer(f"span.{s.kind}").update(s.duration_s)
            except Exception:
                pass  # a broken metrics bridge must not kill the step

    # -- reading ---------------------------------------------------------------
    def _window(self, since: int) -> List[Span]:
        # callers hold self._lock
        start = max(0, since - self._base)
        if start <= 0:
            return list(self._spans)
        if start >= len(self._spans):
            return []
        return list(itertools.islice(self._spans, start, None))

    def snapshot(self, since: int = 0) -> List[Span]:
        """Spans recorded at sequence position >= ``since`` that are still
        in the ring (a stale ``since`` below the ring floor returns the
        whole surviving window)."""
        with self._lock:
            return self._window(since)

    def mark(self) -> int:
        """Current buffer position (monotonic sequence number — survives
        ring wrap-around) — pass to :meth:`profile_for` as ``since`` so a
        per-job rollup scans only the spans that job recorded, not the
        whole process history."""
        with self._lock:
            return self._base + len(self._spans)

    def drain(self, since: int) -> Tuple[List[Span], int]:
        """Atomic ``(snapshot(since), mark())``: the spans at position >=
        ``since`` plus the position to resume from. The one-lock read is
        what makes a collector loop exact — a concurrent producer between
        a separate ``mark()`` and ``snapshot()`` would be delivered twice.
        Spans are never removed; the returned mark is the cursor."""
        with self._lock:
            return self._window(since), self._base + len(self._spans)

    def clear(self) -> None:
        with self._lock:
            # sequence positions stay monotonic: a mark taken before
            # clear() yields only post-clear spans, never a replay
            self._base += len(self._spans)
            self._spans.clear()
            self.dropped = 0

    def profile_for(self, root_id: Optional[str] = None, since: int = 0):
        """A :class:`FitProfile` over the spans descending from ``root_id``
        (or every recorded span when None), starting at buffer position
        ``since`` (a :meth:`mark` taken before the root span opened)."""
        from cycloneml_tpu.observe.profile import FitProfile
        with self._lock:
            spans = self._window(since)
            dropped = self.dropped
        prof = FitProfile.from_spans(spans, root_id=root_id)
        prof.spans_dropped = dropped
        return prof

    def export_chrome_trace(self, path: str) -> str:
        from cycloneml_tpu.observe.export import export_chrome_trace
        return export_chrome_trace(self, path)


# -- process-global switch -----------------------------------------------------
# The disabled hot path is ONE read of this module global (the same
# discipline as faults._active); no lock, no allocation.
_lock = threading.Lock()
_tracer: Optional[Tracer] = None


def enable(max_spans: int = 100_000, registry=None) -> Tracer:
    """Install (or return the already-installed) process-global FULL
    tracer. An installed flight-recorder ring (``Tracer.full`` False) is
    UPGRADED: replaced by a fresh full tracer — full tracing supersedes
    the always-on ring, whose recent window is discarded (it exists to
    cover the runs that did not pay for this)."""
    global _tracer
    with _lock:
        if _tracer is None or not _tracer.full:
            _tracer = Tracer(max_spans=max_spans, registry=registry)
        return _tracer


def install_if_absent(tracer: Tracer) -> Tracer:
    """Install ``tracer`` only when no tracer is active; returns whichever
    tracer is installed afterwards (observe/flight.py uses this so the
    ring never displaces a full tracer)."""
    global _tracer
    with _lock:
        if _tracer is None:
            _tracer = tracer
        return _tracer


def disable() -> Optional[Tracer]:
    """Uninstall and return the global tracer (None when already off). The
    returned tracer stays readable — export after disabling is fine."""
    global _tracer
    with _lock:
        t, _tracer = _tracer, None
        return t


def active() -> Optional[Tracer]:
    return _tracer


def full_active() -> Optional[Tracer]:
    """The active tracer ONLY when it is a full one — the gate for sites
    whose traced path costs real work (XLA cost harvest, budget checks,
    per-job profile rollups). Under the flight-recorder ring this returns
    None: flight mode records spans and nothing else, which is what keeps
    always-on cheap."""
    t = _tracer
    if t is None or not t.full:
        return None
    return t


def span(kind: str, name: str = "", **attrs):
    """Open a span under the current thread's context; a shared no-op when
    tracing is disabled (one global read, zero allocation)."""
    t = _tracer
    if t is None:
        return NOOP_SPAN
    return t.span(kind, name, **attrs)


def instant(name: str, **attrs) -> None:
    t = _tracer
    if t is not None:
        t.instant(name, **attrs)


def counter(name: str, value: float) -> None:
    t = _tracer
    if t is not None:
        t.counter(name, value)


def current_span_id() -> str:
    t = _tracer
    if t is None:
        return ""
    return t.current_span_id()


def nbytes(tree: Any) -> int:
    """Byte size of a host pytree (dicts/lists/tuples of arrays+scalars) —
    used to annotate ``transfer`` spans after a ``jax.device_get``."""
    if isinstance(tree, dict):
        return sum(nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(nbytes(v) for v in tree)
    n = getattr(tree, "nbytes", None)
    if n is not None:
        return int(n)
    return 8 if isinstance(tree, (int, float, complex, bool)) else 0
