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
``staging``    what jax staged, as jax reports it (``jax.monitoring``):
               ``trace`` (jaxpr tracing), ``lower`` (jaxpr → MLIR; Pallas
               kernels lower to Mosaic here), ``compile`` (XLA compile, or
               the persistent cache's retrieval and load). Attrs ``fun``,
               ``nested``; on ``compile`` also ``cache`` and ``retrieval_s``
``phase``      host work of a fit between its dispatches: ``fit.stats`` /
               ``fit.prepare`` / ``fit.optimize`` / ``fit.finish`` in the
               estimator, ``optim.iteration`` per turn of an optimizer's
               host loop (its self time — duration less its ``dispatch``
               children — is the host optimizer's own work);
               ``context.start`` ⊃ ``context.mesh`` / ``context.services``
               around ``CycloneContext.__init__``
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

Staging: ``compile`` is this program's bracket — the first dispatch of a
program object the program cache did not hold — and cannot tell a cache
load from a compile, nor see what jit re-stages beneath an old program
object. jax reports each staging step where it happens; the ``on_staging_*``
/ ``on_cache_*`` functions below are the listeners ``CycloneContext``
registers for them (plain functions of jax's ``(event, value, **kw)``: this
module stays jax-free). Only the OUTERMOST staging event on a thread becomes
a span (an outer trace holds hundreds of one-primitive traces, and an eager
constant inside it a whole trace/lower/compile of its own: all are counted in
``nested`` and are the outer step's time already), opened at jax's entry
event and closed at its exit, so it nests under the thread's open span and is
mirrored into a profiler capture like any other. Staging on a thread with no
open span is not the program's (a caller's own ``jit``): it is added to the
``staging.outside`` total and opens no span.

Totals: every closed span also updates ``{n, seconds, first_s, max_s}`` for
its ``kind.name`` (:meth:`Tracer.totals`), which outlive the ring: the first
fit of a process (``first_s`` of ``job.<Estimator>.fit``) and the context's
start are long gone from a 2,048-span ring when anyone reads.

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
    "nbytes", "on_staging_start", "on_staging_span", "on_cache_event",
    "on_cache_duration",
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

#: the ``jax.monitoring`` events that bracket a staging step (an entry
#: scalar, then a duration and a time span at exit, each with ``fun_name``)
STAGING_STEPS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: what the persistent cache says inside a ``compile`` step (a miss is
#: reported when the new executable is written; jax says nothing of a compile
#: it neither found nor kept: ``cache`` stays ``off``)
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: distinct ``kind.name`` totals kept; past it a new name joins ``<kind>.*``
#: (the totals sit beside a bounded ring and must not outgrow it)
MAX_TOTAL_NAMES = 1024


def _new_totals() -> Dict[Tuple[str, str], List[float]]:
    # totals that no span carries are there from the start: 0 cache misses
    # is a reading, not a missing one
    return {("staging", name): [0, 0.0, 0.0, 0.0]
            for name in ("cache_hit", "cache_miss", "outside")}


class _ThreadStaging:
    """One thread's open staging steps (``Tracer._local.staging``)."""

    __slots__ = ("open", "live", "nested", "cache", "retrieval_s",
                 "accounts")

    def __init__(self):
        self.open: List[str] = []     # steps jax has entered and not left
        self.live: Optional[_LiveSpan] = None   # the outermost one's span
        self.nested = 0               # events folded into the outermost
        self.cache = "off"            # of the compile step in flight
        self.retrieval_s: Optional[float] = None
        self.accounts: List[Dict[str, Any]] = []   # one per open job


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
        # (kind, name) -> [n, seconds, first_s, max_s]; outlives the ring
        self._totals = _new_totals()

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

    def reserve_span_id(self) -> str:
        """An id for a span recorded later (``record_span(span_id=...)``):
        a retroactive parent is recorded AFTER its children, since a
        thread's spans are recorded in the order they close, and the
        children must name it."""
        return f"s{next(self._ids)}"

    def record_span(self, kind: str, name: str = "", t0: float = 0.0,
                    t1: float = 0.0, parent: str = "", span_id: str = "",
                    **attrs) -> Span:
        """Record an already-timed span retroactively (``t0``/``t1`` are
        ``perf_counter`` readings). For producers whose phases span
        threads — the serving batcher times a request's queue phase on
        the submitting thread and its dispatch on the worker, then
        records one request span after the fact; a context-manager span
        could not bracket that lifetime. For the same reason it is not
        mirrored into a profiler capture: the region is over, and not this
        thread's."""
        s = Span(span_id or f"s{next(self._ids)}", parent, kind,
                 name or kind, threading.get_ident(), attrs)
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
            if s.kind != "instant" and s.kind != "counter":
                self._add_total((s.kind, s.name), s.duration_s)
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

    def _add_total(self, key: Tuple[str, str], seconds: float) -> None:
        # callers hold self._lock
        t = self._totals.get(key)
        if t is None:
            if len(self._totals) >= MAX_TOTAL_NAMES:
                key = (key[0], "*")
            t = self._totals.setdefault(key, [0, 0.0, 0.0, 0.0])
        if t[0] == 0:
            t[2] = seconds
        t[0] += 1
        t[1] += seconds
        if seconds > t[3]:
            t[3] = seconds

    # -- staging (jax.monitoring's events; see the module docstring) -----------
    def _staging(self) -> _ThreadStaging:
        st = getattr(self._local, "staging", None)
        if st is None:
            st = self._local.staging = _ThreadStaging()
        return st

    def staging_enter(self, step: str, fun: str) -> None:
        """jax announced the start of ``step`` on this thread."""
        st = self._staging()
        if st.open:
            st.nested += 1
        else:
            st.nested = 0
            # a thread with no open span is not running the program
            st.live = self.span("staging", step, fun=fun) \
                if self._stack() else None
            if st.live is not None:
                st.live.__enter__()
        st.open.append(step)
        if step == "compile":
            st.cache, st.retrieval_s = "off", None

    def staging_cache(self, cache: Optional[str] = None,
                      retrieval_s: Optional[float] = None) -> None:
        """The persistent cache's word on the compile step in flight."""
        st = self._staging()
        if cache is not None:
            st.cache = cache
        if retrieval_s is not None:
            st.retrieval_s = retrieval_s

    def staging_exit(self, step: str, fun: str, wall_t0: float,
                     wall_t1: float) -> None:
        """jax reported the end of ``step`` with its wall-clock window."""
        st = self._staging()
        if step not in st.open:
            # an exit whose entry this tracer never saw (it was installed
            # mid-step): nothing was opened, nothing is recorded
            return
        # steps entered above this one lost their exits: they go with it
        while st.open.pop() != step:
            pass
        live = st.live
        if step == "compile" and live is not None:
            # every executable built or loaded beneath the program's spans
            # counts, folded into an outer step or not
            for acc in st.accounts:
                acc["programs"] += 1
            if st.cache != "off":
                answer = "cache_" + st.cache
                for acc in st.accounts:
                    acc[answer] += 1
                with self._lock:
                    self._add_total(("staging", answer), 0.0)
        if st.open:
            return
        st.live = None
        if live is None:
            with self._lock:
                self._add_total(("staging", "outside"), wall_t1 - wall_t0)
            return
        attrs = live.span.attrs
        attrs["nested"] = st.nested
        if step == "compile":
            attrs["cache"] = st.cache
            if st.cache == "hit" and st.retrieval_s is not None:
                attrs["retrieval_s"] = st.retrieval_s
        live.__exit__(None, None, None)
        took = live.span.duration_s
        for acc in st.accounts:
            acc[step] += took
            if took > acc["slowest_s"]:
                acc["slowest_fun"], acc["slowest_s"] = fun, took

    def open_staging_account(self) -> Dict[str, Any]:
        """What this thread stages from here to the matching
        :meth:`close_staging_account`, kept as the staging exits happen:
        ``run_job`` reads it instead of scanning the ring."""
        acc = {"programs": 0, "trace": 0.0, "lower": 0.0, "compile": 0.0,
               "cache_hit": 0, "cache_miss": 0, "slowest_fun": "",
               "slowest_s": 0.0}
        self._staging().accounts.append(acc)
        return acc

    def close_staging_account(self, acc: Dict[str, Any]) -> None:
        accounts = self._staging().accounts
        # by identity: two fresh accounts are equal
        accounts[:] = [a for a in accounts if a is not acc]
        # one entry a job, staged or not: ``first_s`` is what the first
        # job of the process staged, which no whole-process total says
        with self._lock:
            self._add_total(("staging", "job"),
                            acc["trace"] + acc["lower"] + acc["compile"])

    # -- reading ---------------------------------------------------------------
    def totals(self) -> Dict[str, Dict[str, float]]:
        """``kind.name -> {n, seconds, first_s, max_s}`` over every span
        this tracer closed since construction or :meth:`clear` (instants
        and counter samples excluded), whether or not the ring still holds
        it; plus ``staging.cache_hit`` / ``staging.cache_miss`` (``n``: the
        persistent cache's answers beneath program spans),
        ``staging.outside`` (staging on threads with no open span) and
        ``staging.job`` (one entry a closed job: the seconds of the
        ``staging`` spans beneath it, so ``first_s`` is the first job's)."""
        with self._lock:
            return {f"{kind}.{name}": {"n": t[0], "seconds": t[1],
                                       "first_s": t[2], "max_s": t[3]}
                    for (kind, name), t in self._totals.items()}

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
            self._totals = _new_totals()

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


# -- jax.monitoring listeners (registered by CycloneContext, once a process) -----
# Plain functions of jax's listener signatures; each reads the module global
# once and returns when no tracer is installed. A warm call fires none.

def on_staging_start(event: str, value: float = 0.0, **kw) -> None:
    """Scalar listener: jax announces a staging step's start."""
    t = _tracer
    if t is None:
        return
    step = STAGING_STEPS.get(event)
    if step is not None:
        t.staging_enter(step, str(kw.get("fun_name", "")))


def on_staging_span(event: str, start_time: float, end_time: float,
                    **kw) -> None:
    """Time-span listener: a staging step's end, with its wall window."""
    t = _tracer
    if t is None:
        return
    step = STAGING_STEPS.get(event)
    if step is not None:
        t.staging_exit(step, str(kw.get("fun_name", "")), start_time,
                       end_time)


def on_cache_event(event: str, **kw) -> None:
    """Event listener: the persistent cache used, hit or missed."""
    t = _tracer
    if t is None:
        return
    cache = CACHE_EVENTS.get(event)
    if cache is not None:
        t.staging_cache(cache=cache)


def on_cache_duration(event: str, duration_secs: float, **kw) -> None:
    """Duration listener: what a persistent-cache hit took to retrieve."""
    t = _tracer
    if t is None:
        return
    if event == CACHE_RETRIEVAL_EVENT:
        t.staging_cache(retrieval_s=float(duration_secs))


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
