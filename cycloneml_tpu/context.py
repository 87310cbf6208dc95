"""CycloneContext — the driver entry point.

Analog of ``SparkContext`` (ref: core/src/main/scala/org/apache/spark/
SparkContext.scala:83): owns the conf, the device mesh (≈ executor fleet),
the listener bus + event journal (≈ LiveListenerBus + EventLoggingListener),
dataset factories (≈ parallelize/textFile), broadcast, accumulators, and
shutdown. Unlike the reference there is no DAG scheduler: "jobs" are
jit-compiled SPMD steps on the mesh, so the scheduling layer collapses to
step dispatch + the event journal.
"""

from __future__ import annotations

import atexit
import os
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from cycloneml_tpu import mesh as mesh_mod
from cycloneml_tpu.conf import (
    APP_NAME, CHECKPOINT_DIR, CycloneConf, DEFAULT_PARALLELISM,
    EVENT_LOG_DIR, EVENT_LOG_ENABLED, MASTER, METRICS_CSV_DIR,
    METRICS_PERIOD_S, METRICS_SINKS, PROMETHEUS_PORT,
)
from cycloneml_tpu.observe import tracing as _tracing
from cycloneml_tpu.util.events import (
    ApplicationEnd, ApplicationStart, BlocksMigrated, CycloneEvent,
    EventJournal, FitProfileCompleted, JobEnd, JobStart, ListenerBus, MeshUp,
    StepCompleted,
)
from cycloneml_tpu.util.metrics import ConsoleSink, CsvSink, MetricsSystem
from cycloneml_tpu.util.status import AppStatusListener
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

_active_lock = threading.Lock()
_active_context: Optional["CycloneContext"] = None
_staging_listeners_registered = False


def _register_staging_listeners() -> None:
    """Hand ``observe/tracing.py``'s listeners to ``jax.monitoring``, once a
    process (jax keeps no set: a second registration would fire twice). They
    stay for the process's life and read ``tracing.active()`` per event."""
    global _staging_listeners_registered
    with _active_lock:
        if _staging_listeners_registered:
            return
        _staging_listeners_registered = True
    import jax.monitoring as monitoring
    monitoring.register_scalar_listener(_tracing.on_staging_start)
    monitoring.register_event_time_span_listener(_tracing.on_staging_span)
    monitoring.register_event_listener(_tracing.on_cache_event)
    monitoring.register_event_duration_secs_listener(
        _tracing.on_cache_duration)


def active_context() -> Optional["CycloneContext"]:
    """The live context, or None (used by layers — e.g. the SQL engine's
    exchange routing — that cannot thread a ctx handle through)."""
    with _active_lock:
        if _active_context is not None and not _active_context._stopped:
            return _active_context
    return None


class Broadcast:
    """Replicated pytree on every device (replaces TorrentBroadcast,
    ref: core/.../broadcast/TorrentBroadcast.scala:58 — replication is an
    XLA transfer onto the replicated sharding, no torrent protocol needed)."""

    def __init__(self, ctx: "CycloneContext", value: Any, bid: int):
        self.id = bid
        self._value = value
        self._device_value = None
        self._ctx = ctx

    @property
    def value(self) -> Any:
        return self._value

    @property
    def device_value(self) -> Any:
        if self._device_value is None:
            self._device_value = self._ctx.mesh_runtime.device_put_replicated(self._value)
        return self._device_value

    def unpersist(self) -> None:
        self._device_value = None

    def destroy(self) -> None:
        self._device_value = None
        self._value = None


class Accumulator:
    """Driver-merged counter (ref: util/AccumulatorV2.scala:44). In the SPMD
    model task-side partials are device scalars summed into host state after
    each step."""

    def __init__(self, initial: float = 0.0, name: str = ""):
        self.name = name
        self._value = initial
        self._lock = threading.Lock()

    def add(self, v) -> None:
        with self._lock:
            self._value += float(v)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0.0


class CycloneContext:
    def __init__(self, conf: Optional[CycloneConf] = None,
                 master: Optional[str] = None, app_name: Optional[str] = None):
        global _active_context
        # the start's own spans are recorded at the end, once a tracer is
        # installed (it is installed half-way down)
        t_start = time.perf_counter()
        with _active_lock:
            if _active_context is not None and not _active_context._stopped:
                raise RuntimeError(
                    "An active CycloneContext already exists in this process; "
                    "use CycloneContext.get_or_create() or stop() it first.")
        self.conf = (conf or CycloneConf()).clone()
        if master is not None:
            self.conf.set(MASTER, master)
        if app_name is not None:
            self.conf.set(APP_NAME, app_name)
        self.app_id = f"cyclone-{int(time.time())}-{uuid.uuid4().hex[:6]}"
        self.app_name = self.conf.get(APP_NAME)

        self.listener_bus = ListenerBus()
        self._journal: Optional[EventJournal] = None
        if self.conf.get(EVENT_LOG_ENABLED):
            d = self.conf.get(EVENT_LOG_DIR)
            os.makedirs(d, exist_ok=True)
            self._journal = EventJournal(os.path.join(d, f"{self.app_id}.jsonl"))
            self.listener_bus.add_listener(self._journal)
        self.listener_bus.start()

        self._status_listener = AppStatusListener()
        self.listener_bus.add_listener(self._status_listener)

        # multihost conf (cyclone.multihost.*) feeds the bootstrap defaults
        # and the hierarchical mesh shape; a mesh built ahead of the
        # context (the worker-script idiom) is adopted as-is
        from cycloneml_tpu.conf import (MULTIHOST_BARRIER_TIMEOUT_MS,
                                        MULTIHOST_CPU_COLLECTIVES,
                                        MULTIHOST_MODEL_PARALLELISM,
                                        MULTIHOST_REPLICAS)
        from cycloneml_tpu.multihost import bootstrap as _bootstrap
        _bootstrap.configure(
            cpu_collectives=self.conf.get(MULTIHOST_CPU_COLLECTIVES),
            barrier_timeout_ms=self.conf.get(MULTIHOST_BARRIER_TIMEOUT_MS))
        mesh_kw: Dict[str, Any] = {}
        if self.conf.get(MULTIHOST_REPLICAS):
            mesh_kw["n_replicas"] = self.conf.get(MULTIHOST_REPLICAS)
        if self.conf.get(MULTIHOST_MODEL_PARALLELISM) > 1:
            mesh_kw["model_parallelism"] = \
                self.conf.get(MULTIHOST_MODEL_PARALLELISM)
        t_mesh = time.perf_counter()
        self.mesh_runtime = mesh_mod.get_or_create(self.conf.get(MASTER),
                                                   **mesh_kw)
        t_services = time.perf_counter()

        # context-owned storage tiers (BlockManager analog): every
        # persisted/cached numeric dataset registers here, so conf budgets
        # bound HBM/RAM held by cold cached blocks (r3 verdict item 6 —
        # the manager was opt-in construction before)
        from cycloneml_tpu.conf import (STORAGE_DEVICE_BUDGET,
                                        STORAGE_HOST_BUDGET)
        from cycloneml_tpu.dataset.storage import StorageManager
        dev_b = self.conf.get(STORAGE_DEVICE_BUDGET)
        host_b = self.conf.get(STORAGE_HOST_BUDGET)
        self.storage = StorageManager(
            device_budget=dev_b or None, host_budget=host_b or None)

        self._next_broadcast = 0
        self._next_job = 0
        self._job_stack: List[int] = []
        # job/rebuild mutual exclusion: run_job brackets count themselves
        # in under this condition, and a mesh rebuild (allocation scale-up)
        # may only begin while the count is zero — closing the window where
        # a job starting between "is a job active?" and rebuild_mesh() had
        # its compiled step torn down mid-flight (advisor r4)
        self._job_cond = threading.Condition()
        self._active_jobs = 0
        self._mesh_rebuild_in_flight = False
        self._job_steps: Dict[int, int] = {}
        self._stopped = False
        self._accumulators: List[Accumulator] = []
        self._heartbeats = None
        self._hb_lock = threading.Lock()
        self._speculators: List[Any] = []  # armed by mesh_supervisor()
        self._autoscalers: List[Any] = []  # built by autoscaler()

        # cross-process liveness: when a driver heartbeat address is
        # configured, this process pings it over TCP (the wire leg of
        # HeartbeatReceiver; ref HeartbeatReceiver.scala:37)
        self._hb_sender = None
        self._hb_server = None
        from cycloneml_tpu.conf import (DRIVER_HEARTBEAT_ADDRESS,
                                        HEARTBEAT_INTERVAL_MS, WORKER_ID)
        hb_addr = self.conf.get(DRIVER_HEARTBEAT_ADDRESS)
        if hb_addr:
            import socket as _socket
            from cycloneml_tpu.parallel.resilience import HeartbeatSender
            wid = self.conf.get(WORKER_ID) or \
                f"{_socket.gethostname()}:{os.getpid()}"
            self._hb_sender = HeartbeatSender(
                wid, hb_addr,
                interval_s=self.conf.get(HEARTBEAT_INTERVAL_MS) / 1000.0)

        self.metrics = MetricsSystem("driver", self.conf.get(METRICS_PERIOD_S))
        for name in [s.strip() for s in self.conf.get(METRICS_SINKS).split(",")
                     if s.strip()]:
            if name == "console":
                self.metrics.register_sink(ConsoleSink())
            elif name == "csv":
                self.metrics.register_sink(CsvSink(self.conf.get(METRICS_CSV_DIR)))
            elif name == "prometheus":
                self.prometheus_port = self.metrics.start_prometheus(
                    self.conf.get(PROMETHEUS_PORT))
            else:
                logger.warning("unknown metrics sink %r", name)
        self.metrics.registry.gauge("mesh.devices",
                                    lambda: self.mesh_runtime.n_devices)
        self.metrics.registry.gauge(
            "listenerBus.queued", lambda: self.listener_bus.metrics["queued"])
        # live device-memory telemetry (HBM gauges where the backend
        # reports memory_stats; always a 1/0 availability gauge — CPU has
        # none, see docs/observability.md backend matrix)
        from cycloneml_tpu.observe import costs as _costs
        _costs.register_memory_gauges(self.metrics.registry)
        self.metrics.start()

        # step-level tracing (observe/): conf or CYCLONE_TRACE env var; the
        # context only disables a tracer it installed itself, so a tracer
        # enabled programmatically (tests, bench) survives ctx teardown.
        # A configured trace-collector address (the deploy launch env's
        # conf seed) also demands full tracing — the submitting process
        # asked for a distributed trace of this app.
        from cycloneml_tpu.conf import (
            COLLECT_ADDRESS, COLLECT_INTERVAL_MS, COLLECT_MAX_BATCH,
            FLIGHT_ENABLED, FLIGHT_MIN_INTERVAL_MS, FLIGHT_RING_SPANS,
            SKEW_ENABLED, TRACE_ENABLED, TRACE_MAX_SPANS,
        )
        self._trace_owner = False
        collect_addr = self.conf.get(COLLECT_ADDRESS)
        want_trace = self.conf.get(TRACE_ENABLED) or bool(collect_addr) or \
            os.environ.get("CYCLONE_TRACE", "").lower() not in \
            ("", "0", "false", "no")
        if want_trace and _tracing.full_active() is None:
            # enable() also UPGRADES an installed flight ring to full
            _tracing.enable(max_spans=self.conf.get(TRACE_MAX_SPANS),
                            registry=self.metrics.registry)
            self._trace_owner = True

        # always-on flight recorder: a bounded span ring when full tracing
        # is off, dumped to cyclone.trace.dir on triggers (fault firing,
        # mesh rebuild, serving shed, SLO breach) — observe/flight.py
        from cycloneml_tpu.observe import flight as _flight
        self._flight_owner = False
        if self.conf.get(FLIGHT_ENABLED) and _tracing.active() is None:
            _flight.enable(ring_spans=self.conf.get(FLIGHT_RING_SPANS))
            self._flight_owner = True
        from cycloneml_tpu.conf import DOCTOR_FLIGHT_DIAGNOSIS as _DOCTOR_FD
        from cycloneml_tpu.conf import TRACE_DIR as _TRACE_DIR
        _flight.configure(
            dump_dir=self.conf.get(_TRACE_DIR) or None,
            min_interval_s=self.conf.get(FLIGHT_MIN_INTERVAL_MS) / 1e3,
            diagnose=self.conf.get(_DOCTOR_FD))

        # one clock: every live span of whichever tracer is active (flight
        # ring or full) is also an event of a jax.profiler capture, so
        # ``with ctx.profile(dir):`` shows the program's spans above the
        # device operations. TraceAnnotation records only while a profiler
        # session is open; observe/ itself stays jax-free
        tracer = _tracing.active()
        if tracer is not None:
            import jax
            tracer.annotation = jax.profiler.TraceAnnotation
        # and what jax stages beneath the program's spans becomes ``staging``
        # spans of whichever tracer is active then (observe/tracing.py): a
        # tracer enabled after this context is served too
        _register_staging_listeners()

        # distributed-trace adoption + span shipping (observe/collect.py):
        # a deploy-launched app joins the submitting process's trace
        trace_env_id = os.environ.get("CYCLONE_TRACE_ID", "")
        if tracer is not None and trace_env_id:
            tracer.set_trace_context(
                trace_env_id, os.environ.get("CYCLONE_TRACE_PARENT", ""))
        self._shipper = None
        if collect_addr and tracer is not None:
            from cycloneml_tpu.conf import WORKER_ID as _WORKER_ID
            from cycloneml_tpu.observe.collect import SpanShipper
            label = self.conf.get(_WORKER_ID)
            if not label:
                proc_id = os.environ.get("CYCLONE_PROC_ID", "")
                label = f"proc{proc_id}" if proc_id else \
                    f"{__import__('socket').gethostname()}:{os.getpid()}"
            batch = self.conf.get(COLLECT_MAX_BATCH)
            self._shipper = SpanShipper(
                collect_addr, host_label=label,
                interval_s=self.conf.get(COLLECT_INTERVAL_MS) / 1e3,
                max_batch=batch,
                # the conf contract: a collector outage buffers 16x a
                # batch before drop-counting oldest
                max_buffer=16 * batch)

        # online skew/straggler detection (observe/skew.py): installed
        # process-globally so the oocore/serving/heartbeat lanes feed it
        # with one global read per sample
        from cycloneml_tpu.observe import skew as _skew
        self._skew_owner = False
        if self.conf.get(SKEW_ENABLED) and _skew.active() is None:
            _skew.install(_skew.SkewDetector.from_conf(
                self.conf, bus=self.listener_bus,
                registry=self.metrics.registry))
            self._skew_owner = True
        self.skew_detector = _skew.active()

        # usage attribution (observe/attribution.py): the per-job /
        # per-tenant metering ledger + its periodic UsageReport feed. The
        # context only disables a ledger it installed itself (tests and
        # bench enable programmatically). The reporter also carries the
        # telemetry drop-counter rollup so the status store / REST / web
        # UI see span loss without a scrape.
        from cycloneml_tpu.conf import (USAGE_ENABLED,
                                        USAGE_REPORT_INTERVAL_MS)
        from cycloneml_tpu.observe import attribution as _attribution
        self._usage_owner = False
        if self.conf.get(USAGE_ENABLED) and _attribution.active() is None:
            _attribution.enable(self.conf, registry=self.metrics.registry)
            self._usage_owner = True
        self._usage_reporter = None
        if _attribution.active() is not None:
            from cycloneml_tpu.conf import WORKER_ID as _WID
            host = self.conf.get(_WID)
            if not host:
                proc_id = os.environ.get("CYCLONE_PROC_ID", "")
                host = f"proc{proc_id}" if proc_id else ""
            self._usage_reporter = _attribution.UsageReporter(
                self.listener_bus,
                interval_s=self.conf.get(USAGE_REPORT_INTERVAL_MS) / 1e3,
                host=host, telemetry_fn=self._telemetry_stats)
            self._usage_reporter.start()

        from cycloneml_tpu.conf import PLUGINS
        from cycloneml_tpu.plugin import load_plugins
        self._plugins = load_plugins(
            self, self.conf.get(PLUGINS).split(","))

        self.listener_bus.post(ApplicationStart(app_name=self.app_name, app_id=self.app_id))
        self.listener_bus.post(MeshUp(
            n_devices=self.mesh_runtime.n_devices,
            platform=self.mesh_runtime.platform,
            mesh_shape=str(dict(zip(self.mesh_runtime.mesh.axis_names,
                                    self.mesh_runtime.mesh.devices.shape)))))
        with _active_lock:
            _active_context = self
        atexit.register(self.stop)
        if tracer is not None:
            # context.mesh holds the backend's initialisation when this
            # context is the first to touch jax; context.services the bus,
            # metrics, reporters and plug-ins. In the order they closed
            t_end = time.perf_counter()
            start_id = tracer.reserve_span_id()
            tracer.record_span("phase", "context.mesh", t0=t_mesh,
                               t1=t_services, parent=start_id)
            tracer.record_span("phase", "context.services", t0=t_services,
                               t1=t_end, parent=start_id)
            tracer.record_span("phase", "context.start", t0=t_start,
                               t1=t_end, span_id=start_id)

    # -- factories -------------------------------------------------------------
    @classmethod
    def get_or_create(cls, conf: Optional[CycloneConf] = None, **kw) -> "CycloneContext":
        with _active_lock:
            if _active_context is not None and not _active_context._stopped:
                return _active_context
        return cls(conf, **kw)

    @property
    def default_parallelism(self) -> int:
        n = self.conf.get(DEFAULT_PARALLELISM)
        return n if n > 0 else self.mesh_runtime.n_devices

    def broadcast(self, value: Any) -> Broadcast:
        self._next_broadcast += 1
        return Broadcast(self, value, self._next_broadcast)

    def accumulator(self, initial: float = 0.0, name: str = "") -> Accumulator:
        acc = Accumulator(initial, name)
        self._accumulators.append(acc)
        return acc

    def parallelize(self, data, num_partitions: Optional[int] = None):
        from cycloneml_tpu.dataset.dataset import PartitionedDataset
        return PartitionedDataset.from_sequence(
            self, list(data), num_partitions or self.default_parallelism)

    def read_libsvm(self, path: str, n_features: Optional[int] = None):
        from cycloneml_tpu.dataset.io import read_libsvm
        return read_libsvm(self, path, n_features)

    # -- job bracketing (events only; execution is jit dispatch) --------------
    def run_job(self, description: str, fn: Callable[[], Any]) -> Any:
        with self._job_cond:
            while self._mesh_rebuild_in_flight:
                self._job_cond.wait()
            self._active_jobs += 1
        self._next_job += 1
        jid = self._next_job
        # traced jobs open a root 'job' span; every span the fit opens in
        # this thread nests under it, and the rollup posts as a FitProfile
        tracer = _tracing.active()
        job_span = tracer.span("job", description) if tracer is not None \
            else None
        sid = ""
        mark = 0
        staged = None
        if job_span is not None:
            mark = tracer.mark()  # rollup scans only this job's spans
            job_span.__enter__()
            sid = job_span.span_id
            # what jax stages beneath this job, kept as it happens: the
            # line below and the profile read it, nothing scans the ring
            staged = tracer.open_staging_account()
        # usage attribution bracket: an un-scoped job gets an automatic
        # "job-{id}" scope (a caller's explicit attribution.scope wins),
        # and the scope row's delta across the fit lands on the profile
        from cycloneml_tpu.observe import attribution as _attribution
        led = _attribution.active()
        job_scope = None
        usage_key = ""
        usage_before = None
        if led is not None:
            sc = _attribution.current_scope()
            if sc is None:
                job_scope = _attribution.scope(f"job-{jid}")
                sc = job_scope.__enter__()
            usage_key = sc.key
            usage_before = led.row(usage_key)
        self.listener_bus.post(JobStart(job_id=jid, description=description,
                                        span_id=sid))
        self._job_stack.append(jid)
        self.metrics.registry.counter("jobs.started").inc()
        try:
            with self.metrics.registry.timer("job.duration"):
                out = fn()
        except Exception as e:
            self.listener_bus.post(JobEnd(job_id=jid, succeeded=False, error=str(e)))
            self.metrics.registry.counter("jobs.failed").inc()
            raise
        finally:
            self._job_stack.pop()
            with self._job_cond:
                self._active_jobs -= 1
                self._job_cond.notify_all()
            if job_scope is not None:
                job_scope.__exit__(None, None, None)
            if job_span is not None:
                job_span.__exit__(None, None, None)
                tracer.close_staging_account(staged)
                if staged["programs"] or staged["slowest_s"]:
                    logger.info(
                        "job %d (%s) staged %d programs: trace %.3f s, "
                        "lower %.3f s, compile %.3f s; persistent cache "
                        "%d hits / %d misses; slowest %s (%.3f s)",
                        jid, description, staged["programs"],
                        staged["trace"], staged["lower"], staged["compile"],
                        staged["cache_hit"], staged["cache_miss"],
                        staged["slowest_fun"], staged["slowest_s"])
            if job_span is not None and tracer.full:
                # profile rollups are a FULL-tracing feature: the flight
                # ring records the job span for post-hoc dumps but must
                # not pay a per-job scan/event (the always-on contract)
                try:
                    prof = tracer.profile_for(sid, since=mark)
                    prof.job_id = jid
                    prof.description = description
                    prof.staged_programs = staged["programs"]
                    prof.staging_seconds = {
                        step: staged[step]
                        for step in ("trace", "lower", "compile")}
                    prof.staging_cache_hits = staged["cache_hit"]
                    prof.staging_cache_misses = staged["cache_miss"]
                    prof.staging_slowest_fun = staged["slowest_fun"]
                    if usage_before is not None:
                        prof.job_usage = _attribution.usage_delta(
                            usage_before, led.row(usage_key))
                    self.listener_bus.post(FitProfileCompleted(
                        job_id=jid, profile=prof.to_dict()))
                except Exception:
                    logger.exception("fit profile rollup failed")
        self.listener_bus.post(JobEnd(job_id=jid, succeeded=True))
        self.metrics.registry.counter("jobs.succeeded").inc()
        return out

    def try_begin_mesh_rebuild(self) -> bool:
        """Atomically claim the mesh for a rebuild IFF no ``run_job``
        bracket is active. While claimed, new jobs block at entry until
        :meth:`end_mesh_rebuild` — so a fit starting concurrently with an
        allocation scale-up either runs entirely before the rebuild or
        entirely on the rebuilt mesh, never across it."""
        with self._job_cond:
            if self._active_jobs or self._mesh_rebuild_in_flight:
                return False
            self._mesh_rebuild_in_flight = True
            return True

    def end_mesh_rebuild(self) -> None:
        with self._job_cond:
            self._mesh_rebuild_in_flight = False
            self._job_cond.notify_all()

    @property
    def current_job_id(self) -> int:
        return self._job_stack[-1] if self._job_stack else 0

    def record_step(self, step_metrics: Dict[str, float]) -> None:
        """Post per-step metrics (≈ TaskMetrics travelling with each task;
        here one jitted step = one 'stage' of work)."""
        jid = self.current_job_id
        step = self._job_steps.get(jid, 0)
        self._job_steps[jid] = step + 1
        self.listener_bus.post(StepCompleted(
            job_id=jid, step=step, metrics=dict(step_metrics),
            span_id=_tracing.current_span_id()))
        reg = self.metrics.registry
        reg.counter("steps.completed").inc()
        for k, v in step_metrics.items():
            try:
                reg.histogram(f"step.{k}").update(float(v))
            except (TypeError, ValueError):
                pass

    def _telemetry_stats(self) -> Dict[str, Any]:
        """Drop-counter rollup across this process's telemetry stack —
        tracer ring overflow, span-shipper delivery loss, bus queue depth
        — the ``TelemetryStatsUpdated`` payload the usage reporter posts.
        A lossy pipeline must say so where the usage numbers are read."""
        stats: Dict[str, Any] = {
            "busQueued": int(self.listener_bus.metrics["queued"])}
        tracer = _tracing.active()
        if tracer is not None:
            stats["spansDropped"] = int(tracer.spans_dropped)
        shipper = getattr(self, "_shipper", None)
        if shipper is not None:
            stats["shipper"] = shipper.delivery_stats()
        return stats

    @property
    def status_store(self):
        """Live application status (≈ AppStatusStore:35, REST api/v1)."""
        return self._status_listener.store

    @property
    def heartbeat_receiver(self):
        """Host-worker liveness registry (≈ HeartbeatReceiver endpoint).
        Created lazily — single-host runs have no worker fleet to track."""
        with self._hb_lock:  # double-start would orphan a sweep thread
            if self._stopped:
                raise RuntimeError("context is stopped")
            if self._heartbeats is None:
                from cycloneml_tpu.conf import NETWORK_TIMEOUT_MS
                from cycloneml_tpu.parallel.resilience import HeartbeatReceiver
                self._heartbeats = HeartbeatReceiver(
                    timeout_s=self.conf.get(NETWORK_TIMEOUT_MS) / 1000.0,
                    listener_bus=self.listener_bus)
                self._heartbeats.start()
            return self._heartbeats

    def mesh_supervisor(self, **kw):
        """Degraded-mesh recovery + elastic-scheduling supervisor wired to
        this context: worker loss (heartbeat expiry or a step's
        DeviceLostError) → program-cache clear + mesh rebuild over the
        survivors + re-shard + resume-from-checkpoint; capacity events
        (the process-global elastic channel) → in-place reshape; latched
        straggler verdicts → speculative re-dispatch when
        ``cyclone.elastic.speculation`` is set. Pass the result as
        ``train_with_checkpoints(..., supervisor=...)``; see
        docs/resilience.md for the failure and elasticity models."""
        from cycloneml_tpu.conf import (ELASTIC_DRAIN_WINDOW_MS,
                                        ELASTIC_MAX_RESHAPES,
                                        ELASTIC_SPECULATION)
        from cycloneml_tpu.elastic import capacity as _capacity
        from cycloneml_tpu.elastic import speculation as _speculation
        from cycloneml_tpu.parallel.resilience import MeshSupervisor
        kw.setdefault("max_reshapes", self.conf.get(ELASTIC_MAX_RESHAPES))
        kw.setdefault("drain_window_s",
                      self.conf.get(ELASTIC_DRAIN_WINDOW_MS) / 1e3)
        # scale announcements (API / SIGTERM / elastic.capacity chaos
        # point) reach the training loop through the process-global
        # channel unless the caller wired its own
        kw.setdefault("capacity", _capacity.channel())
        sup = MeshSupervisor(self, **kw)
        sup.attach(self.heartbeat_receiver)
        if self.skew_detector is not None:
            # straggler verdicts land in sup.stragglers() — the elastic
            # re-dispatch's mitigation input (ROADMAP item 4)
            sup.attach_skew(self.skew_detector)
        if self.conf.get(ELASTIC_SPECULATION) \
                and _speculation.active() is None:
            sp = _speculation.Speculator(sup.stragglers)
            _speculation.install(sp)
            self._speculators.append(sp)  # disarmed + closed on stop
        from cycloneml_tpu.conf import AUTOSCALE_ENABLED
        if self.conf.get(AUTOSCALE_ENABLED) and not self._autoscalers:
            # close the elastic loop: sensors (skew/SLO/occupancy) →
            # policy → this supervisor's capacity channel. Opt-in, one
            # per context; stopped (latched) before supervisors on stop()
            self.autoscaler().start()
        return sup

    def autoscaler(self, **kw):
        """Build the SLO control loop (elastic/autoscale.py) wired to
        this context's signal plane: serving p99 from the metrics
        registry, straggler pressure + step-SLO latches from the skew
        detector, occupancy from the memory gauges — announcing on the
        process-global capacity channel. Returned unstarted (call
        ``.start()`` for the daemon loop, or drive ``tick()`` yourself);
        stopped with the context. ``cyclone.autoscale.enabled`` makes
        ``mesh_supervisor()`` arm one automatically."""
        from cycloneml_tpu.conf import AUTOSCALE_ACQUIRE_TIMEOUT_MS
        from cycloneml_tpu.elastic import autoscale as _autoscale
        from cycloneml_tpu.elastic import capacity as _capacity
        from cycloneml_tpu.elastic.policy import AutoscalePolicy
        policy = kw.pop("policy", None)
        if policy is None:
            policy = AutoscalePolicy.from_conf(self.conf)
        kw.setdefault("channel", _capacity.channel())
        kw.setdefault("detector", self.skew_detector)
        kw.setdefault("registry", self.metrics.registry)
        kw.setdefault("bus", self.listener_bus)
        kw.setdefault("used_fn", lambda: self.mesh_runtime.n_devices)
        kw.setdefault("acquire_timeout_s",
                      self.conf.get(AUTOSCALE_ACQUIRE_TIMEOUT_MS) / 1e3)
        kw.setdefault("occupancy_fn",
                      lambda: _autoscale.occupancy_fraction(self.conf))
        auto = _autoscale.Autoscaler(policy, **kw)
        self._autoscalers.append(auto)
        return auto

    def start_ui(self, host: str = "127.0.0.1", port: int = 0):
        """Serve the live status web UI (≈ SparkUI.scala:40 — jobs/steps/
        failures over the status store). Returns the server; ``.url`` is the
        address. Stopped automatically with the context."""
        from cycloneml_tpu.observe import attribution as _attribution
        from cycloneml_tpu.util.webui import StatusWebUI

        def _live_usage():
            # live ledger beats the store's last periodic UsageReport;
            # with attribution off the store (possibly replayed) serves
            led = _attribution.active()
            return led.snapshot() if led is not None \
                else self.status_store.usage_rollup()

        if getattr(self, "_web_ui", None) is None:
            self._web_ui = StatusWebUI(
                self.status_store, host, port,
                storage_usage=self.storage.usage,
                usage=_live_usage, telemetry=self._telemetry_stats)
        return self._web_ui

    def start_heartbeat_server(self, host: str = "127.0.0.1", port: int = 0):
        """Start the driver-side TCP heartbeat endpoint (≈ the
        HeartbeatReceiver RPC endpoint registration). Point each worker's
        ``cyclone.driver.heartbeatAddress`` at the returned server's
        ``.address``; expiry lands on the listener bus as WorkerLost."""
        from cycloneml_tpu.parallel.resilience import HeartbeatServer
        receiver = self.heartbeat_receiver  # raises if stopped; outside the
        # lock below because it takes _hb_lock itself
        with self._hb_lock:  # no double-start, no post-stop leak
            if self._stopped:
                raise RuntimeError("context is stopped")
            if self._hb_server is None:
                self._hb_server = HeartbeatServer(receiver, host, port)
            elif (host, port) not in ((self._hb_server.host,
                                       self._hb_server.port),
                                      ("127.0.0.1", 0)):
                raise ValueError(
                    f"heartbeat server already bound to "
                    f"{self._hb_server.address}; cannot rebind to "
                    f"{host}:{port}")
        return self._hb_server

    def with_resources(self, profile) -> "CycloneContext":
        """Stage-level scheduling decision (ref: RDD.withResources,
        rdd/RDD.scala:1806): ensure the mesh matches the profile's slice
        topology, rebuilding it when it does not. Raises if the attached
        hardware cannot satisfy the request."""
        if profile.satisfied_by(self.mesh_runtime):
            return self
        # validate feasibility BEFORE the destructive rebuild — a failed
        # request must not leave the caller without its previous mesh/data
        master = self.conf.get(MASTER)
        n = mesh_mod.probe_device_count(master)
        if n is not None:
            if profile.min_devices and n < profile.min_devices:
                raise RuntimeError(
                    f"resource profile needs {profile.min_devices} devices; "
                    f"master {master!r} provides {n}")
            split = profile.replicas * profile.model_parallelism
            if n % split != 0:
                raise RuntimeError(
                    f"{n} devices not divisible by replicas×model = {split}")
        self.rebuild_mesh(**profile.mesh_kwargs())
        if not profile.satisfied_by(self.mesh_runtime):
            raise RuntimeError(
                f"mesh for master {master!r} "
                f"({self.mesh_runtime.n_devices} devices) cannot satisfy "
                f"profile {profile}")
        return self

    def decommission(self, master: Optional[str] = None, **mesh_kwargs):
        """Planned scale-down with cached-block MIGRATION (ref:
        storage/BlockManagerDecommissioner.scala:40 — a draining executor
        pushes its cached RDD blocks to surviving peers before exiting).

        On a device mesh the draining unit is the device set, so while the
        OLD mesh is still alive every device-tier managed dataset is
        pulled to the host tier (the migration hop; on multihost JAX the
        re-place below is a resharding device transfer), the mesh is
        rebuilt onto the surviving devices, and the datasets are re-placed
        there eagerly — bit-identical data, no recompute from source, no
        checkpoint read. UNPLANNED loss still takes :meth:`rebuild_mesh`'s
        checkpoint-based contract: after a crash there is no live mesh to
        migrate from, which is exactly the reference's split between
        decommissioning and failure recovery."""
        if not self.try_begin_mesh_rebuild():
            raise RuntimeError(
                "cannot decommission while jobs are active; retry when "
                "run_job brackets have drained")
        try:
            # raises BEFORE any teardown if a dataset cannot leave the
            # device tier — the old mesh stays intact on failure
            migrated, moved_bytes = self.storage.migrate_device_to_host()
            rt = self._rebuild_mesh_locked(master, **mesh_kwargs)
            for ds in migrated:
                ds.x  # eager re-place on the surviving devices
            self.listener_bus.post(BlocksMigrated(
                n_datasets=len(migrated), bytes=moved_bytes,
                n_devices=rt.n_devices))
            logger.info("decommission: migrated %d cached datasets "
                        "(%d bytes) onto %d devices",
                        len(migrated), moved_bytes, rt.n_devices)
            return rt
        finally:
            self.end_mesh_rebuild()

    def rebuild_mesh(self, master: Optional[str] = None, **mesh_kwargs):
        """Elastic recovery (SURVEY §5.3): tear down the mesh and bring up a
        new one — possibly smaller, possibly a spare slice — after device or
        host loss. Device-resident data dies with the old mesh; callers
        restore datasets from host copies or checkpoints and resume from the
        last optimizer-state checkpoint (lineage recomputation does not
        translate to TPU; checkpoint-based recovery does). For a PLANNED
        scale-down prefer :meth:`decommission`, which migrates cached
        blocks instead."""
        return self._rebuild_mesh_locked(master, **mesh_kwargs)

    def _rebuild_mesh_locked(self, master: Optional[str] = None,
                             **mesh_kwargs):
        mesh_mod.reset()
        self.mesh_runtime = mesh_mod.get_or_create(
            master or self.conf.get(MASTER), **mesh_kwargs)
        self.listener_bus.post(MeshUp(
            n_devices=self.mesh_runtime.n_devices,
            platform=self.mesh_runtime.platform,
            mesh_shape=str(dict(zip(self.mesh_runtime.mesh.axis_names,
                                    self.mesh_runtime.mesh.devices.shape)))))
        logger.info("mesh rebuilt: %d devices", self.mesh_runtime.n_devices)
        return self.mesh_runtime

    def profile(self, log_dir: str):
        """Capture a device trace for a code region (≈ §5.1: per-step
        XPlane traces replace the reference's per-task metrics UI):
        ``with ctx.profile('/tmp/trace'): step()`` then inspect with
        TensorBoard/xprof."""
        import jax
        return jax.profiler.trace(log_dir)

    def export_trace(self, path: str) -> str:
        """Write the step-level Chrome trace (observe/) collected so far to
        ``path``; requires tracing to be enabled (cyclone.trace.enabled /
        CYCLONE_TRACE). Load the file in Perfetto or chrome://tracing."""
        tracer = _tracing.active()
        if tracer is None:
            raise RuntimeError(
                "tracing is not enabled; set cyclone.trace.enabled=true "
                "(or CYCLONE_TRACE=1) before creating the context")
        return tracer.export_chrome_trace(path)

    def fit_profile(self, job_id: Optional[int] = None):
        """FitProfile dict for ``job_id`` (default: the most recent job
        that has one), or {} when tracing was off."""
        store = self.status_store
        if job_id is not None:
            return store.profile(job_id)
        return store.latest_profile()

    def diagnose(self, spans=None):
        """Run the performance doctor (observe/diagnose.py) over the
        live telemetry plane: the active tracer's spans (or ``spans``),
        the installed SkewDetector's lane snapshot, the latest serving
        rollup and the shard-set cache stats. Posts a
        ``DiagnosisCompleted`` event so ``/api/v1/diagnosis``, the web
        UI and journal replay all see the report; returns it."""
        from cycloneml_tpu.observe.diagnose import diagnose as _diagnose
        from cycloneml_tpu.util.events import DiagnosisCompleted
        if spans is None:
            tracer = _tracing.active()
            spans = tracer.snapshot() if tracer is not None else []
        report = _diagnose(
            spans=spans, conf=self.conf,
            serving_stats=self.status_store.serving_stats() or None,
            source="live")
        self.listener_bus.post(DiagnosisCompleted(
            source=report.source, n_findings=len(report.findings),
            report=report.to_dict()))
        return report

    @property
    def checkpoint_dir(self) -> str:
        return self.conf.get(CHECKPOINT_DIR)

    def set_checkpoint_dir(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        self.conf.set(CHECKPOINT_DIR, path)

    def stop(self) -> None:
        global _active_context
        # stopped-flag flip AND heartbeat-machinery capture in ONE lock
        # acquisition, pairing with the lazy creators: two concurrent
        # stop() calls race the unguarded check-then-act (double
        # ApplicationEnd, double plugin shutdown), and a creator between
        # the flag flip and the old unguarded `self._hb_server` read
        # leaves an orphaned server thread. The (blocking) .stop() joins
        # run AFTER release — holding `_hb_lock` across a thread join
        # convoys every heartbeat_receiver caller.
        with self._hb_lock:
            if self._stopped:
                return
            self._stopped = True
            heartbeats, self._heartbeats = self._heartbeats, None
            hb_sender, self._hb_sender = self._hb_sender, None
            hb_server, self._hb_server = self._hb_server, None
        self.listener_bus.post(ApplicationEnd(app_id=self.app_id))
        for p in getattr(self, "_plugins", []):
            try:
                p.shutdown()
            except Exception:
                logger.exception("plugin shutdown failed")
        if heartbeats is not None:
            heartbeats.stop()
        if hb_sender is not None:
            hb_sender.stop()
        if hb_server is not None:
            hb_server.stop()
        if getattr(self, "_web_ui", None) is not None:
            self._web_ui.stop()
        if getattr(self, "storage", None) is not None:
            self.storage.close()  # spill files + dir, never leaked to /tmp
        try:
            # release the exchange listener THIS context's conf introduced
            # (servers are shared across rounds, not across contexts with
            # different addresses — advisor r4)
            from cycloneml_tpu.conf import EXCHANGE_ADDRESSES, EXCHANGE_RANK
            addrs_s = self.conf.get(EXCHANGE_ADDRESSES)
            if addrs_s:
                addrs = [a.strip() for a in addrs_s.split(",") if a.strip()]
                rank = self.conf.get(EXCHANGE_RANK)
                if 0 <= rank < len(addrs):
                    from cycloneml_tpu.parallel.exchange import \
                        _ExchangeServer
                    _ExchangeServer.close_address(addrs[rank])
        except Exception:
            logger.exception("exchange server shutdown failed")
        if getattr(self, "_shipper", None) is not None:
            # final flush BEFORE any tracer teardown: the collector must
            # see every span this app recorded, including ApplicationEnd's
            self._shipper.stop(flush=True)
        if getattr(self, "_usage_reporter", None) is not None:
            # final UsageReport flush while the tracer/shipper still
            # exist: the journal carries the complete ledger for replay
            # and the last TelemetryStatsUpdated still sees span loss
            try:
                self._usage_reporter.stop()
            except Exception:
                logger.exception("usage reporter shutdown failed")
            self._usage_reporter = None
        self._shipper = None
        if getattr(self.mesh_runtime, "is_multihost", False):
            # barriered multihost teardown: sync every process before
            # disconnecting so no peer exits while another is
            # mid-collective; a dead peer bounds the wait at
            # cyclone.multihost.barrierTimeoutMs
            try:
                from cycloneml_tpu.multihost import bootstrap as _bootstrap
                _bootstrap.shutdown(barrier_first=True)
            except Exception:
                logger.exception("multihost teardown failed")
        for a in getattr(self, "_autoscalers", []):
            # stop the control plane BEFORE the supervisors it feeds:
            # the latch guarantees no decision lands on a stopping mesh
            try:
                a.stop()
            except Exception:
                logger.exception("autoscaler shutdown failed")
        self._autoscalers = []
        for sp in getattr(self, "_speculators", []):
            # disarm BEFORE closing: a staging thread mid-race keeps its
            # already-submitted backup; new sites fall back to plain work
            from cycloneml_tpu.elastic import speculation as _speculation
            _speculation.uninstall(sp)
            try:
                sp.close()
            except Exception:
                logger.exception("speculator shutdown failed")
        self._speculators = []
        if getattr(self, "_skew_owner", False):
            from cycloneml_tpu.observe import skew as _skew
            _skew.uninstall()
        if getattr(self, "_flight_owner", False):
            from cycloneml_tpu.observe import flight as _flight
            _flight.disable()
        if getattr(self, "_trace_owner", False):
            # full_active: the full tracer this context installed (never a
            # flight ring someone else slipped in after a disable)
            tracer = _tracing.full_active()
            if tracer is not None:
                from cycloneml_tpu.conf import TRACE_DIR
                d = self.conf.get(TRACE_DIR)
                if d:
                    try:
                        os.makedirs(d, exist_ok=True)
                        path = os.path.join(d, f"{self.app_id}.trace.json")
                        tracer.export_chrome_trace(path)
                        logger.info("trace exported to %s", path)
                    except Exception:
                        logger.exception("trace export failed")
                _tracing.disable()
        if getattr(self, "_usage_owner", False):
            from cycloneml_tpu.observe import attribution as _attribution
            _attribution.disable()
        self.metrics.stop()
        self.listener_bus.stop()
        if self._journal is not None:
            self._journal.close()
        with _active_lock:
            if _active_context is self:
                _active_context = None

    def __enter__(self) -> "CycloneContext":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
