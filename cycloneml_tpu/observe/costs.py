"""XLA cost & HBM accounting: what the compiler thinks a program costs.

PR 3's spans attribute *time*; this module attributes *work*. The
expensive facts about a staged computation — FLOPs, bytes touched, peak
HBM across arguments/outputs/temporaries — are decided once at compile
time and then normally discarded (the staged-computation blind spot of
Frostig et al., SysML 2018). XLA exposes them on the AOT stages:
``jitted.lower(*args).cost_analysis()`` (flops / bytes accessed, works on
every backend) and ``lowered.compile().memory_analysis()``
(argument/output/temp/generated-code bytes — the OOM-relevant per-device
footprint). This module harvests both at the one narrow waist where every
SPMD program is born — the bounded program cache + the ``compile`` span of
``_instrument_dispatch`` and the chunked-optimizer dispatch loops — into a
process-global per-program registry keyed by program-cache identity, and
:class:`~cycloneml_tpu.observe.profile.FitProfile` rolls the entries up
per fit against the roofline model (Williams et al. 2009, PAPERS.md).

Cost discipline mirrors tracing's: with tracing disabled and no explicit
memory budget configured, NO ``cost_analysis`` call ever happens — the
harvest path at every site is one module-global read (pinned by a no-op
test). When harvesting IS on, each program pays one extra AOT
lower+compile: JAX's dispatch cache and its AOT cache are separate, so the
``memory_analysis`` compile is a second XLA compile of the same program
(absorbed by the persistent compilation cache on TPU deployments; ~ms on
CPU). Availability degrades gracefully per backend: CPU reports
cost_analysis + memory_analysis but ``device.memory_stats()`` is ``None``;
fields that a backend cannot report stay ``None`` ("unavailable") rather
than guessed.

The same numbers feed the compile-time memory budget guard: when a
program's predicted peak HBM exceeds ``cyclone.memory.budgetFraction`` ×
device memory, a ``MemoryBudgetExceeded`` event is posted (warn-only by
default; ``cyclone.memory.budgetAction=raise`` escalates) and the chunked
L-BFGS paths shrink ``deviceChunk`` proportionally instead of OOMing.
"""

from __future__ import annotations

import collections
import os
import threading
import zlib
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

from cycloneml_tpu.observe import tracing
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "ProgramCost", "MemoryBudgetError", "BudgetVerdict", "OutOfCoreRequired",
    "program_id", "analyze", "ensure", "lookup", "snapshot", "clear",
    "analyze_call_count", "note_execution", "check_budget", "guard_armed",
    "select_chunk", "backend_peaks", "device_memory_limit",
    "memory_stats_available", "register_memory_gauges", "sweep_cost",
    "streamed_sweep_cost", "sample_device_peak",
]


class MemoryBudgetError(RuntimeError):
    """Raised when ``cyclone.memory.budgetAction=raise`` and a program's
    predicted peak HBM exceeds the configured budget."""


class OutOfCoreRequired(RuntimeError):
    """Internal degradation signal: the budget guard walked deviceChunk
    down to 1 and the program STILL exceeds the budget, but the caller
    declared a streaming fallback (``cyclone.oocore.mode=auto``) — the fit
    should re-route through the out-of-core epoch engine instead of
    warn-proceeding or raising. Carries the terminal :class:`BudgetVerdict`
    so the streaming path can log what it degraded from. Estimators catch
    this; it must never escape to user code."""

    def __init__(self, name: str, verdict: "BudgetVerdict"):
        super().__init__(
            f"{name}: {verdict.predicted_bytes} bytes/device predicted over "
            f"the {verdict.budget_bytes}-byte budget at deviceChunk 1 — "
            f"degrading to the out-of-core streaming engine")
        self.name = name
        self.verdict = verdict


@dataclass
class BudgetVerdict:
    """Result of one budget check (``None`` fields = limit unknown)."""

    exceeded: bool
    predicted_bytes: Optional[int]
    budget_bytes: Optional[int]
    limit_bytes: Optional[int]
    fraction: float
    action: str


@dataclass
class ProgramCost:
    """What XLA reports for ONE compiled program.

    ``flops`` / ``bytes_accessed`` are per-partition (XLA analyzes the
    per-device SPMD module); ``flops_total`` / ``bytes_accessed_total``
    scale by the device count — the mesh-wide work one execution performs.
    Memory fields are per-device bytes (the OOM-relevant number);
    ``peak_bytes`` = arguments + outputs + temporaries + generated code −
    aliased. ``None`` anywhere means the backend did not report it.
    """

    program_id: str = ""
    name: str = ""
    n_devices: int = 1
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    flops_total: Optional[float] = None
    bytes_accessed_total: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None
    cost_available: bool = False
    memory_available: bool = False

    def to_dict(self) -> Dict[str, Any]:
        import dataclasses
        return dataclasses.asdict(self)


# -- per-program registry (process-global, like the program caches) ------------

_lock = threading.Lock()
# LRU-bounded: program ids embed object identities (compiled programs,
# meshes), so program-cache eviction / mesh rebuilds mint fresh ids — an
# unbounded registry would leak exactly the way BoundedProgramCache
# exists to prevent. Eviction only loses a cost entry for a program that
# would re-harvest on its next traced dispatch.
MAX_REGISTRY_ENTRIES = 512
_registry: "collections.OrderedDict[str, Dict[str, Any]]" = \
    collections.OrderedDict()
_n_analyze_calls = 0
_cumulative_flops = 0.0
# tri-state: None = not probed yet; False = backend has no memory_stats
_mem_stats_ok: Optional[bool] = None


def analyze_call_count() -> int:
    """How many times :func:`analyze` ran — the no-op tests pin that this
    stays flat across untraced fits (the disabled path never lowers)."""
    return _n_analyze_calls


def lookup(pid: str) -> Optional[Dict[str, Any]]:
    with _lock:
        e = _registry.get(pid)
        if e is None:
            return None
        _registry.move_to_end(pid)
        return dict(e)


def snapshot() -> Dict[str, Dict[str, Any]]:
    with _lock:
        return {k: dict(v) for k, v in _registry.items()}


def clear() -> None:
    global _cumulative_flops
    with _lock:
        _registry.clear()
        _cumulative_flops = 0.0


def _describe_part(p: Any) -> str:
    if callable(p):
        return getattr(p, "__qualname__",
                       getattr(p, "__name__", type(p).__name__))
    axis_names = getattr(p, "axis_names", None)
    if axis_names is not None and hasattr(p, "devices"):
        return "mesh[" + ",".join(
            f"{a}={s}" for a, s in zip(axis_names, p.devices.shape)) + "]"
    return repr(p)


def program_id(name: str, key: Any, jitted: Any = None) -> str:
    """Stable-within-process identity string for a program-cache key.

    Readable prefix (the cache key's parts) + a checksum of the full key
    repr, so distinct keys cannot collide on a truncated prefix. Unhashable
    / keyless programs fall back to the jitted object's identity.
    """
    if key is None:
        return f"{name}#anon{(id(jitted) & 0xFFFFFFFF):08x}"
    parts = key if isinstance(key, tuple) else (key,)
    desc = "/".join(_describe_part(p) for p in parts)
    crc = zlib.crc32(repr(parts).encode("utf-8", "replace")) & 0xFFFFFFFF
    return f"{name}/{desc[:80]}#{crc:08x}"


def analyze(jitted: Any, args: tuple, name: str = "",
            pid: str = "") -> ProgramCost:
    """Run XLA's cost + memory analysis over ``jitted`` at ``args``.

    Never raises: every backend gap degrades to ``None`` fields. Pays one
    retrace (``lower``) and — for the memory side — one AOT compile (see
    module docstring for why that compile cannot reuse the dispatch
    cache's executable).
    """
    global _n_analyze_calls
    with _lock:
        _n_analyze_calls += 1
    cost = ProgramCost(program_id=pid, name=name)
    try:
        import jax
        cost.n_devices = jax.device_count()
    except Exception:
        return cost
    try:
        lowered = jitted.lower(*args)
    except Exception:
        logger.debug("cost harvest: lower() failed for %s", name,
                     exc_info=True)
        return cost
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = ca.get("flops")
        nbytes = ca.get("bytes accessed")
        if flops is not None and flops >= 0:
            cost.flops = float(flops)
            cost.flops_total = float(flops) * cost.n_devices
        if nbytes is not None and nbytes >= 0:
            cost.bytes_accessed = float(nbytes)
            cost.bytes_accessed_total = float(nbytes) * cost.n_devices
        cost.cost_available = cost.flops is not None
    except Exception:
        logger.debug("cost harvest: cost_analysis unavailable for %s", name,
                     exc_info=True)
    try:
        ma = lowered.compile().memory_analysis()
        if ma is not None:
            cost.argument_bytes = int(ma.argument_size_in_bytes)
            cost.output_bytes = int(ma.output_size_in_bytes)
            cost.temp_bytes = int(ma.temp_size_in_bytes)
            cost.generated_code_bytes = int(ma.generated_code_size_in_bytes)
            cost.peak_bytes = (cost.argument_bytes + cost.output_bytes
                               + cost.temp_bytes + cost.generated_code_bytes
                               - int(getattr(ma, "alias_size_in_bytes", 0)))
            cost.memory_available = True
    except Exception:
        logger.debug("cost harvest: memory_analysis unavailable for %s",
                     name, exc_info=True)
    return cost


def ensure(name: str, key: Any, jitted: Any, args: tuple) -> str:
    """Harvest-once per program: return the program id, analyzing and
    registering the program on first sight. Callers invoke this ONLY when
    harvesting is on (tracing active or the budget guard armed) — the
    disabled path must never reach here."""
    pid = program_id(name, key, jitted)
    with _lock:
        if pid in _registry:
            _registry.move_to_end(pid)
            return pid
    cost = analyze(jitted, args, name=name, pid=pid)
    with _lock:
        _registry.setdefault(pid, cost.to_dict())
        _registry.move_to_end(pid)
        while len(_registry) > MAX_REGISTRY_ENTRIES:
            _registry.popitem(last=False)
    tr = tracing.active()
    if tr is not None and cost.peak_bytes is not None:
        # one Perfetto counter sample per freshly analyzed program: the
        # predicted-peak timeline next to the spans that ran it
        tr.counter("hbm.predicted_peak_bytes", cost.peak_bytes)
    return pid


def note_execution(tr, pid: str) -> None:
    """Per-dispatch accounting while tracing: bump the cumulative-FLOPs
    counter track and sample live device memory when the backend has it."""
    global _cumulative_flops
    entry = lookup(pid)
    if entry and entry.get("flops_total"):
        with _lock:
            _cumulative_flops += entry["flops_total"]
            cum = _cumulative_flops
        tr.counter("flops.cumulative", cum)
    sample = sample_memory()
    if sample is not None:
        tr.counter("hbm.bytes_in_use", sample)


def sweep_cost(call, *extras, name: str = "sweep") -> ProgramCost:
    """XLA's accounting for ONE optimizer sweep — the canonical
    ``bytes_per_sweep`` measurement (bench.py, ``make bench-bytes`` and the
    tier-1 byte-regression test all read this one implementation).

    ``call`` is a ``tree_aggregate_fn`` call object (``.compiled`` +
    ``.arrays()``); ``extras`` are the replicated arguments the aggregator
    takes after the sharded arrays (standardization vectors, coefficients).
    Lower-only: the program is ANALYZED at its operands' avals, never
    executed — cheap enough for CI, exact enough to be ground truth
    (``bytes_accessed`` is per partition; ``bytes_accessed_total`` is the
    mesh-wide sweep). Explicit calls count toward :func:`analyze_call_count`
    — the zero-cost-when-untraced discipline binds the instrumentation
    sites, not deliberate measurement."""
    compiled = getattr(call, "compiled", call)
    # the program cache hands back the _instrument_dispatch wrapper; the
    # raw jitted program (the thing with .lower) rides its __wrapped__
    compiled = getattr(compiled, "__wrapped__", compiled)
    arrays = call.arrays() if hasattr(call, "arrays") else ()
    return analyze(compiled, (*arrays, *extras), name=name)


def streamed_sweep_cost(prog, shard_args: tuple, n_shards: int,
                        name: str = "oocore.sweep") -> ProgramCost:
    """XLA's accounting for ONE STREAMED optimizer sweep — the out-of-core
    extension of :func:`sweep_cost` (``make bench-oocore`` reads this).

    ``prog`` is the per-shard aggregation program (the
    ``_instrument_dispatch`` wrapper or the raw jitted program) and
    ``shard_args`` one representative operand tuple at the padded shard
    geometry. Work fields (``flops`` / ``bytes_accessed`` and their
    ``*_total`` mesh-wide twins) are scaled by ``n_shards`` — the whole
    epoch's traffic; MEMORY fields stay per-dispatch, because that is the
    point of the streamed sweep: peak HBM is O(shard) no matter how many
    shards the epoch walks. Lower-only, never executes."""
    compiled = getattr(prog, "__wrapped__", prog)
    cost = analyze(compiled, shard_args, name=name)
    k = max(int(n_shards), 1)
    for f in ("flops", "bytes_accessed", "flops_total",
              "bytes_accessed_total"):
        v = getattr(cost, f)
        if v is not None:
            setattr(cost, f, v * k)
    return cost


# -- live device-memory telemetry ----------------------------------------------

def memory_stats_available() -> bool:
    """Whether ``device.memory_stats()`` reports on this backend (TPU/GPU
    yes; CPU returns ``None`` — the availability matrix in
    docs/observability.md)."""
    global _mem_stats_ok
    if _mem_stats_ok is None:
        try:
            import jax
            _mem_stats_ok = jax.devices()[0].memory_stats() is not None
        except Exception:
            _mem_stats_ok = False
    return _mem_stats_ok


def sample_memory() -> Optional[int]:
    """Total ``bytes_in_use`` across devices, or ``None`` when the backend
    does not report (probed once, then one bool read per call on CPU)."""
    if not memory_stats_available():
        return None
    try:
        import jax
        return sum(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                   for d in jax.local_devices())
    except Exception:
        return None


def sample_device_peak() -> Optional[int]:
    """MAX ``bytes_in_use`` over local devices — the admission-relevant
    occupancy: a plain-jit dispatch allocates on one device, so averaging
    the total across an 8-device host would understate the hot device by
    up to 8x. ``None`` when the backend does not report."""
    if not memory_stats_available():
        return None
    try:
        import jax
        return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                   for d in jax.local_devices())
    except Exception:
        return None


def register_memory_gauges(registry) -> bool:
    """Install live ``device.memory_stats()`` gauges into a
    :class:`~cycloneml_tpu.util.metrics.MetricsRegistry`.

    Per local device: ``device.<i>.memory.bytes_in_use`` /
    ``.peak_bytes_in_use`` / ``.bytes_limit``, plus the mesh-wide
    ``device.memory.bytes_in_use.total``. Always registers
    ``device.memoryStats.available`` (1/0) so the backend matrix is
    scrape-visible; on backends without memory_stats (CPU) that gauge is
    the only one installed. A gauge whose poll starts raising is skipped
    by the scrape, not fatal (see MetricsRegistry.values).
    """
    registry.gauge("device.memoryStats.available",
                   lambda: 1.0 if memory_stats_available() else 0.0)
    if not memory_stats_available():
        return False
    import jax

    def _stat(dev, k):
        return float((dev.memory_stats() or {}).get(k, float("nan")))

    for i, dev in enumerate(jax.local_devices()):
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            registry.gauge(f"device.{i}.memory.{k}",
                           lambda d=dev, k=k: _stat(d, k))
    registry.gauge("device.memory.bytes_in_use.total",
                   lambda: float(sample_memory() or 0))
    return True


# -- roofline peak table ---------------------------------------------------------

#: Published per-chip peaks, keyed by the EXACT ``device_kind`` string jax
#: reports: (dense bf16 matmul flop/s, HBM bytes/s). One row per chip the
#: program has been run on, each with its source; a chip that is not here
#: is an error (add its row), never a default or a neighbour's figures.
PEAKS_BY_DEVICE_KIND = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": (197e12, 819e9),
}


def backend_peaks(device=None) -> Tuple[Optional[float], Optional[float]]:
    """(peak matmul flop/s, peak HBM bytes/s) PER DEVICE from
    :data:`PEAKS_BY_DEVICE_KIND`. ``(None, None)`` on the CPU platform only
    (test runs — roofline fields then report unavailable); an accelerator
    whose ``device_kind`` has no row raises rather than borrowing another
    chip's figures. ``device`` defaults to the first attached device (a
    mesh is one kind of chip)."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None, None
    if device.device_kind not in PEAKS_BY_DEVICE_KIND:
        raise KeyError(
            f"no published peaks for {device.platform} device_kind "
            f"{device.device_kind!r}; add a row with its source to "
            f"observe/costs.py PEAKS_BY_DEVICE_KIND (known: "
            f"{sorted(PEAKS_BY_DEVICE_KIND)})")
    return PEAKS_BY_DEVICE_KIND[device.device_kind]


# -- compile-time memory budget guard --------------------------------------------

def device_memory_limit(conf=None) -> Optional[int]:
    """Per-device memory bytes the budget guard divides into:
    ``cyclone.memory.deviceBytes`` when set, else ``bytes_limit`` from
    ``memory_stats()``, else (host-platform devices share host RAM) total
    host RAM. ``None`` when nothing is known."""
    if conf is not None:
        try:
            from cycloneml_tpu.conf import MEMORY_DEVICE_BYTES
            override = int(conf.get(MEMORY_DEVICE_BYTES))
            if override > 0:
                return override
        except Exception:
            pass
    if memory_stats_available():
        try:
            import jax
            limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
            if limit:
                return int(limit)
        except Exception:
            pass
    try:
        return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError, AttributeError):
        return None


def guard_armed(conf) -> bool:
    """The guard costs an AOT analysis, so it arms only when someone asked
    for it: an explicit ``cyclone.memory.budgetFraction`` in the conf, or
    FULL tracing already on (the harvest is then already paid). The
    always-on flight-recorder ring (``Tracer.full`` False) does NOT arm it
    — flight mode's whole contract is recording spans at near-zero cost."""
    from cycloneml_tpu.conf import MEMORY_BUDGET_FRACTION
    return (conf.contains_raw(MEMORY_BUDGET_FRACTION.key)
            or tracing.full_active() is not None)


def check_budget(pid: str, conf=None, bus=None,
                 allow_raise: bool = True) -> Optional[BudgetVerdict]:
    """Compare a registered program's predicted peak HBM against the
    configured budget. On excess: post ``MemoryBudgetExceeded`` (to ``bus``
    or the active context's listener bus), warn, and raise ONLY under
    ``cyclone.memory.budgetAction=raise`` — the default mode never throws.
    Callers with a degradation option (the chunked L-BFGS guard) pass
    ``allow_raise=False`` while candidates remain and escalate themselves
    once the options are exhausted, so raise-mode still degrades first.
    Returns ``None`` when the program/conf/limit is unknown."""
    entry = lookup(pid)
    if entry is None or entry.get("peak_bytes") is None:
        return None
    if conf is None or bus is None:
        from cycloneml_tpu.context import active_context
        ctx = active_context()
        if ctx is not None:
            conf = conf if conf is not None else ctx.conf
            bus = bus if bus is not None else ctx.listener_bus
    if conf is None:
        return None
    from cycloneml_tpu.conf import MEMORY_BUDGET_ACTION, MEMORY_BUDGET_FRACTION
    fraction = float(conf.get(MEMORY_BUDGET_FRACTION))
    action = str(conf.get(MEMORY_BUDGET_ACTION))
    limit = device_memory_limit(conf)
    if not limit:
        return None
    budget = int(limit * fraction)
    peak = int(entry["peak_bytes"])
    verdict = BudgetVerdict(exceeded=peak > budget, predicted_bytes=peak,
                            budget_bytes=budget, limit_bytes=limit,
                            fraction=fraction, action=action)
    if not verdict.exceeded:
        return verdict
    logger.warning(
        "memory budget exceeded: program %s predicts %d bytes peak HBM "
        "per device > budget %d (%.3g of %d); action=%s",
        pid, peak, budget, fraction, limit, action)
    if bus is not None:
        from cycloneml_tpu.util.events import MemoryBudgetExceeded
        bus.post(MemoryBudgetExceeded(
            program=pid, predicted_bytes=peak, budget_bytes=budget,
            limit_bytes=limit, fraction=fraction, action=action))
    if action == "raise" and allow_raise:
        raise MemoryBudgetError(
            f"program {pid} predicts {peak} bytes peak HBM per device, "
            f"over the {budget}-byte budget "
            f"({fraction:g} x {limit}); set cyclone.memory.budgetAction="
            f"warn (default) to degrade instead")
    return verdict


def select_chunk(chunk: int, predicted_bytes: int, budget_bytes: int) -> int:
    """FIRST GUESS at a degraded ``deviceChunk`` for an over-budget chunk
    program: proportional scale-down, floored at 1 and always strictly
    below the chunk that was just predicted not to fit. Much of a chunk
    program's footprint is chunk-INDEPENDENT (data arrays, coefficients,
    curvature history), so this guess can still be over budget — callers
    (``device_lbfgs._budget_guarded_chunk``) must re-analyze the rebuilt
    program and iterate (with halving, which guarantees progress) until it
    fits or chunk reaches 1. Chunk size never changes the trajectory
    (pinned by the chunk-size-invariance tests), only the dispatch count."""
    if predicted_bytes <= budget_bytes or chunk <= 1:
        return chunk
    scaled = int(chunk * budget_bytes / max(predicted_bytes, 1))
    return max(1, min(scaled, chunk - 1))
