"""Per-fit profile aggregation over recorded spans.

The TPU-native analog of the reference's ``TaskMetrics`` rollup (ref:
executor/TaskMetrics.scala aggregated per stage by AppStatusListener): one
:class:`FitProfile` summarises where a fit's wall clock went — staging
(trace + XLA compile) vs steady-state dispatch vs device→host transfer —
plus the reliability counters a chaos run cares about (faults, retries,
mesh rebuilds). ``CycloneContext.run_job`` computes one per job when
tracing is enabled and posts it as a ``FitProfileCompleted`` event, so the
status store / web UI / history replay all carry it.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence


@dataclass
class FitProfile:
    """Aggregate of one fit's spans (see tracing.py for the kind taxonomy).

    ``eval_count`` sums the ``evals`` attr on dispatch spans — it matches
    the optimizer's ``n_evals`` ledger (``bench.py``'s "loss/grad evals")
    the same way ``dispatch_count`` matches ``n_dispatches``.
    ``steady_seconds`` is dispatch time excluding dispatches that paid a
    compile (their wall time is staging, not steady state).
    ``phase_seconds`` is the host's side of the fit: SELF time by
    ``phase`` span name (``fit.stats`` / ``fit.prepare`` / ``fit.optimize``
    / ``fit.finish`` / ``optim.iteration``) — a phase's duration less the
    ``phase`` and ``dispatch`` spans directly inside it — so the values sum
    to the phases' union less the dispatch time under them.
    ``staged_programs`` / ``staging_seconds`` / ``staging_cache_hits`` /
    ``staging_cache_misses`` / ``staging_slowest_fun`` are what jax reported
    staging beneath the job (``compile_seconds`` is the program's bracket:
    first dispatches of new program objects, execution included).
    ``n_models`` is the model-axis width of the fit's dispatches (stacked
    fits — ``n_models`` > 1 — amortize every compile in this profile over
    that many models; see docs/multi-model.md).

    The cost block comes from XLA's own accounting (``observe.costs``;
    docs/observability.md has the units + backend availability matrix):
    ``programs`` holds one entry per program-cache identity the fit
    dispatched (executions × what XLA reports per execution);
    ``total_flops`` / ``total_bytes_accessed`` are the mesh-wide totals;
    ``hbm_peak_bytes`` is the largest per-device footprint (arguments +
    outputs + temporaries + generated code) of any dispatched program —
    the OOM-relevant number; ``achieved_flops`` is the steady-state
    executions' FLOPs over those same executions' dispatch time (staging
    executions excluded from both sides); ``arithmetic_intensity`` is
    FLOPs per byte
    accessed. (No share of a roofline is derived here: XLA's analysis
    cannot see inside a Mosaic custom call, so on the chip the harvest is
    ``unavailable``; the benchmark measures kernel rooflines and the
    fit's share of the HBM peak from the device trace — PERF.md §3.)
    Every cost field is ``None`` — explicitly "unavailable" —
    when the backend (or an untraced run) cannot report it;
    ``cost_availability`` summarizes (``full`` / ``flops_only`` /
    ``unavailable``) and ``memory_stats_available`` records whether live
    ``device.memory_stats()`` telemetry existed.
    """

    job_id: int = 0
    description: str = ""
    wall_seconds: float = 0.0
    compile_count: int = 0
    compile_seconds: float = 0.0
    cache_hits: int = 0
    cache_misses: int = 0
    dispatch_count: int = 0
    dispatch_seconds: float = 0.0
    steady_seconds: float = 0.0
    eval_count: int = 0
    collective_count: int = 0
    collective_seconds: float = 0.0
    transfer_count: int = 0
    transfer_seconds: float = 0.0
    transfer_bytes: int = 0
    checkpoint_saves: int = 0
    checkpoint_restores: int = 0
    checkpoint_seconds: float = 0.0
    retries: int = 0
    rebuilds: int = 0
    faults_injected: int = 0
    n_models: int = 1
    phase_seconds: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    # what jax staged beneath the job (``staging`` spans; run_job fills
    # these from the tracer's per-thread account, the numbers its INFO line
    # prints): compile steps reported, seconds of the outermost events by
    # step, the persistent cache's answers, the slowest event's function
    staged_programs: int = 0
    staging_seconds: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    staging_cache_hits: int = 0
    staging_cache_misses: int = 0
    staging_slowest_fun: str = ""
    # fp8 tier fallbacks during this fit: the envelope probe (or a
    # non-finite fp8 solution) re-routed the fit to bf16 storage — see
    # docs/mixed-precision.md and the PrecisionFallback event
    fp8_fallbacks: int = 0
    # ring overflow during this tracer's lifetime (tracing.Tracer.dropped,
    # oldest-dropped): > 0 means the rollup undercounts — the profile saw
    # only the surviving window
    spans_dropped: int = 0
    # -- XLA cost & HBM accounting (None = unavailable on this backend) --
    total_flops: Optional[float] = None
    total_bytes_accessed: Optional[float] = None
    hbm_peak_bytes: Optional[int] = None
    hbm_argument_bytes: Optional[int] = None
    hbm_output_bytes: Optional[int] = None
    hbm_temp_bytes: Optional[int] = None
    achieved_flops: Optional[float] = None
    arithmetic_intensity: Optional[float] = None
    n_devices: int = 0
    cost_availability: str = "unavailable"
    memory_stats_available: bool = False
    programs: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    # this job's usage-ledger delta (observe.attribution): what the job's
    # scope row gained between run_job entry and exit — device-seconds,
    # FLOPs, h2d bytes etc. Empty when attribution was off for the fit.
    job_usage: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FitProfile":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    @classmethod
    def from_spans(cls, spans: Sequence[Any],
                   root_id: Optional[str] = None,
                   cost_lookup: Optional[Any] = None) -> "FitProfile":
        """Fold spans into a profile. With ``root_id``, only spans whose
        parent chain reaches that span (plus the root itself) count — the
        per-job scoping ``run_job`` uses. ``cost_lookup`` maps a program id
        (the ``program`` attr harvest puts on dispatch/collective spans) to
        its registered cost entry; defaults to the process-global
        ``observe.costs`` registry."""
        if root_id:
            parent = {s.span_id: s.parent_id for s in spans}
            selected: List[Any] = []
            member: Dict[str, bool] = {root_id: True}

            def in_tree(sid: str) -> bool:
                chain = []
                while sid and sid not in member:
                    chain.append(sid)
                    sid = parent.get(sid, "")
                verdict = bool(sid) and member[sid]
                for c in chain:
                    member[c] = verdict
                return verdict

            for s in spans:
                if s.span_id == root_id or in_tree(s.span_id):
                    selected.append(s)
            spans = selected

        p = cls()
        compiles: List[Any] = []
        dispatches: List[Any] = []
        for s in spans:
            dur = s.duration_s
            k = s.kind
            if k == "job":
                if root_id is None or s.span_id == root_id:
                    p.wall_seconds = max(p.wall_seconds, dur)
                    p.description = p.description or s.name
            elif k == "compile":
                p.compile_count += 1
                p.compile_seconds += dur
                compiles.append(s)
            elif k == "dispatch":
                p.dispatch_count += 1
                p.dispatch_seconds += dur
                p.eval_count += int(s.attrs.get("evals", 0))
                p.n_models = max(p.n_models,
                                 int(s.attrs.get("n_models", 1)))
                dispatches.append(s)
            elif k == "collective":
                p.collective_count += 1
                p.collective_seconds += dur
            elif k == "transfer":
                p.transfer_count += 1
                p.transfer_seconds += dur
                p.transfer_bytes += int(s.attrs.get("bytes", 0))
            elif k == "checkpoint":
                if s.name == "save":
                    p.checkpoint_saves += 1
                    p.checkpoint_seconds += dur
                elif s.name == "restore":
                    p.checkpoint_restores += 1
                    p.checkpoint_seconds += dur
            elif k == "rebuild":
                p.rebuilds += 1
            elif k == "instant":
                if s.name == "fault":
                    p.faults_injected += 1
                elif s.name == "retry":
                    p.retries += 1
                elif s.name == "cache.hit":
                    p.cache_hits += 1
                elif s.name == "cache.miss":
                    p.cache_misses += 1
                elif s.name == "precision.fallback":
                    p.fp8_fallbacks += 1
        # steady state = dispatches that did not pay a compile anywhere in
        # their subtree. A compile may nest more than one level down
        # (loss.eval dispatch → tree_aggregate collective → compile), so
        # every ANCESTOR of a compile span is staging, not steady state.
        parents = {s.span_id: s.parent_id for s in spans}
        staging = set()
        for c in compiles:
            sid = c.parent_id
            while sid and sid not in staging:
                staging.add(sid)
                sid = parents.get(sid, "")
        p.steady_seconds = sum(
            s.duration_s for s in dispatches if s.span_id not in staging)
        p.phase_seconds = _phase_self_seconds(spans)
        p._fold_costs(spans, cost_lookup, staging)
        return p

    def _fold_costs(self, spans: Sequence[Any], cost_lookup,
                    staging) -> None:
        """Join the spans' per-program execution counts onto the harvested
        XLA cost registry and derive the rate fields.

        ``achieved_flops`` keeps numerator and denominator consistent:
        steady-state executions' FLOPs over those same spans' wall time.
        Staging executions (a compile in the span's subtree) are excluded
        from BOTH sides — counting their flops against steady time would
        inflate the rate ~2x on short fits — and the denominator is the
        cost-carrying spans' own durations, so programs dispatched outside
        any optimizer dispatch span (summary/weight-sum aggregations)
        cannot contribute flops without contributing time."""
        execs: Dict[str, int] = {}
        steady_execs: Dict[str, int] = {}
        steady_cost_seconds = all_cost_seconds = 0.0
        for s in spans:
            if s.kind in ("dispatch", "collective"):
                pid = s.attrs.get("program")
                if pid:
                    execs[pid] = execs.get(pid, 0) + 1
                    all_cost_seconds += s.duration_s
                    if s.span_id not in staging:
                        steady_execs[pid] = steady_execs.get(pid, 0) + 1
                        steady_cost_seconds += s.duration_s
        if not execs:
            return
        from cycloneml_tpu.observe import costs as _costs
        if cost_lookup is None:
            cost_lookup = _costs.lookup
        self.memory_stats_available = _costs.memory_stats_available()
        flops_total = bytes_total = steady_flops = 0.0
        any_flops = any_mem = False
        for pid, n in sorted(execs.items()):
            entry = cost_lookup(pid)
            if entry is None:
                self.programs[pid] = {"executions": n,
                                      "cost_available": False}
                continue
            entry = dict(entry)
            entry["executions"] = n
            self.programs[pid] = entry
            self.n_devices = max(self.n_devices,
                                 int(entry.get("n_devices") or 0))
            if entry.get("flops_total"):
                any_flops = True
                flops_total += entry["flops_total"] * n
                steady_flops += entry["flops_total"] * steady_execs.get(pid, 0)
            if entry.get("bytes_accessed_total"):
                bytes_total += entry["bytes_accessed_total"] * n
            peak = entry.get("peak_bytes")
            if peak is not None and (self.hbm_peak_bytes is None
                                     or peak > self.hbm_peak_bytes):
                any_mem = True
                self.hbm_peak_bytes = int(peak)
                self.hbm_argument_bytes = entry.get("argument_bytes")
                self.hbm_output_bytes = entry.get("output_bytes")
                self.hbm_temp_bytes = entry.get("temp_bytes")
        if any_flops:
            self.total_flops = flops_total
            if bytes_total:
                self.total_bytes_accessed = bytes_total
                self.arithmetic_intensity = flops_total / bytes_total
            # steady executions over steady cost-span time; a fit whose
            # every cost-carrying dispatch paid a compile falls back to
            # total work over total cost-span time (still consistent)
            if steady_flops and steady_cost_seconds > 0:
                self.achieved_flops = steady_flops / steady_cost_seconds
            elif all_cost_seconds > 0:
                self.achieved_flops = flops_total / all_cost_seconds
        self.cost_availability = (
            "full" if any_flops and any_mem
            else "flops_only" if any_flops
            else "unavailable")

    def phase_summary(self) -> Dict[str, float]:
        """The compile-vs-steady-state breakdown bench.py prints."""
        return {
            "compile_s": round(self.compile_seconds, 4),
            "steady_s": round(self.steady_seconds, 4),
            "transfer_s": round(self.transfer_seconds, 4),
            "checkpoint_s": round(self.checkpoint_seconds, 4),
            "wall_s": round(self.wall_seconds, 4),
        }


def _phase_self_seconds(spans: Sequence[Any]) -> Dict[str, float]:
    """Self seconds by ``phase`` span name: each phase's duration less the
    ``phase`` and ``dispatch`` spans whose nearest ancestor of either kind
    it is (a dispatch nested in a dispatch is its parent's time already)."""
    by_id = {s.span_id: s for s in spans}
    out: Dict[str, float] = {}
    for s in spans:
        if s.kind not in ("phase", "dispatch"):
            continue
        if s.kind == "phase":
            out[s.name] = out.get(s.name, 0.0) + s.duration_s
        above = by_id.get(s.parent_id)
        while above is not None and above.kind not in ("phase", "dispatch"):
            above = by_id.get(above.parent_id)
        if above is not None and above.kind == "phase":
            out[above.name] = out.get(above.name, 0.0) - s.duration_s
    return out
