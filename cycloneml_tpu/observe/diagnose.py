"""Performance doctor: deterministic, evidence-joined bottleneck diagnosis.

``diagnose(profile | trace | flight-ring) -> DiagnosisReport`` turns five
PRs of sensors into answers. Every rule JOINS evidence the telemetry
plane already records — no new instrumentation:

==================== ==========================================================
finding kind         evidence joined
==================== ==========================================================
recompile-storm      compile spans recurring past warm-up, keyed by
                     program-cache identity (span name)
transfer-stall       non-streaming transfer-span seconds vs dispatch +
                     collective seconds — the runtime twin of JX001
straggler            SkewDetector lane snapshot (latched median+MAD verdicts)
                     and/or per-lane stats recomputed from oocore.stage spans
under-lapped-        stage/compute overlap fraction from the stream spans
streaming            (same interval math as scripts/bench_oocore.py)
serving-pressure     batcher tallies (shed counters, per-model p99) vs
                     ``cyclone.telemetry.slo.servingMs``
precision-churn      precision.fallback instants (the fp8 envelope re-proving
                     itself instead of staying settled)
cache-restream       ShardSetCache stats (LRU thrash: evictions + misses
                     outrunning hits on a re-fit)
fault-pressure       chaos instants (injected faults) + staging retries
==================== ==========================================================

Rules ABSTAIN when their evidence plane is absent (no profile,
no stream spans, no serving stats) — a clean warm fit diagnoses to ZERO
findings. The report is deterministic: same inputs => byte-identical
canonical JSON (``DiagnosisReport.to_json``), no wall-clock fields, all
orderings explicit. Import-light on purpose: nothing here touches jax.
"""

import json
import statistics
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from cycloneml_tpu.conf import (DOCTOR_FALLBACK_MIN, DOCTOR_MIN_STREAM_SPANS,
                                DOCTOR_OVERLAP_MIN, DOCTOR_RECOMPILE_MIN,
                                DOCTOR_SHED_MIN,
                                DOCTOR_TRANSFER_MIN_COUNT,
                                DOCTOR_TRANSFER_STALL_FRACTION,
                                SKEW_MAD_FACTOR, SKEW_MIN_GAP_MS,
                                SKEW_MIN_SAMPLES, SKEW_REL_FACTOR,
                                SLO_SERVING_MS)
from cycloneml_tpu.observe.profile import FitProfile

# severity rank for the deterministic sort (higher = earlier)
_SEVERITY_RANK = {"critical": 2, "warning": 1, "info": 0}

# sentinel: "look the live source up yourself" (pass None to disable)
_LIVE = object()


@dataclass
class Finding:
    """One convicted bottleneck: the verdict plus the raw numbers that
    convicted it (``evidence``) and the next action (``remedy``)."""

    kind: str
    severity: str                 # "info" | "warning" | "critical"
    score: float                  # rule-relative magnitude, for ranking
    summary: str
    evidence: Dict[str, Any] = field(default_factory=dict)
    remedy: str = ""

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "severity": self.severity,
                "score": self.score, "summary": self.summary,
                "evidence": dict(self.evidence), "remedy": self.remedy}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Finding":
        return cls(kind=d.get("kind", ""), severity=d.get("severity", "info"),
                   score=float(d.get("score", 0.0)),
                   summary=d.get("summary", ""),
                   evidence=dict(d.get("evidence", {})),
                   remedy=d.get("remedy", ""))


@dataclass
class DiagnosisReport:
    """Ranked findings over one analyzed window. No wall-clock fields:
    the same window diagnoses to byte-identical ``to_json`` output."""

    source: str = ""              # "trace" | "profile" | "flight" | "live"
    n_spans: int = 0
    inputs: List[str] = field(default_factory=list)   # evidence planes seen
    findings: List[Finding] = field(default_factory=list)

    @property
    def kinds(self) -> List[str]:
        return [f.kind for f in self.findings]

    def to_dict(self) -> Dict[str, Any]:
        return {"schema": 1, "source": self.source, "n_spans": self.n_spans,
                "inputs": list(self.inputs),
                "findings": [f.to_dict() for f in self.findings]}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DiagnosisReport":
        return cls(source=d.get("source", ""),
                   n_spans=int(d.get("n_spans", 0)),
                   inputs=list(d.get("inputs", [])),
                   findings=[Finding.from_dict(f)
                             for f in d.get("findings", [])])

    def to_json(self) -> str:
        """Canonical JSON: sorted keys, tight separators — the
        byte-identical surface the determinism gate pins."""
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"))

    def render_text(self) -> str:
        lines = [f"doctor: {len(self.findings)} finding(s) over "
                 f"{self.n_spans} span(s) "
                 f"[source={self.source or 'unknown'}; "
                 f"inputs={','.join(self.inputs) or 'none'}]"]
        if not self.findings:
            lines.append("  healthy: every rule abstained or passed")
        for f in self.findings:
            lines.append(f"  [{f.severity}] {f.kind}: {f.summary}")
            ev = json.dumps(f.evidence, sort_keys=True)
            lines.append(f"      evidence: {ev}")
            if f.remedy:
                lines.append(f"      remedy:   {f.remedy}")
        return "\n".join(lines)


@dataclass
class DoctorConfig:
    """Thresholds for every rule; defaults mirror the registered
    ``cyclone.doctor.*`` / skew / SLO conf values."""

    recompile_min: int = 2
    transfer_stall_fraction: float = 0.5
    transfer_min_count: int = 8
    overlap_min: float = 0.30
    min_stream_spans: int = 8
    shed_min: int = 1
    fallback_min: int = 1
    skew_mad_factor: float = 4.0
    skew_rel_factor: float = 1.5
    skew_min_gap_s: float = 0.010
    skew_min_samples: int = 8
    slo_serving_ms: float = 0.0

    @classmethod
    def from_conf(cls, conf) -> "DoctorConfig":
        return cls(
            recompile_min=conf.get(DOCTOR_RECOMPILE_MIN),
            transfer_stall_fraction=conf.get(DOCTOR_TRANSFER_STALL_FRACTION),
            transfer_min_count=conf.get(DOCTOR_TRANSFER_MIN_COUNT),
            overlap_min=conf.get(DOCTOR_OVERLAP_MIN),
            min_stream_spans=conf.get(DOCTOR_MIN_STREAM_SPANS),
            shed_min=conf.get(DOCTOR_SHED_MIN),
            fallback_min=conf.get(DOCTOR_FALLBACK_MIN),
            skew_mad_factor=conf.get(SKEW_MAD_FACTOR),
            skew_rel_factor=conf.get(SKEW_REL_FACTOR),
            skew_min_gap_s=conf.get(SKEW_MIN_GAP_MS) / 1e3,
            skew_min_samples=conf.get(SKEW_MIN_SAMPLES),
            slo_serving_ms=conf.get(SLO_SERVING_MS),
        )


# -- interval math (the bench_oocore overlap contract) -------------------------

def _merge_intervals(intervals: Sequence[Tuple[float, float]]):
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def overlap_fraction(spans) -> Tuple[float, float, float, int, int]:
    """``(frac, stage_s, shard_s, n_stage, n_shard)`` over the stream
    spans: sum |stage ∩ (∪ shard)| / min(sum stage, sum shard)."""
    stage = [(s.t0, s.t1) for s in spans if s.name == "oocore.stage"]
    shard = [(s.t0, s.t1) for s in spans if s.name == "oocore.shard"]
    if not stage or not shard:
        return 0.0, 0.0, 0.0, len(stage), len(shard)
    stage_total = sum(hi - lo for lo, hi in stage)
    shard_total = sum(hi - lo for lo, hi in shard)
    shard_u = _merge_intervals(shard)
    inter = 0.0
    for lo, hi in stage:
        for ulo, uhi in shard_u:
            inter += max(0.0, min(hi, uhi) - max(lo, ulo))
    denom = min(stage_total, shard_total)
    frac = inter / denom if denom > 0 else 0.0
    return frac, stage_total, shard_total, len(stage), len(shard)


def lane_stats_from_spans(spans, n_lanes: int = 64) -> Dict[str, List[float]]:
    """Per-lane staging durations recomputed from ``oocore.stage`` spans
    (same ``shard<i mod N>`` folding the live SkewDetector uses), so a
    trace file alone can answer the straggler question."""
    lanes: Dict[str, List[float]] = {}
    for s in spans:
        if s.name != "oocore.stage":
            continue
        shard = s.attrs.get("shard")
        if shard is None:
            continue
        lane = f"shard{int(shard) % n_lanes}"
        lanes.setdefault(lane, []).append(s.duration_s)
    return lanes


def _straggler_lanes(lanes: Dict[str, List[float]],
                     cfg: DoctorConfig) -> List[Dict[str, Any]]:
    """The SkewDetector's 3-gate median+MAD conviction, replayed over
    trace-derived lane samples."""
    meds = {lane: statistics.median(v) for lane, v in sorted(lanes.items())
            if len(v) >= cfg.skew_min_samples}
    if len(meds) < 2:
        return []
    values = [meds[lane] for lane in sorted(meds)]
    group_med = statistics.median(values)
    mad = statistics.median([abs(v - group_med) for v in values])
    out = []
    for lane in sorted(meds):
        mine = meds[lane]
        if (mine > group_med + cfg.skew_mad_factor * mad
                and mine > cfg.skew_rel_factor * group_med
                and mine - group_med > cfg.skew_min_gap_s):
            out.append({"lane": lane, "lane_median_s": round(mine, 6),
                        "group_median_s": round(group_med, 6),
                        "mad_s": round(mad, 6),
                        "n_samples": len(lanes[lane])})
    return out


# -- rules ---------------------------------------------------------------------

def _rule_recompile(spans, cfg: DoctorConfig) -> List[Finding]:
    if not spans:
        return []
    counts: Dict[str, int] = {}
    for s in spans:
        if s.kind == "compile":
            counts[s.name] = counts.get(s.name, 0) + 1
    excess = {name: c - 1 for name, c in sorted(counts.items()) if c > 1}
    total_excess = sum(excess.values())
    if total_excess < cfg.recompile_min:
        return []
    return [Finding(
        kind="recompile-storm", severity="warning",
        score=float(total_excess),
        summary=f"{total_excess} recompile(s) past warm-up across "
                f"{len(excess)} program(s)",
        evidence={"excess_compiles": excess,
                  "total_excess": total_excess,
                  "programs_compiled": len(counts)},
        remedy="stabilize shapes/dtypes feeding the program cache: pad "
               "to buckets, pin the data tier, stop rebuilding meshes "
               "mid-fit")]


def _rule_transfer_stall(spans, profile: Optional[FitProfile],
                         cfg: DoctorConfig) -> List[Finding]:
    if spans:
        # streaming staging spans are transfer-kind too; their health is
        # the overlap rule's job, so readback stall excludes oocore.*
        transfers = [s for s in spans if s.kind == "transfer"
                     and not s.name.startswith("oocore.")]
        dispatch_s = sum(s.duration_s for s in spans
                         if s.kind in ("dispatch", "collective"))
        transfer_s = sum(s.duration_s for s in transfers)
        n_transfers = len(transfers)
    elif profile is not None:
        transfer_s = profile.transfer_seconds
        dispatch_s = profile.dispatch_seconds
        n_transfers = profile.transfer_count
    else:
        return []
    if (n_transfers < cfg.transfer_min_count or dispatch_s <= 0
            or transfer_s < cfg.transfer_stall_fraction * dispatch_s):
        return []
    ratio = transfer_s / dispatch_s
    return [Finding(
        kind="transfer-stall", severity="warning", score=round(ratio, 6),
        summary=f"host transfers cost {ratio:.2f}x device dispatch time "
                f"({n_transfers} transfers)",
        evidence={"transfer_seconds": round(transfer_s, 6),
                  "dispatch_seconds": round(dispatch_s, 6),
                  "transfer_count": n_transfers},
        remedy="keep results on device between steps (the JX001 "
               "discipline at runtime): batch readbacks, drop "
               "per-element device_get loops")]


def _rule_straggler(spans, skew_snapshot: Optional[Dict[str, Any]],
                    cfg: DoctorConfig) -> List[Finding]:
    findings: List[Finding] = []
    seen: Dict[str, List[str]] = {}
    if skew_snapshot:
        for group in sorted(skew_snapshot):
            g = skew_snapshot[group]
            bad = [lane for lane in sorted(g.get("lanes", {}))
                   if g["lanes"][lane].get("straggler")]
            if bad:
                seen[group] = bad
                findings.append(Finding(
                    kind="straggler", severity="warning",
                    score=float(len(bad)),
                    summary=f"{len(bad)} latched straggler lane(s) in "
                            f"{group}",
                    evidence={"group": group, "lanes": bad,
                              "group_median_s": round(
                                  g.get("groupMedianS", 0.0), 6),
                              "mad_s": round(g.get("madS", 0.0), 6),
                              "detector": "live"},
                    remedy="one lane is persistently slow (bad spindle / "
                           "hot host): rebalance shards or let the "
                           "speculation layer race it"))
    if spans:
        lanes = lane_stats_from_spans(spans)
        bad = [b for b in _straggler_lanes(lanes, cfg)
               if b["lane"] not in seen.get("oocore.stage", [])]
        if bad:
            findings.append(Finding(
                kind="straggler", severity="warning", score=float(len(bad)),
                summary=f"{len(bad)} straggler lane(s) in oocore.stage "
                        f"span timings",
                evidence={"group": "oocore.stage", "outliers": bad,
                          "n_lanes": len(lanes), "detector": "trace"},
                remedy="one staging lane is persistently slow: rebalance "
                       "shards or let the speculation layer race it"))
    return findings


def _rule_underlap(spans, cfg: DoctorConfig) -> List[Finding]:
    if not spans:
        return []
    frac, stage_s, shard_s, n_stage, n_shard = overlap_fraction(spans)
    if n_stage < cfg.min_stream_spans or n_shard < cfg.min_stream_spans:
        return []
    if frac >= cfg.overlap_min:
        return []
    return [Finding(
        kind="under-lapped-streaming", severity="warning",
        score=round(cfg.overlap_min - frac, 6),
        summary=f"stage/compute overlap {frac:.2f} below the "
                f"{cfg.overlap_min:.2f} gate",
        evidence={"overlap_fraction": round(frac, 6),
                  "stage_seconds": round(stage_s, 6),
                  "compute_seconds": round(shard_s, 6),
                  "n_stage_spans": n_stage, "n_shard_spans": n_shard},
        remedy="the double buffer is not hiding staging: raise the "
               "prefetch depth, shrink shards, or move shards to "
               "faster storage")]


def _rule_serving(serving_stats: Optional[Dict[str, Any]],
                  cfg: DoctorConfig) -> List[Finding]:
    if not serving_stats:
        return []
    totals = serving_stats.get("totals", {})
    shed = int(totals.get("shed", 0))
    worst_p99, worst_model = 0.0, ""
    for name in sorted(serving_stats.get("models", {})):
        p99 = serving_stats["models"][name].get("latencyMs", {}).get("p99")
        if p99 is not None and p99 > worst_p99:
            worst_p99, worst_model = float(p99), name
    over_slo = cfg.slo_serving_ms > 0 and worst_p99 > cfg.slo_serving_ms
    if shed < cfg.shed_min and not over_slo:
        return []
    bits = []
    if shed >= cfg.shed_min:
        bits.append(f"{shed} request(s) shed")
    if over_slo:
        bits.append(f"p99 {worst_p99:.1f}ms over the "
                    f"{cfg.slo_serving_ms:.0f}ms SLO ({worst_model})")
    return [Finding(
        kind="serving-pressure", severity="warning",
        score=float(shed) + (worst_p99 / cfg.slo_serving_ms
                             if over_slo else 0.0),
        summary="; ".join(bits),
        evidence={"shed": shed,
                  "requests": int(totals.get("requests", 0)),
                  "worst_p99_ms": round(worst_p99, 3),
                  "worst_model": worst_model,
                  "slo_serving_ms": cfg.slo_serving_ms},
        remedy="the batcher is saturating: raise maxBatch/window, add "
               "replicas (the autoscaler's job), or shed earlier at "
               "admission")]


def _rule_precision(profile: Optional[FitProfile],
                    cfg: DoctorConfig) -> List[Finding]:
    if profile is None or profile.fp8_fallbacks < cfg.fallback_min:
        return []
    n = profile.fp8_fallbacks
    return [Finding(
        kind="precision-churn", severity="info", score=float(n),
        summary=f"{n} precision fallback(s): the fp8 envelope keeps "
                f"re-proving itself",
        evidence={"fp8_fallbacks": n},
        remedy="the data violates the narrow tier's envelope: pin the "
               "tier explicitly or normalize the offending columns")]


def _rule_cache(cache_stats: Optional[Dict[str, Any]],
                cfg: DoctorConfig) -> List[Finding]:
    if not cache_stats:
        return []
    evicted = int(cache_stats.get("evictionsLru", 0))
    hits = int(cache_stats.get("hits", 0))
    misses = int(cache_stats.get("misses", 0))
    if evicted < 1 or misses <= hits:
        return []
    return [Finding(
        kind="cache-restream", severity="warning",
        score=float(misses - hits),
        summary=f"shard-set cache thrash: {misses} miss(es) vs {hits} "
                f"hit(s) with {evicted} LRU eviction(s)",
        evidence={"hits": hits, "misses": misses, "evictionsLru": evicted,
                  "evictionsCorrupt": int(
                      cache_stats.get("evictionsCorrupt", 0))},
        remedy="re-fits are re-blocking instead of reusing spilled "
               "shards: raise cyclone.oocore.cacheBytes or shrink the "
               "working set")]


def _rule_faults(profile: Optional[FitProfile], spans,
                 cfg: DoctorConfig) -> List[Finding]:
    faults = profile.faults_injected if profile is not None else 0
    retries = profile.retries if profile is not None else 0
    points: Dict[str, int] = {}
    for s in spans or []:
        if s.kind != "instant":
            continue
        if s.name == "fault":
            p = str(s.attrs.get("point", "?"))
            points[p] = points.get(p, 0) + 1
        elif s.name == "oocore.stage_retry":
            # staging retries carry their own instant name, not "retry"
            retries += 1
    if faults < 1 and retries < 1:
        return []
    return [Finding(
        kind="fault-pressure", severity="info",
        score=float(faults + retries),
        summary=f"{faults} injected fault(s), {retries} staging "
                f"retry(ies) in the window",
        evidence={"faults_injected": faults, "retries": retries,
                  "points": dict(sorted(points.items()))},
        remedy="chaos (or a flaky backend) is active: timings in this "
               "window measure the recovery path, not steady state")]


# -- entry point ---------------------------------------------------------------

def diagnose(subject: Any = None, *,
             spans=None,
             profile: Optional[FitProfile] = None,
             skew: Any = _LIVE,
             serving_stats: Optional[Dict[str, Any]] = None,
             cache_stats: Any = _LIVE,
             conf=None,
             source: str = "") -> DiagnosisReport:
    """Diagnose one analyzed window.

    ``subject`` may be a :class:`FitProfile`, a ``Tracer``, a span list,
    a flight-recorder dump dict (``{"spans": [...]}``) or a Chrome-trace
    dict (``{"traceEvents": [...]}``); keyword planes add or override.
    ``skew``/``cache_stats`` default to the live process-global sources
    (pass ``None`` to diagnose a trace file hermetically — the CLI and
    the flight-dump hook do, which is what makes their reports
    byte-identical across runs).
    """
    if subject is not None:
        if isinstance(subject, FitProfile):
            profile = subject if profile is None else profile
            source = source or "profile"
        elif isinstance(subject, dict) and "spans" in subject:
            spans = subject["spans"] if spans is None else spans
            source = source or "flight"
        elif isinstance(subject, dict) and "traceEvents" in subject:
            from cycloneml_tpu.observe.export import spans_from_chrome_trace
            spans = (spans_from_chrome_trace(subject)
                     if spans is None else spans)
            source = source or "trace"
        elif hasattr(subject, "snapshot"):          # a Tracer
            spans = subject.snapshot() if spans is None else spans
            source = source or "trace"
        else:                                       # a span sequence
            spans = list(subject) if spans is None else spans
            source = source or "trace"
    spans = list(spans) if spans is not None else None
    if profile is None and spans is not None:
        profile = FitProfile.from_spans(spans)

    cfg = DoctorConfig.from_conf(conf) if conf is not None else DoctorConfig()

    skew_snapshot = None
    if skew is _LIVE:
        from cycloneml_tpu.observe import skew as skew_mod
        det = skew_mod.active()
        skew = det
    if skew is not None and hasattr(skew, "lane_snapshot"):
        skew_snapshot = skew.lane_snapshot()
    elif isinstance(skew, dict):
        skew_snapshot = skew

    if cache_stats is _LIVE:
        from cycloneml_tpu.oocore import shard_set_cache
        stats = shard_set_cache().stats()
        # an untouched cache is not evidence of anything
        cache_stats = stats if (stats.get("hits", 0)
                                or stats.get("misses", 0)) else None

    findings: List[Finding] = []
    findings += _rule_recompile(spans, cfg)
    findings += _rule_transfer_stall(spans, profile, cfg)
    findings += _rule_straggler(spans, skew_snapshot, cfg)
    findings += _rule_underlap(spans, cfg)
    findings += _rule_serving(serving_stats, cfg)
    findings += _rule_precision(profile, cfg)
    findings += _rule_cache(cache_stats, cfg)
    findings += _rule_faults(profile, spans, cfg)

    findings.sort(key=lambda f: (-_SEVERITY_RANK.get(f.severity, 0),
                                 -f.score, f.kind))
    inputs = [name for name, present in (
        ("cache", cache_stats is not None and cache_stats is not _LIVE),
        ("profile", profile is not None),
        ("serving", bool(serving_stats)),
        ("skew", skew_snapshot is not None),
        ("spans", spans is not None),
    ) if present]
    return DiagnosisReport(source=source or "unknown",
                           n_spans=len(spans) if spans is not None else 0,
                           inputs=inputs, findings=findings)
