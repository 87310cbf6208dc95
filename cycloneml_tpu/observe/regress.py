"""Regression sentinel: an append-only bench-history ledger plus robust
drift detection over it.

Bench runs accumulate with no machinery watching them — a perf regression
lands silently. This module closes that gap:

- ``rows_from_bench(block, meta)`` flattens one BENCH JSON block into
  gateable metric rows (headline throughput + the nested sub-metrics in
  ``GATED``), each joined to the run's ``meta`` identity (run_id /
  git sha / logical timestamp — NEVER wall clock) and hardware meta.
- ``append(path, rows)`` appends canonical-JSON rows (sorted keys, tight
  separators: the autoscale-sim byte-determinism idiom) to
  ``artifacts/bench_history.jsonl``, idempotently keyed by
  ``(run_id, metric)`` — re-ingesting a run adds nothing.
- ``detect(rows, cfg)`` judges the NEWEST row of each metric against the
  median + MAD of up to ``window`` preceding comparable rows (same
  metric + hardware), with per-direction thresholds: drift past
  ``median +/- max(mad_factor*MAD, rel_tol*median)`` in the bad
  direction is a ``regression`` verdict, in the good direction an
  ``improvement``; too little history is ``insufficient-history``.
- ``gate(verdicts)`` maps verdicts to a process exit code: any
  regression is nonzero.

Deterministic on purpose: rows are ordered by ``(t_logical, file
order)``, verdicts by metric name, and nothing here reads a clock.
Import-light: no jax, no numpy.
"""

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from cycloneml_tpu.conf import (REGRESS_MAD_FACTOR, REGRESS_MIN_RUNS,
                                REGRESS_REL_TOL, REGRESS_WINDOW)

SCHEMA_VERSION = 1

# (nested block, field, direction) of every gated sub-metric; the
# headline ``value`` row is always emitted under the block's own metric
# name. Absent blocks are skipped — old BENCH files stay ingestible.
GATED = (
    ("serving", "requests_per_s", "higher"),
    ("serving", "p99_ms", "lower"),
    ("ovr", "ovr_stacked_speedup", "higher"),
)


@dataclass
class DriftConfig:
    window: int = 5
    mad_factor: float = 4.0
    rel_tol: float = 0.05
    min_runs: int = 3
    # MAD-term ceiling as a fraction of |median|: a fast-improving
    # history (r02->r05 is 13.9x) has a MAD so large that
    # mad_factor*MAD exceeds the median itself, and a gate whose
    # threshold is wider than the measurement can never fire. Capping
    # keeps the gate honest on non-stationary history.
    cap_fraction: float = 0.5

    @classmethod
    def from_conf(cls, conf) -> "DriftConfig":
        return cls(window=conf.get(REGRESS_WINDOW),
                   mad_factor=conf.get(REGRESS_MAD_FACTOR),
                   rel_tol=conf.get(REGRESS_REL_TOL),
                   min_runs=conf.get(REGRESS_MIN_RUNS))


def _median(values: List[float]) -> float:
    vs = sorted(values)
    n = len(vs)
    mid = n // 2
    return vs[mid] if n % 2 else (vs[mid - 1] + vs[mid]) / 2.0


def canonical_row(row: Dict[str, Any]) -> str:
    """One ledger line: canonical JSON, byte-stable across runs."""
    return json.dumps(row, sort_keys=True, separators=(",", ":"))


def rows_from_bench(block: Dict[str, Any],
                    meta: Optional[Dict[str, Any]] = None
                    ) -> List[Dict[str, Any]]:
    """Flatten one parsed BENCH block into ledger rows. ``meta``
    overrides the block's own ``meta`` (the self-test's synthetic rows
    carry no block meta)."""
    meta = dict(meta if meta is not None else block.get("meta", {}))
    hw = block.get("hardware")
    hw_key = ({"platform": hw.get("platform"),
               "device": hw.get("device_kind", hw.get("device")),
               "n_devices": hw.get("n_devices")} if isinstance(hw, dict)
              else None)
    base = {"schema": SCHEMA_VERSION,
            "run_id": str(meta.get("run_id", "")),
            "git_sha": str(meta.get("git_sha", "")),
            "t_logical": int(meta.get("t_logical", 0)),
            "hw": hw_key}
    rows: List[Dict[str, Any]] = []
    if "metric" in block and "value" in block:
        rows.append(dict(base, metric=str(block["metric"]),
                         value=float(block["value"]),
                         unit=str(block.get("unit", "")),
                         direction="higher"))
    for sub, fld, direction in GATED:
        inner = block.get(sub)
        if isinstance(inner, dict) and isinstance(
                inner.get(fld), (int, float)):
            rows.append(dict(base, metric=f"{sub}.{fld}",
                             value=float(inner[fld]), unit="",
                             direction=direction))
    return rows


def load(path: str) -> List[Dict[str, Any]]:
    """Ledger rows in file order; corrupt lines are skipped (the ledger
    is append-only — one torn tail line must not poison history)."""
    rows: List[Dict[str, Any]] = []
    if not os.path.exists(path):
        return rows
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if isinstance(row, dict) and "metric" in row:
                rows.append(row)
    return rows


def append(path: str, rows: List[Dict[str, Any]]) -> int:
    """Append rows not already present (keyed by run_id + metric);
    returns how many were written."""
    existing = {(r.get("run_id"), r.get("metric")) for r in load(path)}
    fresh = [r for r in rows
             if (r.get("run_id"), r.get("metric")) not in existing]
    if not fresh:
        return 0
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    with open(path, "a", encoding="utf-8") as fh:
        for r in fresh:
            fh.write(canonical_row(r) + "\n")
    return len(fresh)


def _comparable(row: Dict[str, Any], cand: Dict[str, Any]) -> bool:
    if row.get("metric") != cand.get("metric"):
        return False
    hw_a, hw_b = row.get("hw"), cand.get("hw")
    # rows without hardware meta (pre-meta backfills) compare to anything
    if hw_a is None or hw_b is None:
        return True
    return hw_a == hw_b


def detect(rows: List[Dict[str, Any]],
           cfg: Optional[DriftConfig] = None) -> List[Dict[str, Any]]:
    """One verdict per metric, judging its newest row against history."""
    cfg = cfg or DriftConfig()
    by_metric: Dict[str, List[Dict[str, Any]]] = {}
    for row in sorted(rows, key=lambda r: int(r.get("t_logical", 0))):
        by_metric.setdefault(str(row.get("metric")), []).append(row)
    verdicts: List[Dict[str, Any]] = []
    for metric in sorted(by_metric):
        series = by_metric[metric]
        cand = series[-1]
        history = [r for r in series[:-1] if _comparable(r, cand)]
        window = history[-cfg.window:]
        base: Dict[str, Any] = {
            "metric": metric, "value": float(cand.get("value", 0.0)),
            "run_id": cand.get("run_id", ""),
            "direction": cand.get("direction", "higher"),
            "window_n": len(window)}
        if len(window) < cfg.min_runs:
            verdicts.append(dict(base, verdict="insufficient-history",
                                 median=None, threshold=None))
            continue
        values = [float(r.get("value", 0.0)) for r in window]
        med = _median(values)
        mad = _median([abs(v - med) for v in values])
        threshold = max(cfg.mad_factor * mad, cfg.rel_tol * abs(med))
        if med:
            threshold = max(min(threshold, cfg.cap_fraction * abs(med)),
                            cfg.rel_tol * abs(med))
        value = float(cand.get("value", 0.0))
        higher = cand.get("direction", "higher") != "lower"
        delta = value - med if higher else med - value
        if delta < -threshold:
            verdict = "regression"
        elif delta > threshold:
            verdict = "improvement"
        else:
            verdict = "ok"
        verdicts.append(dict(base, verdict=verdict,
                             median=round(med, 6),
                             mad=round(mad, 6),
                             threshold=round(threshold, 6)))
    return verdicts


def gate(verdicts: List[Dict[str, Any]]) -> Tuple[int, List[str]]:
    """(exit code, regressed metric names): nonzero iff any regression."""
    bad = [v["metric"] for v in verdicts if v.get("verdict") == "regression"]
    return (1 if bad else 0), bad
