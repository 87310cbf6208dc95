"""bench-regress: the regression sentinel's gate (observe/regress.py).

Two steps, both deterministic:

1. INGEST (optional): ``--ingest FILE`` appends the BENCH JSON line a
   fresh ``python bench.py > FILE`` run produced (its own ``meta`` block
   is the row identity) to ``artifacts/bench_history.jsonl``. `make
   bench` tees stdout to artifacts/bench_last.json, so `make bench
   bench-regress` gates the run it just made. The ledger starts empty on
   a fresh checkout (artifacts/ is gitignored); until it holds
   ``cyclone.regress.minRuns`` comparable runs every verdict is
   ``insufficient-history``.
2. GATE: judge each metric's newest row against the median+MAD of its
   comparable history (cyclone.regress.* thresholds) and exit nonzero
   on any regression verdict.

``--inject-regression`` is the sentinel's own self-test and touches no
real history: it gates a THROWAWAY ledger seeded with synthetic steady
rows plus one synthetic 40%-of-median headline row, and asserts the gate
trips.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEDGER = os.path.join(REPO, "artifacts", "bench_history.jsonl")
HEADLINE = "logreg_fit_e2e_throughput"


def synthetic_history(n: int = 6):
    """``n`` steady synthetic headline rows (±1% around 100) then one at
    40% of their median — fabricated values for the self-test only."""
    from cycloneml_tpu.observe import regress
    values = [100.0 + (i % 3 - 1) for i in range(n)] + [40.0]
    rows = []
    for t, v in enumerate(values, start=1):
        rows.extend(regress.rows_from_bench(
            {"metric": HEADLINE, "value": v, "unit": "M ops/s"},
            meta={"run_id": f"synthetic-{t:02d}", "git_sha": "",
                  "t_logical": t}))
    return rows


def ingest(ledger: str, path: str) -> int:
    from cycloneml_tpu.observe import regress
    with open(path, "r", encoding="utf-8") as fh:
        block = json.loads(fh.read().strip().splitlines()[-1])
    return regress.append(ledger, regress.rows_from_bench(block))


def main() -> int:
    ap = argparse.ArgumentParser(description="bench history drift gate")
    ap.add_argument("--ledger", default=LEDGER)
    ap.add_argument("--ingest", metavar="FILE",
                    help="BENCH JSON line (e.g. artifacts/bench_last.json)")
    ap.add_argument("--inject-regression", action="store_true",
                    help="self-test: gate a throwaway ledger of synthetic "
                         "rows ending in a 40%%-of-median regression")
    ns = ap.parse_args()

    from cycloneml_tpu.observe import regress

    if ns.inject_regression:
        scratch = ns.ledger + ".selftest"
        os.makedirs(os.path.dirname(scratch) or ".", exist_ok=True)
        try:
            with open(scratch, "w", encoding="utf-8") as fh:
                for r in synthetic_history():
                    fh.write(regress.canonical_row(r) + "\n")
            verdicts = regress.detect(regress.load(scratch))
        finally:
            if os.path.exists(scratch):
                os.remove(scratch)
        rc, bad = regress.gate(verdicts)
        for v in verdicts:
            print(json.dumps(v, sort_keys=True))
        if rc == 0 or HEADLINE not in bad:
            print("FAIL: synthetic 40% regression row did not trip the "
                  "gate", file=sys.stderr)
            return 1
        print("info: synthetic regression correctly tripped the gate",
              file=sys.stderr)
        return 0

    n_ingested = 0
    if ns.ingest and os.path.exists(ns.ingest):
        n_ingested = ingest(ns.ledger, ns.ingest)
    rows = regress.load(ns.ledger)
    print(f"info: ledger {ns.ledger}: {len(rows)} row(s) "
          f"(+{n_ingested} ingested)", file=sys.stderr)

    verdicts = regress.detect(rows)
    for v in verdicts:
        print(json.dumps(v, sort_keys=True))
    rc, bad = regress.gate(verdicts)
    if rc:
        print(f"FAIL: regression in {', '.join(bad)} — the newest run "
              f"drifted past median+MAD of its history", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
