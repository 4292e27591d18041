#!/usr/bin/env python
"""Perf-regression gate over the ``BENCH_*.json`` trajectory.

The measurement driver appends one wrapper file per round
(``{n, cmd, rc, tail, parsed}`` — ``parsed`` is bench.py's one-line
record, ``{metric, value, unit, ...}``, or null when the run failed,
e.g. with no chip reachable).  This gate loads the whole
trajectory, groups parsed records by metric, and compares the NEWEST
record of each metric against the BEST prior one: a drop beyond
``--max-slowdown`` fails the gate (exit 1), so a perf PR cannot land a
regression the trajectory already witnessed being beaten.

Direction is inferred from the unit: rates (``iterations/sec``,
``it/s*rhs``, anything per second) regress DOWNWARD; latency-shaped
units (``s``, ``us/iter``, ...) regress UPWARD.  Metrics with fewer
than two parsed records pass vacuously (nothing to compare — a run
that reached no chip must not fail the gate).

``--dry-run`` prints the full comparison table but always exits 0 on a
well-formed trajectory (the wiring smoke mode bench_suite.py runs after
every sweep and tier-1 smoke-tests, like ``bench_batched.py
--dry-run``).  Malformed JSON / unrecognized wrappers exit 2 even in
dry mode — a broken artifact is a wiring bug, not a regression.

Usage::

  python scripts/check_perf_regression.py [FILES...]
  python scripts/check_perf_regression.py --dir . --max-slowdown 0.15
  python scripts/check_perf_regression.py --dry-run
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from acg_tpu.obs.export import validate_bench_record

# units where a LARGER newest value is the regression (latency-shaped);
# everything else is a rate (higher = better)
_LOWER_IS_BETTER_UNITS = ("s", "sec", "seconds", "us", "us/iter",
                         "ms", "bytes", "edges", "ratio", "gb")


def _lower_is_better(unit: str) -> bool:
    return unit.strip().lower() in _LOWER_IS_BETTER_UNITS


def load_trajectory(paths) -> tuple[list[dict], list[str]]:
    """Parsed bench records from trajectory wrappers (or bare record
    files), each tagged with its round index ``n`` (wrapper ``n``, else
    file order).  Returns (records, problems): records sorted by round;
    problems are malformed-artifact messages (wiring errors)."""
    records, problems = [], []
    for order, path in enumerate(paths):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{path}: unreadable or invalid JSON: {e}")
            continue
        if not isinstance(doc, dict):
            problems.append(f"{path}: not a JSON object")
            continue
        if doc.get("schema") == "acg-tpu-partbench/1":
            # preprocessing-benchmark wrapper: a LIST of bench records
            # sharing one round index (scripts/bench_partition.py)
            n = doc.get("n", order)
            for rec in doc.get("records") or []:
                errs = validate_bench_record(rec)
                if errs:
                    problems.append(f"{path}: " + "; ".join(errs))
                    continue
                if rec.get("value") is None:
                    continue
                records.append({"n": int(n) if isinstance(n, int)
                                else order, "path": path, **rec})
            continue
        if "parsed" in doc:                      # BENCH wrapper
            rec = doc.get("parsed")
            n = doc.get("n", order)
            if rec is None:
                continue                         # failed round: no data
        elif "metric" in doc:                    # bare bench record
            rec, n = doc, order
        else:
            problems.append(f"{path}: unrecognized artifact (expected a "
                            "BENCH wrapper or a bench record)")
            continue
        errs = validate_bench_record(rec)
        if errs:
            problems.append(f"{path}: " + "; ".join(errs))
            continue
        if rec.get("value") is None:
            continue
        records.append({"n": int(n) if isinstance(n, int) else order,
                        "path": path, **rec})
    records.sort(key=lambda r: r["n"])
    return records, problems


def find_regressions(records, max_slowdown: float):
    """Compare each metric's newest record against its best prior one.
    Returns a list of comparison dicts (one per metric with >= 2
    records), each with a bool ``regressed``."""
    by_metric: dict[str, list[dict]] = {}
    for r in records:
        by_metric.setdefault(r["metric"], []).append(r)
    out = []
    for metric, recs in sorted(by_metric.items()):
        if len(recs) < 2:
            continue
        newest = recs[-1]
        prior = recs[:-1]
        lower = _lower_is_better(newest.get("unit", ""))
        best_prior = (min if lower else max)(
            prior, key=lambda r: r["value"])
        new_v, best_v = float(newest["value"]), float(best_prior["value"])
        if lower:
            change = (new_v - best_v) / best_v if best_v else 0.0
        else:
            change = (best_v - new_v) / best_v if best_v else 0.0
        out.append({
            "metric": metric, "unit": newest.get("unit", ""),
            "newest": new_v, "newest_n": newest["n"],
            "best_prior": best_v, "best_prior_n": best_prior["n"],
            "slowdown": change,
            "regressed": change > max_slowdown,
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Fail when the newest BENCH record regresses "
                    "against the best prior one.")
    ap.add_argument("files", nargs="*", metavar="FILE",
                    help="trajectory wrappers / bench records "
                         "[default: --dir glob]")
    ap.add_argument("--dir", default=".",
                    help="directory to glob when no FILEs are given [.]")
    ap.add_argument("--glob", default="BENCH_*.json,PARTBENCH_*.json",
                    help="comma-separated trajectory globs under --dir "
                         "[BENCH_*.json,PARTBENCH_*.json]")
    ap.add_argument("--max-slowdown", type=float, default=0.10,
                    metavar="FRAC",
                    help="tolerated fractional slowdown vs the best "
                         "prior record before the gate fails [0.10]")
    ap.add_argument("--dry-run", action="store_true",
                    help="report comparisons but exit 0 regardless of "
                         "regressions (wiring smoke mode; malformed "
                         "artifacts still exit 2)")
    args = ap.parse_args(argv)

    paths = args.files or sorted(
        p for pat in args.glob.split(",") if pat
        for p in glob.glob(os.path.join(args.dir, pat)))
    records, problems = load_trajectory(paths)
    for msg in problems:
        print(msg, file=sys.stderr)
    if problems:
        return 2
    if not records:
        print("perf gate: no parsed bench records in trajectory "
              f"({len(paths)} file(s)) — nothing to compare")
        return 0

    comparisons = find_regressions(records, args.max_slowdown)
    nreg = 0
    for c in comparisons:
        tag = "REGRESSION" if c["regressed"] else "ok"
        nreg += c["regressed"]
        print(f"{c['metric']}: newest {c['newest']:g} {c['unit']} "
              f"(round {c['newest_n']}) vs best prior {c['best_prior']:g} "
              f"(round {c['best_prior_n']}): "
              f"{c['slowdown'] * 100:+.1f}% slowdown -> {tag}")
    single = len({r['metric'] for r in records}) - len(comparisons)
    if single:
        print(f"perf gate: {single} metric(s) with a single record "
              "(pass vacuously)")
    if nreg and args.dry_run:
        print(f"perf gate (dry-run): {nreg} regression(s) beyond "
              f"{args.max_slowdown:.0%} — NOT failing (dry mode)")
        return 0
    if nreg:
        print(f"perf gate: {nreg} regression(s) beyond "
              f"{args.max_slowdown:.0%}", file=sys.stderr)
        return 1
    print(f"perf gate: {len(comparisons)} metric(s) compared, "
          "no regression")
    return 0


if __name__ == "__main__":
    sys.exit(main())
