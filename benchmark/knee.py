#!/usr/bin/env python3
"""Sweep the arrival rate of a served cell once, to find its knee.

    python3 benchmark/knee.py --workload p7-128-serve --rates 4,5,6 \\
        --seconds 40 --limit-s 0.1

Builds the cell's service once, warms it, then runs the cell's open loop
at each rate in turn and prints one JSON line per rate: requests offered
and completed per second, latency percentiles, how late the generator
ran, the mean batch, the latency of the window's last quarter of
requests over its first (a growing backlog reads well above 1), and
whether the 95th percentile met ``--limit-s``.  The knee is the highest
rate that meets the limit with completions keeping up and no growing
backlog; the cell's traffic file fixes its rate at about four fifths of
that.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(r) for r in s.split(",")])
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--limit-s", type=float, default=0.1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import numpy as np

    from acg_tpu.utils.backend import enable_compile_cache, require_tpu
    from benchmark import harness, sut, traffic as gen

    enable_compile_cache()
    require_tpu()
    cell = harness.find_cell(args.workload)
    mix = cell.traffic
    entry = sut.build(cell.config, mix, cell.operator)
    try:
        entry.ready()
        for i, rate in enumerate(args.rates):
            offsets = gen.arrival_offsets(rate, args.seconds)
            rhs = gen.rhs_set(cell.operator, cell.config, args.seed + i,
                              mix["rhs_pool"])
            if i == 0:
                entry.warm(rhs)
            out, (t0, _t1) = gen.open_loop(
                lambda k, until: entry.request(k % len(rhs),
                                               rhs[k % len(rhs)], until),
                offsets, args.seconds)
            lat = np.array([o.end - o.due for o in out])
            q = len(out) // 4 or 1
            by_due = lat[np.argsort([o.due for o in out])]
            print(json.dumps({
                "rate_per_s": rate, "requests": len(out),
                "failed": sum(not o.ok for o in out),
                "completed_per_s": len(out) / (max(o.end for o in out) - t0),
                "latency_p50_s": gen.percentile(lat, 50),
                "latency_p90_s": gen.percentile(lat, 90),
                "latency_p95_s": gen.percentile(lat, 95),
                "latency_p99_s": gen.percentile(lat, 99),
                "meets_limit": gen.percentile(lat, 95) <= args.limit_s,
                "latency_max_s": float(lat.max()),
                "generator_lag_p95_s": gen.percentile(
                    [o.start - o.due for o in out], 95),
                "batch_mean": float(np.mean([o.extra.get("batch_size", 0)
                                             for o in out])),
                "last_over_first_quarter": float(by_due[-q:].mean()
                                                 / by_due[:q].mean()),
            }), flush=True)
    finally:
        entry.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
