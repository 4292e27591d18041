#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device`` and, traced, the
``breakdown``; ``checks``, last, holds each number compared with its
limit, and the same lines end standard error.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache key); the program takes it from here
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def finite_or_text(v):
    return v if not isinstance(v, float) or math.isfinite(v) else repr(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    try:
        import jax

        from acg_tpu.utils.backend import (NoTpuError, enable_compile_cache,
                                           require_tpu)
        from benchmark import harness
    except ImportError as e:
        print(f"benchmark: cannot import the program: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = require_tpu()
        cell = harness.find_cell(args.workload)
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_START, devices)
    except (NoTpuError, harness.SetupError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    checks = {k: {kk: finite_or_text(vv) for kk, vv in v.items()}
              for k, v in line["checks"].items()}
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
