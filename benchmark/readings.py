#!/usr/bin/env python3
"""Read the numbers that set a cell's correctness limit, in one process.

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds 5

For each of ``--seeds`` it runs the cell's traffic for a short window on
the program, built once, and checks every answer as a run does: the
largest ``true_relres_max`` over sound runs is the lower reading.  Then,
with the program's state freed, for each of ``--control-seeds`` it puts
the plain reference CG in the program's place, computed one precision
below the configuration's (bfloat16 for float32), on the right-hand
sides that seed gives a run (at most ``--control-rhs`` of them): the
smallest of its readings is the upper one.  The same reference at the
configuration's own precision is read beside it.  One JSON line per
reading.  Needs the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LOWER = {"float32": "bfloat16", "float64": "float32"}


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--control-rhs", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax.numpy as jnp

    from acg_tpu.utils.backend import enable_compile_cache, require_tpu
    from benchmark import harness, sut, traffic as gen
    from benchmark.reference import cg_reference, true_relres

    enable_compile_cache()
    devices = require_tpu()
    cell = harness.find_cell(args.workload)
    if len(devices) < int(cell.cell["chips"]):
        print(f"readings: {cell.name} needs {cell.cell['chips']} chips",
              file=sys.stderr)
        return 2
    if args.seeds:
        entry = sut.build(cell.config, cell.traffic, cell.operator)
        entry.ready()
        for seed in args.seeds:
            window = harness.drive(cell, entry, seed, args.seconds)
            outcomes, win, rhs = window()
            correct, failed, checks = harness.check(cell, outcomes, rhs)
            emit(kind="program", workload=cell.name, seed=seed,
                 correct=correct, attempted=len(outcomes), failed=failed,
                 true_relres_max=checks["true_relres_max"]["value"],
                 iterations=sorted({o.niterations for o in outcomes}))
        entry.close()
        del entry
    cfg = cell.config
    apply_np = cell.operator.reference_apply(cfg)
    apply_jnp = cell.operator.reference_apply_jnp(cfg)
    for seed in args.control_seeds:
        rhs = gen.rhs_set(cell.operator, cfg, seed,
                          cell.traffic["rhs_pool"])[:args.control_rhs]
        for dtype in (LOWER[cfg["dtype"]], cfg["dtype"]):
            rels, its = [], []
            for b in rhs:
                x, k = cg_reference(apply_jnp, b, cfg["rtol"], cfg["maxits"],
                                    jnp.dtype(dtype))
                rels.append(true_relres(apply_np, b, x))
                its.append(k)
            emit(kind="control" if dtype != cfg["dtype"] else "reference",
                 workload=cell.name, seed=seed, dtype=dtype,
                 true_relres_max=max(rels), true_relres=rels,
                 iterations=its)
    return 0


if __name__ == "__main__":
    sys.exit(main())
