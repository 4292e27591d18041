"""Batched multi-RHS CG sweep: B ∈ {1, 4, 16} on one operator.

Measures the request-batching lever (ISSUE 2 / ROADMAP "serve heavy
traffic"): solving B right-hand sides against the same operator re-reads
the DIA band stream once per iteration instead of B times, so per-chip
throughput (reported as **it/s·rhs** — marginal loop iterations/sec × B;
every loop iteration advances all B systems, see PERF.md "Batched
multi-RHS methodology") rises with B until the vector streams dominate.

One JSON line per B through the shared :func:`bench_record` schema
(acg_tpu/obs/export.py — the same payload ``scripts/check_stats_schema.py``
lints inside BENCH_*.json trajectory wrappers), tagged with ``nrhs`` and
the kernel tier that actually ran.

Protocol is bench.py's two-point marginal over end-to-end wall time of
``cg()`` calls, which return after the solution is on the host (see
bench.py's timing note).

Usage:
  python scripts/bench_batched.py [--grid N] [--batches 1,4,16]
  python scripts/bench_batched.py --dry-run      # CPU-sized smoke pass

``--dry-run`` shrinks everything (tiny grid, 2-point {2, 4} iteration
protocol, one rep) so the full sweep wiring — batched solve, record
schema, kernel reporting — executes in seconds on the CPU backend; the
tier-1 smoke test runs exactly this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def run_batch_point(dev, rng, nrhs: int, i1: int, i2: int, reps: int):
    """Two-point marginal it/s·rhs for one batch size.  Returns
    (rate, SolveResult of the last timed solve)."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.solvers.cg import cg

    # independent RHS per system — the same construction bench.py --nrhs
    # uses, so the two capture commands measure identically-built
    # batches (a replicated batch would do identical work per system,
    # which measures the same bytes but invites doubt)
    n_pad, nrows = dev.nrows_padded, dev.nrows
    shape = (n_pad,) if nrhs == 1 else (nrhs, n_pad)
    b = np.zeros(shape, dtype=np.dtype(dev.vec_dtype))
    b[..., :nrows] = rng.standard_normal(
        shape[:-1] + (nrows,)).astype(b.dtype)
    bb = jnp.asarray(b)
    jax.block_until_ready(bb)
    tsolve = {}
    res = None
    for iters in (i1, i2):
        opts = SolverOptions(maxits=iters, residual_rtol=0.0)
        cg(dev, bb, options=opts)           # warmup: compile + run
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            res = cg(dev, bb, options=opts)
            best = min(best, time.perf_counter() - t0)
        tsolve[iters] = best
    # clamp the denominator: a dry-run's 2-iteration solves can time
    # inside clock jitter (dt <= 0), and the record schema wants a number
    dt = max(tsolve[i2] - tsolve[i1], 1e-9)
    return (i2 - i1) / dt * nrhs, res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Batched multi-RHS CG throughput sweep (it/s·rhs).")
    ap.add_argument("--grid", type=int, default=128,
                    help="3-D Poisson grid edge (128 => 2.1M DOF) [128]")
    ap.add_argument("--batches", default="1,4,16",
                    help="comma-separated batch sizes to sweep [1,4,16]")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU-sized smoke pass: tiny grid, 2-point {2,4} "
                         "protocol, 1 rep — exercises the full wiring "
                         "without a device")
    args = ap.parse_args(argv)

    from acg_tpu.obs.export import bench_record
    from acg_tpu.ops.dia import DeviceDia, DiaMatrix
    from acg_tpu.sparse import poisson3d_7pt

    if args.dry_run:
        grid, i1, i2, reps = 8, 2, 4, 1
    else:
        from acg_tpu.utils.backend import require_tpu

        require_tpu()
        grid, i1, i2, reps = args.grid, 500, 8000, 3

    dtype = np.dtype(args.dtype).type
    A = poisson3d_7pt(grid, dtype=dtype)
    dev = DeviceDia.from_dia(DiaMatrix.from_csr(A), dtype=dtype,
                             mat_dtype="auto")
    rng = np.random.default_rng(0)

    for nrhs in (int(s) for s in args.batches.split(",")):
        rate, res = run_batch_point(dev, rng, nrhs, i1, i2, reps)
        print(json.dumps(bench_record(
            metric=f"cg_batched_its_rhs_poisson7pt_{grid}cubed"
                   f"_{np.dtype(dtype).name}_b{nrhs}",
            value=round(rate, 3),
            unit="it/s*rhs",
            nrhs=nrhs,
            nrows=A.nrows,
            mat_storage=str(dev.bands.dtype),
            format=res.operator_format,
            kernel=res.kernel,
            dry_run=bool(args.dry_run),
        )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
