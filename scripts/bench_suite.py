"""Milestone benchmark suite (BASELINE.md configs), one JSON line per run.

Configs mirror the reference's benchmark protocol (per-op stats harness,
warmup, residual-rtol stopping — acg/cg.c:676-694, cuda/acg-cuda.c:511)
on generator inputs (zero-egress stand-ins for the SuiteSparse set):

  p2d-1024     5-pt 2D Poisson 1024^2   (1.0M DOF, bf16-exact bands)
  p3d-128      7-pt 3D Poisson 128^3    (2.1M DOF, bf16-exact bands)
  p3d-var-96   variable-coef 7-pt 96^3  (0.9M DOF, full-width bands)
  p3d-128-pipe pipelined CG on 128^3

Usage: python scripts/bench_suite.py [--configs a,b,...] [--dtype float32]
Runs on a TPU (``require_tpu``); through the chip tool, one process.
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np

# two-point marginal-rate protocol over END-TO-END WALL TIME of cg()
# calls (see bench.py: cg() returns after the solution copy-back).  Slow
# per-iteration configs use a narrower spread + fewer reps.
ITERS1, ITERS2, REPS = 500, 8000, 3
SLOW = {"rand-512k": (100, 500, 1), "p3d-464-100M": (200, 1200, 1),
        "p3d-256": (500, 4000, 2)}


def run_config(name, make_A, solver, dtype, nrhs: int = 1,
               fmt: str = "auto"):
    import jax
    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.solvers.cg import (build_device_operator, cg,
                                    cg_pipelined, cg_pipelined_deep,
                                    cg_sstep)

    A = make_A(dtype)
    if solver.startswith("dist-"):
        return run_dist_config(name, A, solver, dtype, fmt)
    dev = build_device_operator(A, dtype=dtype, mat_dtype="auto",
                                fmt=fmt)
    n_pad = dev.nrows_padded
    rng = np.random.default_rng(0)
    # multi-RHS configs solve an (nrhs, n) batch — independent systems,
    # one operator stream (scripts/bench_batched.py runs the full sweep)
    shape = (n_pad,) if nrhs == 1 else (nrhs, n_pad)
    b_host = np.zeros(shape, dtype=dtype)
    b_host[..., : A.nrows] = rng.standard_normal(
        shape[:-1] + (A.nrows,)).astype(dtype)
    b = jnp.asarray(b_host)
    jax.block_until_ready(b)

    sstep = int(solver[5:]) if solver.startswith("sstep") else 0
    # deepL = depth-L pipelined CG (ISSUE 17): L reductions in flight;
    # single-chip the latency hiding is moot, but the segment arithmetic
    # and redispatch cadence are exactly what these rows time
    depth = int(solver[4:]) if solver.startswith("deep") else 0
    fn = (cg_sstep if sstep else
          cg_pipelined_deep if depth else
          cg_pipelined if solver == "pipelined" else cg)
    # pipelined timing solves carry the production drift correction: past
    # the f32 convergence floor the uncorrected recurrence restarts
    # endlessly at a poor floor, so measure the configuration users run
    # (the deep solver replaces at every segment boundary by design)
    replace = 50 if solver == "pipelined" else 0
    # slow per-iteration paths (gather ELL; 100M-DOF XLA streams) must
    # bound single-program runtime (a device program past ~60 s was
    # killed on the pre-bring-up chip access; not re-measured since).
    # Segments are numerically identical; the extra dispatch per segment
    # is sub-0.5% of these configs' per-iteration cost.
    segment = {"rand-512k": 150, "p3d-464-100M": 400}.get(name, 0)
    i1, i2, reps = SLOW.get(name, (ITERS1, ITERS2, REPS))
    tsolve = {}
    for iters in (i1, i2):
        opts = SolverOptions(maxits=iters, residual_rtol=0.0,
                             replace_every=replace,
                             segment_iters=segment, sstep=sstep,
                             pipeline_depth=depth if depth else 1)
        fn(dev, b, options=opts)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(dev, b, options=opts)   # returns after x reaches the host
            best = min(best, time.perf_counter() - t0)
        tsolve[iters] = best
    # per-chip throughput: each loop iteration advances nrhs systems
    # (it/s·rhs for batched configs; plain it/s when nrhs == 1)
    ips = (i2 - i1) / (tsolve[i2] - tsolve[i1]) * nrhs
    print(json.dumps({
        "config": name, "nrows": A.nrows, "nnz": A.nnz,
        "solver": solver, "nrhs": nrhs,
        # the analytic distributed psum model of this solver variant
        # (CommAudit proof: tests/test_hlo_audit.py): classic 2/iter,
        # pipelined 1/iter, s-step 1/s per iter
        "psums_per_iter": (f"1/{sstep}" if sstep
                           else "1/1" if solver == "pipelined" or depth
                           else "2/1"),
        "mat_storage": (
            "none (matrix-free)" if not hasattr(dev, "bands")
            and not hasattr(dev, "vals")
            else str(dev.bands.dtype) if hasattr(dev, "bands")
            else str(dev.vals.dtype)),
        "operator_stream_bytes": int(dev.operator_stream_bytes()),
        "iters_per_sec": round(ips, 1),
        "us_per_iter": round(1e6 / ips, 1),
        # each two-point rate is min-of-N wall times per point; N recorded
        # so readers can weigh runs against run-to-run variance
        "min_of": reps, "iters_points": [i1, i2],
    }), flush=True)


def run_dist_config(name, A, solver, dtype, fmt):
    """Distributed rows ("dist-<solver>-<wire>"): the halo wire-format
    A/B needs a mesh — sharded over every attached device, pipelined
    CG with the named wire encoding (ISSUE 17; PERF.md "Open
    measurements" queues the TPU numbers)."""
    import jax

    from acg_tpu.config import SolverOptions
    from acg_tpu.solvers.cg_dist import build_sharded, cg_pipelined_dist

    wire = {"f32": "f32", "bf16": "bf16",
            "i16": "int16-delta"}[solver.rsplit("-", 1)[-1]]
    nparts = len(jax.devices())
    ss = build_sharded(A, nparts=nparts, dtype=dtype, fmt=fmt)
    b = np.random.default_rng(0).standard_normal(A.nrows).astype(dtype)
    i1, i2, reps = SLOW.get(name, (ITERS1, ITERS2, REPS))
    tsolve = {}
    for iters in (i1, i2):
        opts = SolverOptions(maxits=iters, residual_rtol=0.0,
                             replace_every=50, halo_wire=wire)
        cg_pipelined_dist(ss, b, options=opts)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            cg_pipelined_dist(ss, b, options=opts)
            best = min(best, time.perf_counter() - t0)
        tsolve[iters] = best
    ips = (i2 - i1) / (tsolve[i2] - tsolve[i1])
    print(json.dumps({
        "config": name, "nrows": A.nrows, "nnz": A.nnz,
        "solver": solver, "nrhs": 1, "nparts": nparts,
        "halo_wire": wire, "psums_per_iter": "1/1",
        "mat_storage": f"sharded-{ss.local_fmt}",
        "operator_stream_bytes": 0,
        "iters_per_sec": round(ips, 1),
        "us_per_iter": round(1e6 / ips, 1),
        "min_of": reps, "iters_points": [i1, i2],
    }), flush=True)


def _fem(n, dim, dt):
    from acg_tpu.sparse.mesh import fem_delaunay_spd

    return fem_delaunay_spd(n, dim=dim, dtype=dt)


def _aniso(n, dt):
    from acg_tpu.sparse.mesh import poisson3d_7pt_aniso

    return poisson3d_7pt_aniso(n, dtype=dt)


def main():
    from acg_tpu.sparse import (poisson2d_5pt, poisson3d_7pt,
                                poisson3d_7pt_dia, poisson3d_7pt_varcoef,
                                random_spd)

    # constant-coefficient Poisson configs would RECOGNIZE as stencils,
    # so the stored-tier baselines pin fmt="dia" explicitly — on TPU
    # (stencil probe green) fmt="auto" would silently flip them
    # matrix-free and the stored-vs-stencil A/B would compare the new
    # tier against itself (trajectory continuity: these metrics have
    # measured the stored dia tier since round 1)
    cfgs = {
        "p2d-1024": (lambda dt: poisson2d_5pt(1024, dtype=dt), "cg", 1,
                     "dia"),
        "p3d-128": (lambda dt: poisson3d_7pt(128, dtype=dt), "cg", 1,
                    "dia"),
        # past the resident-x VMEM bound: exercises the HBM-resident
        # (clustered window DMA) fused kernel end-to-end
        "p3d-256": (lambda dt: poisson3d_7pt_dia(256, dtype=dt), "cg",
                    1, "dia"),
        "p3d-var-96": (lambda dt: poisson3d_7pt_varcoef(96, dtype=dt),
                       "cg"),
        "p3d-128-pipe": (lambda dt: poisson3d_7pt(128, dtype=dt),
                         "pipelined", 1, "dia"),
        # matrix-free stencil tier (ISSUE 12): the SAME 128^3 system
        # with the band stream deleted — A/B against p3d-128 (stored
        # dia) is the whole-solve matrix-free speedup; the emitted
        # operator_stream_bytes field is 0 here, and the perf gate
        # tracks the new tier's it/s from its first TPU round
        "p3d-128-stencil": (lambda dt: poisson3d_7pt_dia(128, dtype=dt),
                            "cg", 1, "stencil"),
        "p3d-128-pipe-stencil": (lambda dt: poisson3d_7pt_dia(
            128, dtype=dt), "pipelined", 1, "stencil"),
        # s-step configs (ISSUE 7): one Gram reduction per s iterations;
        # single-chip the collective count is moot, but the basis-build
        # arithmetic and the MXU Gram are exactly what these time — the
        # perf-gate trajectory covers the new path end to end
        "p3d-128-sstep2": (lambda dt: poisson3d_7pt(128, dtype=dt),
                           "sstep2", 1, "dia"),
        "p3d-128-sstep4": (lambda dt: poisson3d_7pt(128, dtype=dt),
                           "sstep4", 1, "dia"),
        # depth-l pipelined configs (ISSUE 17): l reductions in flight,
        # one psum per iteration; gated out of the default list until
        # the first TPU round lands the numbers (PERF.md "Open
        # measurements")
        "p3d-128-deep2": (lambda dt: poisson3d_7pt(128, dtype=dt),
                          "deep2", 1, "dia"),
        "p3d-128-deep4": (lambda dt: poisson3d_7pt(128, dtype=dt),
                          "deep4", 1, "dia"),
        # compressed halo wire A/B (ISSUE 17): pipelined CG sharded over
        # every attached device, bf16 wire — compare against the same
        # row at f32 wire; gated (needs a real multi-chip mesh to mean
        # anything)
        "p3d-128-wire-bf16": (lambda dt: poisson3d_7pt(128, dtype=dt),
                              "dist-pipe-bf16", 1, "dia"),
        # multi-RHS batched configs (ISSUE 2): same operator, B systems,
        # rate in it/s·rhs — the full B sweep lives in bench_batched.py
        "p3d-128-b4": (lambda dt: poisson3d_7pt(128, dtype=dt), "cg", 4,
                       "dia"),
        "p3d-128-b16": (lambda dt: poisson3d_7pt(128, dtype=dt), "cg",
                        16, "dia"),
        # unstructured random graph (no recoverable band): exercises the
        # gather-based ELL tier end-to-end — the SuiteSparse stand-in for
        # Queen_4147/Bump_2911/Serena (BASELINE.md; the workload of the
        # reference's merge SpMV, acg/cg-kernels-cuda.cu:340-441)
        "rand-512k": (lambda dt: random_spd(1 << 19, degree=8, dtype=dt),
                      "cg"),
        # the BASELINE.md north-star scale: 464^3 = 99.9M DOF, built
        # directly in DIA band form (no COO/CSR transient); NOT in the
        # default list — allow several minutes
        "p3d-464-100M": (lambda dt: poisson3d_7pt_dia(464, dtype=dt),
                         "cg", 1, "dia"),
        # the FEM differential family (VERDICT r4 item 7): SuiteSparse-
        # shaped problems generated locally, full matrix -> tier-routing
        # -> solve pipeline.  fem-1M: 1M-point 2-D Delaunay mesh in a
        # shuffled ordering (expected tier: RCM -> sgell); fem3d-200k:
        # 3-D mesh, degree ~15; p3d-aniso-128: anisotropic constant
        # coefficients (full-width DIA storage, fused f32 loop)
        "fem-1M": (lambda dt: _fem(1 << 20, 2, dt), "cg"),
        "fem3d-200k": (lambda dt: _fem(200_000, 3, dt), "cg"),
        "p3d-aniso-128": (lambda dt: _aniso(128, dt), "cg"),
    }
    default = ("p2d-1024,p3d-128,p3d-256,p3d-var-96,p3d-128-pipe,"
               "p3d-128-stencil,p3d-128-pipe-stencil,rand-512k")
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=default)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--with-serve", action="store_true",
                    help="append the closed-loop serving sweep "
                         "(scripts/bench_serve.py: requests/s, cold vs "
                         "amortized wall over a Session) after the "
                         "solver configs")
    args = ap.parse_args()
    from acg_tpu.utils.backend import require_tpu
    require_tpu()
    dtype = np.dtype(args.dtype).type
    for name in args.configs.split(","):
        make_A, solver, *rest = cfgs[name.strip()]
        t0 = time.perf_counter()
        run_config(name.strip(), make_A, solver, dtype,
                   nrhs=rest[0] if rest else 1,
                   fmt=rest[1] if len(rest) > 1 else "auto")
        print(f"# {name}: total {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)

    if args.with_serve:
        # the serving sweep emits its own bench_record lines (req/s,
        # cold vs amortized wall) onto the same trajectory
        from scripts.bench_serve import main as bench_serve_main
        bench_serve_main(["--dtype", args.dtype])

    # perf-regression gate, dry mode: surface the BENCH_* trajectory
    # comparison at the end of every suite run (same wiring tier as the
    # bench_batched --dry-run smoke; the GATING invocation is
    # scripts/check_perf_regression.py without --dry-run)
    import os

    from scripts.check_perf_regression import main as perf_gate_main
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rc = perf_gate_main(["--dry-run", "--dir", root])
    if rc:
        # dry mode returns nonzero only for malformed artifacts — a
        # wiring bug the suite must surface, not swallow
        sys.exit(rc)


if __name__ == "__main__":
    main()
