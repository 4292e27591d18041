#!/usr/bin/env python3
"""Prove that the solver's main path runs on a TPU, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip path and its reference

Each phase prints one JSON line with what ran and its numbers.  The last
line is ``{"ok": true, "device": {...}}`` and appears only when every
phase passed.  Without a TPU the script exits non-zero at once: nothing
here falls back to the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

RTOL = 1e-5          # fp32 solves: relative residual target
CERT = 2e-5          # bound on the host float64 true relative residual
MAXITS = 5000
# two solves of one system by the same algorithm, on one chip and on
# four, differ only in rounding order: iteration counts within ITS_SLACK
# and solutions within AGREE.  Readings (PR 21): classic 1 vs 4 chips at
# 256³, 532 = 532 iterations and 3.6e-7 apart (chip); pipelined on a
# 4-part CPU mesh, 157 = 157 and 1.7e-6 apart at 64³, 64 = 64 and 3.0e-5
# at 24³; stpipe2d vs the open-coded loop, 1.6e-4 apart after 100
# unconverged iterations at 128³ (chip).  AGREE is 6x the largest.
ITS_SLACK = 5
AGREE = 1e-3
# stpipe2d against the open-coded loop after SHORT_ITS iterations: each
# iteration rounds every vector entry a few dozen times in fp32, so
# without amplification the two differ by at most about SHORT_ITS x 16
# x 2**-24 = 1e-5 relative; a kernel fault (a wrong halo row, arm or
# coefficient) is visible from the first iteration.  After LONG_ITS,
# CG has amplified rounding along its slow modes (1.6e-4 apart at 100
# iterations, PR 21) but a fault would move the residual: the true
# residuals must agree within RES_AGREE.
SHORT_ITS, SHORT_AGREE = 10, 1e-5
LONG_ITS, RES_AGREE = 100, 1e-2


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}, default=str), flush=True)


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def true_relres(A, b, x) -> float:
    """|b - A x| / |b| on the host in float64."""
    b64 = np.asarray(b, np.float64)
    r = b64 - A.matvec(np.asarray(x, np.float64))
    return float(np.linalg.norm(r) / np.linalg.norm(b64))


def host_dia(A):
    """Float64 host copy of a DIA operator for certification."""
    import dataclasses

    return dataclasses.replace(A, bands=A.bands.astype(np.float64))


def planned_kernel(A, pipelined: bool, nrhs: int = 1) -> str:
    """The kernel name the plan functions select for ``A`` (fp32 vectors,
    mat_dtype="auto", a batch of ``nrhs``) on this backend, derived
    without the solver."""
    from acg_tpu.ops.dia import resolve_mat_dtype
    from acg_tpu.ops.pallas_kernels import (fused_plan_for,
                                            pallas_2d_batched_plan,
                                            pallas_spmv_available,
                                            pipe2d_rt_for)
    from acg_tpu.ops.stencil import (prefer_stencil, stencil_available,
                                     stencil_batched_plan,
                                     stencil_pipe_plan, stencil_plan,
                                     try_device_stencil)

    f32 = np.dtype(np.float32)
    st, _ = try_device_stencil(A, dtype=f32)
    if st is not None and stencil_available() and prefer_stencil(
            st.nrows_padded, st.offsets, st.coeffs, f32, "auto", nrhs):
        if (pipelined and nrhs == 1 and stencil_available("stpipe2d")
                and stencil_pipe_plan(st.nrows_padded, st.offsets,
                                      f32) is not None):
            return "pallas-stpipe2d"
        rt = (stencil_batched_plan(nrhs, st.nrows_padded, st.offsets, f32)
              if nrhs > 1 else stencil_plan(st.nrows_padded, st.offsets,
                                            f32))
        return "pallas-stencil" if rt is not None else "xla-gridshift"
    mdt = np.dtype(resolve_mat_dtype(A.bands, "auto", f32))
    if nrhs > 1:
        ok = (pallas_spmv_available("batched2d") and pallas_2d_batched_plan(
            nrhs, A.nrows_padded, A.offsets, f32, mdt) is not None)
        return "pallas-resident-batched" if ok else "xla-shift"
    plan = fused_plan_for(A.nrows_padded, A.offsets, f32, mdt)
    if plan is None:
        return "xla-shift"
    if pipelined and pipe2d_rt_for(A.nrows_padded, A.offsets, f32, mdt,
                                   plan) is not None:
        return "pallas-pipe2d"
    return f"pallas-{plan[0]}"


def rhs(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def solve_phase(name: str, grid: int, dev, seed: int) -> bool:
    """cg and cg_pipelined with --format auto and the solvers' default
    options on the grid³ Poisson system: converged, certified in
    float64, and through the planned kernel."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.solvers.cg import cg, cg_pipelined
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    t0 = time.perf_counter()
    A = poisson3d_7pt_dia(grid, dtype=np.float32)
    b = rhs(A.nrows, seed)
    Ah = host_dia(A)
    setup_s = time.perf_counter() - t0
    ok = True
    for label, solver, pipelined in (("cg", cg, False),
                                     ("cg-pipelined", cg_pipelined, True)):
        opts = SolverOptions(maxits=MAXITS, residual_rtol=RTOL)
        want = planned_kernel(A, pipelined)
        t1 = time.perf_counter()
        solver(A, b, options=opts, dtype=np.float32)    # compile + run
        cold_s = time.perf_counter() - t1
        t1 = time.perf_counter()
        res = solver(A, b, options=opts, dtype=np.float32)
        wall_s = time.perf_counter() - t1
        rel = true_relres(Ah, b, res.x[: A.nrows])
        good = (bool(res.converged) and rel <= CERT
                and res.kernel == want
                and (dev.platform != "tpu" or want.startswith("pallas-")))
        ok = ok and good
        emit(name, solver=label, grid=grid, unknowns=A.nrows, ok=good,
             iterations=int(res.niterations), wall_s=wall_s,
             cold_s=cold_s, setup_s=setup_s,
             iters_per_sec=res.niterations / wall_s,
             true_relres=rel, converged=bool(res.converged),
             operator_format=res.operator_format, kernel=res.kernel,
             planned_kernel=want, kernel_note=res.kernel_note or None,
             peak_bytes_in_use=peak_bytes(dev))
    return ok


def cli_phase(grid: int, outdir: str) -> bool:
    """acg_tpu.cli.main in-process on a seeded grid³ .mtx file."""
    from acg_tpu.cli import main as cli_main
    from acg_tpu.io import write_mtx
    from acg_tpu.io.mtxfile import MtxFile
    from acg_tpu.sparse import poisson3d_7pt

    A = poisson3d_7pt(grid, dtype=np.float32)
    r, c, v = A.to_coo()
    keep = r >= c
    path = os.path.join(outdir, f"poisson3d_{grid}.mtx")
    write_mtx(path, MtxFile(symmetry="symmetric", nrows=A.nrows,
                            ncols=A.ncols, nnz=int(keep.sum()),
                            rowidx=r[keep], colidx=c[keep], vals=v[keep]))
    stats = os.path.join(outdir, f"cli_stats_{grid}.json")
    t0 = time.perf_counter()
    rc = cli_main([path, "--dtype", "float32", "--solver", "acg-pipelined",
                   "--manufactured-solution", "--max-iterations",
                   str(MAXITS), "--residual-rtol", str(RTOL),
                   "--output-stats-json", stats, "-q"])
    wall_s = time.perf_counter() - t0
    with open(stats) as f:
        doc = json.load(f)
    res, caps = doc["result"], doc["capabilities"]
    ok = (rc == 0 and bool(res["converged"])
          and caps["platforms"] == ["tpu"])
    emit("cli", ok=ok, rc=rc, grid=grid, wall_s=wall_s,
         platforms=caps["platforms"], converged=res["converged"],
         iterations=res["niterations"],
         relative_residual=res["relative_residual"],
         kernel=res["kernel"], operator_format=res["operator_format"])
    return ok


def serve_phase(grid: int, dev, nreq: int = 4) -> bool:
    """A SolverService over a Session answers nreq seeded requests."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.serve.service import SolverService
    from acg_tpu.serve.session import Session
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    A = poisson3d_7pt_dia(grid, dtype=np.float32)
    Ah = host_dia(A)
    opts = SolverOptions(maxits=MAXITS, residual_rtol=RTOL)
    sess = Session(A, dtype=np.float32, options=opts, prep_cache=None,
                   nrhs=nreq)
    svc = SolverService(sess, solver="cg", max_batch=nreq)
    ok = True
    try:
        bs = [rhs(A.nrows, 100 + i) for i in range(nreq)]
        t0 = time.perf_counter()
        reqs = [svc.submit(b, request_id=f"r{i}") for i, b in enumerate(bs)]
        resps = [q.response(timeout=600) for q in reqs]
        wall_s = time.perf_counter() - t0
        for b, resp in zip(bs, resps):
            res = resp.result
            rel = (true_relres(Ah, b, res.x[: A.nrows])
                   if res is not None else None)
            # the batch width picks the kernel: at 128³ no batched Pallas
            # kernel plans for 2..4 systems (their x block exceeds VMEM)
            want = planned_kernel(A, False, nrhs=resp.batch_size or 1)
            good = (resp.ok and resp.status == "SUCCESS"
                    and rel is not None and rel <= CERT
                    and res.kernel == want)
            ok = ok and good
            emit("serve", ok=good, request=resp.request_id,
                 status=resp.status, true_relres=rel,
                 iterations=None if res is None else int(res.niterations),
                 kernel=None if res is None else res.kernel,
                 planned_kernel=want, batched=resp.batch_size,
                 wall_s=resp.wall)
        emit("serve", ok=ok, requests=nreq, wall_s=wall_s,
             peak_bytes_in_use=peak_bytes(dev))
    finally:
        svc.close()
        sess.close()
    return ok


def pipe_compare_phase(grid: int, seed: int) -> bool:
    """The matrix-free pipelined mega-kernel (stpipe2d) against the
    open-coded pipelined loop (XLA recurrences around the stencil SpMV
    kernel; ``segment_iters`` keeps the open-coded body) for the same
    fixed iterations on the same system (see SHORT_ITS / LONG_ITS)."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.solvers.cg import cg_pipelined
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    A = poisson3d_7pt_dia(grid, dtype=np.float32)
    Ah = host_dia(A)
    b = rhs(A.nrows, seed)
    ok = True
    for its in (SHORT_ITS, LONG_ITS):
        runs = []
        for o in (SolverOptions(maxits=its, residual_rtol=0.0),
                  SolverOptions(maxits=its, residual_rtol=0.0,
                                segment_iters=its)):
            res = cg_pipelined(A, b, options=o, dtype=np.float32)
            x = np.asarray(res.x[: A.nrows], np.float64)
            runs.append((res, x, true_relres(Ah, b, x)))
        (r1, x1, rel1), (r2, x2, rel2) = runs
        agree = float(np.linalg.norm(x1 - x2) / np.linalg.norm(x2))
        good = (r1.niterations == r2.niterations == its
                and r1.kernel == planned_kernel(A, True) == "pallas-stpipe2d"
                and r2.kernel == planned_kernel(A, False)
                and (agree <= SHORT_AGREE if its == SHORT_ITS
                     else abs(rel1 - rel2) <= RES_AGREE * rel2))
        ok = ok and good
        emit("pipe-compare", ok=good, grid=grid, iterations=its,
             kernels=[r1.kernel, r2.kernel], true_relres=[rel1, rel2],
             rel_diff=agree)
    return ok


def probes_phase() -> bool:
    from acg_tpu.ops.pallas_kernels import probe_all

    from acg_tpu.ops.pallas_kernels import OUT_OF_AUTO

    errors = probe_all()
    for group, err in errors.items():
        # a group out of automatic routing may fail: it is reported, and
        # forcing its kernel raises (acg_tpu/ops/pallas_spmv.py)
        emit("probe", group=group, ok=err is None, error=err,
             auto_routed=group not in OUT_OF_AUTO)
    return all(e is None for g, e in errors.items() if g not in OUT_OF_AUTO)


def four_chip_phase(grid: int, devs) -> bool:
    """cg_dist / cg_pipelined_dist over 4 chips (ppermute halo, then the
    rdma halo) against cg / cg_pipelined on device 0, all on the grid³
    Poisson system with one seeded right-hand side and default options."""
    import jax

    from acg_tpu.config import HaloMethod, SolverOptions
    from acg_tpu.errors import AcgError
    from acg_tpu.solvers.cg import cg, cg_pipelined
    from acg_tpu.solvers.cg_dist import (build_sharded, cg_dist,
                                         cg_pipelined_dist)
    from acg_tpu.sparse import poisson3d_7pt
    from acg_tpu.sparse.poisson import (grid_partition_vector,
                                        poisson3d_7pt_dia)

    opts = SolverOptions(maxits=MAXITS, residual_rtol=RTOL)
    t0 = time.perf_counter()
    A = poisson3d_7pt(grid, dtype=np.float32)
    part = grid_partition_vector((grid,) * 3, (4, 1, 1))
    t1 = time.perf_counter()
    ss = build_sharded(A, part=part, dtype=np.float32,
                       method=HaloMethod.PPERMUTE)
    prep_s = time.perf_counter() - t1
    emit("setup-4chip", grid=grid, unknowns=A.nrows,
         matrix_s=t1 - t0, partition_and_upload_s=prep_s,
         local_fmt=ss.local_fmt)
    mesh_devs = list(ss.mesh.devices.flat)
    ids = sorted(d.id for d in mesh_devs)
    ok = len(set(ids)) == 4 and all(d.platform == "tpu" for d in mesh_devs)
    b = rhs(A.nrows, 7)
    arrays = {f"operator{i}": a for i, a in
              enumerate(ss.local_op_arrays() + (ss.ivals,))
              if a is not None}
    arrays["b"] = ss.to_sharded(b)
    placed = {}
    for name, arr in arrays.items():
        on = [s.device.id for s in arr.addressable_shards]
        placed[name] = on
        ok = ok and sorted(on) == ids          # one shard per chip
    emit("placement-4chip", ok=ok, mesh_devices=ids, shards=placed)

    Ad = poisson3d_7pt_dia(grid, dtype=np.float32)
    refs = {}
    for label, solver in (("cg", cg), ("cg_pipelined", cg_pipelined)):
        t1 = time.perf_counter()
        r = solver(Ad, b, options=opts, dtype=np.float32)
        x = np.asarray(r.x[: A.nrows])
        rel = true_relres(A, b, x)
        refs[label] = (r, x)
        ok = ok and bool(r.converged) and rel <= CERT
        emit("reference-1chip", solver=label, device=int(devs[0].id),
             iterations=int(r.niterations), true_relres=rel,
             wall_s_with_compile=time.perf_counter() - t1,
             kernel=r.kernel)
    del Ad
    xpp = None
    for label, solver, ref, method in (
            ("cg_dist", cg_dist, "cg", "ppermute"),
            ("cg_pipelined_dist", cg_pipelined_dist, "cg_pipelined",
             "ppermute"),
            ("cg_dist", cg_dist, "cg", "rdma")):
        if method == "rdma":
            try:
                system = build_sharded(ss.ps, dtype=np.float32,
                                       method=HaloMethod.RDMA)
            except AcgError as e:
                good = e.status.name == "ERR_NOT_SUPPORTED"
                ok = ok and good
                emit("solve-4chip", solver=label, halo=method, ok=good,
                     status=e.status.name, error=str(e))
                continue
        else:
            system = ss
        solver(system, b, options=opts)                  # compile + run
        t1 = time.perf_counter()
        res = solver(system, b, options=opts)
        wall_s = time.perf_counter() - t1
        x = np.asarray(res.x[: A.nrows])
        rel = true_relres(A, b, x)
        r1, x1 = refs[ref]
        agree = float(np.linalg.norm(x - x1) / np.linalg.norm(x1))
        dits = int(res.niterations) - int(r1.niterations)
        good = (bool(res.converged) and rel <= CERT and agree <= AGREE
                and abs(dits) <= ITS_SLACK)
        if method == "rdma":
            good = good and float(np.linalg.norm(x - xpp)) <= AGREE * float(
                np.linalg.norm(xpp))
        elif label == "cg_dist":
            xpp = x
        ok = ok and good
        emit("solve-4chip", solver=label, halo=method, ok=good,
             iterations=int(res.niterations), reference=ref,
             iterations_1chip=int(r1.niterations), wall_s=wall_s,
             iters_per_sec=res.niterations / wall_s, true_relres=rel,
             rel_diff_vs_1chip=agree, kernel=res.kernel,
             operator_format=res.operator_format,
             peak_bytes_in_use=[peak_bytes(d) for d in jax.devices()])
    return ok


def run(phases) -> bool:
    """Run every phase even after a failure (one chip call reports all of
    them); True when all passed."""
    ok = True
    for name, fn in phases:
        try:
            good = bool(fn())
        except Exception as e:  # noqa: BLE001 - reported, then failed
            good = False
            emit(name, ok=False, error=f"{type(e).__name__}: {e}",
                 traceback=traceback.format_exc()[-3000:])
        ok = ok and good
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: the four-chip "
                         "distributed path and its single-chip reference")
    ap.add_argument("--out", default=".chip_smoke",
                    help="directory for the CLI phase's files")
    args = ap.parse_args(argv)

    from acg_tpu.utils.backend import (NoTpuError, enable_compile_cache,
                                       f64_report, require_tpu)

    cache = enable_compile_cache()
    try:
        devs = require_tpu()
    except NoTpuError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    dev = devs[0]
    from acg_tpu import native

    built = native.build(verbose=True)
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devs), memory_stats=dev.memory_stats(),
         compile_cache=cache, native_built=built,
         native_loaded=native.available())
    emit("f64", **f64_report())
    os.makedirs(args.out, exist_ok=True)

    if args.chips == 4:
        phases = [("solve-4chip", lambda: four_chip_phase(256, devs))]
    else:
        phases = [("probes", probes_phase),
                  ("solve-1chip", lambda: solve_phase("solve-1chip", 256,
                                                      dev, seed=1)),
                  ("solve-128", lambda: solve_phase("solve-128", 128,
                                                    dev, seed=2)),
                  ("pipe-compare", lambda: pipe_compare_phase(128, seed=3)),
                  ("cli", lambda: cli_phase(64, args.out)),
                  ("serve", lambda: serve_phase(128, dev))]
    if not run(phases):
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
