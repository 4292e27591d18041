"""Kernel-level measurements on the attached TPU chip, one JSON line each.

Answers the measured-decision questions the round-2 verdict posed:

  storage-tiers   auto vs int8-mask vs bf16 vs f32 whole-CG at 128^3,
                  end-to-end wall marginal (which tier is fastest, and
                  what does auto pick?)
  hbm-spmv        XLA vs the HBM-resident 2-D kernel past the VMEM
                  bound at 256^3 (the 100M-DOF road)
  spmv-2d         2-D layout resident Pallas SpMV vs XLA, timed with
                  data-chained iterations (immune to dispatch noise)
  stencil         matrix-free DeviceStencil vs stored dia-bf16/dia-f32
                  at 128^3: SpMV + whole-CG + whole-pipelined marginals
                  with the analytic roofline-ceiling comparison column
                  (operator_bytes == 0 rows show the vector-only
                  ceiling the deleted band stream buys)

(the pipelined-update suite was removed with the kernel it measured:
XLA's in-loop fusion won, speedup 0.981, pre-bring-up record; the ELL
suite went with the Pallas ELL kernel, which Mosaic refuses on v5e)

Usage: python scripts/bench_kernels.py [--suites a,b,...] [--reps N]
Runs on a TPU (``require_tpu``); through the chip tool, one process.
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def timeit(fn, *args, reps=30):
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def emit(**kw):
    print(json.dumps(kw), flush=True)


def suite_storage_tiers(reps):
    """auto/int8-mask/bf16/f32 band storage: whole-CG end-to-end wall
    marginal it/s at 128^3 (VERDICT r2 item 5; the isolated-SpMV column
    was dropped with the tsolve protocol — single-op timings are
    dispatch noise)."""
    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.solvers.cg import cg
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    D = poisson3d_7pt_dia(128, dtype=np.float32)
    rng = np.random.default_rng(0)
    n = D.nrows_padded
    b = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    for tier, mat_dtype in (("auto", "auto"), ("int8-two-value", "int8"),
                            ("bf16", "bfloat16"), ("f32", None)):
        dev = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype=mat_dtype)
        ts = {}
        # end-to-end wall time over a wide spread (see bench.py: the only
        # trustworthy completion signal is the solution copy-back)
        for iters in (500, 8000):
            opts = SolverOptions(maxits=iters, residual_rtol=0.0)
            cg(dev, b, options=opts)
            best = float("inf")
            for _ in range(max(reps // 10, 3)):
                t0 = time.perf_counter()
                cg(dev, b, options=opts)
                best = min(best, time.perf_counter() - t0)
            ts[iters] = best
        ips = (8000 - 500) / (ts[8000] - ts[500])
        emit(suite="storage-tiers", tier=tier,
             mat_storage=str(dev.bands.dtype),
             cg_iters_per_sec=round(ips, 1))


def suite_spmv_2d(reps):
    """2-D layout resident Pallas SpMV vs XLA at 128^3, timed as a
    data-chained `lax.scan` (marginal over chain length) so per-dispatch
    latency cannot pollute the per-matvec number."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.ops.dia import DeviceDia, dia_matvec
    from acg_tpu.ops.pallas_kernels import (_pick_rows_tile,
                                            dia_matvec_pallas_2d)
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    D = poisson3d_7pt_dia(128, dtype=np.float32)
    CHAIN = 50
    for tier, mat_dtype in (("bf16", "bfloat16"), ("f32", None)):
        dev = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype=mat_dtype)
        n = dev.nrows_padded
        rt = _pick_rows_tile(n)
        x0 = jnp.asarray(np.random.default_rng(7)
                         .standard_normal(n).astype(np.float32))
        ideal = dev.bands.size * dev.bands.dtype.itemsize + 2 * n * 4
        variants = [
            ("xla", lambda x: dia_matvec(dev.bands, dev.offsets, x,
                                         scales=dev.scales)),
            ("pallas2d", lambda x: dia_matvec_pallas_2d(
                dev.bands, dev.offsets, x, rows_tile=rt,
                scales=dev.scales)),
            ("pallas2d-rt128", lambda x: dia_matvec_pallas_2d(
                dev.bands, dev.offsets, x, rows_tile=128,
                scales=dev.scales)),
        ]
        for vname, mv in variants:
            def chain_fn(length, mv=mv):
                @jax.jit
                def chain(x):
                    def body(x, _):
                        return mv(x) * 0.125, None
                    return jax.lax.scan(body, x, None, length=length)[0]
                return chain

            try:
                # two-point marginal over chain length: constant dispatch/
                # sync cost cancels
                t1 = timeit(chain_fn(CHAIN), x0, reps=max(reps // 10, 3))
                t2 = timeit(chain_fn(9 * CHAIN), x0,
                            reps=max(reps // 10, 3))
                t = (t2 - t1) / (8 * CHAIN)
            except Exception as e:
                emit(suite="spmv-2d", tier=tier, variant=vname,
                     error=f"{type(e).__name__}")
                continue
            emit(suite="spmv-2d", tier=tier, variant=vname,
                 us_per_matvec=round(t * 1e6, 1),
                 gbps_vs_ideal=round(ideal / t / 1e9, 1))


def suite_hbm_spmv(reps):
    """DIA SpMV past the resident VMEM bound: XLA vs the HBM-resident 2-D
    kernel (clustered window DMAs), chained-marginal timed (see spmv-2d),
    at 256^3 (f32 vectors, bf16 bands) for both storage widths."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.ops.dia import DeviceDia, dia_matvec
    from acg_tpu.ops.pallas_kernels import (LANES, dia_matvec_pallas_hbm2d,
                                            pad_dia_operands,
                                            padded_halo_rows,
                                            pallas_2d_plan,
                                            pallas_hbm2d_plan)
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    D = poisson3d_7pt_dia(256, dtype=np.float32)
    CHAIN = 20
    for tier, mat_dtype in (("bf16", "bfloat16"), ("f32", None)):
        dev = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype=mat_dtype)
        n = dev.nrows_padded
        assert pallas_2d_plan(n, dev.offsets, np.float32,
                              dev.bands.dtype) is None
        rt = pallas_hbm2d_plan(n, dev.offsets, np.float32, dev.bands.dtype)
        x0 = jnp.asarray(np.random.default_rng(7)
                         .standard_normal(n).astype(np.float32))
        ideal = dev.bands.size * dev.bands.dtype.itemsize + 2 * n * 4
        variants = [
            ("xla", lambda x: dia_matvec(dev.bands, dev.offsets, x,
                                         scales=dev.scales))]
        if rt is not None:
            def hbm(x, rt=rt):
                bp, (xp,) = pad_dia_operands(dev.bands, (x,), rt,
                                             dev.offsets)
                hp = padded_halo_rows(dev.offsets, rt) * LANES
                y = dia_matvec_pallas_hbm2d(bp, dev.offsets, xp,
                                            rows_tile=rt,
                                            scales=dev.scales)
                return y[hp: hp + n]
            variants.append((f"hbm2d-rt{rt}", hbm))
        for vname, mv in variants:
            def chain_fn(length, mv=mv):
                @jax.jit
                def chain(x):
                    def body(x, _):
                        return mv(x) * 0.125, None
                    return jax.lax.scan(body, x, None, length=length)[0]
                return chain

            try:
                t1 = timeit(chain_fn(CHAIN), x0, reps=3)
                t2 = timeit(chain_fn(5 * CHAIN), x0, reps=3)
                t = (t2 - t1) / (4 * CHAIN)
            except Exception as e:
                emit(suite="hbm-spmv", tier=tier, variant=vname,
                     error=f"{type(e).__name__}")
                continue
            emit(suite="hbm-spmv", tier=tier, variant=vname, n=n,
                 us_per_matvec=round(t * 1e6, 1),
                 gbps_vs_ideal=round(ideal / t / 1e9, 1))


def suite_sgell(reps):
    """Segmented-gather ELL kernel vs the XLA gather formulation
    (acg_tpu/ops/sgell.py — the unstructured tier, VERDICT r3 item 2).
    Two regimes: an FEM-like local matrix (the tier's home turf: rows
    touch few x segments) and the uniform-random rand-512k shape (fill
    collapses; the XLA path is expected to remain production there)."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.ops.sgell import TILE, build_device_sgell, sgell_available
    from acg_tpu.ops.spmv import ell_matvec
    from acg_tpu.sparse.csr import coo_to_csr
    from acg_tpu.sparse.ell import EllMatrix

    rng = np.random.default_rng(3)
    CHAIN = 5
    configs = [
        ("fem-1M", 1 << 20, 16, 5000),       # local: ±5000 window
        ("rand-512k", 1 << 19, 8, None),     # uniform random columns
    ]
    for name, n, deg, spread in configs:
        r = np.repeat(np.arange(n), deg)
        if spread is None:
            c = rng.integers(0, n, n * deg)
        else:
            c = np.clip(r + rng.integers(-spread, spread + 1, n * deg),
                        0, n - 1)
        A = coo_to_csr(np.r_[r, np.arange(n)], np.r_[c, np.arange(n)],
                       np.r_[rng.standard_normal(n * deg) * 0.01,
                             np.full(n, 4.0 * deg)], n, n, symmetrize=True)
        E = EllMatrix.from_csr(A, row_align=1024)
        vals = jnp.asarray(E.vals.astype(np.float32))
        cols = jnp.asarray(E.colidx)
        x0 = jnp.asarray(rng.standard_normal(E.nrows_padded)
                         .astype(np.float32))
        # 0.002 is the traffic-model break-even; below it the pack's slot
        # arrays would dwarf the matrix and the XLA path wins anyway
        dev = build_device_sgell(A, dtype=np.float32, min_fill=0.002)
        if dev is None:
            from acg_tpu.ops.sgell import pack_sgell

            rowids = np.repeat(np.arange(A.nrows), A.rowlens)
            meta = pack_sgell(rowids, A.colidx.astype(np.int64),
                              A.vals.astype(np.float32), A.nrows,
                              min_fill=1.0)
            emit(suite="sgell", config=name, probe=sgell_available(),
                 S=meta["S"], fill=round(meta["fill"], 5),
                 skipped="fill below break-even or probe failed")
            continue

        def chain_fn(length, mv):
            @jax.jit
            def chain(x):
                def body(x, _):
                    return mv(x) * 0.125, None
                return jax.lax.scan(body, x, None, length=length)[0]
            return chain

        out = dict(suite="sgell", config=name, n=n,
                   width=int(E.vals.shape[1]), S=dev.S,
                   fill=round(dev.fill, 4), probe=sgell_available())
        for vname, mv, xv in (
                ("xla", lambda x: ell_matvec(vals, cols, x), x0),
                ("sgell", dev.matvec,
                 jnp.asarray(np.asarray(x0)[: dev.nrows_padded]
                             if dev.nrows_padded <= E.nrows_padded else
                             np.pad(np.asarray(x0),
                                    (0, dev.nrows_padded - E.nrows_padded)))),
        ):
            try:
                t1 = timeit(chain_fn(CHAIN, mv), xv, reps=3)
                t2 = timeit(chain_fn(3 * CHAIN, mv), xv, reps=3)
                out[f"{vname}_us"] = round((t2 - t1) / (2 * CHAIN) * 1e6, 1)
            except Exception as e:
                out[f"{vname}_error"] = f"{type(e).__name__}"
        if "xla_us" in out and "sgell_us" in out:
            out["speedup"] = round(out["xla_us"] / out["sgell_us"], 2)
        emit(**out)


def suite_stencil(reps):
    """Matrix-free stencil tier vs the stored DIA tiers at 128^3
    (ISSUE 12): chained-marginal SpMV for each path, whole-CG and
    whole-pipelined-CG end-to-end marginals, and the analytic
    roofline-ceiling comparison column (predicted it/s at the tier's
    own stream model — the stencil rows carry operator_bytes == 0, so
    the column IS the bands:vectors ceiling multiple the matrix-free
    formulation buys)."""
    import jax
    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.obs.roofline import roofline_for_operator
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.ops.stencil import DeviceStencil
    from acg_tpu.solvers.cg import cg, cg_pipelined
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    D = poisson3d_7pt_dia(128, dtype=np.float32)
    rng = np.random.default_rng(0)
    devs = [
        ("dia-bf16", DeviceDia.from_dia(D, dtype=np.float32,
                                        mat_dtype="auto")),
        ("dia-f32", DeviceDia.from_dia(D, dtype=np.float32,
                                       mat_dtype=None)),
        ("stencil", DeviceStencil.from_matrix(D, dtype=np.float32)),
    ]
    n = devs[0][1].nrows_padded
    x0 = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    CHAIN = 50
    for tier, dev in devs:
        model = roofline_for_operator(dev, solver="cg")
        out = dict(suite="stencil", tier=tier,
                   operator_bytes_per_iter=int(model.operator_bytes),
                   bytes_per_iter=int(model.bytes_per_iter),
                   predicted_ceiling_iters_per_sec=round(
                       model.predicted_iters_per_sec, 1))

        def chain_fn(length):
            @jax.jit
            def chain(x):
                def body(x, _):
                    return dev.matvec(x) * 0.125, None
                return jax.lax.scan(body, x, None, length=length)[0]
            return chain

        try:
            # two-point marginal over chain length (dispatch cancels)
            t1 = timeit(chain_fn(CHAIN), x0, reps=max(reps // 10, 3))
            t2 = timeit(chain_fn(9 * CHAIN), x0, reps=max(reps // 10, 3))
            out["us_per_matvec"] = round((t2 - t1) / (8 * CHAIN) * 1e6, 1)
        except Exception as e:
            out["matvec_error"] = f"{type(e).__name__}"
        # whole-CG end-to-end marginal (the storage-tiers protocol)
        for solver, fn, key in (("cg", cg, "cg_iters_per_sec"),
                                ("pipelined", cg_pipelined,
                                 "pipe_iters_per_sec")):
            try:
                ts = {}
                for iters in (500, 8000):
                    opts = SolverOptions(maxits=iters, residual_rtol=0.0)
                    fn(dev, b, options=opts)
                    best = float("inf")
                    for _ in range(max(reps // 10, 3)):
                        t0 = time.perf_counter()
                        fn(dev, b, options=opts)
                        best = min(best, time.perf_counter() - t0)
                    ts[iters] = best
                out[key] = round((8000 - 500) / (ts[8000] - ts[500]), 1)
            except Exception as e:
                out[f"{solver}_error"] = f"{type(e).__name__}"
        emit(**out)


SUITES = {
    "storage-tiers": suite_storage_tiers,
    "spmv-2d": suite_spmv_2d,
    "sgell": suite_sgell,
    "hbm-spmv": suite_hbm_spmv,
    "stencil": suite_stencil,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suites", default=",".join(SUITES))
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args()
    from acg_tpu.utils.backend import require_tpu

    dev0 = require_tpu()[0]
    emit(platform=dev0.platform, device=dev0.device_kind)
    for name in args.suites.split(","):
        t0 = time.perf_counter()
        SUITES[name.strip()](args.reps)
        print(f"# {name}: {time.perf_counter() - t0:.1f}s", file=sys.stderr,
              flush=True)


if __name__ == "__main__":
    main()
