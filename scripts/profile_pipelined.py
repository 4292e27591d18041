"""Decompose the pipelined fused-path gap on the chip (VERDICT r4 item 3).

Round-4 measured pipelined CG through the fused kernel at 3,588 it/s at
128³ vs classic's 17,165 — PERF.md's 2× byte model explains ~8.6k, so
~2.4× is unaccounted.  This script isolation-times every piece of the
pipelined loop body (chained through data dependencies so XLA cannot
fold repeats) and A/Bs the exit-certifier branch, so the missing time is
ATTRIBUTED, not guessed:

  1. q = Aw through the fused kernel (the only HBM band stream)
  2. the 6-output/7-stream vector update alone
  3. the (γ, δ) = (r·r, w·r) fused dot pair alone
  4. update + dots together (tests whether XLA fuses the dots into the
     update pass or re-reads r, w)
  5. the full pipelined loop, certify=True vs certify=False (the static
     no-criteria path landed in round 5) — if the conditional carries
     hidden buffer copies on TPU, this pair exposes them
  6. the full classic fused loop (the 17k reference point)

Run on the chip: python scripts/profile_pipelined.py [grid]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

GRID = int(sys.argv[1]) if len(sys.argv) > 1 else 128
REPS = 300


def main():
    from acg_tpu.utils.backend import require_tpu

    print("device_kind:", require_tpu()[0].device_kind, flush=True)

    import jax
    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.ops.pallas_kernels import fused_plan_for, pad_dia_operands
    from acg_tpu.ops.dia import DeviceDia, DiaMatrix
    from acg_tpu.solvers.cg import cg, cg_pipelined, _fused_ops
    from acg_tpu.sparse import poisson3d_7pt

    dtype = np.float32
    A = poisson3d_7pt(GRID, dtype=dtype)
    D = DiaMatrix.from_csr(A)
    op = DeviceDia.from_dia(D, dtype=dtype, mat_dtype="auto")
    n = op.nrows_padded
    plan = fused_plan_for(n, op.offsets, np.dtype(dtype), op.bands.dtype)
    print(f"n={A.nrows:,} plan={plan} mat={op.bands.dtype}", flush=True)
    if plan is None:
        print("no fused plan on this backend — aborting")
        return 1
    kind, rt = plan

    rng = np.random.default_rng(0)

    def vec():
        return jnp.asarray(rng.standard_normal(n).astype(dtype))

    vs = [vec() for _ in range(7)]
    bands_pad, padded = pad_dia_operands(op.bands, tuple(vs), rt,
                                         op.offsets)
    q, z, r, p, w, s, x = padded
    mv, _ = _fused_ops(op, bands_pad, rt, kind)
    B = np.dtype(dtype).itemsize
    npad = q.shape[0]

    def chain(name, step, init, streams):
        """Time REPS data-chained applications of ``step``."""
        def loop(c):
            def body(i, c):
                return step(i, c)
            return jax.lax.fori_loop(0, REPS, body, c)

        f = jax.jit(loop)
        out = f(init)
        jax.block_until_ready(out)
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            out = f(init)
            # device fetch: the result is on the host
            jax.device_get(jax.tree_util.tree_leaves(out)[0][:1])
            best = min(best, time.perf_counter() - t0)
        per = best / REPS
        bw = streams * npad * B / per / 1e9
        print(f"{name:38s} {per*1e6:9.1f} us/iter  "
              f"(~{streams} streams -> {bw:7.1f} GB/s eff)", flush=True)
        return per

    # 1. q = Aw alone (bands + read w + write q)
    chain("q=Aw fused kernel", lambda i, c: (mv(c[0]), c[0]),
          (w, q), streams=2 + 7 * op.bands.dtype.itemsize / B)

    # 1b. the single-kernel pipelined iteration (pipe2d), if Mosaic
    # accepts it: SpMV + update + dots in one pass, 13 streams + bands
    from acg_tpu.ops.pallas_kernels import (cg_pipelined_iter_pallas,
                                            pallas_spmv_available)

    if pallas_spmv_available("pipe2d"):
        def mega(i, c):
            z, r, p, w, s, x = c
            a = 0.0002 * i + 0.25
            bt = 0.0001 * i + 0.5
            z2, p2, s2, x2, r2, w2, g, d = cg_pipelined_iter_pallas(
                bands_pad, op.offsets, w, z, r, p, s, x,
                jnp.asarray(a, dtype), jnp.asarray(bt, dtype),
                rows_tile=rt, scales=op.scales)
            return z2, r2, p2, w2, s2, x2

        chain("pipe2d mega-kernel (whole iter)", mega,
              (z, r, p, w, s, x),
              streams=12 + 7 * op.bands.dtype.itemsize / B)
    else:
        print("pipe2d probe FAILED on this backend (mega-kernel skipped)",
              flush=True)

    # 2. the 6-output update alone (reads q,z,r,p,w,s,x writes 6)
    def upd(i, c):
        q, z, r, p, w, s, x = c
        beta = 0.0001 * i + 0.5
        alpha = 0.0002 * i + 0.25
        z2 = q + beta * z
        p2 = r + beta * p
        s2 = w + beta * s
        x2 = x + alpha * p2
        r2 = r - alpha * s2
        w2 = w - alpha * z2
        return q, z2, r2, p2, w2, s2, x2

    chain("6-vector update alone", upd, (q, z, r, p, w, s, x), streams=13)

    # 3. the fused dot pair alone
    def dots(i, c):
        r, w, acc = c
        g = jnp.vdot(r, r)
        d = jnp.vdot(w, r)
        return r + (g - g), w + (d - d), acc + g + d

    chain("(r.r, w.r) dot pair alone", dots,
          (r, w, jnp.asarray(0.0, dtype)), streams=2)

    # 4. update + dots in one step (does XLA fuse the dots in?)
    def upd_dots(i, c):
        q, z, r, p, w, s, x = upd(i, c)
        g = jnp.vdot(r, r)
        d = jnp.vdot(w, r)
        return q, z, r + (g - g), p, w + (d - d), s, x

    chain("update + dot pair", upd_dots, (q, z, r, p, w, s, x),
          streams=13)

    # 5/6. the full loops, end-to-end wall marginal (cg() protocol)
    b_host = np.zeros(n, dtype=dtype)
    b_host[: A.nrows] = rng.standard_normal(A.nrows).astype(dtype)

    from acg_tpu.errors import AcgError

    def run_quiet(fn, o):
        # a not-converged raise (atol enabled, fixed iterations) happens
        # AFTER the timed device loop — the wall time is still the solve
        try:
            fn(op, jnp.asarray(b_host), options=o)
        except AcgError:
            pass

    def marginal(fn, atol=0.0):
        ts = {}
        for iters in (300, 3000):
            o = SolverOptions(maxits=iters, residual_rtol=0.0,
                              residual_atol=atol)
            run_quiet(fn, o)
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                run_quiet(fn, o)
                best = min(best, time.perf_counter() - t0)
            ts[iters] = best
        return (3000 - 300) / (ts[3000] - ts[300])

    print(f"classic fused loop:              {marginal(cg):10.0f} it/s",
          flush=True)
    print(f"pipelined (certify OFF, rtol=0): "
          f"{marginal(cg_pipelined):10.0f} it/s", flush=True)
    # atol=1e-30 never fires at these sizes, so this measures the COST OF
    # THE BRANCH'S PRESENCE (buffer aliasing), not of taking it
    print(f"pipelined (certify ON, atol=1e-30): "
          f"{marginal(cg_pipelined, atol=1e-30):10.0f} it/s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
