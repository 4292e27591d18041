"""The rand-512k experiment (VERDICT r4 item 9): can anything beat the
XLA gather tier's 7.7 it/s on uniform-random sparsity?

Candidates, each measured end-to-end (marginal it/s over segmented
fixed-iteration solves, the PERF.md wall protocol):

  1. auto        — the production route (XLA gather ELL after the fill
                   gate excludes sgell); the 7.7 it/s baseline.
  2. sgell       — the segmented-gather tier FORCED below its break-even
                   fill (--format sgell semantics, min_fill=0).  The
                   traffic model says this is DMA-COUNT bound here:
                   fill ~0.002 => ~500x cell inflation => ~1.8M slot DMAs
                   per iteration; the measurement decides.
  3. ell+rcm     — RCM-reordered gather (bandwidth reduction cannot help
                   an expander, but the claim should be a number, not a
                   shrug).

Run on the chip: python scripts/bench_rand512k.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

N = 1 << 19
DEG = 8
ITERS1, ITERS2 = 30, 150
SEG = 150


def main():
    from acg_tpu.utils.backend import require_tpu

    print("device_kind:", require_tpu()[0].device_kind, flush=True)

    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.solvers.cg import build_device_operator, cg
    from acg_tpu.sparse.poisson import random_spd

    A = random_spd(N, degree=DEG, dtype=np.float32)
    print(f"rand-512k: n={A.nrows:,} nnz={A.nnz:,}", flush=True)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(A.nrows).astype(np.float32)

    def marginal(dev):
        ts = {}
        for iters in (ITERS1, ITERS2):
            o = SolverOptions(maxits=iters, residual_rtol=0.0,
                              segment_iters=SEG)
            cg(dev, b, options=o)
            best = 1e9
            for _ in range(2):
                t0 = time.perf_counter()
                res = cg(dev, b, options=o)
                best = min(best, time.perf_counter() - t0)
            ts[iters] = best
        rate = (ITERS2 - ITERS1) / (ts[ITERS2] - ts[ITERS1])
        return rate, res

    # 1. production auto route
    dev = build_device_operator(A, dtype=np.float32)
    rate, res = marginal(dev)
    print(f"auto [{res.operator_format}/{res.kernel}]: "
          f"{rate:8.2f} it/s", flush=True)

    # 2. forced sgell (fill gate lifted)
    try:
        dev_sg = build_device_operator(A, dtype=np.float32, fmt="sgell")
        print(f"sgell pack: S={dev_sg.S} ntiles={dev_sg.ntiles} "
              f"fill={dev_sg.fill:.5f} "
              f"({1.0 / max(dev_sg.fill, 1e-30):.0f}x inflation)",
              flush=True)
        rate, res = marginal(dev_sg)
        print(f"sgell forced [{res.kernel}]: {rate:8.2f} it/s", flush=True)
    except Exception as e:
        print(f"sgell forced: unavailable ({e})", flush=True)

    # 3. RCM + gather (the permuted ELL route, forced)
    from acg_tpu.sparse.rcm import permute_symmetric, rcm_order

    perm = rcm_order(A)
    Ap = permute_symmetric(A, perm)
    bw_before = int(np.abs(np.repeat(np.arange(A.nrows), A.rowlens)
                           - A.colidx).max())
    bw_after = int(np.abs(np.repeat(np.arange(Ap.nrows), Ap.rowlens)
                          - Ap.colidx).max())
    print(f"rcm bandwidth: {bw_before:,} -> {bw_after:,}", flush=True)
    dev_rcm = build_device_operator(Ap, dtype=np.float32, fmt="ell")
    rate, res = marginal(dev_rcm)
    print(f"ell+rcm [{res.kernel}]: {rate:8.2f} it/s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
