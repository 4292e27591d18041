"""Full-scale convergence + independent-residual check at 464^3 = 100M DOF.

Solves through the production path (the fused HBM Pallas kernel when its
probe passes) and re-derives the residual with the XLA dia_matvec — a
DIFFERENT code path than the kernel that produced x, so agreement is an
independent full-scale correctness certificate for the kernel.

The certified solve uses a MANUFACTURED RANDOM solution (b = A x*): for
rough x* the floor ratio ||A||*||x||/||b|| is O(1), so the f32 true
residual can actually track the recurred one and the certificate
measures the KERNEL, not f32 conditioning.  A smooth RHS (b = ones) puts
the f32 attainable-accuracy floor at ~eps*kappa — 1.2e-7 * 4.4e4 ≈ 5e-3
at 464³ — which the 2026-07-31 diagnosis confirmed: claimed 9.9e-5 vs
true 2.0e-2 through BOTH the fused kernel and the pure XLA path, while
the kernel matvec itself is bit-exact vs XLA at every shape through
464³.  Pass --ones to measure that floor explicitly (reported, not
pass/fail — it is a property of f32 CG at this condition number, shared
by any f32 implementation of the reference's algorithm).

Usage: python scripts/check_100m_convergence.py [--ones]  (attached TPU)
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np


def log(*a):
    print(round(time.time() - T0, 1), *a, flush=True)


T0 = time.time()


def main():
    from acg_tpu.utils.backend import require_tpu

    require_tpu()
    import jax
    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.ops.dia import DeviceDia, dia_matvec
    from acg_tpu.solvers.cg import _fused_plan, cg
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    ones = "--ones" in sys.argv[1:]
    D = poisson3d_7pt_dia(464, dtype=np.float32)
    log("bands built")
    dev = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype="auto")
    log("device op; fused plan:", _fused_plan(dev))
    n = dev.nrows_padded

    @jax.jit
    def mv_xla(bands, scales, v):
        return dia_matvec(bands, dev.offsets, v, scales=scales)

    if ones:
        b = jnp.ones((n,), jnp.float32)
    else:
        xstar = jnp.asarray(np.random.default_rng(464)
                            .standard_normal(n, dtype=np.float32))
        b = mv_xla(dev.bands, dev.scales, xstar)   # XLA path builds b
        jax.block_until_ready(b)
        log("manufactured rhs")
    res = cg(dev, b, options=SolverOptions(maxits=1500, residual_rtol=1e-4,
                                           segment_iters=500))
    log("solve: converged", res.converged, "iters", res.niterations,
        "claimed relres", res.relative_residual)
    x = jnp.asarray(res.x)
    r = b - mv_xla(dev.bands, dev.scales, jnp.pad(x, (0, n - x.shape[0])))
    relres = float(jnp.linalg.norm(r) / jnp.linalg.norm(b))
    log("XLA-path true relres:", relres)
    if ones:
        # informational: the f32 attainable-accuracy floor at kappa~4.4e4
        print(f'{{"check_100m_ones_floor": {relres}, '
              f'"iters": {res.niterations}, '
              f'"claimed": {res.relative_residual}}}')
        return 0
    ok = res.converged and relres < 3e-4
    print(f'{{"check_100m": "{"ok" if ok else "FAILED"}", '
          f'"iters": {res.niterations}, "true_relres": {relres}}}')
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
