"""Differential solver fuzz: random SPD systems x random configurations.

Every trial draws a matrix family (banded / scrambled-banded / random
sparse / diagonal / disconnected blocks), a dtype, an operator format, a
partitioner, a halo schedule, and a solver variant, then checks the
returned solution's TRUE residual against the SciPy-computed right-hand
side.  This is the test-pyramid layer the reference lacks entirely
(SURVEY §4: its correctness story is operational) and the layer that
catches cross-configuration crashes unit tests miss — the round-2
verdict's fmt="auto" crash was exactly this class.

Usage: python scripts/fuzz_solvers.py [--trials N] [--seed S]
                                      [--nmin N] [--nmax N] [--faults]
Exit code 1 if any trial fails; each failure prints its full config.
Runs on an 8-device virtual CPU mesh (forced below — no environment
variables needed).

``--faults`` switches to the RESILIENCE fuzz (acg_tpu/robust/): every
trial draws a fault (kind × mode × iteration × solver variant × mesh
width × host faults with checkpointing), runs it through
``solve_resilient()``, and asserts the certified TRUE residual — the
randomized extension of the deterministic injection matrix in
tests/test_resilience.py.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the fuzzer is a CPU-mesh tool by design; force the mesh BEFORE any
# backend init (XLA_FLAGS is read when the CPU client starts)
from acg_tpu.utils.backend import force_cpu_mesh

force_cpu_mesh(8)

import numpy as np

import jax


def rand_spd(rng, kind, n):
    """Random SPD matrix of the given structural family."""
    import scipy.sparse as sp

    from acg_tpu.sparse.csr import coo_to_csr

    if kind == "band":
        k = int(rng.integers(1, 4))
        offs = sorted({0, *rng.integers(1, max(2, n // 4), k).tolist()})
        rows, cols, vals = [], [], []
        for o in offs:
            if o == 0:
                continue
            i = np.arange(n - o)
            v = rng.standard_normal(n - o) * 0.3
            rows += [i, i + o]
            cols += [i + o, i]
            vals += [v, v]
        rows.append(np.arange(n))
        cols.append(np.arange(n))
        vals.append(np.full(n, 4.0 * len(offs)))
        return coo_to_csr(np.concatenate(rows), np.concatenate(cols),
                          np.concatenate(vals), n, n)
    if kind == "scrambled":
        A = rand_spd(rng, "band", n)
        p = rng.permutation(n)
        S = sp.csr_matrix((A.vals, A.colidx, A.rowptr), shape=(n, n))
        S = S[p][:, p].tocoo()
        return coo_to_csr(S.row, S.col, S.data, n, n)
    if kind == "random":
        # the packaged unstructured stand-in, one definition (sparse/)
        from acg_tpu.sparse import random_spd

        return random_spd(n, degree=int(rng.integers(2, 6)),
                          seed=int(rng.integers(1 << 31)))
    if kind == "diag":
        d = rng.uniform(0.5, 5.0, n)
        return coo_to_csr(np.arange(n), np.arange(n), d, n, n)
    if kind == "blocks":
        A1, A2 = rand_spd(rng, "band", n // 2), rand_spd(rng, "band",
                                                         n - n // 2)
        r1, c1, v1 = A1.to_coo()
        r2, c2, v2 = A2.to_coo()
        return coo_to_csr(np.r_[r1, r2 + n // 2], np.r_[c1, c2 + n // 2],
                          np.r_[v1, v2], n, n)
    raise ValueError(kind)


def fuzz_faults(args) -> int:
    """Resilience fuzz: random fault × solver × mesh trials through
    solve_resilient(), certified-true-residual checked every time."""
    import tempfile

    import scipy.sparse as sp

    from acg_tpu.config import SolverOptions
    from acg_tpu.errors import AcgError
    from acg_tpu.robust.faults import FaultSpec
    from acg_tpu.robust.supervisor import solve_resilient

    rng = np.random.default_rng(args.seed)
    ndev = jax.device_count()
    fails = 0
    vacuous = 0
    tmpdir = tempfile.mkdtemp(prefix="acg-fault-fuzz-")
    kind_counts = {}
    for trial in range(args.trials):
        mkind = rng.choice(["band", "random", "diag"])
        n = int(rng.integers(args.nmin, args.nmax + 1))
        dtype = rng.choice([np.float32, np.float64])
        nparts = int(rng.choice([v for v in (1, 2, 4, ndev) if v <= n]))
        solver = str(rng.choice(["cg", "cg-pipelined"]))
        fkind = str(rng.choice(["spmv", "halo", "reduction", "carry",
                                "segment-kill", "checkpoint-corrupt"]))
        mode = str(rng.choice(["nan", "inf", "scale"]))
        maxits = 20 * n + 200
        host = fkind in ("segment-kill", "checkpoint-corrupt")
        ckpt_every = int(rng.choice([0, 5, 17])) if not host \
            else int(rng.choice([5, 17]))
        # host faults strike a SEGMENT ordinal; device faults a loop
        # iteration inside the (first) supervised run.  halo faults
        # start at iteration 1: classic CG's empty direction history
        # (beta_0 = 0) annihilates a scale-mode halo corruption at 0,
        # and a trial that injects nothing proves nothing (faults.py)
        # device-fault iterations are drawn EARLY (first 8 iterations):
        # these small SPD families converge in ~10-30 iterations, and a
        # fault scheduled past convergence never fires — the trial
        # would "pass" having injected nothing.  Trials whose solve
        # still ends before the window are counted as vacuous below,
        # not as coverage.
        it = int(rng.integers(0, 4)) if host \
            else int(rng.integers(1 if fkind == "halo" else 0, 8))
        spec = FaultSpec(kind=fkind, iteration=it,
                         mode="nan" if host else mode,
                         index=int(rng.integers(0, n)))
        kind_counts[fkind] = kind_counts.get(fkind, 0) + 1
        rtol = 1e-10 if dtype == np.float64 else 1e-5
        opts = SolverOptions(maxits=maxits, residual_rtol=rtol)
        ckpt = os.path.join(tmpdir, f"ck{trial}.npz")
        A = rand_spd(rng, mkind, n)
        S = sp.csr_matrix((A.vals, A.colidx, A.rowptr), shape=(n, n))
        b = S @ rng.standard_normal(n)
        desc = (f"trial {trial}: {mkind} n={n} {np.dtype(dtype).name} "
                f"nparts={nparts} solver={solver} fault={spec} "
                f"ckpt_every={ckpt_every}")
        try:
            res, rep = solve_resilient(
                A, b, options=opts, solver=solver, nparts=nparts,
                dtype=dtype, faults=[spec],
                checkpoint_path=ckpt if ckpt_every else None,
                checkpoint_every=ckpt_every)
            x = np.asarray(res.x, dtype=np.float64)
            rel = np.linalg.norm(S @ x - b) / np.linalg.norm(b)
            tol = 1e-7 if dtype == np.float64 else 2e-3
            if not (res.converged and np.all(np.isfinite(x))
                    and rel < tol):
                print(f"WRONG ({rel=:.2e}, conv={res.converged}): {desc}")
                fails += 1
            elif rep.restarts > 0 and rep.fixed_by is None:
                print(f"REPORT-HOLE (recovered but fixed_by empty): "
                      f"{desc}")
                fails += 1
            elif not host and any(s.action == "fault-unfired"
                                  for s in rep.steps):
                # the solve ended before the fault window: correct
                # behavior, but the trial injected nothing — counted
                # separately so the summary never overstates coverage
                vacuous += 1
        except AcgError as e:
            print(f"UNRECOVERED: {desc}: {e}")
            fails += 1
        except Exception as e:
            import traceback
            print(f"CRASH: {desc}: {type(e).__name__}: {e}")
            traceback.print_exc(limit=6)
            fails += 1
        finally:
            if os.path.exists(ckpt):
                os.remove(ckpt)
    print(f"{args.trials} fault trials, {fails} failures, "
          f"{vacuous} vacuous (fault window never reached) "
          f"(kinds: {kind_counts})")
    return 1 if fails else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=120)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nmin", type=int, default=12,
                    help="smallest matrix dimension drawn (inclusive)")
    ap.add_argument("--nmax", type=int, default=400,
                    help="largest matrix dimension drawn (inclusive)")
    ap.add_argument("--solver", default="any",
                    choices=["any", "cg", "cg-pipelined", "cg-sstep",
                             "cg-pipelined-deep", "cg-recycled"],
                    help="restrict trials to one solver family; "
                         "cg-sstep draws a random s in {2..8} per trial "
                         "(the s-step loop certifies its true residual "
                         "and falls back to classic CG on an indefinite "
                         "Gram — both paths are differential-checked "
                         "here); cg-pipelined-deep draws a random depth "
                         "l in {2..6} x a random halo wire format per "
                         "trial (every exit is true-residual certified; "
                         "persistent drift/breakdown falls back to "
                         "classic CG at the identity wire — both paths "
                         "differential-checked); cg-recycled draws a "
                         "random deflation rank k in {2..8} per trial "
                         "(W = QR of a random n x k block, WtAW exact "
                         "via the host matrix — the SETUP-only Galerkin "
                         "correction must never cost correctness) [any]")
    ap.add_argument("--faults", action="store_true",
                    help="fuzz the resilience layer: random fault "
                         "injection trials through solve_resilient() "
                         "with the certified true residual asserted "
                         "(see module docstring)")
    args = ap.parse_args()
    if not 2 <= args.nmin <= args.nmax:
        ap.error("need 2 <= --nmin <= --nmax")
    if args.faults:
        return fuzz_faults(args)

    import scipy.sparse as sp

    from acg_tpu.config import HaloMethod, SolverOptions
    from acg_tpu.errors import AcgError
    from acg_tpu.solvers.cg import (cg, cg_pipelined,
                                    cg_pipelined_deep, cg_recycled,
                                    cg_sstep)
    from acg_tpu.solvers.cg_dist import (cg_dist, cg_pipelined_deep_dist,
                                         cg_pipelined_dist,
                                         cg_recycled_dist,
                                         cg_sstep_dist)

    from acg_tpu.solvers.cg_host import cg_host

    rng = np.random.default_rng(args.seed)
    ndev = jax.device_count()
    fails = 0
    force_counts = {}
    for trial in range(args.trials):
        kind = rng.choice(["band", "scrambled", "random", "diag", "blocks"])
        n = int(rng.integers(args.nmin, args.nmax + 1))
        dtype = rng.choice([np.float32, np.float64])
        fmt = rng.choice(["auto", "dia", "ell"])
        # 0 = host solver; nparts must not exceed nrows (a partition
        # of more parts than rows is a clean config error, not a bug)
        nparts = int(rng.choice([v for v in (0, 1, 2, 3, 4, ndev)
                                 if v <= n]))
        # interpret-forced kernel tiers (single-chip f32 only): "sgell"
        # lowers the sgell gate so the unstructured tier solves route
        # through the slot kernel; "ring" forces the ring HBM kernel as
        # the fused/matvec path — both probe-gated off on CPU otherwise,
        # so the fuzzer would never exercise their packing/ring logic.
        # Decided BEFORE the matrix/desc: "ring" needs a lane-aligned
        # padded size or the plan refuses (n rounds up to 128k), and
        # "sgell" routes via fmt="auto" — desc must print what runs.
        force = "none"
        if nparts == 1 and dtype == np.float32:
            force = str(rng.choice(["none", "none", "sgell", "ring",
                                    "pipe2d"]))
        if force == "ring":
            n = max(128, -(-n // 128) * 128)
        elif force == "pipe2d":
            # the single-kernel pipelined iteration: the resident plan
            # requires R = n/128 divisible by 8, i.e. n a multiple of
            # 1024 (review finding: 128-rounding silently tested nothing)
            n = max(1024, -(-n // 1024) * 1024)
            fmt = "dia"
        elif force == "sgell":
            fmt = "auto"
        A = rand_spd(rng, kind, n)
        if rng.integers(0, 4) == 0:      # idx64 tier (acgidx_t analog)
            A.rowptr = A.rowptr.astype(np.int64)
            A.colidx = A.colidx.astype(np.int64)
        S = sp.csr_matrix((A.vals, A.colidx, A.rowptr), shape=(n, n))
        b = S @ rng.standard_normal(n)
        x0 = (rng.standard_normal(n)
              if rng.integers(0, 3) == 0 else None)
        halo = rng.choice(["ppermute", "allgather"])
        pmethod = rng.choice(["auto", "chunk", "rb", "bfs", "kway",
                              "multilevel"])
        mat_dtype = rng.choice(["auto", None], p=[0.7, 0.3])
        if args.solver == "any":
            variant = str(rng.choice(["cg", "cg", "cg-pipelined",
                                      "cg-sstep"]))
        else:
            variant = args.solver
        if force == "pipe2d":
            # the mega-kernel lives in the pipelined solver
            variant = "cg-pipelined"
        pipe = variant == "cg-pipelined"
        deep = variant == "cg-pipelined-deep"
        recyc = variant == "cg-recycled"
        # randomized deflation rank k in {2..8} (ISSUE 20): W is the QR
        # of a random n x k block, WtAW the exact host Gram — a useless
        # random subspace on purpose, so the SETUP-only Galerkin
        # correction is exercised where it cannot help, only hurt if
        # wrong; the delegated classic solve must still certify
        kdefl = int(rng.integers(2, 9)) if recyc else 0
        if recyc and nparts == 0:
            nparts = 1      # the host oracle has no recycled variant
        W = WtAW = None
        if recyc:
            Wq, _ = np.linalg.qr(rng.standard_normal((n, kdefl)))
            W = np.asarray(Wq, np.float64)
            WtAW = W.T @ (S @ W)
        # randomized depth l in {2..6} x wire format (ISSUE 17): deep
        # certifies every exit against the TRUE residual and falls back
        # to classic CG (identity wire) on persistent drift/breakdown —
        # compressed wire formats at tight tolerances exercise exactly
        # that reliability path
        depth = int(rng.integers(2, 7)) if deep else 1
        wire = str(rng.choice(["f32", "bf16", "int16-delta"])) if deep \
            else "f32"
        if deep and nparts == 0:
            nparts = 1      # the host oracle has no deep variant
        # randomized s in {2..8} (ISSUE 7): large s at small n makes the
        # Krylov basis degenerate on purpose — the indefinite-Gram
        # fallback must still deliver a certified-true-residual solve
        sstep = int(rng.integers(2, 9)) if variant == "cg-sstep" else 0
        if sstep and nparts == 0:
            nparts = 1          # the host oracle has no s-step variant
        check_every = int(rng.choice([1, 1, 7]))
        # segment_iters exercises the carry-resumed segmented loops
        # (classic AND pipelined since PR 7; must be indistinguishable
        # from the single-program solve)
        segment = int(rng.choice([0, 0, 0, 13, 64]))
        rtol = 1e-10 if dtype == np.float64 else 1e-5
        # the s-step outer carry is not segmented (nor is the deep
        # host-redispatch loop — its re-dispatch IS the segmentation);
        # distributed segmentation is exercised by tests (keep the fuzz
        # matrix lean)
        segment = 0 if (sstep or deep or nparts != 1) else segment
        opts = SolverOptions(maxits=20 * n + 200, residual_rtol=rtol,
                             check_every=check_every,
                             replace_every=50 if pipe else 0,
                             segment_iters=segment, sstep=sstep,
                             pipeline_depth=depth, halo_wire=wire)
        desc = (f"trial {trial}: {kind} n={n} {np.dtype(dtype).name} "
                f"fmt={fmt} nparts={nparts} halo={halo} pm={pmethod} "
                f"sv={variant}{sstep or ''}"
                + (f" k={kdefl}" if recyc else "")
                + (f" l={depth} wire={wire}" if deep else "")
                + f" ce={check_every} "
                f"seg={segment} md={mat_dtype} "
                f"idx={A.colidx.dtype.itemsize * 8} x0={x0 is not None} "
                f"force={force}")
        force_counts[force] = force_counts.get(force, 0) + 1
        import acg_tpu.ops.pallas_kernels as pk
        import acg_tpu.ops.sgell as sgell_mod

        unpatch = []
        if force == "sgell":
            orig_bds = sgell_mod.build_device_sgell

            def forced_bds(mat, dtype=None, mat_dtype="auto",
                           min_fill=0.0, interpret=False, _probing=False):
                return orig_bds(mat, dtype=dtype, mat_dtype=mat_dtype,
                                min_fill=0.0, interpret=True)

            sgell_mod.build_device_sgell = forced_bds
            unpatch.append(lambda: setattr(sgell_mod, "build_device_sgell",
                                           orig_bds))
        elif force == "pipe2d":
            orig_pad = pk.dia_matvec_pallas_2d_padded
            orig_iter = pk.cg_pipelined_iter_pallas
            force_calls = {"iter": 0}

            def interp_pad(*a, **k):
                k["interpret"] = True
                return orig_pad(*a, **k)

            def interp_iter(*a, **k):
                force_calls["iter"] += 1
                k["interpret"] = True
                return orig_iter(*a, **k)

            pk.dia_matvec_pallas_2d_padded = interp_pad
            pk.cg_pipelined_iter_pallas = interp_iter
            pk._SPMV_PROBE["fused2d"] = True
            pk._SPMV_PROBE["pipe2d"] = True
            # a jit cache hit from an earlier identical configuration
            # would bypass the patched kernel and break the call counter
            # (trace-time import): trace fresh per forced trial
            import importlib

            _cgm = importlib.import_module("acg_tpu.solvers.cg")
            _cgm._cg_pipelined_device_fused.clear_cache()
            unpatch += [
                lambda: setattr(pk, "dia_matvec_pallas_2d_padded",
                                orig_pad),
                lambda: setattr(pk, "cg_pipelined_iter_pallas", orig_iter),
                lambda: pk._SPMV_PROBE.pop("fused2d", None),
                lambda: pk._SPMV_PROBE.pop("pipe2d", None),
                _cgm._cg_pipelined_device_fused.clear_cache]
        elif force == "ring":
            orig_plan2d = pk.pallas_2d_plan
            orig_ring = pk.dia_matvec_pallas_hbm2d_ring

            def interp_ring(*a, **k):
                k["interpret"] = True
                return orig_ring(*a, **k)

            pk.pallas_2d_plan = lambda *a, **k: None
            pk.dia_matvec_pallas_hbm2d_ring = interp_ring
            pk._SPMV_PROBE["hbm2dr"] = True
            unpatch += [lambda: setattr(pk, "pallas_2d_plan", orig_plan2d),
                        lambda: setattr(pk, "dia_matvec_pallas_hbm2d_ring",
                                        orig_ring),
                        lambda: pk._SPMV_PROBE.pop("hbm2dr", None)]
        try:
            if nparts == 0:
                res = cg_host(A, b.astype(dtype), x0=x0, options=opts)
            elif nparts > 1:
                fn = (cg_recycled_dist if recyc
                      else cg_sstep_dist if sstep
                      else cg_pipelined_deep_dist if deep
                      else cg_pipelined_dist if pipe else cg_dist)
                res = fn(A, b, x0=x0, options=opts, nparts=nparts,
                         dtype=dtype, method=HaloMethod(halo),
                         partition_method=pmethod, fmt=fmt,
                         mat_dtype=mat_dtype,
                         **(dict(W=W, WtAW=WtAW) if recyc else {}))
            else:
                fn = (cg_recycled if recyc
                      else cg_sstep if sstep
                      else cg_pipelined_deep if deep
                      else cg_pipelined if pipe else cg)
                res = fn(A, b, x0=x0, options=opts, dtype=dtype, fmt=fmt,
                         mat_dtype=mat_dtype,
                         **(dict(W=W, WtAW=WtAW) if recyc else {}))
            x = np.asarray(res.x, dtype=np.float64)
            rel = np.linalg.norm(S @ x - b) / np.linalg.norm(b)
            tol = 1e-7 if dtype == np.float64 else 2e-3
            if not (np.all(np.isfinite(x)) and rel < tol):
                print(f"WRONG ({rel=:.2e}): {desc}")
                fails += 1
            if (force == "pipe2d" and force_calls["iter"] == 0
                    and res.kernel == "pallas-resident"):
                # the resident plan ran but the mega-kernel never did: a
                # harness bug, not coverage (review finding, round 5).
                # Unstructured kinds whose diagonal count blows the VMEM
                # plan legitimately fall back (kernel != pallas-resident)
                # and still count as ordinary differential trials.
                print(f"FORCED-TIER-MISS: {desc} "
                      f"(kernel={res.kernel})")
                fails += 1
        except AcgError as e:
            print(f"SOLVER-ERROR: {desc}: {e}")
            fails += 1
        except Exception as e:
            import traceback
            print(f"CRASH: {desc}: {type(e).__name__}: {e}")
            traceback.print_exc(limit=6)
            fails += 1
        finally:
            for f in unpatch:
                f()
    print(f"{args.trials} trials, {fails} failures "
          f"(forced tiers: {force_counts})")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
