"""Distributed CG over a device mesh: shard_map + halo + psum.

The multi-chip solver (reference acg/cgcuda.c:398-1109
``acgsolvercuda_solvempi`` and the pipelined/device variants), TPU-native:

- row shards live on a 1-D mesh (acg_tpu/parallel/mesh.py);
- the operator application is ``A_local x_own`` (independent of the halo,
  so XLA's latency-hiding scheduler overlaps it with the collective — the
  reference's split-phase begin/local-SpMV/end/interface-SpMV schedule,
  acg/cgcuda.c:847-883, falls out of the data dependences) followed by
  ``A_iface ghosts``;
- scalar reductions are ``psum`` over the mesh axis (ref acgcomm_allreduce,
  acg/comm.c:350-394); the pipelined variant reduces one length-2 vector
  per iteration (ref acg/cgcuda.c:1694-1701);
- the entire while_loop runs inside ONE ``shard_map``-ed jitted program —
  zero host round-trips per iteration, the semantics the reference needs
  NVSHMEM's device-initiated monolithic kernel for
  (acg/cg-kernels-cuda.cu:627-970).

Usage: :func:`cg_dist` / :func:`cg_pipelined_dist` take a host
:class:`CsrMatrix` + nparts (or a prebuilt :class:`ShardedSystem`) and a
global right-hand side, and return a global :class:`SolveResult`.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from acg_tpu.config import HaloMethod, SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.obs.trace import span
from acg_tpu.ops.spmv import ell_matvec
from acg_tpu.parallel.mesh import PARTS_AXIS
from acg_tpu.parallel.sharded import ShardedSystem, resolve_local_fmt
from acg_tpu.partition.graph import PartitionedSystem, partition_system
from acg_tpu.partition.partitioner import partition_graph
from acg_tpu.solvers.base import (SolveResult, SolveStats,
                                  require_unpreconditioned)
from acg_tpu.solvers.cg import (_CONVERGED, _GRAM_BAD, _cheb_leja_nodes,
                                _deflate_x0, _device_wait, _finish,
                                _pipelined_continue, _power_lmax,
                                _run_segmented, _sstep_certify,
                                _sstep_fallback, _sstep_fallback_stop,
                                _sstep_fallback_x0, _sstep_validate,
                                _traced_call)
from acg_tpu.solvers.loops import (cg_pipelined_deep_while,
                                   cg_pipelined_while, cg_sstep_while,
                                   cg_while)


def _dist_monitor(k, rr):
    """Live-progress hook for the sharded loops: the residual is psum'd
    (replicated), so only mesh position 0 enqueues the host callback —
    without the gate every shard of the CPU test mesh would print its
    own copy of each line (the reference prints from rank 0 only)."""
    def _emit(kk, g):
        from acg_tpu.obs.monitor import emit_residual_line

        # this IS the throttled monitor tier's distributed gate (rank-0
        # + monitor_every throttle), not an unthrottled callback
        jax.debug.callback(emit_residual_line, kk, g)  # acg: allow-debug-callback

    jax.lax.cond(jax.lax.axis_index(PARTS_AXIS) == 0,
                 lambda args: _emit(*args), lambda args: None, (k, rr))


def _dist_fused_plan(ss: ShardedSystem):
    """Per-shard fused-kernel plan: (kind, rows_tile) — kind a
    ``fused_kernels()`` key: "resident" | "hbm-ring" | "hbm" — when the
    padded Pallas path applies to every shard's local DIA block, else
    None — the distributed face of the shared gate
    (acg_tpu/ops/pallas_kernels.py ``fused_plan_for``) with n = the
    uniform padded shard length: shards are padded to one static shape
    (parallel/sharded.py), so ONE plan serves the whole mesh."""
    from acg_tpu.ops.pallas_kernels import fused_plan_for

    if ss.local_fmt != "dia":
        return None
    return fused_plan_for(ss.nown_max, ss.loffsets,
                          np.dtype(ss.vec_dtype), ss.lbands.dtype)


def _dist_pipe_rt(ss: ShardedSystem, plan):
    """rows_tile for the per-shard single-kernel pipelined iteration, or
    None — the distributed face of the shared pipe2d gate, factored out
    so the solver builder AND the path report (``_solve_dist``) apply the
    IDENTICAL guard (a result claiming "pallas-resident" while the
    pipe2d kernel ran was the round-5 advisor finding)."""
    if plan is None:
        # plan is not None implies the DIA local tier, so ss.lbands
        # exists (ell/sgell shards carry lbands=None — evaluating the
        # arguments unguarded crashed every non-DIA pipelined dist solve;
        # found by fuzz seed 239, 14/120 trials)
        return None
    from acg_tpu.ops.pallas_kernels import pipe2d_rt_for

    return pipe2d_rt_for(ss.nown_max, ss.loffsets,
                         np.dtype(ss.vec_dtype), ss.lbands.dtype,
                         plan)


def _shard_solver(ss: ShardedSystem, kind: str, maxits: int,
                  track_diff: bool, check_every: int = 1,
                  replace_every: int = 0, certify: bool = True,
                  monitor_every: int = 0, nrhs: int = 1,
                  guard: bool = False, has_fault: bool = False,
                  segment: int = 0, resume: bool = False,
                  sstep: int = 0, deep=None, depth: int = 0,
                  wire: str = "f32", ext_shifts: bool = False):
    """Build (and cache) the jitted shard_map solve for one system.

    The cache lives ON the system instance (not in a global dict keyed by
    ``id(ss)`` — Python reuses ids after garbage collection, which would
    hand a new system a stale jitted program bound to another mesh).

    ``nrhs`` > 1 builds the multi-RHS program: per-shard vectors carry a
    (B, NOWN) system block, the halo exchange moves (B, nghost) packs
    through the SAME number of collectives per iteration (one ppermute
    round set / one all_gather for ALL systems — the per-iteration
    collective count divides by B relative to sequential solves), and
    the psum'd reduction carries per-system (B,) scalars.

    ``guard``/``has_fault`` are the resilience hooks (acg_tpu/robust/):
    the guard tests the psum'd (replicated) scalars for finiteness —
    uniform across the mesh, so the while predicate never diverges and
    NO new collective is issued; ``has_fault`` appends a replicated
    DeviceFaultPlan argument to the shard program (the plan is data —
    one compiled program covers every fault kind/iteration).  Both off
    (the default) build the exact pre-existing program.

    ``segment`` > 0 builds the SEGMENTED program (classic kind only —
    the distributed face of SolverOptions.segment_iters, threading
    cg_while's carry-resume through shard_map exactly as the single-chip
    _cg_device_seg/_cg_device_seg_resume pair does): the while_loop
    additionally stops after ``segment`` iterations and the loop carry
    rides out as extra outputs — the three per-shard vectors under the
    sharded spec, everything else replicated.  ``resume=True`` builds
    the continuation twin, which takes those carry arrays back in place
    of a fresh x0 and re-enters the SAME loop body — numerically
    identical to the single-program solve.

    ``depth`` > 0 builds the deep-pipelined program (kind
    "cg-pipelined-deep"): the shard program runs ONE pipeline segment of
    loops.cg_pipelined_deep_while — the deep-ghost matrix-power fill
    chain (one depth-l exchange feeding l local extended SpMVs, the
    s-step skin machinery at depth l), the steady while_loop with its
    single fused (2l+1)-dot psum per body, and the true-residual exit
    certification — and takes the restart operands
    (k_start/rr0/flags/hist[/ksys]) as replicated inputs so the host
    re-dispatch driver (`_solve_dist`) reuses ONE executable.

    ``wire`` selects the halo WIRE format (SolverOptions.halo_wire) for
    every kind's exchanges: "f32" traces the exact pre-existing program
    (bit-identical, the zero-overhead clause); "bf16"/"int16-delta"
    halve the ppermute/all_gather payload bytes while the collective
    COUNTS — and the psum payloads, per the C10 upcast law — stay
    untouched (pinned by tests/test_halo_wire.py)."""
    cache = getattr(ss, "_solver_cache", None)
    if cache is None:
        cache = {}
        ss._solver_cache = cache
    key = (kind, maxits, track_diff, check_every, replace_every, certify,
           monitor_every, nrhs, guard, has_fault, segment, resume, sstep,
           depth, wire, ext_shifts)
    fn = cache.get(key)
    if fn is not None:
        return fn
    batched = nrhs > 1
    # carry pytree lengths under want_carry: classic cg_while carries 9
    # elements (+ per-system ksys when batched) + rr0, with the first
    # THREE (x, r, p) per-shard vectors; the pipelined loop carries 14
    # (+ done/ksys when batched) + gamma0 + the device continue bit,
    # with the first SIX (x, r, w, p, s, z) per-shard
    if kind == "cg":
        ncarry = (10 if batched else 9) + 1
        nshard_carry = 3
    else:
        ncarry = (16 if batched else 14) + 2
        nshard_carry = 6
    monitor = _dist_monitor if monitor_every > 0 else None
    deep_kind = kind == "cg-pipelined-deep"

    halo_fn = ss.shard_halo_fn(wire=wire)
    # the deep solver's exit certificates (and entry residuals) stand on
    # the UNCOMPRESSED operator: a compressed hot loop must not be able
    # to certify against its own wire noise (both sites are outside the
    # audited body, so the contract counts are untouched)
    cert_halo_fn = (ss.shard_halo_fn(wire="f32")
                    if deep_kind and wire != "f32" else None)
    local_mv = ss.local_matvec_fn()
    iface_add, iface_vec = ss.iface_fns()
    # the padded fused-coupled formulation and the single-kernel pipelined
    # iteration are 1-D tiers; batched solves run the plain formulation,
    # whose per-shard matvec still routes (B, n) blocks through the
    # batched SpMV kernel when its own gate passes (dia_matvec_best);
    # the s-step basis builder likewise runs the plain per-shard tier
    # (its extended-domain recurrence has no padded-carry formulation)
    plan = (None if (batched or kind == "cg-sstep" or deep_kind)
            else _dist_fused_plan(ss))
    # single-kernel pipelined iteration per shard: probe + VMEM plan
    # decided HERE (the shared gate, outside the traced function) so the
    # outcome is baked consistently into the cached executable
    pipe_rt = None
    if kind == "cg-pipelined" and not has_fault:
        # the single-kernel pipelined iteration exposes no injection
        # sites — injection programs run the open-coded body
        pipe_rt = _dist_pipe_rt(ss, plan)
    method = ss.method
    if sstep or deep_kind:
        deep_perms, deep_gdeep = deep.perms, deep.gdeep
    mesh = ss.mesh
    spec_v = P(PARTS_AXIS)      # (P, ...) arrays, sharded on leading axis
    spec_r = P()                # replicated scalars

    def solve_shard(lops, iops, sidx, ridx, ptnr, pidx, gsp, gpp,
                    b, x0, stop2, diffstop, *rest):
        # optional trailing arguments, in order: the deep-ghost layer's
        # ten sharded tables (s-step and deep-pipelined programs), the
        # deep-pipelined restart operands (replicated), the ``ncarry``
        # resumed loop-carry elements (resume programs only), then the
        # replicated fault plan (present iff has_fault — the argument
        # list, like the program, is shaped by what was requested)
        rest = list(rest)
        deep_ops = None
        if sstep or deep_kind:
            deep_ops = [a[0] for a in rest[:10]]
            rest = rest[10:]
        ext_sh = None
        if sstep and ext_shifts:
            # the recycled shift schedule rides as a replicated operand
            # (spectral recycling, ISSUE 20): the power-iteration /
            # Chebyshev seeding prelude is dropped from this program
            ext_sh = rest[0]
            rest = rest[1:]
        restart_in = None
        if deep_kind:
            n_restart = 5 if batched else 4
            restart_in = rest[:n_restart]
            rest = rest[n_restart:]
        carry_in = None
        if resume:
            carry_in = rest[:ncarry]
            rest = rest[ncarry:]
        fault = rest[0] if rest else None
        # shard_map blocks keep the sharded axis with size 1 -> drop it
        lops = tuple(a[0] for a in lops)
        if carry_in is not None:    # per-shard vectors lose the axis too
            carry_in = tuple(a[0] if i < nshard_carry else a
                             for i, a in enumerate(carry_in))
        iops = tuple(a[0] for a in iops)
        sidx, ridx, ptnr, pidx, gsp, gpp = (
            sidx[0], ridx[0], ptnr[0], pidx[0], gsp[0], gpp[0])
        b, x0 = b[0], x0[0]
        nown = b.shape[-1]

        def halo_of(x_own):
            # the halo collective has no data dependence on the local SpMV,
            # so XLA overlaps them — the reference's split-phase
            # begin/local/end/interface schedule (acg/cgcuda.c:847-883)
            return halo_fn(x_own, sidx, ridx, ptnr, pidx, gsp, gpp)

        from acg_tpu.ops.blas1 import allreduce, batched_dot

        def dot(a, c):
            # batched_dot is exactly jnp.vdot on 1-D shards; per-system
            # (B,) on batched shards — ONE psum either way
            return allreduce(batched_dot(a, c), PARTS_AXIS)

        def dot2(a1, b1, a2, b2):
            s = allreduce(jnp.stack([batched_dot(a1, b1),
                                     batched_dot(a2, b2)]), PARTS_AXIS)
            return s[0], s[1]

        coupled = None
        iter_step = None
        front = 0
        if plan is None:
            def matvec(x):
                # Split-phase schedule (ref acg/cgcuda.c:847-883
                # begin/local/end/interface): the halo collective and the
                # local SpMV are data-independent; the barrier asks XLA to
                # keep them independent THROUGH compilation — without it
                # elementwise fusion merges the local band compute INTO
                # the ghost-correction add, making the compiled local SpMV
                # depend on the collective (observed in the optimized
                # CPU-mesh HLO, round 5).  XLA:CPU expands the barrier
                # before fusion (the serialization persists there — halo
                # START independence is what tests/test_overlap_hlo.py
                # pins for this formulation; harmless on CPU, whose
                # collectives are synchronous anyway); the fused Pallas
                # path below is structurally unfusable and is pinned in
                # BOTH directions.  The named scopes are what the HLO
                # tests key on.
                gh = halo_of(x)
                with jax.named_scope("acg.spmv.local"):
                    y_local = jax.lax.optimization_barrier(
                        local_mv(x, lops))
                with jax.named_scope("acg.spmv.iface"):
                    return iface_add(y_local, iops, gh)
        else:
            # the fused padded path, per shard: vectors carry a permanent
            # zero halo (padded once per SOLVE, zero per-iteration pads —
            # the distributed extension of _cg_device_fused) and the local
            # SpMV kernel emits its p'Ap partial in-kernel; the interface
            # correction p·(A_iface ghosts) rides the same psum.  The
            # reference spends its kernel budget on exactly this overlapped
            # hot loop (acg/cgcuda.c:847-894).
            from acg_tpu.ops.pallas_kernels import (LANES, fused_kernels,
                                                    pad_dia_operands,
                                                    padded_halo_rows)

            fkind, rt = plan
            kernel = fused_kernels()[fkind]
            offsets = ss.loffsets
            scales = lops[1] if len(lops) > 1 else None
            bands_pad, (b, x0) = pad_dia_operands(lops[0], (b, x0), rt,
                                                  offsets)
            front = padded_halo_rows(offsets, rt) * LANES

            def own_view(xp):
                return jax.lax.slice(xp, (front,), (front + nown,))

            def matvec(xp):
                with jax.named_scope("acg.halo.pack"):
                    xo = own_view(xp)
                gh = halo_of(xo)
                with jax.named_scope("acg.spmv.local"):
                    t = kernel(bands_pad, offsets, xp, rows_tile=rt,
                               scales=scales)
                with jax.named_scope("acg.spmv.iface"):
                    return iface_add(t, iops, gh, front)

            def coupled(r, p, beta):
                with jax.named_scope("acg.blas1"):
                    p = r + beta * p
                    po = own_view(p)
                gh = halo_of(po)
                with jax.named_scope("acg.spmv.local"):
                    t, pdot = kernel(bands_pad, offsets, p, rows_tile=rt,
                                     with_dot=True, scales=scales)
                with jax.named_scope("acg.spmv.iface"):
                    iface = iface_vec(iops, gh)
                    t = t.at[front: front + nown].add(iface)
                with jax.named_scope("acg.blas1"):
                    pdot = pdot + jnp.vdot(po, iface)
                ptap = allreduce(pdot, PARTS_AXIS)
                return p, t, ptap

            if pipe_rt is not None:
                from acg_tpu.ops.pallas_kernels import \
                    cg_pipelined_iter_pallas

                def iter_step(z, r, p, w, s, x, alpha, beta):
                    # the whole local iteration in ONE kernel; the
                    # interface correction I = A_iface·ghosts(w) is
                    # linear, so it folds in afterwards:
                    #   z' = z_k + I,  w' = w_k - alpha·I,
                    #   delta = delta_k - alpha·<I, r'>
                    # (p, s, x, r, gamma are q-free and unaffected;
                    # derivation in PERF.md round 5)
                    with jax.named_scope("acg.halo.pack"):
                        wo = own_view(w)
                    gh = halo_of(wo)
                    with jax.named_scope("acg.spmv.local"):
                        zk, pk, sk, xk, rk, wk, gk, dk = \
                            cg_pipelined_iter_pallas(
                                bands_pad, offsets, w, z, r, p, s, x,
                                alpha, beta, rows_tile=pipe_rt,
                                scales=scales)
                    with jax.named_scope("acg.spmv.iface"):
                        iface = iface_vec(iops, gh)
                        z2 = zk.at[front: front + nown].add(iface)
                        w2 = wk.at[front: front + nown].add(-alpha * iface)
                    with jax.named_scope("acg.blas1"):
                        dloc = dk - alpha * jnp.vdot(iface, own_view(rk))
                    tot = allreduce(jnp.stack([gk, dloc]), PARTS_AXIS)
                    return z2, pk, sk, xk, rk, w2, tot[0], tot[1]

        carry_out = ()
        if kind == "cg" and segment > 0:
            x, k, rr, dxx, flag, rr0, hist, carry = cg_while(
                matvec, dot, b, None if resume else x0, stop2, diffstop,
                maxits, track_diff,
                check_every=check_every, coupled_step=coupled,
                segment=segment, carry_in=carry_in, want_carry=True,
                monitor=monitor, monitor_every=monitor_every,
                fault=fault, guard=guard)
            # per-shard carry vectors re-enter the mesh under the
            # sharded spec (mirrors the x output below)
            carry_out = tuple(c[None] if i < 3 else c
                              for i, c in enumerate(carry))
        elif kind == "cg":
            x, k, rr, dxx, flag, rr0, hist = cg_while(
                matvec, dot, b, x0, stop2, diffstop, maxits, track_diff,
                check_every=check_every, coupled_step=coupled,
                monitor=monitor, monitor_every=monitor_every,
                fault=fault, guard=guard)
        elif kind == "cg-sstep":
            # ── s-step CG (ISSUE 7): inside the while body, ONE deep
            # halo exchange of the stacked (x, p) seeds and ONE Gram
            # psum per s iterations; everything else is shard-local.
            # The deep ghost zones (acg_tpu/parallel/deep.py) let each
            # shard run the 2s basis applications redundantly in the
            # overlap skin: owned rows through the shard's own local
            # tier + a deep-remapped interface ELL, ghost-interior rows
            # through a small ELL skin over [owned | deep ghosts].
            from acg_tpu.ops.blas1 import gram
            from acg_tpu.parallel.halo import (halo_allgather,
                                               halo_ppermute)

            (dsi, dri, _dptn, dpck, dgsp, dgpp,
             difv, difc, dgrv, dgrc) = deep_ops
            s = sstep
            gd = deep_gdeep

            def deep_halo(v):
                # the ppermute tier generalizes to any leading axes,
                # but halo_allgather supports ONE — flatten the stacked
                # batched seed pack (2, B, nown) -> (2B, nown) and
                # restore, so both tiers see a supported rank (and the
                # collective count stays independent of the leading
                # shape either way)
                lead = v.shape[:-1]
                if v.ndim > 2:
                    with jax.named_scope("acg.halo.pack"):
                        v = v.reshape((-1, v.shape[-1]))
                if method == HaloMethod.PPERMUTE:
                    out = halo_ppermute(v, dsi, dri, deep_perms,
                                        gd, PARTS_AXIS, wire=wire)
                else:
                    out = halo_allgather(v, dpck, dgsp, dgpp,
                                         PARTS_AXIS, wire=wire)
                with jax.named_scope("acg.halo.unpack"):
                    return (out.reshape(lead + out.shape[-1:])
                            if len(lead) > 1 else out)

            def ext_mv(ve):
                with jax.named_scope("acg.spmv"):
                    vo = jax.lax.slice_in_dim(ve, 0, nown, axis=-1)
                    vg = jax.lax.slice_in_dim(ve, nown, nown + gd,
                                              axis=-1)
                    with jax.named_scope("acg.spmv.local"):
                        yo = local_mv(vo, lops)
                    with jax.named_scope("acg.spmv.iface"):
                        yo = yo + ell_matvec(difv, difc, vg)
                    with jax.named_scope("acg.spmv.skin"):
                        yg = ell_matvec(dgrv, dgrc, ve)
                    return jnp.concatenate([yo, yg], axis=-1)

            bce = (lambda t: t[..., None]) if nrhs > 1 else (lambda t: t)
            # b's deep-ghost values are loop constants: exchanged once
            # in the prelude, closed over by every block's replacement
            b_ext = jnp.concatenate([b, deep_halo(b)], axis=-1)

            def block_fn(x, p, shifts):
                gh = deep_halo(jnp.stack([x, p]))
                xe = jnp.concatenate([x, gh[0]], axis=-1)
                pe = jnp.concatenate([p, gh[1]], axis=-1)
                re = b_ext - ext_mv(xe)     # replaced residual, valid
                basis = [pe]                # to skin depth s-1
                for j in range(s):
                    v = basis[-1]
                    basis.append(ext_mv(v) - bce(shifts[..., j]) * v)
                basis.append(re)
                for j in range(s - 1):
                    v = basis[-1]
                    basis.append(ext_mv(v) - bce(shifts[..., j]) * v)
                V = jnp.stack([jax.lax.slice_in_dim(v, 0, nown, axis=-1)
                               for v in basis])
                return V, gram(V, axis_name=PARTS_AXIS)   # the ONE psum

            r0 = b - matvec(x0)
            rr0 = dot(r0, r0)
            if ext_sh is not None:
                # recycled schedule: the seeding prelude (6 power-
                # iteration matvecs + Chebyshev nodes) is NOT traced
                shifts0 = ext_sh
            else:
                lam = _power_lmax(matvec, dot, b)
                shifts0 = lam[..., None] * jnp.asarray(
                    _cheb_leja_nodes(s), b.dtype)
            x, k, rr, flag, hist, sh_out = cg_sstep_while(
                block_fn, b, x0, r0, rr0, shifts0, stop2, s, maxits,
                monitor=monitor, monitor_every=monitor_every)
            # certify every exit on a fresh true residual (post-loop:
            # one ordinary halo + one psum, outside the audited body)
            rT = b - matvec(x)
            rrT = dot(rT, rT)
            flag, hist = _sstep_certify(rrT, k, flag, hist, stop2, rr0,
                                        nrhs > 1)
            rr = rrT
            dxx = jnp.asarray(jnp.inf, b.dtype)
            # the FINAL Ritz-refined Leja-ordered schedule rides out as
            # an extra replicated output — harvested by _solve_dist for
            # spectral recycling (even a cold solve produces it)
            carry_out = (sh_out,)
        elif deep_kind:
            # ── depth-l pipelined CG (loops.cg_pipelined_deep_while):
            # inside the while body ONE halo exchange (through matvec)
            # + ONE fused (2l+1)-dot psum, with l reductions in flight.
            # The fill chain runs the deep-ghost matrix-power pattern —
            # one depth-l exchange feeding l local extended SpMVs, the
            # s-step skin machinery at depth l — in the dispatch
            # prelude, outside the audited body (as are the power-
            # iteration shift seeds and the exit certification).
            from acg_tpu.parallel.halo import (halo_allgather,
                                               halo_ppermute)

            (dsi, dri, _dptn, dpck, dgsp, dgpp,
             difv, difc, dgrv, dgrc) = deep_ops
            gd = deep_gdeep

            def deep_halo(v):
                lead = v.shape[:-1]
                if v.ndim > 2:
                    with jax.named_scope("acg.halo.pack"):
                        v = v.reshape((-1, v.shape[-1]))
                if method == HaloMethod.PPERMUTE:
                    out = halo_ppermute(v, dsi, dri, deep_perms,
                                        gd, PARTS_AXIS, wire=wire)
                else:
                    out = halo_allgather(v, dpck, dgsp, dgpp,
                                         PARTS_AXIS, wire=wire)
                with jax.named_scope("acg.halo.unpack"):
                    return (out.reshape(lead + out.shape[-1:])
                            if len(lead) > 1 else out)

            def ext_mv(ve):
                # owned rows: the shard's own local tier + the deep-
                # remapped interface ELL; ghost-interior rows: the small
                # skin ELL over [owned | deep ghosts] (parallel/deep.py)
                with jax.named_scope("acg.spmv"):
                    vo = jax.lax.slice_in_dim(ve, 0, nown, axis=-1)
                    vg = jax.lax.slice_in_dim(ve, nown, nown + gd,
                                              axis=-1)
                    with jax.named_scope("acg.spmv.local"):
                        yo = local_mv(vo, lops)
                    with jax.named_scope("acg.spmv.iface"):
                        yo = yo + ell_matvec(difv, difc, vg)
                    with jax.named_scope("acg.spmv.skin"):
                        yg = ell_matvec(dgrv, dgrc, ve)
                    return jnp.concatenate([yo, yg], axis=-1)

            bce = (lambda t: t[..., None]) if nrhs > 1 else (lambda t: t)
            lam = _power_lmax(matvec, dot, b)
            shifts0 = lam[..., None] * jnp.asarray(
                _cheb_leja_nodes(depth), b.dtype)

            def fill(z0):
                # the matrix-power fill chain: ONE depth-l exchange; the
                # l shifted applications run redundantly in the skin
                ze = jnp.concatenate([z0, deep_halo(z0)], axis=-1)
                zs = [ze]
                for j in range(depth):
                    v = zs[-1]
                    zs.append(ext_mv(v) - bce(shifts0[..., j]) * v)
                return jnp.stack(
                    [jax.lax.slice_in_dim(v, 0, nown, axis=-1)
                     for v in zs])

            def dots_fn(U, v):
                # the fused (2l+1)-dot block — the body's ONE psum
                d = jnp.moveaxis(jnp.sum(U * v[None], axis=-1), 0, -1)
                return allreduce(d, PARTS_AXIS)

            cert_mv = None
            if cert_halo_fn is not None:
                def cert_mv(v):
                    # uncompressed exchange for the entry residual and
                    # the exit certificate (see _shard_solver docstring)
                    gh = cert_halo_fn(v, sidx, ridx, ptnr, pidx, gsp, gpp)
                    with jax.named_scope("acg.spmv.local"):
                        y = jax.lax.optimization_barrier(
                            local_mv(v, lops))
                    with jax.named_scope("acg.spmv.iface"):
                        return iface_add(y, iops, gh)

            k_start, rr0_in, flags_in, hist_in = restart_in[:4]
            ksys_in = restart_in[4] if batched else None
            (x, k, rr, flag, rr0, hist, kglob, more,
             drift) = cg_pipelined_deep_while(
                matvec, dots_fn, dot, b, x0, stop2, depth, shifts0,
                maxits, check_every=check_every,
                replace_every=replace_every, certify=certify,
                k_start=k_start, rr0_in=rr0_in, flags_in=flags_in,
                hist_in=hist_in, ksys_in=ksys_in, fill=fill,
                cert_matvec=cert_mv, monitor=monitor,
                monitor_every=monitor_every, guard=guard)
            dxx = jnp.asarray(jnp.inf, b.dtype)
            carry_out = (kglob, more, drift)
        elif segment > 0:
            # segmented pipelined solve (PR 7): same body, exact carry,
            # the carry's last element is the device continue bit
            x, k, rr, flag, rr0, hist, carry = cg_pipelined_while(
                matvec, dot2, b, None if resume else x0, stop2, maxits,
                check_every=check_every, replace_every=replace_every,
                certify=certify, iter_step=iter_step,
                monitor=monitor, monitor_every=monitor_every,
                fault=fault, guard=guard,
                segment=segment, carry_in=carry_in, want_carry=True)
            dxx = jnp.asarray(jnp.inf, b.dtype)
            carry_out = tuple(c[None] if i < nshard_carry else c
                              for i, c in enumerate(carry))
        else:
            x, k, rr, flag, rr0, hist = cg_pipelined_while(
                matvec, dot2, b, x0, stop2, maxits,
                check_every=check_every, replace_every=replace_every,
                certify=certify, iter_step=iter_step,
                monitor=monitor, monitor_every=monitor_every,
                fault=fault, guard=guard)
            dxx = jnp.asarray(jnp.inf, b.dtype)
        if plan is not None:
            x = jax.lax.slice(x, (front,), (front + nown,))
        # hist holds psum'd residuals — replicated across shards like the
        # other scalar outputs, so it exits under the replicated spec
        return (x[None], k, rr, dxx, flag, rr0, hist) + carry_out

    seg = segment > 0 and kind in ("cg", "cg-pipelined")
    carry_specs = ((spec_v,) * nshard_carry
                   + (spec_r,) * (ncarry - nshard_carry)) if seg else ()
    # deep-pipelined extras: 4/5 replicated restart operands in, the
    # (kglob, more, drift) dispatch-protocol scalars out
    deep_in = ((spec_r,) * (5 if batched else 4)) if deep_kind else ()
    deep_out = ((spec_r,) * 3) if deep_kind else ()
    # s-step extras: the (replicated) recycled shift schedule in when
    # ext_shifts, the refined schedule out ALWAYS (spectral recycling)
    sstep_in = ((spec_r,) if sstep and ext_shifts else ())
    sstep_out = ((spec_r,) if sstep else ())
    mapped = jax.shard_map(
        solve_shard, mesh=mesh,
        in_specs=(spec_v,) * 10 + (spec_r, spec_r)
        + ((spec_v,) * 10 if sstep or deep_kind else ())
        + sstep_in
        + deep_in
        + (carry_specs if resume else ())
        + ((spec_r,) if has_fault else ()),
        out_specs=(spec_v, spec_r, spec_r, spec_r, spec_r, spec_r,
                   spec_r) + carry_specs + deep_out + sstep_out,
        check_vma=False)
    fn = jax.jit(mapped)
    cache[key] = fn
    return fn


def build_sharded(A, nparts: int | None = None, part=None, mesh=None,
                  dtype=None, method: HaloMethod = HaloMethod.PPERMUTE,
                  partition_method: str = "auto", seed: int = 0,
                  mat_dtype="auto", fmt: str = "auto",
                  sgell_interpret: bool = False,
                  stencil_interpret: bool = False,
                  tier_report: dict | None = None,
                  prep_cache=None, ghash=None) -> ShardedSystem:
    """Partition + upload: the init phase (ref acgsolvercuda_init,
    acg/cgcuda.c:138-328, plus the driver's partition/scatter pipeline,
    cuda/acg-cuda.c:1485-1800).

    ``fmt`` picks the per-shard local operator: "auto" partitions with
    global-id local ordering (band-preserving for contiguous parts) and
    uses the gather-free DIA form when the local blocks are banded enough;
    if they are not, a per-part RCM pass tries to recover a band (the
    distributed extension of the single-chip RCM route); otherwise ELL.

    ``prep_cache`` (a :class:`~acg_tpu.partition.cache.PrepCache`, a
    directory path, ``"auto"``, or ``None`` = off) routes the partition
    vector and the partitioned-system assembly through the
    graph-content-hash cache — the ROADMAP item 4 reuse slice: repeated
    builds on the same operator pay zero preprocessing.  ``ghash`` (a
    :class:`~acg_tpu.partition.cache.GraphHashes` triple) lets a caller
    that already hashed ``A`` (the serve Session) skip the O(nnz)
    re-hash; anything else — including a legacy full-hash string —
    cannot address the cache's structure tier and triggers a re-hash."""
    if isinstance(A, ShardedSystem):
        return A
    if method == HaloMethod.RDMA:
        # the kernel (parallel/rdma_halo.py) puts into a peer's VMEM with
        # no barrier handshake that the peer's kernel is running, and it
        # has never run across chips: refused until a mesh run proves it
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "--halo rdma (device-initiated Pallas remote DMA) "
                       "is not supported: the kernel has no barrier "
                       "handshake and is unverified across chips; use "
                       "ppermute or allgather")
    from acg_tpu.config import ensure_x64_for
    # mirror ShardedSystem.build's dtype resolution (sharded.py: defaults
    # to float64 when no dtype is given and A carries no value dtype)
    want = dtype if dtype is not None else getattr(
        getattr(A, "vals", None), "dtype", np.float64)
    ensure_x64_for(np.dtype(want))
    if isinstance(A, PartitionedSystem):
        ps = A
    else:
        from acg_tpu.partition.cache import (cached_partition_graph,
                                             cached_partition_system,
                                             graph_hashes,
                                             resolve_prep_cache)

        cache = resolve_prep_cache(prep_cache)
        if ghash is None and cache is not None:
            ghash = graph_hashes(A)
        if part is None:
            if nparts is None:
                raise AcgError(Status.ERR_INVALID_VALUE,
                               "need nparts or a part vector")
            with span("acg.prep.partition"):
                part = cached_partition_graph(A, nparts,
                                              method=partition_method,
                                              seed=seed, cache=cache,
                                              ghash=ghash)
        with span("acg.prep.assemble"):
            ps = cached_partition_system(A, np.asarray(part),
                                         local_order="band", cache=cache,
                                         ghash=ghash)
    # one shared resolver (acg_tpu/parallel/sharded.py) decides
    # DIA vs sgell vs ELL, here WITH the per-part RCM recovery pass; the
    # resolved offsets / packs ride along so ShardedSystem.build never
    # re-sweeps the parts
    # the sgell gate must see the dtype the SOLVE will run at —
    # ShardedSystem.build resolves vdt = dtype or float64 (it does NOT
    # read A's value dtype), so gating on `want` here would admit f32
    # packs into an f64 solve the f32-only lane gather cannot run
    solve_dtype = np.dtype(dtype) if dtype is not None else np.float64
    import time as _time

    from acg_tpu.partition.cache import PREP_STAGE_SECONDS

    t0 = _time.perf_counter()
    with span("acg.prep.assemble"):
        ps, fmt, extra = resolve_local_fmt(
            ps, fmt, try_rcm=True, vec_dtype=solve_dtype,
            sgell_interpret=sgell_interpret,
            stencil_interpret=stencil_interpret, tier_report=tier_report,
            mat_dtype=mat_dtype)
    with span("acg.prep.upload"):
        ss = ShardedSystem.build(ps, mesh=mesh, dtype=dtype, method=method,
                                 mat_dtype=mat_dtype, fmt=fmt,
                                 loffsets=extra if fmt == "dia" else None,
                                 spacks=extra if fmt == "sgell" else None,
                                 sgell_interpret=sgell_interpret,
                                 stspec=extra if fmt == "stencil" else None,
                                 stencil_interpret=stencil_interpret)
    # prep-stage telemetry (no-op until enable_metrics()): the fmt
    # resolution + stack/upload wall — "shard" beside the cache layer's
    # "partition"/"system" stages (partition/cache.py)
    PREP_STAGE_SECONDS.labels(stage="shard").observe(
        _time.perf_counter() - t0)
    return ss


def _split7(out):
    """Split a segmented shard-solver's flat output into the 7 regular
    results + the carry tuple (the shape _run_segmented drives on)."""
    return out[:7] + (out[7:],)


def _with_deep_note(path: tuple, note: str) -> tuple:
    """Fold the deep-pipeline dispatch note into the kernel note, ahead
    of any disengagement reason (as the single-chip solver reports it)."""
    return path[:2] + (note + ("; " + path[2] if path[2] else ""),)


def _solve_dist(kind: str, A, b, x0, options: SolverOptions,
                stats: SolveStats | None, fault=None,
                atol2_floor=None, recycle=None, **build_kw) -> SolveResult:
    o = options
    require_unpreconditioned(o, f"{kind} over a mesh")
    with span("acg.call.prepare"):
        b = np.asarray(b)
        nrhs = b.shape[0] if b.ndim == 2 else 1
        batched = b.ndim == 2
        from acg_tpu.sparse.csr import CsrMatrix
        A_csr = A if isinstance(A, CsrMatrix) else None
        ss = build_sharded(A, **build_kw)
        if batched and ss.method == HaloMethod.RDMA:
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "multi-RHS solves support the ppermute/allgather "
                           "halo tiers (the Pallas remote-DMA halo moves 1-D "
                           "packs)")
        if o.halo_wire != "f32" and ss.method == HaloMethod.RDMA:
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "halo_wire compression applies to the ppermute/"
                           "allgather halo tiers (the Pallas remote-DMA "
                           "halo writes raw vector words)")
        sstep = 0
        depth = 0
        deep = None
        if kind == "cg-sstep":
            sstep = _sstep_validate(o, fault)
            if ss.method == HaloMethod.RDMA:
                raise AcgError(Status.ERR_NOT_SUPPORTED,
                               "s-step solves support the ppermute/allgather "
                               "halo tiers (the Pallas remote-DMA halo moves "
                               "1-D distance-1 packs, not the stacked deep "
                               "ghost exchange)")
            from acg_tpu.parallel.deep import build_deep_device

            # the deep ghost zones (one halo exchange per s-iteration block;
            # acg_tpu/parallel/deep.py), cached on the system per depth
            deep = build_deep_device(ss, sstep, A=A_csr)
        elif kind == "cg-pipelined-deep":
            from acg_tpu.solvers.cg import _deep_validate

            depth = _deep_validate(o, fault)
            if ss.method == HaloMethod.RDMA:
                raise AcgError(Status.ERR_NOT_SUPPORTED,
                               "deep-pipelined solves support the ppermute/"
                               "allgather halo tiers (the Pallas remote-DMA "
                               "halo moves 1-D distance-1 packs, not the "
                               "depth-l ghost exchange)")
            from acg_tpu.parallel.deep import build_deep_device

            # the depth-l ghost zones feed the fill chain's matrix powers
            deep = build_deep_device(ss, depth, A=A_csr)
        vdt = np.dtype(ss.vec_dtype)
        if x0 is not None:
            # the shared multi-RHS x0 shape contract (base.conform_x0_batch):
            # broadcast a 1-D x0 across the batch, reject any other mismatch
            from acg_tpu.solvers.base import conform_x0_batch

            x0 = conform_x0_batch(np.asarray(x0), b.shape,
                                  lambda v: np.tile(v[None, :], (nrhs, 1)))
        b_sh = ss.to_sharded(b)
        x0_sh = ss.to_sharded(x0) if x0 is not None \
            else ss.zeros_sharded(nrhs if batched else None)
        # atol2_floor: scalar or per-system (B,) squared-absolute threshold
        # floor — the s-step fallback restoring each system's original
        # criterion (cg.py _sstep_fallback_stop); replicated, so the spec_r
        # stop2 operand carries it unchanged
        stop2 = (jnp.asarray(o.residual_atol ** 2 if atol2_floor is None
                             else np.maximum(o.residual_atol ** 2,
                                             atol2_floor), vdt),
                 jnp.asarray(o.residual_rtol ** 2, vdt))
        track_diff = o.diffatol > 0 or o.diffrtol > 0
        if kind != "cg" and track_diff:
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "pipelined CG supports residual-based stopping "
                           "only")
        diffstop = jnp.asarray(o.diffatol ** 2, vdt)
        if o.diffrtol > 0:
            if batched:
                x0n = (jnp.linalg.norm(jnp.asarray(x0, dtype=vdt), axis=-1)
                       if x0 is not None else jnp.zeros((nrhs,), vdt))
                diffstop = jnp.maximum(diffstop,
                                       ((o.diffrtol * x0n) ** 2).astype(vdt))
            else:
                x0n = float(jnp.linalg.norm(np.asarray(x0, dtype=vdt))) \
                    if x0 is not None else 0.0
                diffstop = jnp.maximum(diffstop,
                                       jnp.asarray((o.diffrtol * x0n) ** 2,
                                                   vdt))
        # the resilience hooks, resolved exactly as the single-chip solver
        # does (acg_tpu/solvers/cg.py): guard from the options, the fault
        # plan converted to device arrays at the solve dtype
        from acg_tpu.solvers.cg import _fault_plan
        fplan = _fault_plan(fault, vdt)
        guard = o.guard_nonfinite
        # static certify: fixed-iteration pipelined solves drop the exit
        # certifier branch (see loops.cg_pipelined_while; PERF.md round 5)
        common = dict(certify=o.residual_atol > 0 or o.residual_rtol > 0,
                      monitor_every=o.monitor_every, nrhs=nrhs,
                      guard=guard, has_fault=fplan is not None,
                      wire=o.halo_wire)
        args = (ss.local_op_arrays(), ss.iface_op_arrays(), ss.send_idx,
                ss.recv_idx, ss.partner, ss.pack_idx, ss.ghost_src_part,
                ss.ghost_src_pos, b_sh, x0_sh, stop2, diffstop)
        ftail = () if fplan is None else (fplan,)
        dtail = () if deep is None else deep.arrays()
        bnrm2 = (np.linalg.norm(b, axis=-1) if batched
                 else float(np.linalg.norm(b)))
    with span("acg.call.dispatch"):
        fb_why = None
        t0 = time.perf_counter()
        if kind == "cg-pipelined-deep":
            # host re-dispatch driver (the loop's dispatch protocol): each
            # dispatch runs ONE pipeline segment of the SAME executable —
            # re-entry replaces the residual from its definition — until
            # the device-computed state says done, a guard fault surfaces,
            # or _DEEP_MAX_BAD consecutive breakdown/drift dispatches send
            # the solve to the classic-CG fallback below
            from acg_tpu.solvers.cg import (_BREAKDOWN, _DEEP_MAX_BAD,
                                            _FAULT, _OK)

            fn = _shard_solver(ss, kind, o.maxits, track_diff,
                               o.check_every, o.replace_every, deep=deep,
                               depth=depth, **common)
            sshape = (nrhs,) if batched else ()
            x_sh = x0_sh
            k_op = jnp.zeros((), jnp.int32)
            rr0_op = jnp.zeros(sshape, vdt)
            flags_op = jnp.zeros(sshape, jnp.int32)
            hist_op = jnp.zeros(sshape + (o.maxits + 1,), vdt)
            ktail = (jnp.zeros(sshape, jnp.int32),) if batched else ()
            fails = ndisp = 0
            while True:
                ndisp += 1
                (x_sh, kret, rr, dxx, flag, rr0_op, hist_op, k_op, more,
                 drift) = fn(*args[:9], x_sh, *args[10:], *dtail,
                             k_op, rr0_op, flags_op, hist_op, *ktail)
                if batched:
                    ktail = (kret,)
                flags_h = np.atleast_1d(np.asarray(jax.device_get(flag)))
                drift_h = np.atleast_1d(np.asarray(jax.device_get(drift)))
                k_h = int(jax.device_get(k_op))
                if np.any(flags_h == _FAULT):
                    break    # the guard fired: no restart, surface it
                bad = bool(np.any(flags_h == _BREAKDOWN)
                           or np.any(drift_h))
                fails = fails + 1 if bad else 0
                if fails >= _DEEP_MAX_BAD:
                    fb_why = ("indefinite Gram/LDL pivot"
                              if np.any(flags_h == _BREAKDOWN)
                              else "certified-exit drift")
                    break
                # breakdown systems restart with a replaced residual; drift
                # systems are still _OK and simply keep iterating
                flags_op = jnp.where(flag == _BREAKDOWN, _OK,
                                     flag).astype(jnp.int32)
                live = np.any((flags_h == _OK) | (flags_h == _BREAKDOWN))
                if not (live and k_h < o.maxits):
                    break
            x, k, rr0, hist = x_sh, kret, rr0_op, hist_op
        elif o.segment_iters > 0 and kind != "cg-sstep":
            # host loop over device segments, the distributed twin of the
            # single-chip _run_segmented driver: each dispatch runs the SAME
            # shard_map'd loop body for segment_iters iterations and hands
            # the exact loop carry to the next one — numerically identical
            # to the single-program solve (pinned by test_cg_dist).  The
            # pipelined carry (PR 7) ends with a device-computed continue
            # bit; the classic carry keeps its k/flag predicate.
            first = _shard_solver(ss, kind, o.maxits, track_diff,
                                  o.check_every, o.replace_every,
                                  segment=o.segment_iters, **common)
            cont = _shard_solver(ss, kind, o.maxits, track_diff,
                                 o.check_every, o.replace_every,
                                 segment=o.segment_iters, resume=True,
                                 **common)
            x, k, rr, dxx, flag, rr0, hist = _run_segmented(
                lambda: _split7(first(*args, *ftail)),
                lambda c: _split7(cont(*args, *c, *ftail)),
                o.maxits,
                continue_fn=(_pipelined_continue if kind == "cg-pipelined"
                             else None))
        else:
            # spectral recycling (ISSUE 20): a RecycleState holding a
            # refined schedule for this block size selects the ext_shifts
            # program variant — the recycled schedule rides in as a
            # replicated operand and the power/Chebyshev seeding prelude is
            # gone from the traced program.  Either variant OUTPUTS its
            # final Ritz-refined schedule, harvested below (a cold solve
            # seeds the recycle state for the next one).
            ext0 = None
            if kind == "cg-sstep" and recycle is not None:
                ext0 = recycle.get_shifts(sstep)
            stail = ()
            if ext0 is not None:
                ext0 = np.asarray(ext0, vdt)
                if batched and ext0.ndim == 1:
                    # the loop carries PER-SYSTEM shifts: tile the shared
                    # (s,) schedule to (B, s), exactly as cg_sstep does
                    ext0 = np.tile(ext0[None, :], (nrhs, 1))
                stail = (jnp.asarray(ext0),)
            fn = _shard_solver(ss, kind, o.maxits, track_diff, o.check_every,
                               o.replace_every, sstep=sstep, deep=deep,
                               ext_shifts=ext0 is not None, **common)
            out = fn(*args, *dtail, *stail, *ftail)
            x, k, rr, dxx, flag, rr0, hist = out[:7]
            if kind == "cg-sstep" and recycle is not None:
                sh_new = out[-1]
                flags_h = np.atleast_1d(np.asarray(jax.device_get(flag)))
                if np.any(flags_h == _CONVERGED):
                    recycle.put_shifts(
                        sstep, np.asarray(jax.device_get(sh_new)))
    k = _device_wait(x, k)        # scalar, or per-system (B,) when batched
    tsolve = time.perf_counter() - t0
    if kind == "cg-sstep":
        flags = np.atleast_1d(np.asarray(jax.device_get(flag)))
        if np.any(flags == _GRAM_BAD):
            # indefinite/non-finite Gram: classic distributed CG
            # re-solves from the last good iterate (and re-diagnoses a
            # truly indefinite operator); surfaced via kernel_note
            ksys = np.asarray(k) if batched else None
            k_done = int(np.max(np.asarray(k)))
            x_part = _sstep_fallback_x0(ss.from_sharded(x), x0, rr, rr0)
            o2 = dataclasses.replace(o, sstep=0,
                                     maxits=max(o.maxits - k_done, 0))
            floor = _sstep_fallback_stop(o, rr0)
            from acg_tpu.solvers.base import cg_flops_per_iter
            return _sstep_fallback(
                lambda: _solve_dist("cg", ss, b, x_part, o2, stats,
                                    atol2_floor=floor, **build_kw),
                k_done, ksys, sstep, "indefinite/non-finite Gram matrix",
                spent_flops=k_done * cg_flops_per_iter(ss.nnz, ss.nrows,
                                                       sstep=sstep))
    if kind == "cg-pipelined-deep" and fb_why is not None:
        # mirrors the s-step Gram fallback: classic distributed CG
        # re-solves from the last deep iterate under the original
        # stopping criterion; surfaced via kernel_note
        ksys = np.asarray(k) if batched else None
        k_done = int(np.max(np.asarray(k)))
        x_part = _sstep_fallback_x0(ss.from_sharded(x), x0, rr, rr0)
        # the reliability path runs at full wire precision: a compressed
        # exchange may be WHY the deep basis drifted
        o2 = dataclasses.replace(o, pipeline_depth=1, halo_wire="f32",
                                 maxits=max(o.maxits - k_done, 0))
        floor = _sstep_fallback_stop(o, rr0)
        from acg_tpu.solvers.base import cg_flops_per_iter
        return _sstep_fallback(
            lambda: _solve_dist("cg", ss, b, x_part, o2, stats,
                                atol2_floor=floor, **build_kw),
            k_done, ksys, depth, fb_why,
            spent_flops=k_done * cg_flops_per_iter(ss.nnz, ss.nrows,
                                                   pipelined=True),
            label=f"cg-pipelined-deep(l={depth})")

    class _Meta:  # duck-typed for _finish (nrows/nnz for flop model)
        nrows = ss.nrows
        nnz = ss.nnz

    # which local-operator format + kernel tier ran (the interface form
    # rides after the note: ShardedSystem.iface_note); naming shared
    # with the single-chip solver via path_names — including the pipe2d
    # report: when the single-kernel pipelined iteration gate
    # is active the in-loop kernel is pipe2d, not the plan's SpMV tier
    from acg_tpu.solvers.base import path_names

    plan = (_dist_fused_plan(ss)
            if ss.local_fmt == "dia" and not batched
            and kind not in ("cg-sstep", "cg-pipelined-deep") else None)
    # the path report must mirror _shard_solver's gate: injection
    # programs run the open-coded pipelined body, never the pipe2d kernel
    pipe_rt = (_dist_pipe_rt(ss, plan)
               if kind == "cg-pipelined" and fplan is None else None)
    stk = None
    if ss.local_fmt == "stencil":
        # which per-shard kernel the stencil routing resolves (the
        # closure decides inside local_matvec_fn; report the same gate)
        from acg_tpu.ops.stencil import stencil_kernel_kind

        stk = stencil_kernel_kind(ss.nown_max, ss.st_offsets,
                                  np.dtype(ss.vec_dtype), nrhs=nrhs,
                                  interpret=ss.st_interpret)
    path = path_names(ss.local_fmt,
                      plan_kind=plan[0] if plan else stk,
                      interpret=ss.sg_interpret,
                      rcm=getattr(ss.ps, "rcm_localized", False),
                      pipe2d=pipe_rt is not None)
    from acg_tpu.solvers.base import kernel_disengagement_note
    path = path + (kernel_disengagement_note(
        kind == "cg-pipelined", plan, pipe_rt, fplan,
        forced_fmt=build_kw.get("fmt", "auto")),)
    if kind == "cg-pipelined-deep":
        path = _with_deep_note(path, f"deep pipeline depth {depth}, "
                               f"{ndisp} dispatch(es), wire={o.halo_wire}")
    return _finish(_Meta, x, k, rr, flag, rr0, o, tsolve,
                   pipelined=(kind in ("cg-pipelined",
                                       "cg-pipelined-deep")),
                   bnrm2=bnrm2,
                   dxx=dxx if track_diff else None, stats=stats,
                   to_host=ss.from_sharded, path=path + (ss.iface_note,),
                   hist=hist, sstep=sstep,
                   solver=("cg-pipelined-deep"
                           if kind == "cg-pipelined-deep" else None))


def lowered_step(A, b=None, x0=None,
                 options: SolverOptions = SolverOptions(),
                 pipelined: bool = False, solver: str | None = None,
                 **build_kw):
    """Lower — without executing — the sharded jitted program
    :func:`cg_dist` / :func:`cg_pipelined_dist` would run; returns a
    ``jax.stages.Lowered``.  The distributed face of the introspection
    hook (see :func:`acg_tpu.solvers.cg.lowered_step`): compiling this
    and auditing it (acg_tpu/obs/hlo.py) is how the "one halo exchange +
    one psum per pipelined iteration, collective count independent of B"
    claims are CHECKED rather than asserted in prose.

    ``A`` may be a prebuilt :class:`ShardedSystem`; ``b``/``x0``
    (optional — zeros by default, shapes are all that matter for
    lowering) select the multi-RHS program when either is ``(B, n)``."""
    o = options
    require_unpreconditioned(o, "a distributed solver")
    if solver == "cg-recycled":
        # deflation is SETUP-only host work (x0 preconditioning): the
        # shard program cg_recycled_dist dispatches IS cg_dist's — the
        # audit of one is the audit of the other (the zero added
        # per-iteration collectives clause of the contract)
        solver = "cg"
    if solver is not None:
        pipelined = solver == "cg-pipelined"
    from acg_tpu.sparse.csr import CsrMatrix
    A_csr = A if isinstance(A, CsrMatrix) else None
    ss = build_sharded(A, **build_kw)
    b = None if b is None else np.asarray(b)
    x0 = None if x0 is None else np.asarray(x0)
    nrhs = next((a.shape[0] for a in (b, x0)
                 if a is not None and a.ndim == 2), 1)
    if x0 is not None and b is not None:
        # the shared multi-RHS x0 shape contract (_solve_dist does the
        # same): broadcast a 1-D guess across the batch
        from acg_tpu.solvers.base import conform_x0_batch

        x0 = conform_x0_batch(x0, b.shape,
                              lambda v: np.tile(v[None, :], (nrhs, 1)))
    vdt = np.dtype(ss.vec_dtype)
    if solver == "cg-pipelined-deep" and o.pipeline_depth <= 1:
        solver = "cg-pipelined"     # depth 1 IS the pipelined program
        pipelined = True
    kind = (solver if solver in ("cg-sstep", "cg-pipelined-deep")
            else ("cg-pipelined" if pipelined else "cg"))
    track_diff = (kind == "cg") and (o.diffatol > 0 or o.diffrtol > 0)
    if pipelined and (o.diffatol > 0 or o.diffrtol > 0):
        # the same rejection the solve applies (_solve_dist) — an audit
        # must not be printed for a program the solve refuses to run
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "pipelined CG supports residual-based stopping only")
    if o.halo_wire != "f32" and ss.method == HaloMethod.RDMA:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "halo_wire compression applies to the ppermute/"
                       "allgather halo tiers (the Pallas remote-DMA "
                       "halo writes raw vector words)")
    sstep = 0
    depth = 0
    deep = None
    if kind == "cg-sstep":
        # the same validations + deep layer the solve builds: what the
        # audit inspects is what the solve runs
        sstep = _sstep_validate(o, None)
        if ss.method == HaloMethod.RDMA:
            # mirror _solve_dist's rejection — an audit must not be
            # produced for a program the solve refuses (solve_shard's
            # deep_halo would silently take the allgather branch)
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "s-step solves support the ppermute/allgather "
                           "halo tiers (the Pallas remote-DMA halo moves "
                           "1-D distance-1 packs, not the stacked deep "
                           "ghost exchange)")
        from acg_tpu.parallel.deep import build_deep_device

        deep = build_deep_device(ss, sstep, A=A_csr)
    elif kind == "cg-pipelined-deep":
        from acg_tpu.solvers.cg import _deep_validate

        depth = _deep_validate(o, None)
        if ss.method == HaloMethod.RDMA:
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "deep-pipelined solves support the ppermute/"
                           "allgather halo tiers (the Pallas remote-DMA "
                           "halo moves 1-D distance-1 packs, not the "
                           "depth-l ghost exchange)")
        from acg_tpu.parallel.deep import build_deep_device

        deep = build_deep_device(ss, depth, A=A_csr)
    fn = _shard_solver(ss, kind, o.maxits, track_diff, o.check_every,
                       o.replace_every,
                       certify=o.residual_atol > 0 or o.residual_rtol > 0,
                       monitor_every=o.monitor_every, nrhs=nrhs,
                       guard=o.guard_nonfinite, sstep=sstep, deep=deep,
                       depth=depth, wire=o.halo_wire)
    b_sh = (ss.to_sharded(b) if b is not None
            else ss.zeros_sharded(nrhs if nrhs > 1 else None))
    x0_sh = (ss.to_sharded(x0.astype(vdt)) if x0 is not None
             else ss.zeros_sharded(nrhs if nrhs > 1 else None))
    stop2 = (jnp.asarray(o.residual_atol ** 2, vdt),
             jnp.asarray(o.residual_rtol ** 2, vdt))
    # the diffstop the solve would pass, including the per-system (B,)
    # threshold a batched diffrtol derives (_solve_dist) — the lowered
    # signature must match the executed one or --explain audits (and
    # pre-warms the compile cache of) a different program
    diffstop = jnp.asarray(o.diffatol ** 2, vdt)
    if o.diffrtol > 0:
        batched = nrhs > 1
        if batched:
            x0n = (jnp.linalg.norm(jnp.asarray(x0, dtype=vdt), axis=-1)
                   if x0 is not None else jnp.zeros((nrhs,), vdt))
            diffstop = jnp.maximum(diffstop,
                                   ((o.diffrtol * x0n) ** 2).astype(vdt))
        else:
            x0n = float(np.linalg.norm(np.asarray(x0, dtype=vdt))) \
                if x0 is not None else 0.0
            diffstop = jnp.maximum(diffstop,
                                   jnp.asarray((o.diffrtol * x0n) ** 2,
                                               vdt))
    # the deep-pipelined program's restart operands (dispatch-protocol
    # state threaded by _solve_dist's host loop) — zeros here: shapes
    # and dtypes are all that matter for lowering
    dtail = ()
    if kind == "cg-pipelined-deep":
        sshape = (nrhs,) if nrhs > 1 else ()
        dtail = (jnp.zeros((), jnp.int32), jnp.zeros(sshape, vdt),
                 jnp.zeros(sshape, jnp.int32),
                 jnp.zeros(sshape + (o.maxits + 1,), vdt))
        if nrhs > 1:
            dtail = dtail + (jnp.zeros(sshape, jnp.int32),)
    return fn.lower(
        ss.local_op_arrays(), ss.iface_op_arrays(), ss.send_idx,
        ss.recv_idx, ss.partner, ss.pack_idx, ss.ghost_src_part,
        ss.ghost_src_pos, b_sh, x0_sh, stop2, diffstop,
        *(deep.arrays() if deep is not None else ()), *dtail)


def compile_step(A, b=None, x0=None,
                 options: SolverOptions = SolverOptions(),
                 pipelined: bool = False, solver: str | None = None,
                 **build_kw):
    """Compiled twin of :func:`lowered_step` (``jax.stages.Compiled``):
    the object :func:`acg_tpu.obs.hlo.audit_compiled` consumes."""
    return lowered_step(A, b=b, x0=x0, options=options,
                        pipelined=pipelined, solver=solver,
                        **build_kw).compile()


def declared_contract(A, b=None, options: SolverOptions = SolverOptions(),
                      pipelined: bool = False, solver: str | None = None,
                      **build_kw):
    """Distributed twin of
    :func:`acg_tpu.solvers.cg.declared_contract`: the
    :class:`~acg_tpu.analysis.contracts.SolverContract` this sharded
    configuration declares — per-iteration psum count from the solver
    kind (2 classic / 1 pipelined / 1-per-s-block s-step), ppermute
    rounds from the actual edge-colored halo (or deep-ghost) schedule of
    the built system, psum payload law at the reduction width.  What
    :func:`compile_step` lowers is what this contract is verified
    against (``scripts/check_contracts.py``)."""
    from acg_tpu.analysis.registry import contract_for

    if solver is None:
        solver = "cg-pipelined" if pipelined else "cg"
    ss = build_sharded(A, **build_kw)
    b = None if b is None else np.asarray(b)
    nrhs = b.shape[0] if b is not None and b.ndim == 2 else 1
    return contract_for(solver, options, ss=ss, nrhs=nrhs)


def aot_step(A, b=None, x0=None,
             options: SolverOptions = SolverOptions(),
             pipelined: bool = False, solver: str | None = None,
             **build_kw):
    """Distributed twin of :func:`acg_tpu.solvers.cg.aot_step`: build the
    reusable AOT executable for the sharded classic/pipelined program at
    this static signature and return an
    :class:`~acg_tpu.solvers.cg.AotSolve` whose ``solve(b, x0)``
    dispatches straight into it — zero retracing, zero recompilation,
    results bit-identical to :func:`cg_dist` / :func:`cg_pipelined_dist`
    (pinned by tests/test_serve.py).  The operator tables ride as fixed
    device operands; only ``b``/``x0``/tolerances move per request."""
    from acg_tpu.solvers.base import (kernel_disengagement_note,
                                      path_names)
    from acg_tpu.solvers.cg import AotSolve

    o = options
    require_unpreconditioned(o, "a distributed solver")
    if solver is not None:
        pipelined = solver == "cg-pipelined"
    if solver == "cg-pipelined-deep" and o.pipeline_depth <= 1:
        solver, pipelined = "cg-pipelined", True    # depth 1 IS pipelined
    if solver not in (None, "cg", "cg-pipelined", "cg-pipelined-deep"):
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       f"aot_step compiles the classic/pipelined/"
                       f"deep-pipelined programs (solver {solver!r})")
    if o.segment_iters > 0:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "segment_iters re-dispatches per segment; use the "
                       "ordinary solver functions")
    kind = (solver if solver == "cg-pipelined-deep"
            else ("cg-pipelined" if pipelined else "cg"))
    deep_kind = kind == "cg-pipelined-deep"
    ss = build_sharded(A, **build_kw)
    compiled = lowered_step(ss, b=b, x0=x0, options=o,
                            pipelined=pipelined, solver=solver).compile()
    b = None if b is None else np.asarray(b)
    nrhs = b.shape[0] if b is not None and b.ndim == 2 else 1
    batched = nrhs > 1
    vdt = np.dtype(ss.vec_dtype)
    shape = ((nrhs, ss.nrows) if batched else (ss.nrows,))
    track_diff = (kind == "cg") and (o.diffatol > 0 or o.diffrtol > 0)
    static_args = (ss.local_op_arrays(), ss.iface_op_arrays(), ss.send_idx,
                   ss.recv_idx, ss.partner, ss.pack_idx,
                   ss.ghost_src_part, ss.ghost_src_pos)
    darrs = ()
    if deep_kind:
        # the depth-l ghost tables ride as fixed operands too (cached on
        # the system — lowered_step built the same ones)
        from acg_tpu.parallel.deep import build_deep_device
        from acg_tpu.sparse.csr import CsrMatrix

        darrs = tuple(build_deep_device(
            ss, o.pipeline_depth,
            A=A if isinstance(A, CsrMatrix) else None).arrays())
    # path/note exactly as _solve_dist reports them (no fault plan here)
    plan = (_dist_fused_plan(ss)
            if ss.local_fmt == "dia" and not batched and not deep_kind
            else None)
    pipe_rt = (_dist_pipe_rt(ss, plan)
               if kind == "cg-pipelined" else None)
    stk = None
    if ss.local_fmt == "stencil":
        from acg_tpu.ops.stencil import stencil_kernel_kind

        stk = stencil_kernel_kind(ss.nown_max, ss.st_offsets,
                                  np.dtype(ss.vec_dtype), nrhs=nrhs,
                                  interpret=ss.st_interpret)
    path = path_names(ss.local_fmt,
                      plan_kind=plan[0] if plan else stk,
                      interpret=ss.sg_interpret,
                      rcm=getattr(ss.ps, "rcm_localized", False),
                      pipe2d=pipe_rt is not None)
    path = path + (kernel_disengagement_note(
        kind == "cg-pipelined", plan, pipe_rt, None,
        forced_fmt=build_kw.get("fmt", "auto")),)

    class _Meta:    # duck-typed for _finish (flop model inputs)
        nrows = ss.nrows
        nnz = ss.nnz

    @_traced_call(kind + "-dist")
    def solve(b, x0=None, stats=None, options=None) -> SolveResult:
        from acg_tpu.solvers.cg import check_aot_options

        # per-dispatch options: tolerance VALUES re-bind as runtime
        # operands of the SAME executable; static fields must match
        with span("acg.call.prepare"):
            oo = o if options is None else check_aot_options(o, options)
            b = np.asarray(b)
            if b.shape != shape:
                raise AcgError(Status.ERR_INVALID_VALUE,
                               f"AOT signature mismatch: executable was "
                               f"compiled for shape {shape}, got {b.shape}")
            if x0 is not None:
                from acg_tpu.solvers.base import conform_x0_batch

                x0 = conform_x0_batch(np.asarray(x0), b.shape,
                                      lambda v: np.tile(v[None, :],
                                                        (nrhs, 1)))
            b_sh = ss.to_sharded(b)
            x0_sh = (ss.to_sharded(x0) if x0 is not None
                     else ss.zeros_sharded(nrhs if batched else None))
            stop2 = (jnp.asarray(oo.residual_atol ** 2, vdt),
                     jnp.asarray(oo.residual_rtol ** 2, vdt))
            diffstop = jnp.asarray(oo.diffatol ** 2, vdt)
            if oo.diffrtol > 0:
                if batched:
                    x0n = (jnp.linalg.norm(jnp.asarray(x0, dtype=vdt),
                                           axis=-1)
                           if x0 is not None else jnp.zeros((nrhs,), vdt))
                    diffstop = jnp.maximum(
                        diffstop, ((oo.diffrtol * x0n) ** 2).astype(vdt))
                else:
                    x0n = (float(jnp.linalg.norm(np.asarray(x0, dtype=vdt)))
                           if x0 is not None else 0.0)
                    diffstop = jnp.maximum(
                        diffstop, jnp.asarray((oo.diffrtol * x0n) ** 2,
                                              vdt))
            bnrm2 = (np.linalg.norm(b, axis=-1) if batched
                     else float(np.linalg.norm(b)))
        with span("acg.call.dispatch"):
            t0 = time.perf_counter()
            ndisp = 1
            if deep_kind:
                # the host re-dispatch driver of _solve_dist, against the
                # fixed executable: no classic-CG fallback here (AOT never
                # re-traces) — persistent breakdown/drift surfaces as the
                # returned flag instead
                from acg_tpu.solvers.cg import (_BREAKDOWN, _DEEP_MAX_BAD,
                                                _FAULT, _OK)

                sshape = (nrhs,) if batched else ()
                x_sh = x0_sh
                k_op = jnp.zeros((), jnp.int32)
                rr0 = jnp.zeros(sshape, vdt)
                flags_op = jnp.zeros(sshape, jnp.int32)
                hist = jnp.zeros(sshape + (oo.maxits + 1,), vdt)
                ktail = ((jnp.zeros(sshape, jnp.int32),)
                         if batched else ())
                fails = ndisp = 0
                while True:
                    ndisp += 1
                    (x_sh, k, rr, dxx, flag, rr0, hist, k_op, more,
                     drift) = compiled(*static_args, b_sh, x_sh, stop2,
                                       diffstop, *darrs, k_op, rr0,
                                       flags_op, hist, *ktail)
                    if batched:
                        ktail = (k,)
                    flags_h = np.atleast_1d(
                        np.asarray(jax.device_get(flag)))
                    drift_h = np.atleast_1d(
                        np.asarray(jax.device_get(drift)))
                    k_h = int(jax.device_get(k_op))
                    if np.any(flags_h == _FAULT):
                        break
                    bad = bool(np.any(flags_h == _BREAKDOWN)
                               or np.any(drift_h))
                    fails = fails + 1 if bad else 0
                    if fails >= _DEEP_MAX_BAD:
                        break
                    flags_op = jnp.where(flag == _BREAKDOWN, _OK,
                                         flag).astype(jnp.int32)
                    live = np.any((flags_h == _OK)
                                  | (flags_h == _BREAKDOWN))
                    if not (live and k_h < oo.maxits):
                        break
                x = x_sh
            else:
                x, k, rr, dxx, flag, rr0, hist = compiled(
                    *static_args, b_sh, x0_sh, stop2, diffstop)
        k = _device_wait(x, k)          # real sync (see cg())
        tsolve = time.perf_counter() - t0
        path2 = path
        if deep_kind:
            path2 = _with_deep_note(
                path, f"deep pipeline depth {o.pipeline_depth}, "
                f"{ndisp} dispatch(es), wire={o.halo_wire}")
        return _finish(_Meta, x, k, rr, flag, rr0, oo, tsolve,
                       pipelined=(kind in ("cg-pipelined",
                                           "cg-pipelined-deep")),
                       bnrm2=bnrm2,
                       dxx=dxx if track_diff else None, stats=stats,
                       to_host=ss.from_sharded,
                       path=path2 + (ss.iface_note,), hist=hist,
                       solver=("cg-pipelined-deep" if deep_kind
                               else None))

    return AotSolve(compiled, solve, kind=kind, shape=shape,
                    vec_dtype=vdt, path=path)


@_traced_call("cg-dist")
def cg_dist(A, b, x0=None, options: SolverOptions = SolverOptions(),
            stats: SolveStats | None = None, fault=None,
            **build_kw) -> SolveResult:
    """Distributed classic CG (1 halo + 2 psums per iteration).
    ``fault``/``options.guard_nonfinite`` are the resilience hooks
    (see :func:`acg_tpu.solvers.cg.cg`)."""
    return _solve_dist("cg", A, b, x0, options, stats, fault=fault,
                       **build_kw)


@_traced_call("cg-pipelined-dist")
def cg_pipelined_dist(A, b, x0=None,
                      options: SolverOptions = SolverOptions(),
                      stats: SolveStats | None = None, fault=None,
                      **build_kw) -> SolveResult:
    """Distributed pipelined CG (1 halo + ONE 2-scalar psum per iteration)."""
    return _solve_dist("cg-pipelined", A, b, x0, options, stats,
                       fault=fault, **build_kw)


@_traced_call("cg-sstep-dist")
def cg_sstep_dist(A, b, x0=None,
                  options: SolverOptions = SolverOptions(),
                  stats: SolveStats | None = None, fault=None,
                  recycle=None, **build_kw) -> SolveResult:
    """Distributed s-step CG: ONE deep halo exchange + ONE Gram psum per
    ``options.sstep`` iterations — the per-iteration collective count
    drops to 1/s (arXiv:2501.03743; proven via CommAudit in
    tests/test_hlo_audit.py rather than asserted in prose).  The deep
    ghost zones are built (and cached) per system by
    acg_tpu/parallel/deep.py; numerical safety (residual replacement
    every block, certified exits, classic-CG fallback on an indefinite
    Gram) is the contract of loops.cg_sstep_while.

    ``recycle`` (a :class:`~acg_tpu.serve.session.RecycleState`) enables
    spectral recycling: a held refined schedule selects the program
    variant that takes it as a replicated operand (no seeding prelude),
    and every converged solve writes its final Ritz-refined schedule
    back — certified exits make a stale schedule a performance
    question, never a correctness one."""
    return _solve_dist("cg-sstep", A, b, x0, options, stats,
                       fault=fault, recycle=recycle, **build_kw)


@_traced_call("cg-recycled-dist")
def cg_recycled_dist(A, b, x0=None,
                     options: SolverOptions = SolverOptions(),
                     stats: SolveStats | None = None, fault=None,
                     W=None, WtAW=None, recycle=None, matvec=None,
                     **build_kw) -> SolveResult:
    """Distributed deflated CG (ISSUE 20): Galerkin-project the retained
    recycle basis out of the initial residual at SETUP (host-side x0
    preconditioning), then run the ordinary :func:`cg_dist` program —
    zero added per-iteration collectives; the dispatched shard program
    is bit-identical to classic distributed CG.  With no basis available
    the call IS :func:`cg_dist` (cold solves are never penalised)."""
    mv = matvec if matvec is not None else getattr(A, "matvec", None)
    if W is None and recycle is not None:
        W, WtAW = recycle.deflation_basis(mv)
    if W is not None and WtAW is not None and mv is not None:
        x0 = _deflate_x0(mv, b, x0, W, WtAW)
    return _solve_dist("cg", A, b, x0, options, stats, fault=fault,
                       **build_kw)


@_traced_call("cg-pipelined-deep-dist")
def cg_pipelined_deep_dist(A, b, x0=None,
                           options: SolverOptions = SolverOptions(),
                           stats: SolveStats | None = None, fault=None,
                           **build_kw) -> SolveResult:
    """Distributed depth-l pipelined CG (p(l)-CG): still ONE 2l+1-row
    dot-block psum per iteration, but its result is not needed for
    ``options.pipeline_depth`` further iterations — l reductions stay
    in flight, hiding latency ~l× deeper than the depth-1 pipelined
    solver (arXiv:1801.04728 shape; certified true-residual exits and
    the classic-CG fallback are the contract of
    loops.cg_pipelined_deep_while).  The depth-l ghost zones that feed
    the basis fill chain come from acg_tpu/parallel/deep.py; at
    ``pipeline_depth=1`` this IS :func:`cg_pipelined_dist` (same
    executable, bit-identical)."""
    if options.pipeline_depth <= 1:
        return _solve_dist("cg-pipelined", A, b, x0, options, stats,
                           fault=fault, **build_kw)
    return _solve_dist("cg-pipelined-deep", A, b, x0, options, stats,
                       fault=fault, **build_kw)
