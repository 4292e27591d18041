"""Single-chip jitted CG solvers: classic and pipelined.

The entire solve loop runs on device inside one jitted ``lax.while_loop`` —
the TPU analog of the reference's *monolithic device-side CG*, where the
whole solver is a single persistent cooperative kernel with zero host
round-trips per iteration (reference acg/cg-kernels-cuda.cu:627-970
``acgsolvercuda_cg_kernel``).  On TPU this is the natural formulation, not a
special tier: ``jit`` compiles the loop once, control never returns to the
host, and convergence is decided on device (ref :948-957) by the while-loop
predicate.

Two algorithms, matching the reference's solver menu
(ref cuda/acg-cuda.c:120-127):

- :func:`cg` — classic CG: per iteration 1 SpMV, 2 reduction points
  (p'Ap and r'r; ref acg/cgcuda.c:894,933).
- :func:`cg_pipelined` — Ghysels/Vanroose pipelined CG: per iteration
  1 SpMV and ONE fused 2-scalar reduction (γ=(r,r), δ=(w,r);
  ref acg/cgcuda.c:1680-1701), with the fused 6-vector update
  z,t,p,x,r,w (ref acg/cg-kernels-cuda.cu:187-269
  ``pipelined_daxpy_fused``) expressed as fusable XLA element-wise ops.
  On a single chip the reduction count is a latency detail; distributed
  (see cg_dist.py) it is the point — one psum per iteration.

Stopping criteria and breakdown returns mirror the host reference
(acg_tpu/solvers/cg_host.py, reference acg/cg.c:198-380).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.config import SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.obs.metrics import observe_solve_result
from acg_tpu.obs.trace import span
from acg_tpu.ops.blas1 import batched_dot, gram
from acg_tpu.ops.spmv import DeviceEll, pad_vector
from acg_tpu.solvers.base import (SolveResult, SolveStats,
                                  cg_flops_per_iter,
                                  require_unpreconditioned)
from acg_tpu.solvers.loops import (cg_pipelined_deep_while,
                                   cg_pipelined_while, cg_sstep_while,
                                   cg_while)
from acg_tpu.sparse.ell import EllMatrix

# breakdown / fault flags carried out of the device loop
_OK, _CONVERGED, _BREAKDOWN, _FAULT = 0, 1, 2, 3
# s-step only: indefinite/non-finite Gram -> the wrapper falls back to
# classic CG (acg_tpu/solvers/loops.py _GRAM_BAD)
_GRAM_BAD = 4


def _fault_plan(fault, vdt):
    """Resolve a solver-level ``fault`` argument (a host
    :class:`~acg_tpu.robust.faults.FaultSpec`, an already-built
    :class:`~acg_tpu.robust.faults.DeviceFaultPlan`, or None) into the
    traced-as-data device plan at the solve's vector dtype."""
    if fault is None:
        return None
    from acg_tpu.robust.faults import DeviceFaultPlan, FaultSpec

    if isinstance(fault, DeviceFaultPlan):
        return fault
    if isinstance(fault, FaultSpec):
        return fault.device_plan(vdt)
    raise AcgError(Status.ERR_INVALID_VALUE,
                   f"fault must be a FaultSpec or DeviceFaultPlan, got "
                   f"{type(fault).__name__}")


def _traced_call(solver: str):
    """Decorate a solver entry: each call is one ``acg.call`` span
    (acg_tpu/obs/trace.py), inside which the entry opens its
    ``acg.call.*`` stages."""
    def wrap(fn):
        @functools.wraps(fn)
        def entry(*args, **kwargs):
            with span("acg.call", solver=solver):
                return fn(*args, **kwargs)
        return entry
    return wrap


def _device_wait(x, k):
    """Wait for the solve's ``x`` and fetch its iteration count ``k`` —
    the first sync after dispatch (``acg.call.device_wait``)."""
    with span("acg.call.device_wait"):
        jax.block_until_ready(x)
        return jax.device_get(k)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "track_diff", "check_every",
                                    "monitor", "monitor_every", "guard",
                                    "mg"))
def _cg_device(op, b, x0, stop2, diffstop, maxits: int, track_diff: bool,
               check_every: int = 1, monitor=None, monitor_every: int = 0,
               fault=None, guard: bool = False, mg=None):
    """Classic CG; returns (x, k, rnrm2sqr, dxnrm2sqr, flag, r0nrm2sqr,
    hist).

    ``op`` is a device operator pytree (DeviceEll or DeviceDia) whose
    static fields select the SpMV formulation at trace time.  ``fault``
    (a DeviceFaultPlan pytree — data, not trace structure) and ``guard``
    (static) are the resilience hooks of acg_tpu/robust/.  ``mg`` (a
    static :class:`~acg_tpu.ops.mg.MgPlan`) preconditions the loop."""
    return cg_while(op.matvec, batched_dot,
                    b, x0, stop2, diffstop, maxits, track_diff,
                    check_every=check_every,
                    monitor=monitor, monitor_every=monitor_every,
                    fault=fault, guard=guard, precond=_precond_fn(mg))


@functools.partial(jax.jit,
                   static_argnames=("maxits", "track_diff", "check_every",
                                    "segment", "monitor", "monitor_every",
                                    "guard"))
def _cg_device_seg(op, b, x0, stop2, diffstop, maxits: int,
                   track_diff: bool, check_every: int, segment: int,
                   monitor=None, monitor_every: int = 0,
                   fault=None, guard: bool = False):
    """First segment of a segmented solve (see SolverOptions.segment_iters):
    also returns the loop carry for :func:`_cg_device_seg_resume`."""
    return cg_while(op.matvec, batched_dot, b, x0, stop2, diffstop,
                    maxits, track_diff, check_every=check_every,
                    segment=segment, want_carry=True,
                    monitor=monitor, monitor_every=monitor_every,
                    fault=fault, guard=guard)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "track_diff", "check_every",
                                    "segment", "monitor", "monitor_every",
                                    "guard"))
def _cg_device_seg_resume(op, b, carry, stop2, diffstop, maxits: int,
                          track_diff: bool, check_every: int, segment: int,
                          monitor=None, monitor_every: int = 0,
                          fault=None, guard: bool = False):
    """Continue a segmented solve from the exact loop carry — the same
    while_loop body, numerically identical to the single-program solve.
    The fault plan rides along: its iteration is GLOBAL (the carried k),
    so a fault lands in whichever segment contains its iteration."""
    return cg_while(op.matvec, batched_dot, b, None, stop2, diffstop,
                    maxits, track_diff, check_every=check_every,
                    segment=segment, carry_in=carry, want_carry=True,
                    monitor=monitor, monitor_every=monitor_every,
                    fault=fault, guard=guard)


def _run_segmented(first_fn, resume_fn, maxits: int, continue_fn=None):
    """Host loop over device segments: one dispatch per ``segment_iters``
    iterations (bounds single-program runtime where an environment kills
    long device programs — the gather ELL tier at large n runs ~500
    iterations a minute).  ``first_fn()`` runs the first segment,
    ``resume_fn(carry)`` continues from the exact loop carry; both return
    cg_while's ``want_carry=True`` tuple.  ``continue_fn`` overrides the
    classic-carry predicate (the pipelined carry ends with a
    device-computed continue bit — see loops.cg_pipelined_while)."""
    *res, carry = first_fn()

    def _continue(c):
        k, flag = jax.device_get((c[6], c[7]))
        # carry k/flag: continue while the LOOP would (identical to the
        # unsegmented predicate; batched solves carry a per-system flag
        # vector — continue while ANY system is still running)
        return int(k) < maxits and bool(np.any(np.asarray(flag) == _OK))

    if continue_fn is None:
        continue_fn = _continue
    while continue_fn(carry):
        with span("acg.call.segment"):
            *res, carry = resume_fn(carry)
    return res


def _pipelined_continue(carry) -> bool:
    """The pipelined segmented driver's predicate: the carry's last
    element IS the monolithic loop predicate, evaluated on device (see
    loops.cg_pipelined_while ``want_carry``)."""
    return bool(np.asarray(jax.device_get(carry[-1])))


def _fused_ops(op, bands_pad, rows_tile: int, kind: str):
    """(mv, coupled_step) over the padded layout for the given kernel
    body: "resident" (x in VMEM) below the VMEM bound; past it the
    100M-DOF regime — "hbm-ring" (ring-buffered x tiles, 1.0x fetch) or
    "hbm" (clustered window DMAs, the wide-span fallback);
    "resident-batched" is the multi-RHS kernel (vectors (B, n), the band
    stream read once per tile across all B systems, per-system fused
    p'Ap)."""
    from acg_tpu.ops.pallas_kernels import fused_kernels

    kernel = fused_kernels()[kind]
    sc = op.scales
    batched = kind == "resident-batched"

    def mv(v):
        return kernel(bands_pad, op.offsets, v, rows_tile=rows_tile,
                      scales=sc)

    def coupled(r, p, beta):
        # the loop runs this under acg.spmv; the kernel's fused p'Ap
        # stays there, the direction update is BLAS-1
        with jax.named_scope("acg.blas1"):
            p = r + (beta[:, None] if batched else beta) * p
        t, ptap = kernel(bands_pad, op.offsets, p,
                         rows_tile=rows_tile, with_dot=True, scales=sc)
        return p, t, ptap

    return mv, coupled


@functools.partial(jax.jit, static_argnames=("rows_tile",))
def _pad_fused(op, b, x0, rows_tile: int):
    """One-time padding into the fused layout (zero halo rows; see
    pad_dia_operands) — kept OUT of the per-segment functions so
    segmented solves do not re-pad the bands every segment.  The bands
    come back as (D, Rp, 128), the block layout the kernels read, so the
    solver loop carries them with no per-iteration relayout."""
    from acg_tpu.ops.pallas_kernels import pad_dia_operands

    return pad_dia_operands(op.bands, (b, x0), rows_tile, op.offsets)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "track_diff", "check_every",
                                    "rows_tile", "kind", "monitor",
                                    "monitor_every", "guard", "mg"))
def _cg_device_fused(op, b, x0, stop2, diffstop, maxits: int,
                     track_diff: bool, check_every: int, rows_tile: int,
                     kind: str = "resident", monitor=None,
                     monitor_every: int = 0, fault=None,
                     guard: bool = False, mg=None):
    """Classic CG through the padded 2-D Pallas fast path: vectors carry a
    permanent zero halo (no per-iteration pad copy — the naive kernel
    wrapper re-pads x every call, ~17 MB/iter of pure copy at 128³), and
    the SpMV kernel emits p'Ap as a fused per-tile partial (the dot's
    operands are never re-read from HBM).  Falls under the same loop —
    :func:`acg_tpu.solvers.loops.cg_while` — via its ``coupled_step``
    hook, so stopping criteria, breakdown flags and check_every semantics
    are shared, not duplicated."""
    from acg_tpu.ops.pallas_kernels import LANES, padded_halo_rows

    n = b.shape[-1]
    hpad = padded_halo_rows(op.offsets, rows_tile) * LANES
    bands_pad, (bp, xp) = _pad_fused(op, b, x0, rows_tile)
    mv, coupled = _fused_ops(op, bands_pad, rows_tile, kind)
    x, k, rr, dxx, flag, rr0, hist = cg_while(
        mv, batched_dot, bp, xp, stop2, diffstop, maxits, track_diff,
        check_every=check_every, coupled_step=coupled,
        monitor=monitor, monitor_every=monitor_every,
        fault=fault, guard=guard, precond=_precond_fn(mg, hpad))
    return (jax.lax.slice_in_dim(x, hpad, hpad + n, axis=-1),
            k, rr, dxx, flag, rr0, hist)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "track_diff", "check_every",
                                    "rows_tile", "kind", "segment",
                                    "monitor", "monitor_every", "guard"))
def _cg_fused_seg(op, bands_pad, bp, xp, stop2, diffstop, maxits: int,
                  track_diff: bool, check_every: int, rows_tile: int,
                  kind: str, segment: int, monitor=None,
                  monitor_every: int = 0, fault=None, guard: bool = False):
    """First segment of a segmented fused-path solve (operands already
    padded by :func:`_pad_fused`)."""
    mv, coupled = _fused_ops(op, bands_pad, rows_tile, kind)
    return cg_while(mv, batched_dot, bp, xp, stop2, diffstop, maxits,
                    track_diff, check_every=check_every,
                    coupled_step=coupled, segment=segment, want_carry=True,
                    monitor=monitor, monitor_every=monitor_every,
                    fault=fault, guard=guard)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "track_diff", "check_every",
                                    "rows_tile", "kind", "segment",
                                    "monitor", "monitor_every", "guard"))
def _cg_fused_seg_resume(op, bands_pad, bp, carry, stop2, diffstop,
                         maxits: int, track_diff: bool, check_every: int,
                         rows_tile: int, kind: str, segment: int,
                         monitor=None, monitor_every: int = 0,
                         fault=None, guard: bool = False):
    mv, coupled = _fused_ops(op, bands_pad, rows_tile, kind)
    return cg_while(mv, batched_dot, bp, None, stop2, diffstop, maxits,
                    track_diff, check_every=check_every,
                    coupled_step=coupled, segment=segment,
                    carry_in=carry, want_carry=True,
                    monitor=monitor, monitor_every=monitor_every,
                    fault=fault, guard=guard)


def _describe_path(dev, perm, plan, pipe_rt=None,
                   nrhs: int = 1) -> tuple[str, str]:
    """(operator_format, kernel) actually in effect for this solve — the
    observability the reference gets from reporting its chosen SpMV
    algorithm in the driver stats (cuda/acg-cuda.c:329-376).  ``plan`` is
    the fused-plan result governing the in-loop SpMV for DIA operators;
    ``pipe_rt`` non-None means the single-kernel pipelined iteration
    (cg_pipelined_iter_pallas) ran the loop body, which supersedes the
    plan's SpMV tier in the report (kernel "pallas-pipe2d" — round-5
    advisor finding: a pipe2d solve must not claim "pallas-resident").
    Naming shared with the distributed solver via path_names."""
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.ops.sgell import DeviceSgell
    from acg_tpu.ops.stencil import DeviceStencil, stencil_kernel_kind
    from acg_tpu.solvers.base import path_names

    if isinstance(dev, DeviceSgell):
        return path_names("sgell", interpret=dev.interpret,
                          rcm=perm is not None)
    if isinstance(dev, DeviceStencil):
        # the matrix-free tier routes its kernel inside matvec; report
        # the kind the routing gate resolves for this shape
        kind = stencil_kernel_kind(dev.nrows_padded, dev.offsets,
                                   np.dtype(dev.vec_dtype), nrhs=nrhs,
                                   interpret=dev.interpret)
        return path_names("stencil", plan_kind=kind,
                          pipe2d=pipe_rt is not None)
    if isinstance(dev, DeviceDia):
        return path_names("dia", plan_kind=plan[0] if plan else None,
                          rcm=perm is not None,
                          pipe2d=pipe_rt is not None)
    return path_names("ell", rcm=perm is not None)


def _pipe2d_rt(dev, plan) -> int | None:
    """rows_tile for the single-kernel pipelined iteration, or None when
    it does not apply — the single-chip face of the shared gate
    (pallas_kernels.pipe2d_rt_for; the distributed solver calls it with
    its uniform shard length, so selection cannot diverge)."""
    from acg_tpu.ops.pallas_kernels import pipe2d_rt_for

    if plan is None:
        # guard BEFORE building arguments: only DIA devices carry .bands
        # (the distributed twin of this gate crashed on exactly this
        # argument-evaluation hazard — fuzz seed 239)
        return None
    return pipe2d_rt_for(dev.nrows_padded, dev.offsets,
                         np.dtype(dev.vec_dtype), dev.bands.dtype,
                         plan)


def _fused_plan(dev) -> tuple[str, int] | None:
    """(kind, rows_tile) — kind a ``fused_kernels()`` key: "resident" |
    "hbm-ring" | "hbm" — when a padded fused kernel is the right path for
    this operator, else None; the single-chip face of the shared gate
    (acg_tpu/ops/pallas_kernels.py ``fused_plan_for``)."""
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.ops.pallas_kernels import fused_plan_for

    if not isinstance(dev, DeviceDia):
        return None
    return fused_plan_for(dev.nrows_padded, dev.offsets,
                          np.dtype(dev.vec_dtype), dev.bands.dtype)


def _fused_plan_batched(dev, nrhs: int) -> tuple[str, int] | None:
    """Multi-RHS twin of :func:`_fused_plan`: ("resident-batched",
    rows_tile) when the batched padded kernel applies (resident tier
    only — the (B, Rp, 128) x block must fit VMEM; the HBM kinds have no
    batched variant yet), else None.  Shares the gate with
    dia_matvec_best's batched route (pallas_kernels.pallas_2d_batched_plan
    + the "batched2d" probe), so the classic fused loop and the plain
    batched matvec can never pick different kernels."""
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.ops.pallas_kernels import (pallas_2d_batched_plan,
                                            pallas_spmv_available)

    if not isinstance(dev, DeviceDia) or 0 not in dev.offsets:
        return None
    rt = pallas_2d_batched_plan(nrhs, dev.nrows_padded, dev.offsets,
                                np.dtype(dev.vec_dtype), dev.bands.dtype)
    if rt is None or not pallas_spmv_available("batched2d"):
        return None
    return "resident-batched", rt


def _resolve_monitor(options: SolverOptions):
    """The live-progress hook for this solve, or None when disabled.
    Returns the module-level singleton (acg_tpu.obs.monitor.device_monitor)
    so the jit cache key is stable across solves."""
    if options.monitor_every <= 0:
        return None
    from acg_tpu.obs.monitor import device_monitor

    return device_monitor


def _dot2(a1, b1, a2, b2):
    """The pipelined loop's one reduction point: both scalars of a single
    conceptual reduction (distributed variants psum a stacked pair —
    acg_tpu/solvers/cg_dist.py).  Batched operands reduce per system
    (a (B,) pair) — batched_dot is exactly jnp.vdot on 1-D operands."""
    return batched_dot(a1, b1), batched_dot(a2, b2)


@functools.partial(jax.jit, static_argnames=("maxits", "check_every",
                                             "replace_every", "certify",
                                             "monitor", "monitor_every",
                                             "guard"))
def _cg_pipelined_device(op, b, x0, stop2, maxits: int,
                         check_every: int = 1, replace_every: int = 0,
                         certify: bool = True, monitor=None,
                         monitor_every: int = 0, fault=None,
                         guard: bool = False):
    """Pipelined CG; one fused 2-scalar reduction per iteration
    (see acg_tpu/solvers/loops.py for the recurrences)."""
    return cg_pipelined_while(op.matvec, _dot2, b, x0, stop2,
                              maxits, check_every=check_every,
                              replace_every=replace_every, certify=certify,
                              monitor=monitor, monitor_every=monitor_every,
                              fault=fault, guard=guard)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "check_every",
                                    "replace_every", "certify", "segment",
                                    "monitor", "monitor_every", "guard"))
def _cg_pipelined_device_seg(op, b, x0, stop2, maxits: int,
                             check_every: int, replace_every: int,
                             certify: bool, segment: int, monitor=None,
                             monitor_every: int = 0, fault=None,
                             guard: bool = False):
    """First segment of a segmented pipelined solve (the pipelined twin
    of :func:`_cg_device_seg`; wired in PR 7): also returns the loop
    carry (whose last element is the device-computed continue bit)."""
    return cg_pipelined_while(op.matvec, _dot2, b, x0, stop2,
                              maxits, check_every=check_every,
                              replace_every=replace_every, certify=certify,
                              monitor=monitor, monitor_every=monitor_every,
                              fault=fault, guard=guard, segment=segment,
                              want_carry=True)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "check_every",
                                    "replace_every", "certify", "segment",
                                    "monitor", "monitor_every", "guard"))
def _cg_pipelined_device_seg_resume(op, b, carry, stop2, maxits: int,
                                    check_every: int, replace_every: int,
                                    certify: bool, segment: int,
                                    monitor=None, monitor_every: int = 0,
                                    fault=None, guard: bool = False):
    return cg_pipelined_while(op.matvec, _dot2, b, None, stop2,
                              maxits, check_every=check_every,
                              replace_every=replace_every, certify=certify,
                              monitor=monitor, monitor_every=monitor_every,
                              fault=fault, guard=guard, segment=segment,
                              carry_in=carry, want_carry=True)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "check_every",
                                    "replace_every", "rows_tile", "kind",
                                    "certify", "pipe_rt", "segment",
                                    "monitor", "monitor_every", "guard"))
def _cg_pipelined_fused_seg(op, bands_pad, bp, xp, stop2, maxits: int,
                            check_every: int, replace_every: int,
                            rows_tile: int, kind: str, certify: bool,
                            pipe_rt: int | None, segment: int,
                            monitor=None, monitor_every: int = 0,
                            fault=None, guard: bool = False):
    """First segment of a segmented fused-path pipelined solve (operands
    already padded by :func:`_pad_fused`); x comes back PADDED — the
    caller slices once after the segment loop, like classic."""
    mv, iter_step = _pipelined_fused_parts(op, bands_pad, rows_tile, kind,
                                           pipe_rt)
    return cg_pipelined_while(mv, _dot2, bp, xp, stop2, maxits,
                              check_every=check_every,
                              replace_every=replace_every, certify=certify,
                              iter_step=iter_step, monitor=monitor,
                              monitor_every=monitor_every, fault=fault,
                              guard=guard, segment=segment,
                              want_carry=True)


@functools.partial(jax.jit,
                   static_argnames=("maxits", "check_every",
                                    "replace_every", "rows_tile", "kind",
                                    "certify", "pipe_rt", "segment",
                                    "monitor", "monitor_every", "guard"))
def _cg_pipelined_fused_seg_resume(op, bands_pad, bp, carry, stop2,
                                   maxits: int, check_every: int,
                                   replace_every: int, rows_tile: int,
                                   kind: str, certify: bool,
                                   pipe_rt: int | None, segment: int,
                                   monitor=None, monitor_every: int = 0,
                                   fault=None, guard: bool = False):
    mv, iter_step = _pipelined_fused_parts(op, bands_pad, rows_tile, kind,
                                           pipe_rt)
    return cg_pipelined_while(mv, _dot2, bp, None, stop2, maxits,
                              check_every=check_every,
                              replace_every=replace_every, certify=certify,
                              iter_step=iter_step, monitor=monitor,
                              monitor_every=monitor_every, fault=fault,
                              guard=guard, segment=segment, carry_in=carry,
                              want_carry=True)


def _pipelined_fused_parts(op, bands_pad, rows_tile: int, kind: str,
                           pipe_rt: int | None):
    """(matvec, iter_step-or-None) over the padded fused layout — the
    shared construction of :func:`_cg_pipelined_device_fused` and its
    segmented twins."""
    mv, _ = _fused_ops(op, bands_pad, rows_tile, kind)
    iter_step = None
    if pipe_rt is not None:
        from acg_tpu.ops.pallas_kernels import cg_pipelined_iter_pallas

        offsets, sc = op.offsets, op.scales

        def iter_step(z, r, p, w, s, x, alpha, beta):
            return cg_pipelined_iter_pallas(
                bands_pad, offsets, w, z, r, p, s, x, alpha, beta,
                rows_tile=pipe_rt, scales=sc)

    return mv, iter_step


@functools.partial(jax.jit,
                   static_argnames=("maxits", "check_every",
                                    "replace_every", "rows_tile", "kind",
                                    "certify", "pipe_rt", "monitor",
                                    "monitor_every", "guard"))
def _cg_pipelined_device_fused(op, b, x0, stop2, maxits: int,
                               check_every: int, replace_every: int,
                               rows_tile: int, kind: str,
                               certify: bool = True,
                               pipe_rt: int | None = None,
                               monitor=None, monitor_every: int = 0,
                               fault=None, guard: bool = False):
    """Pipelined CG with the SpMV through the padded Pallas kernel: all
    vectors carry the permanent zero halo (no per-call pad copies), the
    7-stream fused update runs over the padded layout (halo zeros are
    preserved by every linear update), and dots ignore the zero halo by
    construction.  The pipelined recurrences have no <p, Ap>-shaped
    reduction, so only the matvec (not the fused dot) comes from the
    kernel."""
    from acg_tpu.ops.pallas_kernels import LANES, padded_halo_rows

    n = b.shape[-1]
    hpad = padded_halo_rows(op.offsets, rows_tile) * LANES
    bands_pad, (bp, xp) = _pad_fused(op, b, x0, rows_tile)
    # pipe_rt selects the single-kernel pipelined iteration: q never
    # round-trips HBM, w is read once, the dots ride the update pass
    # (see cg_pipelined_iter_pallas) — the minimal 13-stream
    # formulation.  It is decided OUTSIDE jit (probe + its own VMEM
    # plan, _pipe2d_rt) and is part of this function's static cache key,
    # so a probe flip can never be masked by a stale executable.
    mv, iter_step = _pipelined_fused_parts(op, bands_pad, rows_tile,
                                           kind, pipe_rt)
    x, k, rr, flag, rr0, hist = cg_pipelined_while(
        mv, _dot2, bp, xp, stop2, maxits, check_every=check_every,
        replace_every=replace_every, certify=certify, iter_step=iter_step,
        monitor=monitor, monitor_every=monitor_every,
        fault=fault, guard=guard)
    return (jax.lax.slice_in_dim(x, hpad, hpad + n, axis=-1),
            k, rr, flag, rr0, hist)


def _stencil_pipe_rt(dev, fault) -> int | None:
    """rows_tile for the MATRIX-FREE single-kernel pipelined iteration
    (acg_tpu/ops/stencil.py ``cg_pipelined_iter_stencil``), or None —
    the stencil twin of :func:`_pipe2d_rt`, gated in the same order
    (injection → probe → VMEM plan) so the
    disengagement note names the first condition that bit."""
    from acg_tpu.ops.stencil import (DeviceStencil, stencil_available,
                                     stencil_pipe_plan)

    if not isinstance(dev, DeviceStencil):
        return None
    if fault is not None:
        return None
    if not (dev.interpret or stencil_available("stpipe2d")):
        return None
    return stencil_pipe_plan(dev.nrows_padded, dev.offsets,
                             np.dtype(dev.vec_dtype))


@functools.partial(jax.jit,
                   static_argnames=("maxits", "check_every", "certify",
                                     "pipe_rt", "monitor", "monitor_every",
                                    "guard", "replace_every"))
def _cg_pipelined_stencil_fused(op, b, x0, stop2, maxits: int,
                                check_every: int, certify: bool,
                                pipe_rt: int, replace_every=None,
                                monitor=None,
                                monitor_every: int = 0, fault=None,
                                guard: bool = False):
    """Pipelined CG with the WHOLE iteration in the matrix-free stencil
    mega-kernel: vectors carry the permanent zero halo of the padded
    layout (pad once, never per iteration), the iteration's only HBM
    traffic is the 11 vector tile streams — the band stream does not
    exist.  Prelude/certification matvecs run the jnp grid-shift form on
    the padded layout (linear, zero-halo-preserving)."""
    from acg_tpu.ops.pallas_kernels import pad_dia_vectors
    from acg_tpu.ops.stencil import (cg_pipelined_iter_stencil,
                                     stencil_matvec)

    # ``fault`` exists only for AOT call-signature compatibility with
    # the other pipelined programs (aot_step dispatches fault=None into
    # every compiled pipelined step); the _stencil_pipe_rt gate routes
    # every injection solve to the open-coded body, so a real plan here
    # is a wiring bug — refuse at trace time rather than ignore it
    if fault is not None:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "the matrix-free pipelined mega-kernel exposes "
                       "no injection sites (gate: _stencil_pipe_rt)")
    n = b.shape[-1]
    grid, offsets = op.grid, op.offsets
    digits, coeffs, interp = op.digits, op.coeffs, op.interpret
    (bp, xp), front = pad_dia_vectors((b, x0), n, pipe_rt, offsets)

    def mv(v):
        core = jax.lax.slice_in_dim(v, front, front + n, axis=-1)
        y = stencil_matvec(core, grid, digits, coeffs)
        return jnp.pad(y, [(front, v.shape[-1] - front - n)])

    def iter_step(z, r, p, w, s, x, alpha, beta):
        return cg_pipelined_iter_stencil(
            grid, offsets, digits, coeffs, w, z, r, p, s, x, alpha,
            beta, rows_tile=pipe_rt, n=op.nrows, interpret=interp)

    x, k, rr, flag, rr0, hist = cg_pipelined_while(
        mv, _dot2, bp, xp, stop2, maxits, check_every=check_every,
        replace_every=replace_every, certify=certify, iter_step=iter_step,
        monitor=monitor, monitor_every=monitor_every, guard=guard)
    return (jax.lax.slice_in_dim(x, front, front + n, axis=-1),
            k, rr, flag, rr0, hist)


def _cheb_leja_nodes(s: int) -> np.ndarray:
    """Leja-ordered Chebyshev nodes of (0, 1) — scaled by the estimated
    λmax they seed the FIRST s-step block's Newton shifts (blocks after
    that use on-the-fly Ritz estimates, loops.cg_sstep_while).  Leja
    order is scale-invariant, so the host orders the unit nodes once
    and the device only scales them.  This is deliberately a HOST
    (NumPy) twin of loops._leja_order: it runs inside jit TRACING
    (where jnp ops would produce tracers np.asarray cannot consume), so
    the two greedy implementations cannot be merged — keep their
    semantics in sync."""
    j = np.arange(s)
    v = 0.5 * (1.0 + np.cos((2 * j + 1) * np.pi / (2 * s)))
    order = [int(np.argmax(np.abs(v)))]
    for _ in range(s - 1):
        prod = np.ones(s)
        for i in order:
            prod *= np.abs(v - v[i])
        prod[order] = -1.0
        order.append(int(np.argmax(prod)))
    return v[order]


def _sstep_block_fn(mv, b, s: int, batched: bool):
    """The single-chip s-step basis builder (loops.cg_sstep_while
    ``block_fn``): residual replacement r = b - Ax, the Newton-shifted
    P/R Krylov blocks through the operator's own SpMV tier, and the
    Gram matrix as ONE tall-skinny MXU matmul (ops/blas1.py gram).
    The loop runs it under acg.blas1; the operator applications are
    acg.spmv."""
    bc = (lambda v: v[:, None]) if batched else (lambda v: v)

    def spmv(v):
        with jax.named_scope("acg.spmv"):
            return mv(v)

    def block_fn(x, p, shifts):
        r = b - spmv(x)
        basis = [p]
        for j in range(s):
            v = basis[-1]
            basis.append(spmv(v) - bc(shifts[..., j]) * v)
        basis.append(r)
        for j in range(s - 1):
            v = basis[-1]
            basis.append(spmv(v) - bc(shifts[..., j]) * v)
        V = jnp.stack(basis)          # (2s+1, [B,] n)
        return V, gram(V)

    return block_fn


def _power_lmax(mv, dot, b, iters: int = 6):
    """Crude largest-eigenvalue estimate by power iteration from b (6
    operator applications in the compiled prelude — outside the hot
    loop, so the per-iteration collective audit is untouched).  Scales
    the Chebyshev shift seeds; accuracy is uncritical (Ritz refinement
    replaces the shifts after the first block)."""
    v = b
    lam = jnp.zeros(b.shape[:-1], b.dtype)
    for _ in range(iters):
        nv = jnp.sqrt(dot(v, v))
        v = v / jnp.where(nv == 0.0, 1.0, nv)[..., None] \
            if v.ndim == 2 else v / jnp.where(nv == 0.0, 1.0, nv)
        v = mv(v)
        lam = jnp.sqrt(dot(v, v))
    return lam


@functools.partial(jax.jit,
                   static_argnames=("s", "maxits", "monitor",
                                    "monitor_every"))
def _cg_sstep_device(op, b, x0, stop2, s: int, maxits: int,
                     monitor=None, monitor_every: int = 0,
                     shifts0=None):
    """s-step CG on one chip: the whole solve — basis builds, Gram
    matmuls, coefficient recurrences, final true-residual certification
    — is one jitted program (see loops.cg_sstep_while).  Returns
    (x, kiter, rr_true, flag, rr0, hist, shifts); ``rr_true`` is
    certified (a fresh b - Ax reduction after the loop), never a
    recurred estimate; ``shifts`` is the loop's FINAL Ritz-refined
    Leja-ordered shift schedule — the spectral-recycling output a later
    solve against the same operator can feed back as ``shifts0``
    (skipping the power/Chebyshev seeding prelude entirely, ISSUE 20)."""
    mv = op.matvec
    batched = b.ndim == 2
    block_fn = _sstep_block_fn(mv, b, s, batched)
    r0 = b - mv(x0)
    rr0 = batched_dot(r0, r0)
    if shifts0 is None:
        lam = _power_lmax(mv, batched_dot, b)
        nodes = jnp.asarray(_cheb_leja_nodes(s), b.dtype)
        shifts0 = lam[..., None] * nodes
    x, kiter, rr, flag, hist, shifts = cg_sstep_while(
        block_fn, b, x0, r0, rr0, shifts0, stop2, s, maxits,
        monitor=monitor, monitor_every=monitor_every)
    # certify EVERY exit against the true residual (the maxits door and
    # the estimate-paused stragglers included): one fresh reduction
    rT = b - mv(x)
    rrT = batched_dot(rT, rT)
    flag, hist = _sstep_certify(rrT, kiter, flag, hist, stop2, rr0,
                                batched)
    return x, kiter, rrT, flag, rr0, hist, shifts


def _sstep_certify(rrT, kiter, flag, hist, stop2, rr0, batched: bool):
    """Shared s-step exit certification (single-chip and distributed):
    the freshly reduced true |r|² decides convergence, and each system's
    last history sample becomes that certified value."""
    atol2, rtol2 = stop2
    thresh2 = jnp.maximum(atol2, rtol2 * rr0)
    any_crit = (atol2 > 0.0) | (rtol2 > 0.0)
    met = (rrT < thresh2) | (any_crit & (rrT == 0.0))
    # certification is BIdirectional: a block-boundary _CONVERGED whose
    # freshly reduced true residual lands above the threshold (the Gram
    # diagonal and b - Ax round differently) is demoted — the solve
    # reports honest non-convergence rather than success above tolerance
    flag = jnp.where(met, _CONVERGED,
                     jnp.where(flag == _CONVERGED, _OK,
                               flag)).astype(jnp.int32)
    if batched:
        hist = hist.at[jnp.arange(rrT.shape[0]), kiter].set(rrT)
    else:
        hist = hist.at[kiter].set(rrT)
    return flag, hist


def _sstep_validate(o: SolverOptions, fault) -> int:
    """The shared rejection set of the s-step wrappers (single-chip and
    distributed): returns the validated block size."""
    if o.sstep < 2:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       "cg_sstep requires SolverOptions.sstep >= 2 "
                       "(the s-step block size; --sstep on the CLI)")
    if fault is not None:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "fault injection has no sites in the s-step "
                       "coefficient recurrences; inject into the "
                       "classic or pipelined solvers")
    if o.diffatol > 0 or o.diffrtol > 0:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "s-step CG supports residual-based stopping only")
    if o.segment_iters > 0:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "segment_iters is supported by the classic and "
                       "pipelined solvers (the s-step outer carry is "
                       "not segmented; its blocks already bound device "
                       "time per dispatch at maxits*s granularity)")
    return o.sstep


def _sstep_fallback_stop(o: SolverOptions, rr0):
    """The classic-CG fallback's ``atol2_floor``: each system's ORIGINAL
    squared threshold max(atol², rtol²·|r0|²).  The fallback's rtol is
    relative to its OWN starting residual — never looser than the
    user's contract, because _sstep_fallback_x0 guarantees that start
    is either the original x0 (the genuine |r0|²) or an iterate whose
    certified residual is <= |r0|² — but it can be arbitrarily TIGHTER
    (a nearly-converged kept iterate), so the original threshold is
    restored as a per-system absolute floor: a batch of mixed scales
    keeps every system's own criterion exactly (a scalar options field
    could only carry the batch min, over-tightening the rest)."""
    rr0_h = np.asarray(jax.device_get(rr0), dtype=np.float64)
    return np.maximum(o.residual_atol ** 2,
                      o.residual_rtol ** 2 * rr0_h)


def _sstep_fallback_x0(x_part, x0, rrT, rr0):
    """Fallback starting iterate: keep each system's s-step iterate only
    where its CERTIFIED true residual is no worse than the original
    |r0|².  The loop's divergence guard bounds the poison only at block
    boundaries — one bad block can still overflow x — and a poisoned
    start drives the classic recurrence's residual away from the truth,
    letting it exit wrong.  Systems whose progress is discarded restart
    from the user's x0 (zeros when None)."""
    rrT_h = np.atleast_1d(np.asarray(jax.device_get(rrT), np.float64))
    rr0_h = np.atleast_1d(np.asarray(jax.device_get(rr0), np.float64))
    keep = np.isfinite(rrT_h) & (rrT_h <= rr0_h)
    if np.all(keep):
        return x_part
    xp = np.asarray(x_part, dtype=np.float64)
    if xp.ndim == 2:
        x0o = (np.zeros_like(xp) if x0 is None
               else np.broadcast_to(
                   np.asarray(x0, dtype=np.float64), xp.shape))
        return np.where(keep[:, None], xp, x0o)
    if keep[0]:
        return xp
    return np.zeros_like(xp) if x0 is None else np.asarray(
        x0, dtype=np.float64)


def _sstep_fallback(solve_classic, k_done, ksys, s: int, why: str,
                    spent_flops: int = 0, label: str | None = None):
    """Run the classic-CG fallback after an indefinite/non-finite Gram
    (ISSUE 7: never silently wrong) and fold the s-step iterations
    already spent into the returned accounting.  ``solve_classic`` is a
    thunk running classic CG from the s-step loop's last good iterate;
    ``ksys`` the per-system s-step iteration counts (or None);
    ``spent_flops`` the s-step work already performed (priced by
    cg_flops_per_iter(sstep=s), so stats don't undercount the spent
    blocks).  ``label`` overrides the solver name in the note (the
    deep-pipelined wrapper reuses this fallback discipline)."""
    note = (f"{label or f'cg-sstep(s={s})'} fell back to classic cg "
            f"after {k_done} iteration(s): {why}")

    def _fold(res):
        res.kernel_note = (res.kernel_note + "; " + note
                           if res.kernel_note else note)
        if ksys is not None and res.iterations_per_system is not None:
            res.iterations_per_system = (
                np.asarray(res.iterations_per_system) + ksys)
            # the batch summary is the max over PER-SYSTEM totals:
            # adding the max s-step count to the max classic count
            # would pair different systems and overstate
            folded = int(np.max(res.iterations_per_system))
        else:
            folded = res.niterations + int(k_done)
        delta = folded - res.niterations
        res.niterations = folded
        if res.stats is not None:
            res.stats.niterations += delta
            res.stats.ntotaliterations += delta
            res.stats.nflops += int(spent_flops)
        return res

    try:
        return _fold(solve_classic())
    except AcgError as e:
        if getattr(e, "result", None) is not None:
            _fold(e.result)
        raise


@_traced_call("cg-sstep")
def cg_sstep(A, b, x0=None, options: SolverOptions = SolverOptions(),
             dtype=None, fmt: str = "auto", mat_dtype="auto",
             stats: SolveStats | None = None, fault=None,
             shifts0=None, recycle=None) -> SolveResult:
    """s-step (communication-reduced) CG on one chip: one Gram reduction
    per ``options.sstep`` iterations, the basis products on the MXU
    (arXiv:2501.03743; the loop contract is loops.cg_sstep_while).

    On a single chip the reduction count is a latency detail — the point
    here is numerical parity and the shared loop the distributed solver
    (cg_dist.cg_sstep_dist) reuses, where one Gram psum per s iterations
    IS the strong-scaling lever.  Residual replacement every block and
    true-residual certification of every exit are built in; an
    indefinite/non-finite Gram falls back to classic CG from the last
    good iterate, surfaced via ``SolveResult.kernel_note``.

    ``shifts0`` (optional, shape ``(s,)`` or ``(B, s)``) overrides the
    power-iteration/Chebyshev Newton-shift seeds.  ``recycle`` is an
    optional :class:`~acg_tpu.serve.session.RecycleState`: when it
    holds a refined schedule for this block size the solve starts from
    it instead of re-running the seeding prelude, and every solve
    writes its final Ritz-refined schedule back (spectral recycling,
    ISSUE 20 — the certification above makes a stale schedule a
    performance question, never a correctness one)."""
    o = options
    require_unpreconditioned(o, "cg-sstep")
    s = _sstep_validate(o, fault)
    if shifts0 is None and recycle is not None:
        shifts0 = recycle.get_shifts(s)
    with span("acg.call.prepare"):
        dev, b_pad, x0_pad, perm = _prepare(A, b, x0, dtype, fmt, mat_dtype)
        batched = b_pad.ndim == 2
        vdt = b_pad.dtype
        stop2 = (jnp.asarray(o.residual_atol ** 2, vdt),
                 jnp.asarray(o.residual_rtol ** 2, vdt))
        bnrm2 = jnp.linalg.norm(b_pad, axis=-1) if batched \
            else jnp.linalg.norm(b_pad)
        jax.block_until_ready(bnrm2)
        monitor = _resolve_monitor(o)
        if shifts0 is not None:
            shifts0 = jnp.asarray(shifts0, vdt)
            if batched and shifts0.ndim == 1:
                # the loop carries PER-SYSTEM shifts (Ritz refinement is
                # per system): a shared (s,) seed tiles to (B, s)
                shifts0 = jnp.tile(shifts0, (b_pad.shape[0], 1))
    with span("acg.call.dispatch"):
        t0 = time.perf_counter()
        x, k, rr, flag, rr0, hist, shifts_out = _cg_sstep_device(
            dev, b_pad, x0_pad, stop2, s=s, maxits=o.maxits,
            monitor=monitor, monitor_every=o.monitor_every, shifts0=shifts0)
    k = _device_wait(x, k)
    tsolve = time.perf_counter() - t0
    flags = np.atleast_1d(np.asarray(jax.device_get(flag)))
    if recycle is not None and np.any(flags == _CONVERGED):
        # persist the refined schedule for the NEXT solve against this
        # operator (put_shifts validates finiteness/positivity; a
        # non-converged solve's schedule is not worth keeping)
        recycle.put_shifts(s, np.asarray(jax.device_get(shifts_out)))
    if np.any(flags == _GRAM_BAD):
        # indefinite/non-finite Gram: classic CG re-solves from the last
        # good iterate (and re-diagnoses — a truly indefinite operator
        # surfaces as ERR_NOT_CONVERGED_INDEFINITE_MATRIX there)
        ksys = np.asarray(k) if batched else None
        k_done = int(np.max(k))
        x_part = _unpermute(x, dev.nrows, perm)
        if x_part is None:
            x_part = np.asarray(x)[..., : dev.nrows]
        x_part = _sstep_fallback_x0(x_part, x0, rr, rr0)
        o2 = dataclasses.replace(o, sstep=0,
                                 maxits=max(o.maxits - k_done, 0))
        floor = _sstep_fallback_stop(o, rr0)
        return _sstep_fallback(
            lambda: cg(A, b, x0=x_part, options=o2, dtype=dtype, fmt=fmt,
                       mat_dtype=mat_dtype, stats=stats,
                       atol2_floor=floor),
            k_done, ksys, s, "indefinite/non-finite Gram matrix",
            spent_flops=k_done * cg_flops_per_iter(dev.nnz, dev.nrows,
                                                   sstep=s))
    from acg_tpu.solvers.base import kernel_disengagement_note
    note = kernel_disengagement_note(False, None, None, None,
                                     forced_fmt=fmt)
    return _finish(dev, x, k, rr, flag, rr0, o, tsolve, pipelined=False,
                   bnrm2=bnrm2, stats=stats,
                   to_host=_host_solution(dev.nrows, perm),
                   path=_describe_path(dev, perm, None) + (note,),
                   hist=hist, sstep=s)


class PermutedOperator:
    """Device operator applied in a permuted row/column ordering.

    ``dev`` acts on vectors in the permuted space; ``perm`` maps original
    indices to permuted positions (v_perm = v[perm]).  The solvers permute
    b/x0 on entry and un-permute the solution on exit, so callers never
    see the reordering — the same transparency the reference gets from
    partition-local numbering plus gather/scatter at the boundaries
    (acg/graph.c:813+ reordered node numbering).
    """

    def __init__(self, dev, perm: np.ndarray):
        self.dev = dev
        self.perm = np.asarray(perm)

    def __getattr__(self, name):
        return getattr(self.dev, name)


def _prefer_stencil(st, mat_dtype, nrhs: int) -> bool:
    """Auto takes the recognized stencil ``st`` per
    :func:`acg_tpu.ops.stencil.prefer_stencil`."""
    from acg_tpu.ops.stencil import prefer_stencil

    return st is not None and prefer_stencil(
        st.nrows_padded, st.offsets, st.coeffs, st.vec_dtype, mat_dtype,
        nrhs)


def _dia_keeping_stencil(D, st, dtype, mat_dtype):
    """The stored-band operator of ``D``, carrying the stencil that
    ``st`` (a DeviceStencil, or None) recognized in it."""
    from acg_tpu.ops.dia import DeviceDia

    dev = DeviceDia.from_dia(D, dtype=dtype, mat_dtype=mat_dtype)
    return dev if st is None else dataclasses.replace(dev, stencil=st.spec)


def _mg_of(dev, o: SolverOptions, batched: bool = False):
    """The V-cycle plan (acg_tpu/ops/mg.py) of a classic CG solve on
    ``dev`` under ``o``, None without a preconditioner, or
    ERR_NOT_SUPPORTED."""
    if o.precond == "none":
        return None
    from acg_tpu.ops.mg import mg_plan
    from acg_tpu.ops.stencil import DeviceStencil

    if batched:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "precond='mg' solves one right-hand side at a "
                       "time, not a batch")
    if o.segment_iters > 0:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "precond='mg' runs as one program "
                       "(segment_iters=0)")
    spec = (dev.spec if isinstance(dev, DeviceStencil)
            else getattr(dev, "stencil", None))
    with span("acg.prep.mg"):
        return mg_plan(spec, o.mg_levels)


def _precond_fn(mg, front: int = 0):
    """z = M r over the loop's flat vectors, or None."""
    if mg is None:
        return None
    from acg_tpu.ops.mg import vcycle_flat

    return vcycle_flat(mg, front)


def build_device_operator(A, dtype=None, fmt: str = "auto",
                          mat_dtype="auto", nrhs: int = 1):
    """Build the device operator (the upload half of solver init, reference
    acg/cgcuda.c:138-328).  ``fmt``: "auto" picks DIA (gather-free
    shifted-multiply SpMV, acg_tpu/ops/dia.py) when the diagonal fill is
    dense enough, else padded-ELL gather form; or force "ell"/"dia".

    ``mat_dtype`` controls operator *storage* precision (compute stays at
    the vector dtype): "auto" stores bfloat16 when the narrowing is exact
    (integer/dyadic stencil coefficients — bit-identical results, half the
    dominant HBM stream), a concrete dtype forces mixed-precision-CG
    storage, None stores at the vector dtype.

    Note the TPU-specific cliff behind fmt="auto": arbitrary gathers run at
    ~10 GB/s effective on TPU (measured; two orders below HBM bandwidth),
    so the gather-free DIA form wins whenever the matrix has enough
    diagonal structure — see acg_tpu/ops/dia.py."""
    from acg_tpu.config import ensure_x64_for
    from acg_tpu.ops.dia import DeviceDia, DiaMatrix, dia_efficiency
    from acg_tpu.sparse.csr import CsrMatrix

    from acg_tpu.ops.sgell import DeviceSgell
    from acg_tpu.ops.stencil import (DeviceStencil, stencil_available,
                                     try_device_stencil)

    if isinstance(A, (DeviceEll, DeviceDia, DeviceSgell, DeviceStencil,
                      PermutedOperator)):
        return A
    # the tier choice, any host format conversion, and the device copy
    with span("acg.prep.upload"):
        host_vals = getattr(A, "vals", getattr(A, "bands", None))
        if dtype is not None:
            ensure_x64_for(np.dtype(dtype))
        elif host_vals is not None:
            ensure_x64_for(host_vals.dtype)
        if isinstance(A, EllMatrix):
            return DeviceEll.from_ell(A, dtype=dtype, mat_dtype=mat_dtype)
        if fmt == "stencil" and isinstance(A, (DiaMatrix, CsrMatrix)):
            # forced matrix-free tier: recognize or ERROR (never a silent
            # fallback — what a benchmark measures is what it asked for);
            # the Pallas kernel inside is still probe-gated, the jnp
            # grid-shift formulation is the everywhere-fallback
            vdt = (np.dtype(dtype) if dtype is not None
                   else np.dtype(host_vals.dtype))
            return DeviceStencil.from_matrix(A, dtype=vdt)
        if isinstance(A, DiaMatrix):
            st, _rep = try_device_stencil(A, dtype=dtype)
            if fmt == "auto" and stencil_available():
                # the matrix-free tier outranks every stored tier when the
                # system IS a verified constant-coefficient stencil and the
                # kernel probe is green (ROADMAP item 2): zero operator
                # stream, no band storage.  Probe-gated like every tier —
                # off-TPU the stored ladder below is unchanged.
                if _prefer_stencil(st, mat_dtype, nrhs):
                    return st
            return _dia_keeping_stencil(A, st, dtype, mat_dtype)
        if isinstance(A, CsrMatrix):
            if fmt not in ("auto", "dia", "ell", "sgell", "stencil"):
                raise AcgError(Status.ERR_INVALID_VALUE,
                               f"unknown operator format {fmt!r} "
                               "(auto|dia|ell|sgell|stencil)")
            if fmt == "auto" and stencil_available():
                vdt = (np.dtype(dtype) if dtype is not None
                       else np.dtype(A.vals.dtype))
                st, _rep = try_device_stencil(A, dtype=vdt)
                if _prefer_stencil(st, mat_dtype, nrhs):
                    return st
            if fmt == "sgell":
                # Forced tier (the reference's explicit SpMV-algorithm
                # selection, cuda/acg-cuda.c:329-376 --cusparse-spmv-alg):
                # build the segmented-gather operator or ERROR — never a
                # silent fallback, so what a benchmark measures is what it
                # asked for.  The fill gate is lifted (min_fill=0): auto
                # applies the break-even economics; a forced tier is for
                # measuring them.
                from acg_tpu.ops.sgell import (build_device_sgell,
                                               sgell_require_available)

                vdt = np.dtype(dtype) if dtype is not None else A.vals.dtype
                sgell_require_available(vdt)
                sg = build_device_sgell(A, dtype=dtype, mat_dtype=mat_dtype,
                                        min_fill=0.0)
                if sg is None:
                    raise AcgError(Status.ERR_NOT_SUPPORTED,
                                   "format 'sgell' forced but the matrix did "
                                   "not pack (degenerate geometry, or more "
                                   "slots than the kernel's SMEM tables hold)")
                return sg
            if fmt == "auto":
                if dia_efficiency(A) >= 0.25:
                    fmt = "dia"
                else:
                    # bandwidth reduction before giving up on the gather-free
                    # form: RCM often recovers a banded structure from a
                    # scattered ordering (acg_tpu/sparse/rcm.py) — gathers on
                    # TPU run two orders below HBM bandwidth, so a permuted
                    # DIA operator beats ELL whenever RCM succeeds
                    from acg_tpu.sparse.rcm import permute_symmetric, rcm_order

                    perm = rcm_order(A)
                    Ap = permute_symmetric(A, perm)
                    if dia_efficiency(Ap) >= 0.25:
                        dev = DeviceDia.from_dia(DiaMatrix.from_csr(Ap),
                                                 dtype=dtype,
                                                 mat_dtype=mat_dtype)
                        return PermutedOperator(dev, perm)
                    # RCM could not recover a band, but its bandwidth
                    # reduction is exactly what the sgell pack feeds on
                    # (locality => few x segments per 128-row group): try the
                    # sgell tier on the PERMUTED matrix first — the
                    # SuiteSparse-class answer for FEM meshes delivered in
                    # arbitrary orderings
                    from acg_tpu.ops.sgell import build_device_sgell

                    sg = build_device_sgell(Ap, dtype=dtype,
                                            mat_dtype=mat_dtype)
                    if sg is not None:
                        return PermutedOperator(sg, perm)
                    # the permuted ordering has equal-or-better locality, so
                    # a failed pack on Ap decides the sgell question for the
                    # original ordering too — fall through to the XLA gather
                    # ELL tier (the role of the reference's merge-path CSR
                    # kernel, acg/cg-kernels-cuda.cu:340-441, when neither
                    # DIA recovery nor segment packing applies)
                    fmt = "ell"
            if fmt == "dia":
                D = DiaMatrix.from_csr(A)
                return _dia_keeping_stencil(
                    D, try_device_stencil(D, dtype=dtype)[0], dtype,
                    mat_dtype)
            # an explicitly forced fmt="ell" keeps its documented contract and
            # pins the XLA gather form (the A/B baseline)
            return DeviceEll.from_ell(EllMatrix.from_csr(A), dtype=dtype,
                                      mat_dtype=mat_dtype)
        raise AcgError(Status.ERR_INVALID_VALUE,
                       f"unsupported operator type {type(A).__name__}")


def _prepare(A, b, x0, dtype, fmt: str = "auto", mat_dtype="auto"):
    """Returns (dev, b_pad, x0_pad, perm).  When fmt="auto" routed through
    RCM, ``dev`` acts in the permuted ordering: b/x0 are permuted here and
    the solvers un-permute x on exit (``perm`` is new_to_old; see
    PermutedOperator).  A 2-D ``b`` of shape (B, n) selects the multi-RHS
    path: b_pad/x0_pad come back (B, nrp)."""
    dev = build_device_operator(A, dtype=dtype, fmt=fmt, mat_dtype=mat_dtype,
                                nrhs=np.shape(b)[0] if np.ndim(b) == 2 else 1)
    perm = None
    if isinstance(dev, PermutedOperator):
        perm, dev = dev.perm, dev.dev
    vdt = np.dtype(getattr(dev, "vec_dtype", "float32"))
    nrp = dev.nrows_padded

    def to_dev(v):
        # device-resident vectors of the right shape/dtype pass through
        # untouched — no download/re-upload round trip (the reference
        # likewise uploads b once at init, acg/cgcuda.c:259-328)
        if perm is not None:
            v = np.asarray(v, dtype=vdt)[..., perm]
        elif (isinstance(v, jax.Array) and v.ndim in (1, 2)
                and v.shape[-1] == nrp and v.dtype == vdt):
            return v
        return jnp.asarray(pad_vector(np.asarray(v, dtype=vdt), nrp))

    b_pad = to_dev(b)
    x0_pad = (jnp.zeros(b_pad.shape[:-1] + (nrp,), dtype=vdt)
              if x0 is None else to_dev(x0))
    # the shared multi-RHS x0 shape contract (base.conform_x0_batch):
    # broadcast a 1-D x0 across the batch, reject any other mismatch
    from acg_tpu.solvers.base import conform_x0_batch

    x0_pad = conform_x0_batch(
        x0_pad, b_pad.shape,
        lambda v: jnp.tile(v[None, :], (b_pad.shape[0], 1)))
    return dev, b_pad, x0_pad, perm


def _host_solution(nrows: int, perm):
    """``x -> host solution`` for a padded device ``x``: un-permuted by
    ``perm`` (new_to_old), else sliced to the ``nrows`` real rows."""
    def to_host(x):
        x_host = _unpermute(x, nrows, perm)
        return np.asarray(x)[..., :nrows] if x_host is None else x_host
    return to_host


def _unpermute(x, nrows: int, perm):
    """Host solution in the caller's original ordering (perm is new_to_old:
    x_orig[perm] = x_permuted).  Batched x un-permutes every system."""
    if perm is None:
        return None  # _finish slices the padded device vector itself
    xp = np.asarray(x)[..., :nrows]
    x_host = np.empty_like(xp)
    x_host[..., perm] = xp
    return x_host


def _finish(A, x, k, rr, flag, rr0, options, tsolve, pipelined, bnrm2,
            dxx=None, stats=None, path=("", ""), hist=None,
            sstep: int = 0, solver: str | None = None, *, to_host,
            precond: str = ""):
    """Assemble the SolveResult.  ``tsolve`` is the measured device-solve
    time (timer around the compiled loop only, matching the reference's
    tsolve which excludes the solution copyback, acg/cgcuda.c:1022-1107).
    All device scalars are fetched in ONE transfer: every device round-trip
    costs latency, the TPU analog of
    the reference batching its D2H copies on a dedicated copystream
    (acg/cgcuda.c:946-951).  ``hist`` is the on-device residual-norm²
    history buffer (rides the same batched fetch; trimmed to the k+1
    live entries here).  ``to_host(x)`` is the caller's host solution
    (:func:`_host_solution` for a padded single-chip ``x``)."""
    has_dxx = dxx is not None
    has_hist = hist is not None
    with span("acg.call.fetch"):
        k, flag, rr, rr0, bnrm2, dxx, hist = jax.device_get(
            (k, flag, rr, rr0, bnrm2, dxx if has_dxx else rr,
             hist if has_hist else rr))
    with span("acg.call.copyback"):
        x_host = to_host(x)
    # SolveResult assembly, the finiteness check, the metrics hook and
    # the status classification
    with span("acg.call.finish"):
        batched = np.ndim(k) == 1
        if batched:
            # per-system arrays; the scalar norms below summarize the WORST
            # system BY RELATIVE RESIDUAL — rnrm2 and r0nrm2 must come from
            # the SAME system or relative_residual pairs one system's
            # residual with another's r0 (review finding: a converged
            # huge-|r0| system could mask a stalled unit-scale one by an
            # arbitrary factor)
            ksys = np.asarray(k, dtype=np.int64)
            flags = np.asarray(flag, dtype=np.int64)
            rnrm2s = np.sqrt(np.asarray(rr, dtype=np.float64))
            r0nrm2s = np.sqrt(np.asarray(rr0, dtype=np.float64))
            k = int(ksys.max()) if ksys.size else 0
            # a faulted system dominates the batch summary (the recovery
            # decision is batch-wide), then breakdown, then convergence
            flag = (_FAULT if np.any(flags == _FAULT)
                    else _BREAKDOWN if np.any(flags == _BREAKDOWN)
                    else (_CONVERGED if np.all(flags == _CONVERGED) else _OK))
            rel = rnrm2s / np.where(r0nrm2s > 0, r0nrm2s, 1.0)
            worst = int(np.argmax(rel)) if rel.size else 0
            rnrm2 = float(rnrm2s[worst]) if rnrm2s.size else 0.0
            r0nrm2 = float(r0nrm2s[worst]) if r0nrm2s.size else 0.0
            # bnrm2 from the SAME worst system (a max over a different
            # system would make |r|/|b| computed from the export wrong by
            # the spread of the batch's b scales)
            if np.ndim(bnrm2) == 1:
                bnrm2 = np.asarray(bnrm2, dtype=np.float64)[worst]
            nrhs = int(ksys.shape[0])
            niters_total = int(ksys.sum())
        else:
            k = int(k)
            flag = int(flag)
            rnrm2 = float(np.sqrt(float(rr)))
            r0nrm2 = float(np.sqrt(float(rr0)))
            nrhs = 1
            niters_total = k
        st = stats if stats is not None else SolveStats()
        st.nsolves += 1
        st.ntotaliterations += k
        st.niterations = k
        # useful flops: each system advances only while it is active
        st.nflops += niters_total * cg_flops_per_iter(A.nnz, A.nrows,
                                                      pipelined=pipelined,
                                                      sstep=sstep)
        st.tsolve += tsolve
        o = options
        if has_hist:
            # trim the fixed-size buffer to the iterations actually run
            # (slots past k — per system for batched solves — are NaN fill,
            # see loops._history_init); host NumPy by the device_get above
            hist = np.asarray(hist, dtype=np.float64)[..., : k + 1]
        res = SolveResult(
            x=x_host, converged=(flag == _CONVERGED), niterations=k,
            bnrm2=float(np.max(bnrm2)), r0nrm2=r0nrm2, rnrm2=rnrm2,
            dxnrm2=(float(np.sqrt(np.max(np.asarray(dxx, dtype=np.float64))))
                    if has_dxx else float("inf")),
            stats=st,
            fpexcept=("none" if (np.isfinite(rnrm2)
                                 and np.all(np.isfinite(x_host)))
                      else "non-finite values in solution or residual"),
            operator_format=path[0], kernel=path[1],
            kernel_note=path[2] if len(path) > 2 else "",
            iface=path[3] if len(path) > 3 else "",
            precond=precond,
            residual_history=hist if has_hist else None,
            nrhs=nrhs,
            iterations_per_system=ksys if batched else None,
            rnrm2_per_system=rnrm2s if batched else None,
            r0nrm2_per_system=r0nrm2s if batched else None,
            converged_per_system=(flags == _CONVERGED) if batched else None)

        def _observed(r):
            # runtime telemetry (acg_tpu/obs/metrics.py; no-op unless
            # enable_metrics()): every terminal path below — raised or
            # returned — records exactly once, with the FINAL status.
            # Host-side, after the device_get above: cannot touch a trace.
            observe_solve_result(r, solver=(solver if solver
                                            else "cg-sstep" if sstep
                                            else "cg-pipelined" if pipelined
                                            else "cg"))
            return r

        if flag == _FAULT or (batched and np.any(flags == _FAULT)):
            # the on-device finiteness guard fired (loops.py, guard=True):
            # a first-class detection, distinct from breakdown — name what
            # was seen (|r|² is returned; a finite |r|² with the flag set
            # means the OTHER reduced scalar, p'Ap or the pipelined δ, was
            # the non-finite witness)
            res.status = Status.ERR_FAULT_DETECTED
            res.fpexcept = (
                f"non-finite residual reduction |r|^2 = {rnrm2!r} detected "
                f"by the on-device guard at iteration {k}"
                if not np.isfinite(rnrm2) else
                f"non-finite reduction (p'Ap / delta) detected by the "
                f"on-device guard at iteration {k} (|r|^2 still finite)")
            err = AcgError(Status.ERR_FAULT_DETECTED,
                           f"solve aborted at iteration {k}: {res.fpexcept}")
            err.result = _observed(res)
            raise err
        if flag == _BREAKDOWN or (batched and np.any(flags == _BREAKDOWN)):
            res.status = Status.ERR_NOT_CONVERGED_INDEFINITE_MATRIX
            err = AcgError(Status.ERR_NOT_CONVERGED_INDEFINITE_MATRIX)
            err.result = _observed(res)
            raise err
        no_criteria = (o.diffatol == 0 and o.diffrtol == 0
                       and o.residual_atol == 0 and o.residual_rtol == 0)
        all_conv = (np.all(flags == _CONVERGED) if batched
                    else flag == _CONVERGED)
        if not all_conv and not no_criteria:
            res.status = Status.ERR_NOT_CONVERGED
            err = AcgError(Status.ERR_NOT_CONVERGED,
                           f"CG did not converge in {o.maxits} iterations "
                           f"(|r|/|r0| = {res.relative_residual:.3e})")
            err.result = _observed(res)
            raise err
        if no_criteria:
            res.converged = True
            if batched:
                res.converged_per_system = np.ones(nrhs, dtype=bool)
        if res.fpexcept != "none":
            # non-finite values in the RESULT with no guard running (or a
            # fixed-iteration solve that ran to maxits on NaNs): classified,
            # not raised — the caller opted out of stopping criteria
            res.status = Status.ERR_NONFINITE
        return _observed(res)


@_traced_call("cg")
def cg(A, b, x0=None, options: SolverOptions = SolverOptions(),
       dtype=None, fmt: str = "auto", mat_dtype="auto",
       stats: SolveStats | None = None, fault=None,
       atol2_floor=None) -> SolveResult:
    """Classic CG on one chip, fully on-device (see module docstring).

    ``b`` of shape (B, n) solves B systems against the one operator in a
    single device loop (multi-RHS batching: the band stream is read once
    per iteration for ALL systems); the result carries per-system
    iteration counts, residuals and histories (SolveResult.nrhs).

    ``fault`` is a deterministic injection plan
    (:class:`~acg_tpu.robust.faults.FaultSpec`) traced into the loop as
    data; pair it with ``options.guard_nonfinite`` to exercise the
    detection path (acg_tpu/robust/)."""
    o = options
    with span("acg.call.prepare"):
        dev, b_pad, x0_pad, perm = _prepare(A, b, x0, dtype, fmt, mat_dtype)
        batched = b_pad.ndim == 2
        vdt = b_pad.dtype
        fplan = _fault_plan(fault, vdt)
        guard = o.guard_nonfinite
        # atol2_floor (the s-step fallback, _sstep_fallback_stop): a scalar
        # or PER-SYSTEM (B,) squared-absolute threshold floor folded into
        # the atol term — each system's criterion can be restored exactly
        # where a scalar options field could only carry the batch min
        stop2 = (jnp.asarray(o.residual_atol ** 2 if atol2_floor is None
                             else np.maximum(o.residual_atol ** 2,
                                             atol2_floor), vdt),
                 jnp.asarray(o.residual_rtol**2, vdt))
        track_diff = o.diffatol > 0 or o.diffrtol > 0
        diffstop = jnp.asarray(o.diffatol**2, vdt)  # diffrtol needs |x0|
        if o.diffrtol > 0:
            if batched:  # per-system |x0| -> per-system diff threshold
                x0n = jnp.linalg.norm(x0_pad, axis=-1)
                diffstop = jnp.maximum(diffstop,
                                       ((o.diffrtol * x0n) ** 2).astype(vdt))
            else:
                x0n = float(jnp.linalg.norm(x0_pad))
                diffstop = jnp.maximum(diffstop,
                                       jnp.asarray((o.diffrtol * x0n) ** 2,
                                                   vdt))
        bnrm2 = jnp.linalg.norm(b_pad, axis=-1) if batched \
            else jnp.linalg.norm(b_pad)     # fetched with the scalar batch
        jax.block_until_ready(bnrm2)        # keep it out of the timed window
        plan = (_fused_plan_batched(dev, b_pad.shape[0]) if batched
                else _fused_plan(dev))
        monitor = _resolve_monitor(o)
        mg = _mg_of(dev, o, batched)
    with span("acg.call.dispatch"):
        t0 = time.perf_counter()
        if plan is not None and o.segment_iters > 0:
            from acg_tpu.ops.pallas_kernels import LANES, padded_halo_rows

            kind, rt = plan
            bands_pad, (bp2, xp2) = _pad_fused(dev, b_pad, x0_pad, rt)
            x, k, rr, dxx, flag, rr0, hist = _run_segmented(
                lambda: _cg_fused_seg(
                    dev, bands_pad, bp2, xp2, stop2, diffstop,
                    maxits=o.maxits, track_diff=track_diff,
                    check_every=o.check_every, rows_tile=rt, kind=kind,
                    segment=o.segment_iters, monitor=monitor,
                    monitor_every=o.monitor_every, fault=fplan, guard=guard),
                lambda c: _cg_fused_seg_resume(
                    dev, bands_pad, bp2, c, stop2, diffstop,
                    maxits=o.maxits, track_diff=track_diff,
                    check_every=o.check_every, rows_tile=rt, kind=kind,
                    segment=o.segment_iters, monitor=monitor,
                    monitor_every=o.monitor_every, fault=fplan, guard=guard),
                o.maxits)
            hpad = padded_halo_rows(dev.offsets, rt) * LANES
            x = jax.lax.slice_in_dim(x, hpad,
                                     hpad + b_pad.shape[-1], axis=-1)
        elif plan is not None:
            kind, rt = plan
            x, k, rr, dxx, flag, rr0, hist = _cg_device_fused(
                dev, b_pad, x0_pad, stop2, diffstop,
                maxits=o.maxits, track_diff=track_diff,
                check_every=o.check_every, rows_tile=rt, kind=kind,
                monitor=monitor, monitor_every=o.monitor_every,
                fault=fplan, guard=guard, mg=mg)
        elif o.segment_iters > 0:
            x, k, rr, dxx, flag, rr0, hist = _run_segmented(
                lambda: _cg_device_seg(
                    dev, b_pad, x0_pad, stop2, diffstop, maxits=o.maxits,
                    track_diff=track_diff, check_every=o.check_every,
                    segment=o.segment_iters, monitor=monitor,
                    monitor_every=o.monitor_every, fault=fplan, guard=guard),
                lambda c: _cg_device_seg_resume(
                    dev, b_pad, c, stop2, diffstop, maxits=o.maxits,
                    track_diff=track_diff, check_every=o.check_every,
                    segment=o.segment_iters, monitor=monitor,
                    monitor_every=o.monitor_every, fault=fplan, guard=guard),
                o.maxits)
        else:
            x, k, rr, dxx, flag, rr0, hist = _cg_device(
                dev, b_pad, x0_pad, stop2, diffstop,
                maxits=o.maxits, track_diff=track_diff,
                check_every=o.check_every,
                monitor=monitor, monitor_every=o.monitor_every,
                fault=fplan, guard=guard, mg=mg)
    k = _device_wait(x, k)        # scalar, or per-system (B,) when batched
    tsolve = time.perf_counter() - t0
    from acg_tpu.solvers.base import kernel_disengagement_note
    note = kernel_disengagement_note(False, plan, None, None,
                                     forced_fmt=fmt)
    return _finish(dev, x, k, rr, flag, rr0, o, tsolve, pipelined=False,
                   bnrm2=bnrm2, dxx=dxx if track_diff else None, stats=stats,
                   to_host=_host_solution(dev.nrows, perm),
                   path=_describe_path(
                       dev, perm, plan,
                       nrhs=b_pad.shape[0] if batched else 1) + (note,),
                   hist=hist, precond=mg.note if mg else "")


def _deflate_x0(matvec, b, x0, W, WtAW):
    """Galerkin-project the retained basis out of the initial residual:
    ``x0' = x0 + W (W'AW)^{-1} W' r0`` with ``r0 = b - A x0``, computed
    host-side in float64 (SETUP-only work — the solve program that runs
    afterwards is literally :func:`cg`'s program).  Returns the deflated
    x0 as float64, or the undeflated ``x0`` when the projection cannot
    be applied soundly (singular W'AW, non-finite correction)."""
    b64 = np.asarray(b, np.float64)
    if x0 is None:
        x064 = np.zeros_like(b64)
        r0 = b64
    else:
        x064 = np.asarray(x0, np.float64)
        ax0 = (np.stack([np.asarray(matvec(row), np.float64)
                         for row in x064])
               if b64.ndim == 2 else
               np.asarray(matvec(x064), np.float64))
        r0 = b64 - ax0
    W = np.asarray(W, np.float64)
    WtAW = np.asarray(WtAW, np.float64)
    try:
        if b64.ndim == 2:               # per-system correction, (B, k)
            coef = np.linalg.solve(WtAW, (r0 @ W).T).T
            x0d = x064 + coef @ W.T
        else:
            x0d = x064 + W @ np.linalg.solve(WtAW, W.T @ r0)
    except np.linalg.LinAlgError:
        return x064
    return x0d if np.all(np.isfinite(x0d)) else x064


def cg_recycled(A, b, x0=None, options: SolverOptions = SolverOptions(),
                dtype=None, fmt: str = "auto", mat_dtype="auto",
                stats: SolveStats | None = None, fault=None,
                W=None, WtAW=None, recycle=None,
                matvec=None) -> SolveResult:
    """Deflated CG: project the k retained (recycled) directions out of
    the initial residual at SETUP, then run the ordinary :func:`cg`
    program — zero added per-iteration collectives, the dispatched
    program is bit-identical to classic CG (the deflation is a host-side
    x0 preconditioning, certified by the same true-residual exit).

    ``W`` (n, k) with ``WtAW = W'AW`` (k, k) is the retained basis;
    when absent it is resolved from ``recycle``
    (:class:`acg_tpu.serve.session.RecycleState`.``deflation_basis``),
    and when no basis is available the call delegates to :func:`cg`
    unchanged (cold solves are NEVER penalised)."""
    mv = matvec if matvec is not None else getattr(A, "matvec", None)
    if W is None and recycle is not None:
        W, WtAW = recycle.deflation_basis(mv)
    if W is None or WtAW is None or mv is None:
        return cg(A, b, x0, options, dtype, fmt, mat_dtype,
                  stats=stats, fault=fault)
    x0d = _deflate_x0(mv, b, x0, W, WtAW)
    return cg(A, b, x0d, options, dtype, fmt, mat_dtype,
              stats=stats, fault=fault)


def lowered_step(A, b, x0=None, options: SolverOptions = SolverOptions(),
                 dtype=None, fmt: str = "auto", mat_dtype="auto",
                 pipelined: bool = False, fault=None,
                 solver: str | None = None):
    """Lower — without executing — the jitted device program that
    :func:`cg` / :func:`cg_pipelined` / :func:`cg_sstep` would run for
    exactly these arguments; returns a ``jax.stages.Lowered``.
    ``solver`` ("cg" | "cg-pipelined" | "cg-sstep" |
    "cg-pipelined-deep") overrides the ``pipelined`` flag; the s-step
    program requires ``options.sstep >= 2``, the deep-pipelined one
    lowers the single dispatch executable every pipeline segment reuses
    (``options.pipeline_depth == 1`` lowers the ordinary pipelined
    program — the zero-overhead clause).

    The introspection hook of the observability layer
    (acg_tpu/obs/hlo.py): ``lowered_step(...).compile()`` (or
    :func:`compile_step`) yields the optimized executable whose HLO a
    :class:`~acg_tpu.obs.hlo.CommAudit` prices — the same plan gates
    (fused kernel / batched kernel / XLA fallback) the real solve takes,
    so what the audit inspects is what the solve runs.  Segmented solves
    (``options.segment_iters``) are lowered as the single monolithic
    program: segmentation re-dispatches the SAME loop body, so the
    per-iteration audit is identical."""
    o = options
    if solver == "cg-recycled":
        # deflation is SETUP-only host work (x0 preconditioning): the
        # device program cg_recycled dispatches IS cg's program — the
        # audit of one is the audit of the other (the zero added
        # per-iteration collectives clause of the contract)
        solver = "cg"
    if solver == "cg-pipelined-deep" and o.pipeline_depth <= 1:
        solver = "cg-pipelined"     # depth 1 IS the pipelined program
    if solver is not None:
        pipelined = solver == "cg-pipelined"
    dev, b_pad, x0_pad, _perm = _prepare(A, b, x0, dtype, fmt, mat_dtype)
    batched = b_pad.ndim == 2
    vdt = b_pad.dtype
    # the SAME guard/fault resolution as the solve: an --explain audit
    # of a guarded (or injection) solve must inspect the program that
    # actually runs — and with both off, the audit proves the default
    # program is byte-identical to the unguarded one
    fplan = _fault_plan(fault, vdt)
    guard = o.guard_nonfinite
    stop2 = (jnp.asarray(o.residual_atol**2, vdt),
             jnp.asarray(o.residual_rtol**2, vdt))
    # the SAME monitor resolution as the solve: an --explain audit of a
    # monitored solve must see the callback ops the hot loop carries
    monitor = _resolve_monitor(o)
    if solver in ("cg-sstep", "cg-pipelined-deep") or pipelined:
        require_unpreconditioned(o, solver or "cg-pipelined")
    if solver == "cg-sstep":
        # the same rejections cg_sstep applies
        s = _sstep_validate(o, fault)
        return _cg_sstep_device.lower(
            dev, b_pad, x0_pad, stop2, s=s, maxits=o.maxits,
            monitor=monitor, monitor_every=o.monitor_every)
    if solver == "cg-pipelined-deep":
        # the one-dispatch deep executable (restart state is operands:
        # the host driver reuses this SAME program every segment)
        l = _deep_validate(o, fault)
        sshape = b_pad.shape[:-1]
        return _cg_pipelined_deep_device.lower(
            dev, b_pad, x0_pad, stop2, depth=l, maxits=o.maxits,
            check_every=o.check_every, replace_every=o.replace_every,
            certify=o.residual_atol > 0 or o.residual_rtol > 0,
            k_start=jnp.zeros((), jnp.int32),
            rr0_in=jnp.zeros(sshape, vdt),
            flags_in=jnp.zeros(sshape, jnp.int32),
            hist_in=jnp.zeros(sshape + (o.maxits + 1,), vdt),
            ksys_in=(jnp.zeros(sshape, jnp.int32) if batched else None),
            monitor=monitor, monitor_every=o.monitor_every,
            guard=guard)
    if pipelined:
        # the same rejections cg_pipelined applies — an audit must not
        # be produced for a configuration the solve refuses to run
        if o.diffatol > 0 or o.diffrtol > 0:
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "pipelined CG supports residual-based "
                           "stopping only")
        # segmented pipelined solves (PR 7) lower as the single
        # monolithic program, like classic: segmentation re-dispatches
        # the SAME loop body, so the per-iteration audit is identical
        plan = None if batched else _fused_plan(dev)
        certify = o.residual_atol > 0 or o.residual_rtol > 0
        if plan is not None:
            kind, rt = plan
            return _cg_pipelined_device_fused.lower(
                dev, b_pad, x0_pad, stop2, maxits=o.maxits,
                check_every=o.check_every, replace_every=o.replace_every,
                rows_tile=rt, kind=kind, certify=certify,
                pipe_rt=(None if fplan is not None
                         else _pipe2d_rt(dev, plan)),
                monitor=monitor, monitor_every=o.monitor_every,
                fault=fplan, guard=guard)
        # the matrix-free mega-kernel path, same gate as the solve
        # (cg_pipelined: segmented solves keep the open-coded body)
        st_rt = (None if batched or o.segment_iters > 0
                 else _stencil_pipe_rt(dev, fplan))
        if st_rt is not None:
            return _cg_pipelined_stencil_fused.lower(
                dev, b_pad, x0_pad, stop2, maxits=o.maxits,
                check_every=o.check_every, certify=certify,
                pipe_rt=st_rt, replace_every=o.replace_every,
                monitor=monitor, monitor_every=o.monitor_every,
                fault=None, guard=guard)
        return _cg_pipelined_device.lower(
            dev, b_pad, x0_pad, stop2, maxits=o.maxits,
            check_every=o.check_every, replace_every=o.replace_every,
            certify=certify, monitor=monitor,
            monitor_every=o.monitor_every, fault=fplan, guard=guard)
    track_diff = o.diffatol > 0 or o.diffrtol > 0
    # the diffstop the solve would carry, including the per-system (B,)
    # threshold a batched diffrtol derives from |x0| (cg())
    diffstop = jnp.asarray(o.diffatol**2, vdt)
    if o.diffrtol > 0:
        if batched:
            x0n = jnp.linalg.norm(x0_pad, axis=-1)
            diffstop = jnp.maximum(diffstop,
                                   ((o.diffrtol * x0n) ** 2).astype(vdt))
        else:
            x0n = float(jnp.linalg.norm(x0_pad))
            diffstop = jnp.maximum(diffstop,
                                   jnp.asarray((o.diffrtol * x0n) ** 2,
                                               vdt))
    plan = (_fused_plan_batched(dev, b_pad.shape[0]) if batched
            else _fused_plan(dev))
    mg = _mg_of(dev, o, batched)
    if plan is not None:
        kind, rt = plan
        return _cg_device_fused.lower(
            dev, b_pad, x0_pad, stop2, diffstop, maxits=o.maxits,
            track_diff=track_diff, check_every=o.check_every,
            rows_tile=rt, kind=kind, monitor=monitor,
            monitor_every=o.monitor_every, fault=fplan, guard=guard,
            mg=mg)
    return _cg_device.lower(
        dev, b_pad, x0_pad, stop2, diffstop, maxits=o.maxits,
        track_diff=track_diff, check_every=o.check_every,
        monitor=monitor, monitor_every=o.monitor_every,
        fault=fplan, guard=guard, mg=mg)


def compile_step(A, b, x0=None, options: SolverOptions = SolverOptions(),
                 dtype=None, fmt: str = "auto", mat_dtype="auto",
                 pipelined: bool = False, fault=None,
                 solver: str | None = None):
    """Compiled twin of :func:`lowered_step` (``jax.stages.Compiled``):
    the object :func:`acg_tpu.obs.hlo.audit_compiled` consumes."""
    return lowered_step(A, b, x0=x0, options=options, dtype=dtype,
                        fmt=fmt, mat_dtype=mat_dtype,
                        pipelined=pipelined, fault=fault,
                        solver=solver).compile()


def declared_contract(A, b=None, options: SolverOptions = SolverOptions(),
                      dtype=None, fmt: str = "auto", mat_dtype="auto",
                      pipelined: bool = False, solver: str | None = None):
    """The :class:`~acg_tpu.analysis.contracts.SolverContract` this
    single-chip configuration declares — the verification face of the
    ``lowered_step``/``compile_step`` introspection hooks: what
    :func:`compile_step` produces is what
    :func:`~acg_tpu.analysis.contracts.verify_contract` checks this
    declaration against (no collectives anywhere, gather-free hot loop
    on the DIA tier, no host transfer unless a monitor was requested, no
    f64 below f64).  Every new solver variant must declare itself here
    and in :mod:`acg_tpu.analysis.registry` — an undeclared variant is
    invisible to ``scripts/check_contracts.py``."""
    from acg_tpu.analysis.registry import contract_for

    if solver is None:
        solver = "cg-pipelined" if pipelined else "cg"
    dev = build_device_operator(A, dtype=dtype, fmt=fmt,
                                mat_dtype=mat_dtype)
    b = None if b is None else np.asarray(b)
    nrhs = b.shape[0] if b is not None and b.ndim == 2 else 1
    return contract_for(solver, options, dev=dev, nrhs=nrhs)


class AotSolve:
    """An AOT-compiled solver executable bound to one prepared operator.

    The executable-reuse face of the ``lowered_step``/``compile_step``
    hooks (the serve layer's cache entry, acg_tpu/serve/session.py):
    :func:`aot_step` compiles the EXACT program :func:`cg` /
    :func:`cg_pipelined` would run for the given static signature —
    same plan gates, same loop body — and :meth:`solve` dispatches new
    right-hand sides of the same shape/dtype straight into it with zero
    retracing and zero recompilation, returning a result bit-identical
    to the ordinary solver call (pinned by tests/test_serve.py).

    ``compiled`` is the underlying ``jax.stages.Compiled`` — the object
    :func:`acg_tpu.obs.hlo.audit_compiled` consumes, so a CommAudit of
    the cached executable describes exactly what every warm dispatch
    runs."""

    def __init__(self, compiled, solve_fn, *, kind: str, shape: tuple,
                 vec_dtype, path: tuple):
        self.compiled = compiled
        self._solve = solve_fn
        self.kind = kind
        self.shape = tuple(shape)       # padded device operand shape
        self.vec_dtype = vec_dtype
        self.path = path                # (operator_format, kernel, note)

    def solve(self, b, x0=None, stats: SolveStats | None = None,
              options: SolverOptions | None = None) -> SolveResult:
        """Dispatch one request.  ``options`` may override the compile-
        time options PER CALL as long as every STATIC field matches the
        signature (checked) — tolerance VALUES are runtime operands of
        the compiled program and are re-bound on every dispatch, so one
        executable serves requests at any tolerance of the same
        non-zero-ness."""
        return self._solve(b, x0, stats, options)


def check_aot_options(compiled_o: SolverOptions,
                      o: SolverOptions) -> SolverOptions:
    """Reject a per-dispatch options override whose STATIC fields differ
    from the executable's signature — silently running the compiled
    maxits/check_every/... against different requested ones would
    misreport the solve (tolerance VALUES are the only legal per-call
    variation; their non-zero-ness gates static branches and must
    match)."""
    static = ("maxits", "check_every", "replace_every", "monitor_every",
              "guard_nonfinite", "segment_iters", "sstep",
              "pipeline_depth", "halo_wire", "precond", "mg_levels")
    for f in static:
        if getattr(o, f) != getattr(compiled_o, f):
            raise AcgError(Status.ERR_INVALID_VALUE,
                           f"AOT signature mismatch: options.{f}="
                           f"{getattr(o, f)} vs the executable's "
                           f"{getattr(compiled_o, f)} (static field)")
    for f in ("residual_atol", "residual_rtol", "diffatol", "diffrtol"):
        if (getattr(o, f) > 0) != (getattr(compiled_o, f) > 0):
            raise AcgError(Status.ERR_INVALID_VALUE,
                           f"AOT signature mismatch: options.{f} "
                           "non-zero-ness differs from the executable's "
                           "(it gates a static branch; recompile)")
    return o


def aot_step(A, b, x0=None, options: SolverOptions = SolverOptions(),
             dtype=None, fmt: str = "auto", mat_dtype="auto",
             pipelined: bool = False, solver: str | None = None
             ) -> AotSolve:
    """Build the reusable AOT executable for single-chip classic or
    pipelined CG at this static signature (operator, b shape/dtype,
    static :class:`SolverOptions` fields).  Tolerance VALUES stay
    runtime operands — only their non-zero-ness is static — so a cached
    executable serves any request that shares the signature.

    Fault injection and ``segment_iters`` are not AOT paths (the
    supervisor/segment drivers re-dispatch per segment); callers route
    those through the ordinary solver functions."""
    o = options
    if solver == "cg-pipelined-deep" and o.pipeline_depth <= 1:
        solver = "cg-pipelined"     # depth 1 IS the pipelined program
    if solver is not None:
        pipelined = solver == "cg-pipelined"
    if solver not in (None, "cg", "cg-pipelined", "cg-pipelined-deep"):
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       f"aot_step compiles the classic/pipelined/"
                       f"deep-pipelined programs (solver {solver!r})")
    deep_kind = solver == "cg-pipelined-deep"
    if deep_kind:
        _deep_validate(o, None)
    if o.segment_iters > 0:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "segment_iters re-dispatches per segment; use the "
                       "ordinary solver functions")
    dev, b0_pad, _x00, perm = _prepare(A, b, x0, dtype, fmt, mat_dtype)
    # requests (and the lowering below) re-enter through the
    # ALREADY-BUILT operator — a host matrix here would rebuild its
    # device bands on every dispatch
    A_res = PermutedOperator(dev, perm) if perm is not None else dev
    compiled = lowered_step(A_res, b, x0=x0, options=o, dtype=dtype,
                            fmt=fmt, mat_dtype=mat_dtype,
                            pipelined=pipelined, solver=solver).compile()
    batched = b0_pad.ndim == 2
    vdt = b0_pad.dtype
    shape = b0_pad.shape
    track_diff = o.diffatol > 0 or o.diffrtol > 0
    # the same path/note computation the ordinary solvers report, frozen
    # once (the plan gates are static for a fixed operator + signature)
    plan = (_fused_plan_batched(dev, shape[0]) if batched
            else _fused_plan(dev))
    from acg_tpu.ops.stencil import DeviceStencil
    is_st = isinstance(dev, DeviceStencil)
    if deep_kind:
        from acg_tpu.solvers.base import kernel_disengagement_note
        path = _describe_path(dev, perm, None)
        note = kernel_disengagement_note(False, None, None, None,
                                         forced_fmt=fmt)
    elif pipelined:
        plan1 = None if batched else plan
        pipe_rt = (None if plan1 is None
                   else _pipe2d_rt(dev, plan1))
        st_rt = (None if batched
                 else _stencil_pipe_rt(dev, None))
        from acg_tpu.solvers.base import kernel_disengagement_note
        if batched:
            path = _describe_path(dev, perm, plan, nrhs=shape[0])
            note = kernel_disengagement_note(False, None, None, None,
                                             forced_fmt=fmt)
        else:
            path = _describe_path(dev, perm, plan1,
                                  pipe_rt=pipe_rt if not is_st
                                  else st_rt)
            note = kernel_disengagement_note(
                True, plan1, pipe_rt if not is_st else st_rt,
                None, forced_fmt=fmt, stencil=is_st,
                stencil_interpret=is_st and dev.interpret)
    else:
        from acg_tpu.solvers.base import kernel_disengagement_note
        path = _describe_path(dev, perm, plan,
                              nrhs=shape[0] if batched else 1)
        note = kernel_disengagement_note(False, plan, None, None,
                                         forced_fmt=fmt)
    path = path + (note,)
    mg = None if deep_kind or pipelined else _mg_of(dev, o, batched)

    @_traced_call("cg-pipelined-deep" if deep_kind
                  else "cg-pipelined" if pipelined else "cg")
    def solve(b, x0=None, stats=None, options=None) -> SolveResult:
        # per-dispatch options: tolerance VALUES re-bind as runtime
        # operands of the SAME executable; static fields must match
        with span("acg.call.prepare"):
            oo = o if options is None else check_aot_options(o, options)
            _, b_pad, x0_pad, _ = _prepare(A_res, b, x0, dtype, fmt,
                                           mat_dtype)
            if b_pad.shape != shape or b_pad.dtype != vdt:
                raise AcgError(Status.ERR_INVALID_VALUE,
                               f"AOT signature mismatch: executable was "
                               f"compiled for shape {shape} dtype {vdt}, "
                               f"got {b_pad.shape} {b_pad.dtype}")
            stop2 = (jnp.asarray(oo.residual_atol ** 2, vdt),
                     jnp.asarray(oo.residual_rtol ** 2, vdt))
            # the diffstop the jit path computes (cg()), including the
            # per-system (B,) threshold a batched diffrtol derives from |x0|
            diffstop = jnp.asarray(oo.diffatol ** 2, vdt)
            if oo.diffrtol > 0:
                if batched:
                    x0n = jnp.linalg.norm(x0_pad, axis=-1)
                    diffstop = jnp.maximum(
                        diffstop, ((oo.diffrtol * x0n) ** 2).astype(vdt))
                else:
                    x0n = float(jnp.linalg.norm(x0_pad))
                    diffstop = jnp.maximum(
                        diffstop, jnp.asarray((oo.diffrtol * x0n) ** 2,
                                              vdt))
            bnrm2 = jnp.linalg.norm(b_pad, axis=-1) if batched \
                else jnp.linalg.norm(b_pad)
            jax.block_until_ready(bnrm2)    # out of the timed window (cg())
        with span("acg.call.dispatch"):
            t0 = time.perf_counter()
            path2 = path
            if deep_kind:
                # the host re-dispatch driver of cg_pipelined_deep against
                # the fixed executable: no classic-CG fallback here (AOT
                # never re-traces) — persistent breakdown/drift surfaces as
                # the returned flag instead
                l = oo.pipeline_depth
                sshape = shape[:-1]
                x_op = x0_pad
                k_op = jnp.zeros((), jnp.int32)
                rr0 = jnp.zeros(sshape, vdt)
                flags_op = jnp.zeros(sshape, jnp.int32)
                hist = jnp.zeros(sshape + (oo.maxits + 1,), vdt)
                ksys_op = jnp.zeros(sshape, jnp.int32) if batched else None
                fails = ndisp = 0
                while True:
                    ndisp += 1
                    (x_op, k, rr, flag, rr0, hist, k_op, more,
                     drift) = compiled(dev, b_pad, x_op, stop2,
                                       k_start=k_op, rr0_in=rr0,
                                       flags_in=flags_op, hist_in=hist,
                                       ksys_in=ksys_op)
                    if batched:
                        ksys_op = k
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
                x, dxx = x_op, None
                path2 = path[:-1] + (
                    f"deep pipeline depth {l}, {ndisp} dispatch(es)"
                    + ("; " + path[-1] if path[-1] else ""),)
            elif pipelined:
                x, k, rr, flag, rr0, hist = compiled(
                    dev, b_pad, x0_pad, stop2, fault=None)
                dxx = None
            else:
                x, k, rr, dxx, flag, rr0, hist = compiled(
                    dev, b_pad, x0_pad, stop2, diffstop, fault=None)
        k = _device_wait(x, k)          # real sync (see cg())
        tsolve = time.perf_counter() - t0
        return _finish(dev, x, k, rr, flag, rr0, oo, tsolve,
                       pipelined=pipelined or deep_kind, bnrm2=bnrm2,
                       dxx=dxx if track_diff else None, stats=stats,
                       to_host=_host_solution(dev.nrows, perm),
                       path=path2, hist=hist,
                       solver=("cg-pipelined-deep" if deep_kind
                               else None),
                       precond=mg.note if mg else "")

    return AotSolve(compiled, solve,
                    kind=("cg-pipelined-deep" if deep_kind
                          else "cg-pipelined" if pipelined else "cg"),
                    shape=shape, vec_dtype=vdt, path=path)


@_traced_call("cg-pipelined")
def cg_pipelined(A, b, x0=None, options: SolverOptions = SolverOptions(),
                 dtype=None, fmt: str = "auto", mat_dtype="auto",
                 stats: SolveStats | None = None,
                 fault=None) -> SolveResult:
    """Pipelined CG on one chip (see module docstring).  ``fault`` as in
    :func:`cg`; an injection solve gates off the single-kernel pipelined
    iteration (the mega-kernel exposes no injection sites)."""
    o = options
    require_unpreconditioned(o, "cg-pipelined")
    if o.diffatol > 0 or o.diffrtol > 0:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "pipelined CG supports residual-based stopping only")
    with span("acg.call.prepare"):
        dev, b_pad, x0_pad, perm = _prepare(A, b, x0, dtype, fmt, mat_dtype)
        batched = b_pad.ndim == 2
        vdt = b_pad.dtype
        fplan = _fault_plan(fault, vdt)
        guard = o.guard_nonfinite
        stop2 = (jnp.asarray(o.residual_atol**2, vdt),
                 jnp.asarray(o.residual_rtol**2, vdt))
        bnrm2 = jnp.linalg.norm(b_pad, axis=-1) if batched \
            else jnp.linalg.norm(b_pad)
        jax.block_until_ready(bnrm2)
        # batched pipelined solves run the plain loop: the operator matvec
        # itself routes (B, n) vectors through the batched SpMV kernel when
        # its gate passes (dia_matvec_best), and the pipelined recurrences
        # have no <p, Ap> reduction for the fused-dot kernel to win on
        plan = None if batched else _fused_plan(dev)
        # exit certification is only needed when an exit can be claimed; a
        # fixed-iteration solve (the benchmark protocol) statically drops the
        # certifier branch, whose lax.cond was measured carrying ~4 extra
        # vector streams/iter through the conditional (PERF.md round 5)
        certify = o.residual_atol > 0 or o.residual_rtol > 0
        monitor = _resolve_monitor(o)
        pipe_rt = None
        # the matrix-free single-kernel pipelined iteration (stencil tier):
        # same role as pipe_rt on the DIA tier, gated the same way; the
        # segmented driver keeps the open-coded body (its carry-resume
        # contract is the plain loop's)
        st_rt = (None if batched or o.segment_iters > 0
                 else _stencil_pipe_rt(dev, fplan))
    with span("acg.call.dispatch"):
        t0 = time.perf_counter()
        if plan is not None and o.segment_iters > 0:
            # segmented fused pipelined solve (PR 7: the pipelined twin of
            # classic's carry-resume segmentation): pad once, re-dispatch
            # the SAME loop body per segment from the exact carry
            from acg_tpu.ops.pallas_kernels import LANES, padded_halo_rows

            kind, rt = plan
            pipe_rt = (None if fplan is not None
                       else _pipe2d_rt(dev, plan))
            bands_pad, (bp2, xp2) = _pad_fused(dev, b_pad, x0_pad, rt)
            x, k, rr, flag, rr0, hist = _run_segmented(
                lambda: _cg_pipelined_fused_seg(
                    dev, bands_pad, bp2, xp2, stop2, maxits=o.maxits,
                    check_every=o.check_every,
                    replace_every=o.replace_every, rows_tile=rt, kind=kind,
                    certify=certify, pipe_rt=pipe_rt,
                    segment=o.segment_iters, monitor=monitor,
                    monitor_every=o.monitor_every, fault=fplan, guard=guard),
                lambda c: _cg_pipelined_fused_seg_resume(
                    dev, bands_pad, bp2, c, stop2, maxits=o.maxits,
                    check_every=o.check_every,
                    replace_every=o.replace_every, rows_tile=rt, kind=kind,
                    certify=certify, pipe_rt=pipe_rt,
                    segment=o.segment_iters, monitor=monitor,
                    monitor_every=o.monitor_every, fault=fplan, guard=guard),
                o.maxits, continue_fn=_pipelined_continue)
            hpad = padded_halo_rows(dev.offsets, rt) * LANES
            x = jax.lax.slice_in_dim(x, hpad, hpad + b_pad.shape[-1],
                                     axis=-1)
        elif plan is not None:
            kind, rt = plan
            # the single-kernel pipelined iteration exposes no injection
            # sites — injection solves run the open-coded body instead
            pipe_rt = (None if fplan is not None
                       else _pipe2d_rt(dev, plan))
            x, k, rr, flag, rr0, hist = _cg_pipelined_device_fused(
                dev, b_pad, x0_pad, stop2, maxits=o.maxits,
                check_every=o.check_every, replace_every=o.replace_every,
                rows_tile=rt, kind=kind, certify=certify,
                pipe_rt=pipe_rt,
                monitor=monitor, monitor_every=o.monitor_every,
                fault=fplan, guard=guard)
        elif st_rt is not None:
            x, k, rr, flag, rr0, hist = _cg_pipelined_stencil_fused(
                dev, b_pad, x0_pad, stop2, maxits=o.maxits,
                check_every=o.check_every, certify=certify, pipe_rt=st_rt,
                replace_every=o.replace_every, monitor=monitor,
                monitor_every=o.monitor_every, guard=guard)
        elif o.segment_iters > 0:
            x, k, rr, flag, rr0, hist = _run_segmented(
                lambda: _cg_pipelined_device_seg(
                    dev, b_pad, x0_pad, stop2, maxits=o.maxits,
                    check_every=o.check_every,
                    replace_every=o.replace_every, certify=certify,
                    segment=o.segment_iters, monitor=monitor,
                    monitor_every=o.monitor_every, fault=fplan, guard=guard),
                lambda c: _cg_pipelined_device_seg_resume(
                    dev, b_pad, c, stop2, maxits=o.maxits,
                    check_every=o.check_every,
                    replace_every=o.replace_every, certify=certify,
                    segment=o.segment_iters, monitor=monitor,
                    monitor_every=o.monitor_every, fault=fplan, guard=guard),
                o.maxits, continue_fn=_pipelined_continue)
        else:
            x, k, rr, flag, rr0, hist = _cg_pipelined_device(
                dev, b_pad, x0_pad, stop2, maxits=o.maxits,
                check_every=o.check_every, replace_every=o.replace_every,
                certify=certify, monitor=monitor,
                monitor_every=o.monitor_every, fault=fplan, guard=guard)
    k = _device_wait(x, k)       # k may be per-system
    tsolve = time.perf_counter() - t0
    from acg_tpu.ops.stencil import DeviceStencil
    from acg_tpu.solvers.base import kernel_disengagement_note
    is_st = isinstance(dev, DeviceStencil)
    if batched:
        path = _describe_path(dev, perm, _fused_plan_batched(
            dev, b_pad.shape[0]), nrhs=b_pad.shape[0])
        note = kernel_disengagement_note(False, None, None, None,
                                         forced_fmt=fmt)
    else:
        path = _describe_path(dev, perm, plan,
                              pipe_rt=pipe_rt if not is_st else st_rt)
        note = kernel_disengagement_note(
            True, plan, pipe_rt if not is_st else st_rt,
            fplan, forced_fmt=fmt, stencil=is_st,
            stencil_interpret=is_st and dev.interpret)
    return _finish(dev, x, k, rr, flag, rr0, o, tsolve, pipelined=True,
                   bnrm2=bnrm2, stats=stats,
                   to_host=_host_solution(dev.nrows, perm),
                   path=path + (note,), hist=hist)


def _deep_validate(o: SolverOptions, fault) -> int:
    """The rejection set of the deep-pipelined wrappers (single-chip and
    distributed): returns the validated depth (>= 2; the depth-1 case is
    dispatched to the ordinary pipelined solver before this runs)."""
    if fault is not None:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "fault injection has no sites in the deep-"
                       "pipelined basis recurrences; inject into the "
                       "classic or pipelined solvers")
    if o.diffatol > 0 or o.diffrtol > 0:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "deep-pipelined CG supports residual-based "
                       "stopping only")
    if o.segment_iters > 0:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "segment_iters is supported by the classic and "
                       "pipelined solvers (the deep pipeline already "
                       "bounds device time per dispatch through "
                       "replace_every — each dispatch is one pipeline "
                       "segment)")
    return o.pipeline_depth


# consecutive dispatches ending in breakdown or certified-exit drift
# before the deep solver gives up and falls back to classic CG (the
# s-step _GRAM_BAD discipline; each re-dispatch already IS a residual
# replacement, so three failed restarts mean the basis itself is the
# problem, not drift)
_DEEP_MAX_BAD = 3


@functools.partial(jax.jit,
                   static_argnames=("depth", "maxits", "check_every",
                                    "replace_every", "certify",
                                    "monitor", "monitor_every", "guard"))
def _cg_pipelined_deep_device(op, b, x0, stop2, depth: int, maxits: int,
                              check_every: int, replace_every: int,
                              certify: bool, k_start, rr0_in, flags_in,
                              hist_in, ksys_in=None, monitor=None,
                              monitor_every: int = 0,
                              guard: bool = False, shifts0=None):
    """One deep-pipelined dispatch (pipeline segment) on one chip: the
    fill chain, the steady while_loop, and the true-residual exit
    certification are one jitted program (loops.cg_pipelined_deep_while).
    All restart state is operands, so every dispatch of a solve — first
    or resumed — reuses this ONE compiled executable."""
    mv = op.matvec

    def dots_fn(U, v):
        # the fused (2l+1)-dot block: one reduction over the vector axis
        d = jnp.sum(U * v[None], axis=-1)           # (w, [B])
        return jnp.moveaxis(d, 0, -1)               # ([B,] w)

    if shifts0 is None:
        lam = _power_lmax(mv, batched_dot, b)
        nodes = jnp.asarray(_cheb_leja_nodes(depth), b.dtype)
        shifts0 = lam[..., None] * nodes
    return cg_pipelined_deep_while(
        mv, dots_fn, batched_dot, b, x0, stop2, depth, shifts0,
        maxits, check_every=check_every, replace_every=replace_every,
        certify=certify, k_start=k_start, rr0_in=rr0_in,
        flags_in=flags_in, hist_in=hist_in, ksys_in=ksys_in,
        monitor=monitor, monitor_every=monitor_every, guard=guard)


@_traced_call("cg-pipelined-deep")
def cg_pipelined_deep(A, b, x0=None,
                      options: SolverOptions = SolverOptions(),
                      dtype=None, fmt: str = "auto", mat_dtype="auto",
                      stats: SolveStats | None = None, fault=None,
                      shifts0=None) -> SolveResult:
    """Depth-*l* pipelined CG on one chip: *l* global reductions in
    flight per iteration (``options.pipeline_depth``; the loop contract
    is loops.cg_pipelined_deep_while).  On a single chip the reduction
    depth is a latency detail — the point here is numerical parity and
    the shared loop the distributed solver (cg_dist.cg_pipelined_deep_dist)
    reuses, where hiding *l* psum latencies IS the strong-scaling lever.

    The host driver re-dispatches the compiled pipeline segment until
    the solve finishes: every re-entry recomputes r = b - Ax (residual
    replacement), every claimed exit is certified against a fresh true
    residual inside the program, and ``_DEEP_MAX_BAD`` consecutive
    dispatches ending in breakdown or certified drift fall back to
    classic CG from the last safe iterate (the s-step fallback
    discipline, surfaced via ``SolveResult.kernel_note``).

    ``pipeline_depth == 1`` dispatches to :func:`cg_pipelined`
    unchanged — same program, same audit, bit-identical results (the
    zero-overhead clause).  ``shifts0`` (``(l,)`` or ``(B, l)``)
    overrides the power-iteration/Chebyshev shift seeds — a testing
    hook."""
    o = options
    require_unpreconditioned(o, "cg-pipelined-deep")
    if o.pipeline_depth == 1:
        return cg_pipelined(A, b, x0, options=o, dtype=dtype, fmt=fmt,
                            mat_dtype=mat_dtype, stats=stats,
                            fault=fault)
    l = _deep_validate(o, fault)
    with span("acg.call.prepare"):
        dev, b_pad, x0_pad, perm = _prepare(A, b, x0, dtype, fmt, mat_dtype)
        batched = b_pad.ndim == 2
        vdt = b_pad.dtype
        stop2 = (jnp.asarray(o.residual_atol ** 2, vdt),
                 jnp.asarray(o.residual_rtol ** 2, vdt))
        bnrm2 = jnp.linalg.norm(b_pad, axis=-1) if batched \
            else jnp.linalg.norm(b_pad)
        jax.block_until_ready(bnrm2)
        certify = o.residual_atol > 0 or o.residual_rtol > 0
        monitor = _resolve_monitor(o)
        if shifts0 is not None:
            shifts0 = jnp.asarray(shifts0, vdt)
            if batched and shifts0.ndim == 1:
                shifts0 = jnp.tile(shifts0, (b_pad.shape[0], 1))
        sshape = b_pad.shape[:-1]
        # restart operands (see the loop's dispatch protocol)
        x_op = x0_pad
        k_op = jnp.zeros((), jnp.int32)
        rr0_op = jnp.zeros(sshape, vdt)
        flags_op = jnp.zeros(sshape, jnp.int32)
        hist_op = jnp.zeros(sshape + (o.maxits + 1,), vdt)
        ksys_op = jnp.zeros(sshape, jnp.int32) if batched else None
        fails = ndisp = 0
    with span("acg.call.dispatch"):
        t0 = time.perf_counter()
        while True:
            (x_op, kret, rr, flag, rr0_op, hist_op, k_op, more,
             drift) = _cg_pipelined_deep_device(
                dev, b_pad, x_op, stop2, depth=l, maxits=o.maxits,
                check_every=o.check_every, replace_every=o.replace_every,
                certify=certify, k_start=k_op, rr0_in=rr0_op,
                flags_in=flags_op, hist_in=hist_op, ksys_in=ksys_op,
                monitor=monitor, monitor_every=o.monitor_every,
                guard=o.guard_nonfinite, shifts0=shifts0)
            ndisp += 1
            if batched:
                ksys_op = kret
            flags_h = np.atleast_1d(np.asarray(jax.device_get(flag)))
            drift_h = np.atleast_1d(np.asarray(jax.device_get(drift)))
            k_h = int(jax.device_get(k_op))
            if np.any(flags_h == _FAULT):
                break    # the finiteness guard fired: no restart, surface it
            bad = bool(np.any(flags_h == _BREAKDOWN) or np.any(drift_h))
            fails = fails + 1 if bad else 0
            if fails >= _DEEP_MAX_BAD:
                # ISSUE 7 discipline: never silently wrong — classic CG
                # re-solves from the last safe iterate
                why = ("indefinite Gram/LDL pivot" if np.any(
                    flags_h == _BREAKDOWN) else "certified-exit drift")
                ksys_h = (np.asarray(jax.device_get(kret)) if batched
                          else None)
                x_part = _unpermute(x_op, dev.nrows, perm)
                if x_part is None:
                    x_part = np.asarray(x_op)[..., : dev.nrows]
                x_part = _sstep_fallback_x0(x_part, x0, rr, rr0_op)
                o2 = dataclasses.replace(o, pipeline_depth=1,
                                         maxits=max(o.maxits - k_h, 0))
                floor = _sstep_fallback_stop(o, rr0_op)
                return _sstep_fallback(
                    lambda: cg(A, b, x0=x_part, options=o2, dtype=dtype,
                               fmt=fmt, mat_dtype=mat_dtype, stats=stats,
                               atol2_floor=floor),
                    k_h, ksys_h, l, why,
                    spent_flops=k_h * cg_flops_per_iter(
                        dev.nnz, dev.nrows, pipelined=True),
                    label=f"cg-pipelined-deep(l={l})")
            # restart: breakdown systems get one more chance with a fresh
            # basis (the re-dispatch replaces their residual); drift systems
            # are still _OK and simply keep iterating
            live = np.any((flags_h == _OK) | (flags_h == _BREAKDOWN))
            flags_op = jnp.where(flag == _BREAKDOWN, _OK,
                                 flag).astype(jnp.int32)
            if not (live and k_h < o.maxits):
                break
    k_get = _device_wait(x_op, kret)
    tsolve = time.perf_counter() - t0
    from acg_tpu.solvers.base import kernel_disengagement_note
    note = kernel_disengagement_note(False, None, None, None,
                                     forced_fmt=fmt)
    note = (f"deep pipeline depth {l}, {ndisp} dispatch(es)"
            + ("; " + note if note else ""))
    return _finish(dev, x_op, k_get, rr, flag, rr0_op, o, tsolve,
                   pipelined=True, bnrm2=bnrm2, stats=stats,
                   to_host=_host_solution(dev.nrows, perm),
                   path=_describe_path(dev, perm, None) + (note,),
                   hist=hist_op, solver="cg-pipelined-deep")
