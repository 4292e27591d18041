"""Typed configuration for solvers, partitioning, and meshes.

One dataclass-based config layer replaces the reference's three config
mechanisms (CMake ``ACG_HAVE_*`` feature macros, hand-rolled CLI parser, and
``config.h`` index-width switch — reference acg/config.h:59-94,
cuda/acg-cuda.c:445-530).  Index width is a dtype parameter; feature gating is
runtime (JAX platform query) rather than compile-time.
"""

from __future__ import annotations

import dataclasses
import enum

import numpy as np


def ensure_x64_for(dtype) -> None:
    """Enable JAX 64-bit mode when a 64-bit value type is requested.

    JAX silently truncates f64/i64 arrays to 32 bits unless
    ``jax_enable_x64`` is set; without this, a solve requested at
    ``--dtype float64`` would run in f32 and iterative recurrences (notably
    pipelined CG's ``denom = delta - beta*gamma/alpha`` breakdown test,
    acg_tpu/solvers/loops.py) hit roundoff breakdown before reaching tight
    tolerances — the reference is natively double everywhere (acg/vector.h),
    so 64-bit requests must be honored, not truncated."""
    if np.dtype(dtype).itemsize >= 8:
        import jax

        jax.config.update("jax_enable_x64", True)


class SolverKind(str, enum.Enum):
    """Solver variants (ref cuda/acg-cuda.c:120-127 ``enum solvertype``).

    The reference's host-initiated/device-initiated distinction collapses on
    TPU: ``CG`` and ``CG_PIPELINED`` both run the entire solve loop on device
    inside one jitted ``lax.while_loop`` (the analog of the reference's
    monolithic device kernel); ``acg-device``/``acg-device-pipelined`` are
    therefore aliases accepted by the CLI.
    """

    HOST = "host"               # numpy reference (ref acg/cg.c)
    CG = "cg"                   # classic CG, 1 halo + 2 allreduce/iter
    CG_PIPELINED = "cg-pipelined"  # Ghysels/Vanroose pipelined, 1 allreduce/iter
    CG_SSTEP = "cg-sstep"       # communication-reduced s-step CG: 1 halo +
    #                             1 Gram allreduce per s iterations
    #                             (arXiv:2501.03743; SolverOptions.sstep)
    CG_DEVICE = "cg-device"           # alias of CG (fully on-device already)
    CG_DEVICE_PIPELINED = "cg-device-pipelined"  # alias of CG_PIPELINED


class HaloMethod(str, enum.Enum):
    """Halo-exchange implementations (replaces the reference's four comm
    backends, ref acg/comm.h:84-92; see acg_tpu/parallel/halo_exchange.py)."""

    PPERMUTE = "ppermute"       # static per-round ppermute schedule (ICI neighbour traffic)
    ALLGATHER = "allgather"     # all_gather of packed border values (robust fallback)
    RDMA = "rdma"               # device-initiated Pallas remote DMA (the
    #                             NVSHMEM-put analog): refused with
    #                             ERR_NOT_SUPPORTED until verified across
    #                             chips (parallel/rdma_halo.py)


@dataclasses.dataclass(frozen=True)
class SolverOptions:
    """Stopping criteria and measurement knobs.

    Mirrors the reference solver signature and CLI defaults
    (ref acg/cg.c:198-208 stopping criteria, cuda/acg-cuda.c:507-511 defaults:
    maxits=100, residual rtol=1e-9, warmup=10).  A tolerance of 0 disables
    that criterion.  Convergence iff any enabled criterion holds:

      ``|dx| < diffatol``, ``|dx| < diffrtol*|x0|``,
      ``|b-Ax| < residual_atol``, ``|b-Ax| < residual_rtol*|b-Ax0|``.
    """

    maxits: int = 100
    diffatol: float = 0.0
    diffrtol: float = 0.0
    residual_atol: float = 0.0
    residual_rtol: float = 1e-9
    warmup: int = 0
    # Convergence is tested on device every `check_every` iterations inside the
    # jitted while_loop; 1 = every iteration (exact parity with reference).
    check_every: int = 1
    # Pipelined CG: recompute r/w/s/z from their definitions every
    # `replace_every` iterations (0 = off), correcting recurrence drift at
    # tight tolerances (see acg_tpu/solvers/loops.py); None = by the
    # vector dtype (replace_period).
    replace_every: int | None = None
    # Run the device while_loop in host-dispatched segments of at most
    # `segment_iters` iterations, resuming from the exact loop carry —
    # numerically identical to the single-program solve, one extra
    # dispatch per segment.  0 = one program (the monolithic-kernel
    # semantics).  Needed where the execution environment bounds a single
    # device program's runtime (the pre-bring-up chip access killed
    # executions past ~60 s; slow paths like the gather ELL tier at large
    # n exceed that within ~500 iterations).  Classic AND pipelined CG, single-
    # chip and distributed (the pipelined carry-resume was wired in PR 7;
    # its carry ends with a device-computed continue bit so the host
    # driver never re-derives the exit predicate).  The s-step solvers
    # raise ERR_NOT_SUPPORTED (their outer carry is not segmented —
    # each dispatch is already bounded at maxits*s block granularity).
    segment_iters: int = 0
    # Live-progress tier (the reference's verbose per-iteration residual
    # printout, acg/cg.c): stream one "iteration k: rnrm2 ..." line every
    # `monitor_every` iterations from inside the fused device loop via a
    # throttled jax.debug.callback (acg_tpu/obs/monitor.py).  0 = off
    # (no callback is traced into the loop at all).  Diagnostic tier:
    # emission is asynchronous and must not be used for timing.
    monitor_every: int = 0
    # s-step (communication-reduced) CG block size: the cg_sstep solvers
    # build an s-dimensional Newton-shifted Krylov basis per outer step,
    # reduce ONE (2s+1)x(2s+1) Gram matrix (one psum), and run the s
    # inner updates as local recurrences on the Gram coefficients — the
    # per-iteration collective count drops to 1/s (arXiv:2501.03743; see
    # acg_tpu/solvers/loops.py cg_sstep_while).  0 = not an s-step solve
    # (the field is ignored by the classic/pipelined solvers); the
    # cg_sstep solvers require 2 <= sstep <= 16.  Numerical safety is
    # certified, not assumed: the residual is replaced from its
    # definition every outer block, every exit is certified against the
    # true residual, and an indefinite/ill-conditioned Gram falls back
    # to classic CG (surfaced via SolveResult.kernel_note).
    sstep: int = 0
    # Deep-pipelined CG depth: the cg-pipelined-deep solvers keep
    # `pipeline_depth` global reductions in flight per iteration by
    # running the iteration on a shifted-Newton auxiliary basis
    # (arXiv:1801.04728 p(l)-CG with the global-reduction pipelining of
    # arXiv:1905.06850; see acg_tpu/solvers/loops.py
    # cg_pipelined_deep_while).  1 = the ordinary one-deep pipelined
    # solver (cg-pipelined-deep dispatches to it bit-identically); the
    # deep loop requires 2 <= pipeline_depth <= 8 (basis conditioning
    # is the practical ceiling, as for sstep).  Ignored by every other
    # solver kind.
    pipeline_depth: int = 1
    # Halo wire format: the on-the-wire encoding of halo-exchange
    # payloads (ppermute / all_gather messages) in the distributed
    # solvers.  "f32" (default) sends border values at the vector dtype
    # — the compiled program is bit-identical to one built before this
    # option existed.  "bf16" truncates each message to bfloat16 on the
    # wire (2x narrower payload, ~8 significand bits); "int16-delta"
    # block-scales each message around its midpoint into int16 (2x
    # narrower, ~16 significand bits across the message's dynamic
    # range).  Both decode to f32 BEFORE any arithmetic — accumulation
    # is always full precision; only the wire is narrow — and every
    # exit still passes the certified true-residual test, so a wire-
    # induced stall surfaces as extra iterations, never as a falsely
    # converged answer.  psum payloads are never compressed (the
    # max(itemsize, 4) upcast law, analysis/contracts.py C10).
    halo_wire: str = "f32"
    # Resilience tier (acg_tpu/robust/): test the iteration's
    # already-reduced scalars (|r|², p'Ap; pipelined γ, δ) for
    # finiteness at the existing `check_every` points and end the solve
    # with SolveResult.status == ERR_FAULT_DETECTED instead of spinning
    # to maxits on NaN.  No new collectives ever; False (the default)
    # traces the exact unguarded program — zero hot-loop cost when off
    # (PERF.md "Resilience overhead").  solve_resilient() forces it on.
    guard_nonfinite: bool = False
    # Preconditioner of single-chip classic CG: "none", or "mg" — HPCG's
    # multigrid V-cycle (acg_tpu/ops/mg.py) over `mg_levels` grids, each
    # halved in every dimension, with one 8-colour symmetric Gauss-Seidel
    # sweep before and after the coarse correction and one on the
    # coarsest grid.  "mg" needs a recognized 3-D constant-coefficient
    # stencil whose grid halves mg_levels - 1 times; every other solver,
    # batch and operator raises ERR_NOT_SUPPORTED, never an
    # unpreconditioned solve.  "none" traces the unpreconditioned loop.
    precond: str = "none"
    mg_levels: int = 4

    def __post_init__(self):
        if self.maxits < 0:
            raise ValueError("maxits must be >= 0")
        if self.check_every < 1:
            raise ValueError("check_every must be >= 1")
        if self.replace_every is not None and self.replace_every < 0:
            raise ValueError("replace_every must be >= 0")
        if self.segment_iters < 0:
            raise ValueError("segment_iters must be >= 0")
        if self.monitor_every < 0:
            raise ValueError("monitor_every must be >= 0")
        if self.sstep != 0 and not 2 <= self.sstep <= 16:
            raise ValueError("sstep must be 0 (not an s-step solve) or "
                             "in [2, 16] (basis conditioning is the "
                             "practical ceiling; see PERF.md)")
        if not 1 <= self.pipeline_depth <= 8:
            raise ValueError("pipeline_depth must be in [1, 8] (1 = the "
                             "ordinary pipelined solver; basis "
                             "conditioning is the practical ceiling, "
                             "see PERF.md)")
        if self.precond not in ("none", "mg"):
            raise ValueError("precond must be 'none' or 'mg' (HPCG's "
                             "multigrid V-cycle)")
        if self.mg_levels < 1:
            raise ValueError("mg_levels must be >= 1")
        if self.halo_wire not in ("f32", "bf16", "int16-delta"):
            raise ValueError("halo_wire must be one of 'f32' (full-width "
                             "wire, the default), 'bf16', 'int16-delta'")


@dataclasses.dataclass(frozen=True)
class PartitionOptions:
    """Partitioning knobs (ref acg/metis.h:39 partitioner enum,
    cuda/acg-cuda.c:341-346 --partition/--seed flags)."""

    nparts: int = 1
    method: str = "auto"        # auto | rb (recursive bisection) | bfs | grid | file
    seed: int = 0
    partition_file: str | None = None


def value_dtype(name: str):
    """Map a precision name to a numpy dtype for matrix/vector values.

    fp64 is the reference's precision (CUDA doubles); on TPU fp64 is emulated
    and slow, so fp32 is the default device precision and fp64 is validated on
    CPU.  See solvers docstrings for the compensated-arithmetic option.
    """
    try:
        dt = np.dtype(name)
    except TypeError as e:
        raise ValueError(f"unknown value dtype {name!r}") from e
    if dt not in (np.dtype(np.float32), np.dtype(np.float64)):
        raise ValueError(f"value dtype must be float32 or float64, got {name!r}")
    return dt


def index_dtype(idx_size: int = 32):
    """acgidx_t analog: 32- or 64-bit indices (ref acg/config.h:59-94)."""
    if idx_size == 32:
        return np.dtype(np.int32)
    if idx_size == 64:
        return np.dtype(np.int64)
    raise ValueError("idx_size must be 32 or 64")


# Residual-replacement period of pipelined CG in fp32 and narrower
# vectors when the caller leaves ``replace_every`` unset: the fp32
# recurrences lose rtol 1e-5 within ~50 iterations at 128³ and diverge
# without replacement; R = 10..100 all converge in 419-430 iterations at
# 160³ (classic CG: 413; CPU, PR 21).  fp64 keeps 0 (off).
REPLACE_EVERY_F32 = 50


def replace_period(replace_every: int | None, dtype) -> int:
    """The replacement period a pipelined solve runs: ``replace_every``
    when set, else :data:`REPLACE_EVERY_F32` for vectors narrower than
    fp64 and 0 for fp64."""
    if replace_every is not None:
        return int(replace_every)
    return REPLACE_EVERY_F32 if np.dtype(dtype).itemsize < 8 else 0
