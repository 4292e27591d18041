"""Per-op performance instrumentation (the ACG_ENABLE_PROFILING tier).

The reference has two instrumentation tiers (SURVEY §5.1): always-on
aggregate counters filled from event pairs around every gemv/dot/axpy/
allreduce/halo call (reference acg/cgcuda.c:583-605, drained at
:1023-1061).  On TPU the hot loop is ONE fused executable, so per-op times
cannot be observed inside it without destroying the fusion that makes it
fast.  Instead, this module times each op class *in isolation* after
warmup — the exact analog of the reference's warmup loops per op class
(reference acg/cgcuda.c:607-705) — and fills the same
:class:`~acg_tpu.solvers.base.OpCounters` table using the known per-op
count cadence of the algorithm (classic CG: 1 gemv, 2 dots, 3 axpys per
iteration, ref acg/cgcuda.c:845-1020; pipelined: 1 gemv, 1 fused 2-dot,
one 6-vector fused update, ref :1676-1788) and the reference's byte/flop
models (3 flops/nnz SpMV ref :885; 12 flops/row fused update ref :1783).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.config import replace_period
from acg_tpu.solvers.base import SolveStats
from acg_tpu.utils.stats import time_op


def _fill(c, t_once: float, n: int, bytes_once: int, flops_once: int):
    c.t += t_once * n
    c.n += n
    c.bytes += bytes_once * n
    c.flops += flops_once * n


def profile_ops(dev, stats: SolveStats, niterations: int,
                pipelined: bool = False,
                replace_every: int = 0) -> SolveStats:
    """Fill per-op counters for a single-chip solve on operator ``dev``
    (DeviceEll or DeviceDia) with ``niterations`` iterations."""
    from acg_tpu.ops import blas1

    n = int(dev.nrows_padded)
    # vectors use the COMPUTE dtype; the operator may be stored narrower
    # (mat_dtype policy) — price the band/vals stream at its own width
    vdt = np.dtype(getattr(dev, "vec_dtype", "float32"))
    vb = vdt.itemsize
    mb = dev.mat_itemsize
    k = max(niterations, 1)

    # per-op byte models (HBM streams)
    if hasattr(dev, "bands"):           # DIA: bands + x read + y write
        gemv_bytes = dev.bands.size * mb + 2 * n * vb
    elif hasattr(dev, "seg"):           # sgell: slot vals + idx + the 8
        #                                 (1,128) segment rows per slot + y
        gemv_bytes = (dev.vals.size * mb
                      + dev.idx.size * dev.idx.dtype.itemsize
                      + dev.vals.size * vb      # segment fetches, 1 row
                      #                           per (slot, sublane)
                      + n * vb)
    else:                               # ELL: vals + colidx + x gather + y
        gemv_bytes = (dev.vals.size * (mb + dev.colidx.dtype.itemsize)
                      + 3 * n * vb)
    gemv_flops = 2 * dev.nnz

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(n).astype(vdt))
    y = jnp.asarray(rng.standard_normal(n).astype(vdt))

    t_gemv = time_op(jax.jit(dev.matvec), x)
    t_dot = time_op(blas1.ddot, x, y)
    t_axpy = time_op(blas1.daxpy, jnp.asarray(1.5, vdt), x, y)
    t_nrm2 = time_op(blas1.dnrm2, x)
    t_copy = time_op(blas1.dcopy, x)

    # counts per the algorithm cadence (+1 gemv/dot for the r0 prologue;
    # +4 matvecs per residual-replacement step, acg_tpu/solvers/loops.py)
    replace_every = replace_period(replace_every, vdt)
    ngemv = k + 1 + (4 * (k // replace_every)
                     if pipelined and replace_every else 0)
    ndots = 2 * k + 1
    naxpy = (3 if not pipelined else 6) * k + 1
    _fill(stats.gemv, t_gemv, ngemv, gemv_bytes, gemv_flops)
    _fill(stats.dot, t_dot, ndots, 2 * n * vb, 2 * n)
    _fill(stats.axpy, t_axpy, naxpy, 3 * n * vb, 2 * n)
    _fill(stats.nrm2, t_nrm2, 1, n * vb, 2 * n)
    _fill(stats.copy, t_copy, 2, 2 * n * vb, 0)
    return stats


def profile_dist_ops(ss, stats: SolveStats, niterations: int,
                     pipelined: bool = False,
                     replace_every: int = 0) -> SolveStats:
    """Fill per-op counters for a sharded system by timing each op class
    in isolation over the real mesh: the compute ops (gemv/dot/axpy) as
    sharded per-shard kernels and the communication schedules (halo,
    allreduce) as their collective programs (ref acghaloexchange profiling
    slots, acg/halo.h:343-351, allreduce event pairs acg/cgcuda.c:599-605,
    and the per-op event pairs acg/cgcuda.c:583-605)."""
    from jax.sharding import PartitionSpec as P

    from acg_tpu.parallel.mesh import PARTS_AXIS

    k = max(niterations, 1)
    vb = np.dtype(ss.vec_dtype).itemsize   # halo moves VECTOR values, not
    #                                        (possibly narrowed) matrix vals
    halo_fn = ss.shard_halo_fn()
    mesh = ss.mesh
    spec_v = P(PARTS_AXIS)

    def halo_shard(x, sidx, ridx, ptnr, pidx, gsp, gpp):
        return halo_fn(x[0], sidx[0], ridx[0], ptnr[0], pidx[0], gsp[0],
                       gpp[0])[None]

    halo_jit = jax.jit(jax.shard_map(
        halo_shard, mesh=mesh, in_specs=(spec_v,) * 7, out_specs=spec_v,
        check_vma=False))
    x_sh = ss.zeros_sharded()
    t_halo = time_op(halo_jit, x_sh, ss.send_idx, ss.recv_idx, ss.partner,
                     ss.pack_idx, ss.ghost_src_part, ss.ghost_src_pos)

    def psum_shard(v):
        return jax.lax.psum(jnp.vdot(v[0], v[0]), PARTS_AXIS)

    psum_jit = jax.jit(jax.shard_map(
        psum_shard, mesh=mesh, in_specs=(spec_v,), out_specs=P(),
        check_vma=False))
    t_allreduce = time_op(psum_jit, x_sh)

    # compute ops, timed as the sharded programs the solve actually runs
    n_tot = int(ss.nparts * ss.nown_max)
    ib = ss.icols.dtype.itemsize
    iface_bytes = sum(int(a.nbytes) for a in ss.iface_op_arrays())
    if ss.local_fmt == "dia":      # bands stream + x read + y write
        local_bytes = int(ss.lbands.size) * ss.lbands.dtype.itemsize
    else:                          # vals + colidx streams + x gather
        local_bytes = int(ss.lvals.size) * (ss.lvals.dtype.itemsize + ib)
    gemv_bytes = local_bytes + iface_bytes + 3 * n_tot * vb

    local_mv = ss.local_matvec_fn()
    iface_add, _ = ss.iface_fns()

    def gemv_shard(lops, iops, x, g):
        # local + interface SpMV, the full operator application the solve
        # performs (ghost values irrelevant for timing — same work)
        lops = tuple(a[0] for a in lops)
        iops = tuple(a[0] for a in iops)
        return iface_add(local_mv(x[0], lops), iops, g[0])[None]

    gemv_jit = jax.jit(jax.shard_map(
        gemv_shard, mesh=mesh, in_specs=(spec_v,) * 4, out_specs=spec_v,
        check_vma=False))
    g_sh = jnp.zeros((ss.nparts, ss.nghost_max),
                     dtype=np.dtype(ss.vec_dtype))
    t_gemv = time_op(gemv_jit, ss.local_op_arrays(), ss.iface_op_arrays(),
                     x_sh, g_sh)

    def dot_shard(u, v):
        # LOCAL vdot only: the psum is priced separately under allreduce
        # (timing vdot+psum here would double-count the reduction)
        return jnp.vdot(u[0], v[0])[None]

    dot_jit = jax.jit(jax.shard_map(
        dot_shard, mesh=mesh, in_specs=(spec_v, spec_v), out_specs=spec_v,
        check_vma=False))
    t_dot = time_op(dot_jit, x_sh, x_sh)

    def axpy_shard(u, v):
        return (v[0] + 1.5 * u[0])[None]

    axpy_jit = jax.jit(jax.shard_map(
        axpy_shard, mesh=mesh, in_specs=(spec_v, spec_v), out_specs=spec_v,
        check_vma=False))
    t_axpy = time_op(axpy_jit, x_sh, x_sh)

    replace_every = replace_period(replace_every, ss.vec_dtype)
    ngemv = k + 1 + (4 * (k // replace_every)
                     if pipelined and replace_every else 0)
    ndots = 2 * k + 1
    naxpy = (3 if not pipelined else 6) * k + 1
    _fill(stats.gemv, t_gemv, ngemv, gemv_bytes, 2 * ss.nnz)
    _fill(stats.dot, t_dot, ndots, 2 * n_tot * vb, 2 * n_tot)
    _fill(stats.axpy, t_axpy, naxpy, 3 * n_tot * vb, 2 * n_tot)

    halo_bytes = ss.halo.total_send_values * vb
    nmsgs = sum(len(p.neighbors) for p in ss.ps.parts)
    nred = (2 * k + 1) if not pipelined else (k + 1)
    _fill(stats.halo, t_halo, k + 1, halo_bytes, 0)
    _fill(stats.allreduce, t_allreduce, nred,
          8 * ss.nparts if not pipelined else 16 * ss.nparts, 0)
    stats.nhalomsgs += nmsgs * (k + 1)
    return stats
