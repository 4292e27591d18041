"""Level-1 BLAS and sparse vector ops on device.

The TPU counterpart of the reference's vector layer (reference
acg/vector.c:482-842): scal/axpy/aypx/dot/nrm2/asum/iamax plus the
sparse-BLAS gather/scatter family (usga/usgz/ussc/usddot/usdaxpy).  In the
solvers these ops appear inline inside jitted loops (XLA fuses them); this
module exposes them as standalone jitted primitives for library users, for
the per-op instrumentation mode (acg_tpu/utils/stats.py), and for tests.

Ghost semantics: packed vectors carry ghost entries at the tail
(reference acg/vector.h:58-161 ``num_ghost_nonzeros`` excluded from
reductions).  Reductions here take an optional static ``nexclude`` —
the number of trailing entries to ignore — mirroring that contract.

Distributed use: pass ``axis_name`` to the reductions inside ``shard_map``
to get the psum-reduced value (reference acgvector_ddotmpi/dnrm2mpi,
acg/vector.c:843-937).

Batched (multi-RHS) semantics: every op accepts an optional leading batch
dimension — vectors are ``(n,)`` or ``(B, n)``; the system axis is always
the LAST one.  Reductions return a ``(B,)`` per-system vector for batched
operands (one value per right-hand side) and a scalar for 1-D operands,
with the 1-D reduction kept bit-identical to the historical ``jnp.vdot``
formulation (B=1 via a 1-D vector preserves today's numerics exactly).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "dscal", "daxpy", "daypx", "dcopy", "dzero", "batched_dot",
    "ddot", "dnrm2", "dnrm2sqr", "dasum", "idamax",
    "gram", "block_dot",
    "usga", "usgz", "ussc", "usddot", "usdaxpy",
]


def allreduce(v, axis_name):
    """``v`` summed over the mesh axis (``acg.allreduce``), or ``v`` off
    the mesh."""
    if not axis_name:
        return v
    with jax.named_scope("acg.allreduce"):
        return jax.lax.psum(v, axis_name)


@functools.partial(jax.jit, inline=True)
def dscal(a, x):
    """x <- a*x (ref acgvector_dscal, acg/vector.c:482)."""
    return a * x


@functools.partial(jax.jit, inline=True)
def daxpy(a, x, y):
    """y <- a*x + y (ref acgvector_daxpy, acg/vector.c:506)."""
    return y + a * x


@functools.partial(jax.jit, inline=True)
def daypx(a, x, y):
    """y <- a*y + x (ref acgvector_daypx, acg/vector.c:533)."""
    return a * y + x


@functools.partial(jax.jit, inline=True)
def dcopy(x):
    """y <- x (ref device dcopy, acg/cg-kernels-cuda.cu:539)."""
    return jnp.copy(x)


def dzero(n, dtype=jnp.float32):
    """y <- 0 (ref device dzero, acg/cg-kernels-cuda.cu:549)."""
    return jnp.zeros(n, dtype=dtype)


def _mask_tail(x, nexclude: int):
    # static slice: ghosts live at the tail of a packed vector (the last
    # axis — batched vectors carry the system axis last); slice_in_dim
    # rather than x[..., :stop], whose ellipsis form lowers to a gather
    if not nexclude:
        return x
    return jax.lax.slice_in_dim(x, 0, x.shape[-1] - nexclude, axis=-1)


def batched_dot(x, y):
    """Per-system dot for ``(B, n)`` operands (a ``(B,)`` result); for 1-D
    operands exactly ``jnp.vdot`` — the ONE place the solvers' batched
    reduction formulation lives, so the B=1-in-1-D path stays bit-identical
    to the historical scalar reduction."""
    if x.ndim == 1:
        return jnp.vdot(x, y)
    return jnp.sum(x * y, axis=-1)


def gram(V, axis_name: str | None = None):
    """Gram matrix of an m-vector block through ONE fused tall-skinny
    matmul — the s-step CG reduction (arXiv:2501.03743): all m² inner
    products of the ``(m, n)`` basis block ``V`` land in a single
    ``(m, m)`` result, which on TPU is one MXU contraction over the long
    axis instead of m² separate VPU reductions.

    Batched operands carry the system axis in the MIDDLE — ``V`` of shape
    ``(m, B, n)`` (the layout a per-system basis stack naturally has:
    ``jnp.stack`` of B-batched vectors) returns a per-system ``(B, m, m)``
    Gram stack.

    Distributed use: pass ``axis_name`` inside ``shard_map`` — the local
    Gram is psum'd as ONE collective of m² scalars, the "one reduction
    per s iterations" communication contract of the s-step loop
    (acg_tpu/solvers/loops.py ``cg_sstep_while``)."""
    # HIGHEST precision: the s-step loop's convergence, divergence-guard
    # and indefinite-Gram decisions all stand on these entries — the TPU
    # default would run f32 contractions in bf16 MXU passes (~1e-3
    # relative error, far above the tolerances the loop certifies)
    prec = jax.lax.Precision.HIGHEST
    if V.ndim == 3:
        G = jnp.einsum("ibn,jbn->bij", V, V, precision=prec)
    else:
        G = jnp.matmul(V, V.T, precision=prec)
    return allreduce(G, axis_name)


def block_dot(V, w, axis_name: str | None = None):
    """All m inner products <V_i, w> of a basis block against one vector
    in a single fused matvec-shaped contraction (an ``(m,)`` result; the
    one-RHS face of :func:`gram`).  Batched: ``V`` of shape ``(m, B, n)``
    against ``w`` of shape ``(B, n)`` returns ``(B, m)``.  ``axis_name``
    psums the result (one collective for all m products)."""
    prec = jax.lax.Precision.HIGHEST      # see gram()
    if V.ndim == 3:
        d = jnp.einsum("ibn,bn->bi", V, w, precision=prec)
    else:
        d = jnp.matmul(V, w, precision=prec)
    return allreduce(d, axis_name)


@functools.partial(jax.jit, static_argnames=("nexclude", "axis_name"))
def ddot(x, y, nexclude: int = 0, axis_name: str | None = None):
    """dot(x, y), excluding ``nexclude`` trailing (ghost) entries; psum'd
    over ``axis_name`` when given (ref acgvector_ddot / _ddotmpi,
    acg/vector.c:561-594,843).  Batched operands reduce per system."""
    d = batched_dot(_mask_tail(x, nexclude), _mask_tail(y, nexclude))
    return allreduce(d, axis_name)


@functools.partial(jax.jit, static_argnames=("nexclude", "axis_name"))
def dnrm2sqr(x, nexclude: int = 0, axis_name: str | None = None):
    """|x|^2 with ghost exclusion (ref acgvector_dnrm2sqr,
    acg/vector.c:620)."""
    xm = _mask_tail(x, nexclude)
    d = batched_dot(xm, xm)
    return allreduce(d, axis_name)


@functools.partial(jax.jit, static_argnames=("nexclude", "axis_name"))
def dnrm2(x, nexclude: int = 0, axis_name: str | None = None):
    """|x|_2 (ref acgvector_dnrm2, acg/vector.c:598 / _dnrm2mpi :902)."""
    return jnp.sqrt(dnrm2sqr(x, nexclude=nexclude, axis_name=axis_name))


@functools.partial(jax.jit, static_argnames=("nexclude", "axis_name"))
def dasum(x, nexclude: int = 0, axis_name: str | None = None):
    """sum |x_i| (ref acgvector_dasum, acg/vector.c:652)."""
    d = jnp.sum(jnp.abs(_mask_tail(x, nexclude)), axis=-1)
    return allreduce(d, axis_name)


@functools.partial(jax.jit, static_argnames=("nexclude",))
def idamax(x, nexclude: int = 0):
    """argmax |x_i| (ref acgvector_iamax, acg/vector.c:684)."""
    return jnp.argmax(jnp.abs(_mask_tail(x, nexclude)), axis=-1)


# ---- sparse BLAS: packed gather/scatter (ref acg/vector.c:716-842) ------
#
# NOTE on TPU cost: arbitrary gathers/scatters run far below HBM bandwidth
# on TPU (measured ~10 GB/s effective); these ops are intended for *small*
# index sets (halo packs over border nodes), exactly how the reference uses
# them, not for bulk data movement.


@functools.partial(jax.jit, inline=True)
def usga(x, idx):
    """Packed gather: z[k] = x[idx[k]] (ref acgvector_usga,
    acg/vector.c:716)."""
    return x[idx]


@functools.partial(jax.jit, inline=True)
def usgz(x, idx):
    """Gather-and-zero: z[k] = x[idx[k]]; x[idx[k]] = 0
    (ref acgvector_usgz, acg/vector.c:744)."""
    z = x[idx]
    return z, x.at[idx].set(0)


@functools.partial(jax.jit, inline=True)
def ussc(x, z, idx):
    """Packed scatter: x[idx[k]] = z[k] (ref acgvector_ussc,
    acg/vector.c:772)."""
    return x.at[idx].set(z)


@functools.partial(jax.jit, inline=True)
def usddot(z, x, idx):
    """Packed dot: sum_k z[k]*x[idx[k]] (ref acgvector_usddot,
    acg/vector.c:796)."""
    return jnp.vdot(z, x[idx])


@functools.partial(jax.jit, inline=True)
def usdaxpy(a, z, x, idx):
    """Packed axpy: x[idx[k]] += a*z[k] (ref acgvector_usdaxpy,
    acg/vector.c:820)."""
    return x.at[idx].add(a * z)
