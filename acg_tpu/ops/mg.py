"""HPCG's multigrid preconditioner, matrix-free on every level.

One application z = M r is one V-cycle of the HPCG reference
(``ComputeMG_ref``, HPCG Technical Specification, SAND2013-8752): on
each level but the coarsest, one symmetric Gauss-Seidel sweep from
x = 0, the residual r - A x, restriction by injection
``rc = (r - A x)[2i, 2j, 2k]``, the V-cycle on the coarse grid,
prolongation as the transpose of injection ``x[2i, 2j, 2k] += xc``, and
one more symmetric sweep; on the coarsest level one symmetric sweep
from zero.  Every level's operator is the fine stencil's arms and
coefficients on a grid halved in each dimension (HPCG rediscretizes
the same 27-point operator), so the hierarchy is static: it compiles
into the executable and uploads nothing.

The smoother is an 8-colour symmetric Gauss-Seidel: the colour of a
point is the parity of (i, j, k), and no arm of a nearest-neighbour
stencil joins two points of one colour, so updating one colour at a
time is exact Gauss-Seidel in the colour ordering (forward: colours
0..7, backward: 7..0).  HPCG permits reordering its SymGS; its
reference sweeps lexicographically (``acg_tpu/solvers/mg_host.py``
keeps both orders for the record).

Layout: within a level a vector is held as its 8 colour sub-lattices,
``part[c] = v[a::2, b::2, c::2]`` for colour c = (a, b, c) in
:data:`COLOURS`, stacked in one array.  Arm d of a point of colour c
reads sub-lattice ``(c + d) % 2`` shifted by ``(c + d) // 2`` (a
zero-filled window, the Dirichlet truncation), so one colour update
costs an eighth of a stencil application.  Each update is written into
the stack in place (``dynamic_update_slice``): the next colour reads it
from memory, and no compiler fuses a chain of updates into one
recomputation.  Sub-lattice (0, 0, 0) IS the coarse grid in its
natural layout: restriction takes the residual at that colour alone and
prolongation adds the coarse correction into it.  Between layouts:
strided slices and interior-padded ``lax.pad`` — no gather and no
scatter anywhere (contract C5).

Layer scopes (acg_tpu/obs/trace.py): ``acg.mg.smooth``,
``acg.mg.residual``, ``acg.mg.restrict`` and ``acg.mg.prolong`` inside
the application; the solve loop that applies M scopes the whole of it
``acg.precond``, as it scopes its operator ``acg.spmv``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math

import jax
import jax.numpy as jnp

from acg_tpu.errors import AcgError, Status

COLOURS = tuple(itertools.product((0, 1), repeat=3))
_CENTRE = (0, 0, 0)
# forward 0..7, then backward; colour 7 is not updated twice in a row
# (its neighbours have not moved, so the repeat would change nothing)
_SWEEP = tuple(range(8)) + tuple(range(6, -1, -1))


@dataclasses.dataclass(frozen=True)
class MgPlan:
    """The V-cycle of one stencil: the fine ``grid``, the stencil's
    ``arms`` as ``(digits, coefficient)`` pairs, and the number of
    ``levels``.  Hashable: it is a static argument of the solve."""

    grid: tuple
    arms: tuple
    levels: int

    @property
    def note(self) -> str:
        """``SolveResult.precond`` for a solve under this plan."""
        return f"mg{self.levels} symgs8c"


def mg_plan(stencil, levels: int) -> MgPlan:
    """The V-cycle for ``stencil`` (a recognized
    :class:`~acg_tpu.ops.stencil.StencilSpec`, or None), or
    ``ERR_NOT_SUPPORTED`` naming why there is none: the operator must be
    a 3-D constant-coefficient nearest-neighbour stencil with a positive
    centre and symmetric arms (7- or 27-point), on a grid that halves
    ``levels - 1`` times to a coarsest grid at least 2 wide."""
    def refuse(why: str):
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       f"precond='mg' (HPCG multigrid): {why}")

    if stencil is None:
        refuse("the operator is not a recognized constant-coefficient "
               "grid stencil")
    grid = tuple(int(n) for n in stencil.grid)
    if len(grid) != 3:
        refuse(f"the stencil is on a {len(grid)}-D grid, not 3-D")
    arms = dict(zip(stencil.digits, stencil.coeffs))
    if arms.get(_CENTRE, 0.0) <= 0.0:
        refuse("the stencil has no positive centre coefficient")
    if any(arms.get(tuple(-g for g in d)) != c for d, c in arms.items()):
        refuse("the stencil's arms are not symmetric")
    step = 1 << (levels - 1)
    if any(n % step or n // step < 2 for n in grid):
        refuse(f"grid {list(grid)} does not halve {levels - 1} time(s) "
               f"to a coarsest grid at least 2 wide (each dimension "
               f"must be a multiple of {step}, at least {2 * step})")
    return MgPlan(grid=grid, arms=tuple(sorted(arms.items())),
                  levels=int(levels))


def _part_shape(shape: tuple, colour: tuple) -> tuple:
    return tuple((n - a + 1) // 2 for n, a in zip(shape, colour))


def _stack_shape(shape: tuple) -> tuple:
    """Every colour part is held at the size of colour (0, 0, 0), the
    largest; on an odd dimension the others end in one zero slot."""
    return _part_shape(shape, _CENTRE)


def split(v: jax.Array) -> tuple:
    """The 8 colour parts of the 3-D array ``v``, each in a buffer of
    :func:`_stack_shape` plus one spare zero plane (see :func:`_put`)."""
    zero = jnp.zeros((), v.dtype)
    big = _stack_shape(v.shape)
    return tuple(jax.lax.pad(jax.lax.slice(v, colour, v.shape, (2, 2, 2)),
                             zero, [(0, b - m + (ax == 0), 0)
                                    for ax, (b, m) in enumerate(
                                        zip(big, _part_shape(v.shape,
                                                             colour)))])
                 for colour in COLOURS)


def _get(parts: tuple, c: int) -> jax.Array:
    """Colour c's part without its spare plane."""
    p = parts[c]
    return jax.lax.slice(p, (0, 0, 0), (p.shape[0] - 1,) + p.shape[1:])


def _put(parts: tuple, c: int, value: jax.Array) -> tuple:
    """``parts`` with colour c's part replaced in place.  The part's
    buffer is one plane larger than ``value``, so the write is a real
    ``dynamic_update_slice``: every colour update is materialized, and
    no compiler fuses a chain of updates into one recomputation."""
    return parts[:c] + (jax.lax.dynamic_update_slice(
        parts[c], value, (0, 0, 0)),) + parts[c + 1:]


def merge(parts: tuple, shape: tuple) -> jax.Array:
    """The 3-D array of ``shape`` whose colour parts are ``parts``: each
    placed by an interior-padded ``lax.pad``."""
    zero = jnp.zeros((), parts[0].dtype)
    out = None
    for c, colour in enumerate(COLOURS):
        m = _part_shape(shape, colour)
        p = jax.lax.slice(parts[c], (0, 0, 0), m)
        placed = jax.lax.pad(p, zero, [(a, n - a - 2 * k + 1, 1)
                                       for n, a, k in zip(shape, colour, m)])
        out = placed if out is None else out + placed
    return out


def _window(src: jax.Array, shift: tuple) -> jax.Array:
    """``out[I] = src[I + shift]`` where in range, else 0 (negative
    padding crops)."""
    zero = jnp.zeros((), src.dtype)
    return jax.lax.pad(src, zero, [(-s, s, 0) for s in shift])


def _arm_sum(plan: MgPlan, parts: tuple, c: int,
             centre: bool) -> jax.Array:
    """Sum over the arms (the centre too when ``centre``) of coefficient
    times neighbour, at the points of colour ``c``: arm d reads colour
    ``(c + d) % 2`` shifted by ``(c + d) // 2``.

    The arms that read one colour differ from c on the same axes and
    form a box of up to 2^3 windows of it; when the box is whole and its
    coefficients equal (the 27-point box, the 7-point cross) it is
    summed separably, one 2-point pass per axis: fewer operations, and
    the CPU compiler's time stays a third of the direct sum's."""
    colour = COLOURS[c]
    groups: dict = {}
    for d, coef in plan.arms:
        if d == _CENTRE and not centre:
            continue
        t = tuple(a + g for a, g in zip(colour, d))
        groups.setdefault(tuple(v % 2 for v in t), []).append(
            (tuple(v // 2 for v in t), coef))
    acc = None
    for s, terms in groups.items():
        src = _get(parts, COLOURS.index(s))
        axes = [ax for ax in range(3) if s[ax] != colour[ax]]
        coefs = {coef for _, coef in terms}
        if len(terms) == 1 << len(axes) and len(coefs) == 1:
            # along a differing axis the shifts are {0, -1} (colour c
            # is even there) or {0, +1} (odd)
            term = src
            for ax in axes:
                step = [0, 0, 0]
                step[ax] = 1 if colour[ax] else -1
                term = term + _window(term, tuple(step))
            term = jnp.asarray(coefs.pop(), src.dtype) * term
        else:
            term = None
            for shift, coef in terms:
                w = jnp.asarray(coef, src.dtype) * _window(src, shift)
                term = w if term is None else term + w
        acc = term if acc is None else acc + term
    return acc


def symgs(plan: MgPlan, x: tuple, r: tuple, shape: tuple) -> tuple:
    """One symmetric 8-colour Gauss-Seidel sweep (forward, then
    backward) on A x = r, both as colour parts of a level of
    ``shape``."""
    diag = jnp.asarray(dict(plan.arms)[_CENTRE], r[0].dtype)
    big = _stack_shape(shape)
    for c in _SWEEP:
        new = (_get(r, c) - _arm_sum(plan, x, c, centre=False)) / diag
        m = _part_shape(shape, COLOURS[c])
        if m != big:
            # a colour that ends short of the stack on an odd dimension
            # keeps zero in its last slot, which its neighbours read
            new = jax.lax.pad(jax.lax.slice(new, (0, 0, 0), m),
                              jnp.zeros((), new.dtype),
                              [(0, b - k, 0) for b, k in zip(big, m)])
        x = _put(x, c, new)
    return x


def _level(plan: MgPlan, r: jax.Array, lvl: int) -> jax.Array:
    """The V-cycle from level ``lvl`` down, on its natural-layout
    right-hand side ``r``; returns x in the same layout."""
    shape = r.shape
    # the fine level's layout changes belong to the application itself
    # (acg.precond); below it they are restriction and prolongation
    def scope(name):
        return jax.named_scope(name) if lvl else contextlib.nullcontext()

    with scope("acg.mg.restrict"):
        rp = split(r)
    with jax.named_scope("acg.mg.smooth"):
        x = symgs(plan, tuple(jnp.zeros_like(p) for p in rp), rp, shape)
    if lvl < plan.levels - 1:
        with jax.named_scope("acg.mg.residual"):
            rc = _get(rp, 0) - _arm_sum(plan, x, 0, centre=True)
        xc = _level(plan, rc, lvl + 1)
        with jax.named_scope("acg.mg.prolong"):
            x = _put(x, 0, _get(x, 0) + xc)
        with jax.named_scope("acg.mg.smooth"):
            x = symgs(plan, x, rp, shape)
    with scope("acg.mg.prolong"):
        return merge(x, shape)


def vcycle(plan: MgPlan, r: jax.Array) -> jax.Array:
    """z = M r for the 3-D ``r`` on ``plan.grid``, at the dtype of r."""
    return _level(plan, r, 0)


def vcycle_flat(plan: MgPlan, front: int = 0):
    """M as a function of a flat solver vector whose grid values start
    at ``front`` (the padded layouts of the solve loops); the entries
    around them come back 0."""
    n = math.prod(plan.grid)

    def apply(r: jax.Array) -> jax.Array:
        v = jax.lax.slice_in_dim(r, front, front + n).reshape(plan.grid)
        z = vcycle(plan, v).reshape(-1)
        return jnp.pad(z, (front, r.shape[-1] - front - n))
    return apply
