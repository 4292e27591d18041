"""Matrix-free constant-coefficient stencil operator: delete the band
stream.

Every solve in this repo is HBM-bound on streaming stored DIA/ELL bands
(obs/roofline.py), yet the dominant structured workloads — the
Poisson-family 5/7/9/27-point operators — have bands that are entirely
*computable* from (grid shape, per-arm coefficient, boundary rule).  This
module regenerates the operator action on the fly instead of reading it
(the matrix-free finite-element argument of Kronbichler et al.,
arXiv:2205.08909): the per-iteration HBM traffic collapses to the vector
streams alone, ``operator_stream_bytes() == 0``, the roofline ceiling
multiplies by the old bands:vectors ratio, and band storage disappears —
the order-of-magnitude capacity step of ROADMAP item 2.

Three layers:

- **Recognition** (:func:`recognize_stencil`): is this stored matrix
  EXACTLY a constant-coefficient nearest-neighbour stencil on a regular
  grid with Dirichlet truncation?  Coefficient uniformity per diagonal
  (the :func:`~acg_tpu.ops.dia.two_value_scales` check), grid hypotheses
  derived from the diagonal offsets, a unique balanced-digit
  decomposition of every offset into per-axis arms, and an EXACT
  zero-pattern match of every band against the predicted boundary mask.
  Only a verified match engages the tier — everything else keeps its
  stored operator, with the reason recorded (the probe-gate discipline
  of every other tier).
- **:class:`DeviceStencil`** — the device operator.  It holds NO device
  arrays: grid, offsets, arm digits and coefficients are all static
  (they compile into the executable; on the Pallas path the coefficients
  live in registers and the boundary masks are synthesized from iota —
  nothing is fetched from HBM).  Its jnp fallback matvec
  (:func:`stencil_matvec`) is bit-compatible with
  ``DeviceDia.matvec`` on the same system: identical per-element
  products in the identical summation order.
- **Pallas kernels** — the resident 2-D SpMV (:func:`stencil_matvec_
  pallas_padded`, optional fused <x, y> like the DIA padded kernel), its
  multi-RHS batched twin, and the single-kernel pipelined-CG iteration
  (:func:`cg_pipelined_iter_stencil`, the matrix-free twin of
  ``_pipe2d_kernel``) — all probe-gated through the shared
  ``pallas_spmv_available`` machinery (groups "stencil2d"/"stpipe2d").
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the stencil kernels share the padded-layout geometry owners with the
# DIA kernels (ONE halo/tail arithmetic for both tiers)
from acg_tpu.ops.pallas_kernels import (LANES, _VMEM_BUDGET, _window_2d,
                                        pad_dia_vectors, padded_halo_rows,
                                        pallas_call)

# recognition is bounded: a "stencil" with more arms than the densest
# supported family (27-pt box) is not one
_MAX_ARMS = 32


# ---------------------------------------------------------------------------
# recognition


@dataclasses.dataclass(frozen=True)
class StencilSpec:
    """A verified constant-coefficient stencil: ``grid`` (row-major, last
    axis fastest), sorted flat diagonal ``offsets``, the per-offset
    per-axis ``digits`` in {-1, 0, 1} (``sum(digits * strides) ==
    offset``), and the per-arm ``coeffs`` (python floats — exact images
    of the stored band values at the recognition dtype)."""

    grid: tuple
    offsets: tuple
    digits: tuple
    coeffs: tuple
    nnz: int

    @property
    def nrows(self) -> int:
        n = 1
        for d in self.grid:
            n *= int(d)
        return n

    def spec_hash(self) -> str:
        """Structure hash of the recognized stencil (grid + arms +
        coefficient bytes at f64) — the identity the tier report and the
        serve-session signature record."""
        h = hashlib.sha256()
        h.update(repr((self.grid, self.offsets, self.digits)).encode())
        h.update(np.asarray(self.coeffs, dtype=np.float64).tobytes())
        return h.hexdigest()[:16]

    def as_report(self) -> dict:
        return {"recognized": True, "grid": [int(d) for d in self.grid],
                "offsets": [int(o) for o in self.offsets],
                "coeffs": [float(c) for c in self.coeffs],
                "arms": len(self.offsets),
                "structure_hash": self.spec_hash(), "reason": None}


def stencil_reject_report(reason: str) -> dict:
    """The tier-report verdict for a system that is NOT a recognized
    stencil (the disengagement record of resolve_local_fmt)."""
    return {"recognized": False, "grid": None, "offsets": None,
            "coeffs": None, "arms": 0, "structure_hash": None,
            "reason": reason}


def _grid_hypotheses(n: int, offsets: tuple) -> list:
    """Candidate grid shapes implied by the positive diagonal offsets:
    the inner stride must be 1 (every supported family couples nearest
    neighbours along the fastest axis), outer strides are positive
    offsets dividing n.  Wrong hypotheses are harmless — the exact
    pattern verification rejects them."""
    pos = [int(o) for o in offsets if o > 0]
    hyps: list = []
    if not pos:
        return [(n,)]               # pure-diagonal operator: 1-D grid
    if pos[0] != 1:
        return []
    hyps.append((n,))
    for a in pos:
        if a > 1 and n % a == 0:
            hyps.append((n // a, a))
            for b in pos:
                if b > a and b % a == 0 and n % b == 0:
                    hyps.append((n // b, b // a, a))
    return hyps


def _decompose_offsets(offsets: tuple, grid: tuple):
    """Per-offset balanced digits in {-1, 0, 1}^k with
    ``dot(digits, strides) == offset`` — or None when any offset has no
    (or no UNIQUE) decomposition (ambiguity means the flat offset does
    not identify one arm: a 2-wide inner dim aliases (+1, -1) onto
    (0, +1); reject rather than guess)."""
    k = len(grid)
    strides = [1] * k
    for i in range(k - 2, -1, -1):
        strides[i] = strides[i + 1] * int(grid[i + 1])
    out = []
    for off in offsets:
        sols = [g for g in itertools.product((-1, 0, 1), repeat=k)
                if sum(gi * si for gi, si in zip(g, strides)) == off]
        if len(sols) != 1:
            return None
        out.append(sols[0])
    return tuple(out)


def _verify_pattern(bands: np.ndarray, n: int, grid: tuple,
                    digits: tuple, chunk: int = 1 << 20) -> bool:
    """Every band's zero pattern must EXACTLY equal the predicted
    Dirichlet boundary mask of its arm (chunked O(D·n) host sweep — the
    verification that makes recognition a proof, not a heuristic)."""
    nrp = bands.shape[1]
    for s in range(0, nrp, chunk):
        e = np.arange(s, min(s + chunk, nrp), dtype=np.int64)
        inb = e < n
        coords = np.unravel_index(np.minimum(e, max(n - 1, 0)), grid)
        for d, dg in enumerate(digits):
            ok = inb.copy()
            for ax, g in enumerate(dg):
                if g:
                    nc = coords[ax] + g
                    ok &= (nc >= 0) & (nc < grid[ax])
            if not np.array_equal(bands[d, s: s + len(e)] != 0, ok):
                return False
    return True


def recognize_stencil(A, dtype=None, offsets=None):
    """(StencilSpec, "") when ``A`` is EXACTLY a constant-coefficient
    nearest-neighbour stencil on a regular grid, else (None, reason).

    ``A`` is a host CsrMatrix or DiaMatrix; ``dtype`` is the vector
    dtype the solve will run at — coefficients are read from the
    dtype-cast bands so the matrix-free action reproduces the stored
    tier's values exactly (the same cast discipline as
    ``DeviceDia.from_dia``).  ``offsets`` is an optional precomputed
    sorted unique-diagonal array for a CsrMatrix input (the fast-tier
    resolution sweeps every part once and shares it here)."""
    from acg_tpu.ops.dia import DiaMatrix, two_value_scales
    from acg_tpu.sparse.csr import CsrMatrix

    if isinstance(A, DiaMatrix):
        D = A
    elif isinstance(A, CsrMatrix):
        if A.nrows != A.ncols:
            return None, "matrix is not square"
        if A.nrows == 0 or A.nnz == 0:
            return None, "empty matrix"
        # apply the arm bound BEFORE materializing bands: an unstructured
        # matrix has O(nnz) distinct diagonals and its (D, n) band array
        # would be enormous (a 512k-row random graph: hundreds of GB) —
        # this structure-only sweep costs O(nnz) ints and no values
        ndiags = (len(offsets) if offsets is not None else
                  len(np.unique(A.colidx.astype(np.int64) - A._rowids())))
        if ndiags > _MAX_ARMS:
            return None, (f"{ndiags} diagonals exceed the "
                          f"{_MAX_ARMS}-arm stencil family bound")
        D = DiaMatrix.from_csr(A)
    else:
        return None, f"unsupported operator type {type(A).__name__}"
    if D.nrows != D.ncols:
        return None, "matrix is not square"
    if len(D.offsets) > _MAX_ARMS:
        return None, (f"{len(D.offsets)} diagonals exceed the "
                      f"{_MAX_ARMS}-arm stencil family bound")
    vdt = np.dtype(dtype if dtype is not None else D.bands.dtype)
    cast = np.asarray(D.bands, dtype=vdt)
    scales = two_value_scales(cast)
    if scales is None:
        return None, ("coefficients are not uniform per diagonal "
                      "(variable-coefficient operator)")
    n = D.nrows
    hyps = _grid_hypotheses(n, D.offsets)
    if not hyps:
        return None, ("diagonal offsets do not include the unit stride "
                      "(not a nearest-neighbour grid stencil)")
    for grid in hyps:
        digits = _decompose_offsets(D.offsets, grid)
        if digits is None:
            continue
        if _verify_pattern(cast, n, grid, digits):
            coeffs = tuple(float(s) for s in scales)
            return (StencilSpec(grid=tuple(int(d) for d in grid),
                                offsets=tuple(int(o) for o in D.offsets),
                                digits=digits, coeffs=coeffs,
                                nnz=int(D.nnz)), "")
    return None, ("no grid hypothesis reproduces the boundary zero "
                  "pattern of the stored bands")


# ---------------------------------------------------------------------------
# the jnp (XLA) matrix-free action


def _grid_shift(t: jax.Array, axis: int, g: int) -> jax.Array:
    """Shift by one along ``axis`` with zero fill (Dirichlet truncation):
    out[..., j, ...] = t[..., j+g, ...] where in bounds, else 0."""
    d = t.shape[axis]
    z = jnp.zeros(t.shape[:axis] + (1,) + t.shape[axis + 1:], t.dtype)
    if g > 0:
        return jnp.concatenate(
            [jax.lax.slice_in_dim(t, 1, d, axis=axis), z], axis=axis)
    return jnp.concatenate(
        [z, jax.lax.slice_in_dim(t, 0, d - 1, axis=axis)], axis=axis)


def stencil_matvec(x: jax.Array, grid: tuple, digits: tuple,
                   coeffs: tuple) -> jax.Array:
    """y = stencil @ x through pure grid shifts — the matrix-free XLA
    formulation: no band arrays, no gathers, no masks (the boundary
    truncation IS the zero fill of each axis shift).

    ``x`` is ``(npad,)`` or batched ``(B, npad)`` with ``npad >=
    prod(grid)``; entries past the grid come back exactly 0 (matching
    the all-zero padded bands of the stored DIA tier).  Arms are applied
    in sorted flat-offset order with per-element products identical to
    ``dia_matvec`` on the equivalent band stack, so the two tiers are
    numerically interchangeable — the parity contract
    tests/test_stencil.py pins."""
    n = 1
    for d in grid:
        n *= int(d)
    lead = x.shape[:-1]
    npad = x.shape[-1]
    xg = x if npad == n else jax.lax.slice_in_dim(x, 0, n, axis=-1)
    xg = xg.reshape(lead + tuple(grid))
    nl = len(lead)
    y = jnp.zeros_like(xg)
    for dg, c in zip(digits, coeffs):
        t = xg
        for ax, g in enumerate(dg):
            if g:
                t = _grid_shift(t, nl + ax, g)
        y = y + jnp.asarray(c, x.dtype) * t
    y = y.reshape(lead + (n,))
    if npad != n:
        y = jnp.pad(y, [(0, 0)] * nl + [(0, npad - n)])
    return y


# ---------------------------------------------------------------------------
# Pallas kernels: the bands synthesized in-register


def _stencil_tile_acc(grid, offsets, digits, coeffs, rows_tile, n, hrows,
                      base, load, dt):
    """One (rows_tile, 128) tile of the synthesized stencil action — the
    matrix-free twin of ``pallas_kernels._banded_tile_acc``: instead of
    band tiles DMA'd from HBM, the band value of each element is
    regenerated as coefficient x boundary mask, with the mask computed
    from an iota-derived element index (coefficients are compile-time
    constants — registers; the whole operator costs a handful of integer
    VPU ops per arm and ZERO HBM traffic)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_tile, LANES), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows_tile, LANES), 0)
    e = (base + row - hrows) * LANES + lane        # logical element index
    inb = (e >= 0) & (e < n)
    ec = jnp.clip(e, 0, max(n - 1, 0))
    coords = []
    rem = ec
    for d in reversed(grid[1:]):
        coords.append(rem % d)
        rem = rem // d
    coords.append(rem)
    coords = coords[::-1]
    acc = jnp.zeros((rows_tile, LANES), dtype=dt)
    for off, dg, c in zip(offsets, digits, coeffs):
        q, r = divmod(off, LANES)
        ok = inb
        for ax, g in enumerate(dg):
            if g:
                nc = coords[ax] + g
                ok = ok & (nc >= 0) & (nc < grid[ax])
        b = jnp.where(ok, jnp.asarray(c, dt), jnp.asarray(0.0, dt))
        acc = acc + b * _window_2d(load, q, r, lane)
    return acc


def _stencil2d_padded_kernel(grid, offsets, digits, coeffs, rows_tile, n,
                             hrows, with_dot, x_ref, y_ref, *dot_ref):
    """Padded-layout resident stencil SpMV (the matrix-free twin of
    ``_dia2d_padded_kernel``): x resident in VMEM with the same zero-halo
    contract; halo/tail tiles synthesize zero bands (``e`` out of
    [0, n)), so they write exact zeros and the padded-layout invariant
    survives without masking.  ``with_dot`` fuses the <x, y> partial
    exactly as the DIA kernel does."""
    i = pl.program_id(0)
    base = i * rows_tile
    Rp = x_ref.shape[0]
    hi_cap = Rp - rows_tile
    load = lambda q: x_ref[pl.ds(jnp.clip(base + q, 0, hi_cap),
                                 rows_tile), :]
    acc = _stencil_tile_acc(grid, offsets, digits, coeffs, rows_tile, n,
                            hrows, base, load, y_ref.dtype)
    y_ref[:, :] = acc
    if with_dot:
        @pl.when(i == 0)
        def _zero():
            dot_ref[0][0, 0] = jnp.asarray(0.0, y_ref.dtype)

        dot_ref[0][0, 0] += jnp.sum(x_ref[pl.ds(base, rows_tile), :] * acc)


@functools.partial(jax.jit,
                   static_argnames=("grid", "offsets", "digits", "coeffs",
                                    "rows_tile", "n", "with_dot",
                                    "interpret"))
def stencil_matvec_pallas_padded(grid: tuple, offsets: tuple,
                                 digits: tuple, coeffs: tuple, x_pad,
                                 rows_tile: int = 512, n: int = 0,
                                 with_dot: bool = False,
                                 interpret: bool = False):
    """y = stencil @ x on the padded layout (same contract as
    ``dia_matvec_pallas_2d_padded``: zero halo in and out, optional
    fused scalar <x, y>) — with NO band operand at all."""
    npad = x_pad.shape[-1]
    assert npad % (rows_tile * LANES) == 0
    Rp = npad // LANES
    ntiles = Rp // rows_tile
    hrows = padded_halo_rows(offsets, rows_tile)
    out_shape = [jax.ShapeDtypeStruct((Rp, LANES), x_pad.dtype)]
    out_specs = [pl.BlockSpec((rows_tile, LANES), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)]
    if with_dot:
        out_shape.append(jax.ShapeDtypeStruct((1, 1), x_pad.dtype))
        out_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0),
                                      memory_space=pltpu.SMEM))
    outs = pallas_call(
        functools.partial(_stencil2d_padded_kernel, grid, offsets, digits,
                          coeffs, rows_tile, n, hrows, with_dot),
        name="acg_stencil2d_padded",
        out_shape=tuple(out_shape),
        grid=(ntiles,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=tuple(out_specs),
        interpret=interpret,
    )(x_pad.reshape(Rp, LANES))
    y = outs[0].reshape(npad)
    if with_dot:
        return y, outs[1][0, 0]
    return y


def _stencil2d_batched_kernel(grid, offsets, digits, coeffs, rows_tile, n,
                              hrows, with_dot, x_ref, y_ref, *dot_ref):
    """Multi-RHS twin (grid (ntiles, B), batch fastest): the synthesized
    band values are recomputed per system — integer VPU ops, free next
    to the HBM stream they replace — while every system's x stays
    resident like the batched DIA kernel's."""
    i = pl.program_id(0)
    s = pl.program_id(1)
    base = i * rows_tile
    Rp = x_ref.shape[1]
    hi_cap = Rp - rows_tile
    load = lambda q: x_ref[s, pl.ds(jnp.clip(base + q, 0, hi_cap),
                                    rows_tile), :]
    acc = _stencil_tile_acc(grid, offsets, digits, coeffs, rows_tile, n,
                            hrows, base, load, y_ref.dtype)
    y_ref[0, :, :] = acc
    if with_dot:
        @pl.when(i == 0)
        def _zero():
            dot_ref[0][0, s] = jnp.asarray(0.0, y_ref.dtype)

        dot_ref[0][0, s] += jnp.sum(x_ref[s, pl.ds(base, rows_tile), :]
                                    * acc)


@functools.partial(jax.jit,
                   static_argnames=("grid", "offsets", "digits", "coeffs",
                                    "rows_tile", "n", "with_dot",
                                    "interpret"))
def stencil_matvec_pallas_padded_batched(grid: tuple, offsets: tuple,
                                         digits: tuple, coeffs: tuple,
                                         x_pad, rows_tile: int = 512,
                                         n: int = 0,
                                         with_dot: bool = False,
                                         interpret: bool = False):
    """Batched padded stencil SpMV: ``x_pad`` (B, npad); returns
    (B, npad) plus the per-system <x_s, y_s> vector when ``with_dot``."""
    B, npad = x_pad.shape
    assert npad % (rows_tile * LANES) == 0
    Rp = npad // LANES
    ntiles = Rp // rows_tile
    hrows = padded_halo_rows(offsets, rows_tile)
    out_shape = [jax.ShapeDtypeStruct((B, Rp, LANES), x_pad.dtype)]
    out_specs = [pl.BlockSpec((1, rows_tile, LANES),
                              lambda i, s: (s, i, 0),
                              memory_space=pltpu.VMEM)]
    if with_dot:
        out_shape.append(jax.ShapeDtypeStruct((1, B), x_pad.dtype))
        out_specs.append(pl.BlockSpec((1, B), lambda i, s: (0, 0),
                                      memory_space=pltpu.SMEM))
    outs = pallas_call(
        functools.partial(_stencil2d_batched_kernel, grid, offsets,
                          digits, coeffs, rows_tile, n, hrows, with_dot),
        name="acg_stencil2d_batched",
        out_shape=tuple(out_shape),
        grid=(ntiles, B),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=tuple(out_specs),
        interpret=interpret,
    )(x_pad.reshape(B, Rp, LANES))
    y = outs[0].reshape(B, npad)
    if with_dot:
        return y, outs[1][0]
    return y


def _stpipe2d_kernel(grid, offsets, digits, coeffs, rows_tile, n, hrows,
                     w_ref, ab_ref, z_ref, r_ref, p_ref, s_ref, x_ref,
                     z_o, p_o, s_o, x_o, r_o, w_o, gd_o):
    """One WHOLE pipelined-CG iteration per grid sweep, matrix-free: the
    ``_pipe2d_kernel`` stream set minus the band tiles — q = (A w)_tile
    synthesized from registers, then the Ghysels/Vanroose 6-vector
    update and both fused dots.  The iteration's entire HBM traffic is
    5 tile reads + 6 tile writes: the band stream is GONE."""
    i = pl.program_id(0)
    base = i * rows_tile
    dt = z_o.dtype
    alpha = ab_ref[0]
    beta = ab_ref[1]
    Rp = w_ref.shape[0]
    hi_cap = Rp - rows_tile
    load = lambda q: w_ref[pl.ds(jnp.clip(base + q, 0, hi_cap),
                                 rows_tile), :]
    acc = _stencil_tile_acc(grid, offsets, digits, coeffs, rows_tile, n,
                            hrows, base, load, dt)
    w_tile = w_ref[pl.ds(base, rows_tile), :]
    z2 = acc + beta * z_ref[:, :]
    p2 = r_ref[:, :] + beta * p_ref[:, :]
    s2 = w_tile + beta * s_ref[:, :]
    x2 = x_ref[:, :] + alpha * p2
    r2 = r_ref[:, :] - alpha * s2
    w2 = w_tile - alpha * z2
    z_o[:, :] = z2
    p_o[:, :] = p2
    s_o[:, :] = s2
    x_o[:, :] = x2
    r_o[:, :] = r2
    w_o[:, :] = w2

    @pl.when(i == 0)
    def _zero():
        gd_o[0, 0] = jnp.asarray(0.0, dt)
        gd_o[0, 1] = jnp.asarray(0.0, dt)

    gd_o[0, 0] += jnp.sum(r2 * r2)
    gd_o[0, 1] += jnp.sum(w2 * r2)


@functools.partial(jax.jit,
                   static_argnames=("grid", "offsets", "digits", "coeffs",
                                    "rows_tile", "n", "interpret"))
def cg_pipelined_iter_stencil(grid: tuple, offsets: tuple, digits: tuple,
                              coeffs: tuple, w_pad, z_pad, r_pad, p_pad,
                              s_pad, x_pad, alpha, beta,
                              rows_tile: int = 512, n: int = 0,
                              interpret: bool = False):
    """One pipelined-CG iteration on the padded layout, matrix-free (see
    :func:`_stpipe2d_kernel`): returns (z', p', s', x', r', w', gamma,
    delta) — the contract of ``cg_pipelined_iter_pallas`` with the band
    operand deleted."""
    npad = w_pad.shape[-1]
    assert npad % (rows_tile * LANES) == 0
    Rp = npad // LANES
    ntiles = Rp // rows_tile
    dt = w_pad.dtype
    hrows = padded_halo_rows(offsets, rows_tile)
    ab = jnp.stack([alpha.astype(dt), beta.astype(dt)])
    tile_spec = pl.BlockSpec((rows_tile, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    vec = jax.ShapeDtypeStruct((Rp, LANES), dt)
    outs = pallas_call(
        functools.partial(_stpipe2d_kernel, grid, offsets, digits, coeffs,
                          rows_tile, n, hrows),
        name="acg_stpipe2d",
        out_shape=(vec,) * 6 + (jax.ShapeDtypeStruct((1, 2), dt),),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),          # w (resident)
            pl.BlockSpec(memory_space=pltpu.SMEM),          # (alpha, beta)
            tile_spec, tile_spec, tile_spec, tile_spec, tile_spec,
        ],
        out_specs=(tile_spec,) * 6 + (
            pl.BlockSpec((1, 2), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),),
        interpret=interpret,
    )(w_pad.reshape(Rp, LANES), ab,
      z_pad.reshape(Rp, LANES), r_pad.reshape(Rp, LANES),
      p_pad.reshape(Rp, LANES), s_pad.reshape(Rp, LANES),
      x_pad.reshape(Rp, LANES))
    z2, p2, s2, x2, r2, w2, gd = outs
    return (z2.reshape(npad), p2.reshape(npad), s2.reshape(npad),
            x2.reshape(npad), r2.reshape(npad), w2.reshape(npad),
            gd[0, 0], gd[0, 1])


# ---------------------------------------------------------------------------
# VMEM plans + probe-gated routing


def stencil_plan(npad: int, offsets: tuple, vec_dtype) -> int | None:
    """rows_tile for the resident stencil kernel, or None.  The DIA
    resident plan minus the band tiles it no longer budgets for — only
    the padded x and double-buffered output tiles occupy VMEM."""
    vb = np.dtype(vec_dtype).itemsize
    if npad % LANES or vb > 4:
        return None
    R = npad // LANES
    for rt in (512, 256, 128, 64, 32, 16, 8):
        H = padded_halo_rows(offsets, rt)
        Rp = R + 2 * H + (-R) % rt           # pad_dia_vectors geometry
        x_bytes = Rp * LANES * vb
        tile_bytes = rt * LANES * vb
        if x_bytes + 2 * tile_bytes <= _VMEM_BUDGET:
            return rt
    return None


def stencil_batched_plan(nrhs: int, npad: int, offsets: tuple,
                         vec_dtype) -> int | None:
    """Batched resident plan: all B padded systems resident, plus B
    double-buffered output tiles."""
    vb = np.dtype(vec_dtype).itemsize
    if nrhs < 1 or npad % LANES or vb > 4:
        return None
    R = npad // LANES
    for rt in (512, 256, 128, 64, 32, 16, 8):
        H = padded_halo_rows(offsets, rt)
        Rp = R + 2 * H + (-R) % rt
        x_bytes = nrhs * Rp * LANES * vb
        tile_bytes = rt * LANES * vb
        if x_bytes + 2 * tile_bytes <= _VMEM_BUDGET:
            return rt
    return None


def stencil_pipe_plan(npad: int, offsets: tuple, vec_dtype) -> int | None:
    """rows_tile for the matrix-free single-kernel pipelined iteration,
    or None — the ``pipe2d_plan`` budget minus the band tile: resident w
    plus 11 double-buffered vector tile streams (5 in + 6 out)."""
    vb = np.dtype(vec_dtype).itemsize
    if npad % LANES or vb > 4:
        return None
    R = npad // LANES
    for rt in (512, 256, 128, 64, 32, 16, 8):
        H = padded_halo_rows(offsets, rt)
        Rp = R + 2 * H + (-R) % rt
        w_bytes = Rp * LANES * vb
        vec_tiles = 11 * rt * LANES * vb
        if w_bytes + 2 * vec_tiles <= _VMEM_BUDGET:
            return rt
    return None


def prefer_stencil(npad: int, offsets: tuple, coeffs, vec_dtype,
                   mat_dtype="auto", nrhs: int = 1) -> bool:
    """THE fmt="auto" choice between a recognized stencil and its stored
    DIA twin, for every builder (single-chip, per-shard, and the batched
    serve operator through ``nrhs``): the matrix-free tier, unless its
    kernel does not plan at this length and batch while the DIA tier's
    Pallas kernel does — past the VMEM bound (256³ f32) the HBM-ring DIA
    kernel, and for a batch of 4 at 128³ the batched resident DIA
    kernel, run instead of the XLA grid-shift form.  ``coeffs`` (the
    stencil's arm values, host side) resolve the DIA twin's band dtype
    exactly as its storage builders do (``resolve_mat_dtype``; "int8"
    is DIA's exact mask tier), so every caller prices the same band
    stream."""
    from acg_tpu.ops.dia import resolve_mat_dtype
    from acg_tpu.ops.pallas_kernels import (fused_plan_for,
                                            pallas_2d_batched_plan,
                                            pallas_spmv_available)

    vdt = np.dtype(vec_dtype)
    mdt = np.dtype(np.int8 if mat_dtype == "int8" else resolve_mat_dtype(
        np.asarray(coeffs, vdt), mat_dtype, vdt))
    if nrhs > 1:
        if stencil_batched_plan(nrhs, npad, offsets, vdt) is not None:
            return True
        return not (0 in offsets and pallas_spmv_available("batched2d")
                    and pallas_2d_batched_plan(nrhs, npad, offsets, vdt,
                                               mdt) is not None)
    return (stencil_plan(npad, offsets, vdt) is not None
            or fused_plan_for(npad, offsets, vdt, mdt) is None)


def stencil_available(kind: str = "stencil2d") -> bool:
    """Probe-gate of the stencil Pallas kernels (groups "stencil2d" /
    "stpipe2d") through the shared once-per-process machinery."""
    from acg_tpu.ops.pallas_kernels import pallas_spmv_available

    return pallas_spmv_available(kind)


def stencil_kernel_kind(npad: int, offsets: tuple, vec_dtype,
                        nrhs: int = 1, interpret: bool = False):
    """"stencil" when the Pallas kernel serves this shape (probe green or
    interpret-forced, VMEM plan admits it), else None (the jnp grid-shift
    formulation runs) — the reporting face shared by the single-chip and
    distributed path descriptions."""
    if not (interpret or stencil_available()):
        return None
    rt = (stencil_batched_plan(nrhs, npad, offsets, vec_dtype)
          if nrhs > 1 else stencil_plan(npad, offsets, vec_dtype))
    return "stencil" if rt is not None else None


def stencil_matvec_any(x: jax.Array, grid: tuple, offsets: tuple,
                       digits: tuple, coeffs: tuple,
                       interpret: bool = False) -> jax.Array:
    """The stencil SpMV through the best available path for this
    shape/backend — the matrix-free analog of ``dia_matvec_best``:
    the Pallas resident kernel when probed (or interpret-forced) and
    planned, else the jnp grid-shift form.  1-D and batched (B, n)."""
    n = 1
    for d in grid:
        n *= int(d)
    npad = x.shape[-1]
    if x.ndim == 2:
        rt = stencil_batched_plan(x.shape[0], npad, offsets, x.dtype)
        if rt is not None and (interpret or stencil_available()):
            (xp,), front = pad_dia_vectors((x,), npad, rt, offsets)
            y = stencil_matvec_pallas_padded_batched(
                grid, offsets, digits, coeffs, xp, rows_tile=rt, n=n,
                interpret=interpret)
            return jax.lax.slice_in_dim(y, front, front + npad, axis=-1)
        return stencil_matvec(x, grid, digits, coeffs)
    rt = stencil_plan(npad, offsets, x.dtype)
    if rt is not None and (interpret or stencil_available()):
        (xp,), front = pad_dia_vectors((x,), npad, rt, offsets)
        y = stencil_matvec_pallas_padded(grid, offsets, digits, coeffs,
                                         xp, rows_tile=rt, n=n,
                                         interpret=interpret)
        return jax.lax.slice_in_dim(y, front, front + npad, axis=-1)
    return stencil_matvec(x, grid, digits, coeffs)


# ---------------------------------------------------------------------------
# the device operator


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceStencil:
    """Matrix-free device operator: the operator IS its static spec.

    Every field is static — the pytree has ZERO array leaves, so nothing
    is uploaded, nothing is streamed, and ``operator_stream_bytes() ==
    0`` (the roofline model then predicts the vector-only ceiling).  The
    spec compiles into the executable: grid/offsets/digits select the
    shift pattern at trace time exactly as DIA's static offsets do,
    and the coefficients become in-kernel constants."""

    grid: tuple = dataclasses.field(metadata=dict(static=True),
                                    default=())
    offsets: tuple = dataclasses.field(metadata=dict(static=True),
                                       default=())
    digits: tuple = dataclasses.field(metadata=dict(static=True),
                                      default=())
    coeffs: tuple = dataclasses.field(metadata=dict(static=True),
                                      default=())
    nrows: int = dataclasses.field(metadata=dict(static=True), default=0)
    ncols: int = dataclasses.field(metadata=dict(static=True), default=0)
    nnz: int = dataclasses.field(metadata=dict(static=True), default=0)
    vec_dtype: str = dataclasses.field(metadata=dict(static=True),
                                       default="float32")
    # CPU-test hook: force the Pallas kernels through interpret mode
    # (the probe never passes off-TPU; the kernels still must be
    # correctness-testable everywhere — the sgell discipline)
    interpret: bool = dataclasses.field(metadata=dict(static=True),
                                        default=False)

    @classmethod
    def from_spec(cls, spec: StencilSpec, dtype=None,
                  interpret: bool = False) -> "DeviceStencil":
        vdt = np.dtype(dtype if dtype is not None else np.float64)
        return cls(grid=spec.grid, offsets=spec.offsets,
                   digits=spec.digits, coeffs=spec.coeffs,
                   nrows=spec.nrows, ncols=spec.nrows, nnz=spec.nnz,
                   vec_dtype=vdt.name, interpret=interpret)

    @classmethod
    def from_matrix(cls, A, dtype=None,
                    interpret: bool = False) -> "DeviceStencil":
        """Recognize-or-raise: the forced fmt="stencil" entry (a forced
        tier must error, never silently run something else)."""
        vdt = np.dtype(dtype) if dtype is not None else None
        spec, why = recognize_stencil(A, dtype=vdt)
        if spec is None:
            from acg_tpu.errors import AcgError, Status

            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "format 'stencil' forced but the matrix is "
                           f"not a recognized constant-coefficient "
                           f"stencil: {why}")
        if vdt is None:
            vals = getattr(A, "vals", getattr(A, "bands", None))
            vdt = np.dtype(vals.dtype if vals is not None else np.float64)
        return cls.from_spec(spec, dtype=vdt, interpret=interpret)

    @property
    def nrows_padded(self) -> int:
        # the same row_align=8 padding as DiaMatrix.from_csr, so padded
        # right-hand sides are shape-compatible across the two tiers
        return max(-(-self.nrows // 8) * 8, 8)

    @property
    def mat_itemsize(self) -> int:
        return 0

    @property
    def spec(self) -> StencilSpec:
        return StencilSpec(self.grid, self.offsets, self.digits,
                           self.coeffs, self.nnz)

    def spec_hash(self) -> str:
        return self.spec.spec_hash()

    def operator_stream_bytes(self) -> int:
        """ZERO: the whole point.  No band arrays exist; the roofline
        model charges only the vector streams."""
        return 0

    def matvec(self, x: jax.Array) -> jax.Array:
        return stencil_matvec_any(x, self.grid, self.offsets,
                                  self.digits, self.coeffs,
                                  interpret=self.interpret)


def try_device_stencil(A, dtype=None, interpret: bool = False):
    """(DeviceStencil, report) when ``A`` recognizes, else (None,
    report) — the fmt="auto" entry (never raises)."""
    vdt = np.dtype(dtype) if dtype is not None else None
    spec, why = recognize_stencil(A, dtype=vdt)
    if spec is None:
        return None, stencil_reject_report(why)
    if vdt is None:
        vals = getattr(A, "vals", getattr(A, "bands", None))
        vdt = np.dtype(vals.dtype if vals is not None else np.float64)
    return (DeviceStencil.from_spec(spec, dtype=vdt, interpret=interpret),
            spec.as_report())


# ---------------------------------------------------------------------------
# probes (registered in pallas_kernels._PROBE_GROUPS)


def _probe_shapes():
    """Production-shaped probe stencils: a 3-D 7-pt grid whose strides
    exercise the sublane shift (±nx·ny), the lane-rotation blend (±nz
    with nz % 128 != 0) and the ±1 rotation; and a 2-D 5-pt grid at the
    small-tile extreme."""
    return (
        ((16, 16, 16), 16),       # n=4096: offsets ±256, ±16, ±1
        ((8, 24), 8),             # n=192 padded to lane multiples below
    )


def _probe_grid_spec(grid, center=6.0, off=-1.0):
    """Spec of the Dirichlet Laplacian on ``grid`` (unit arms)."""
    k = len(grid)
    strides = [1] * k
    for i in range(k - 2, -1, -1):
        strides[i] = strides[i + 1] * grid[i + 1]
    arms = [(tuple(0 for _ in range(k)), float(center) + 0.0, 0)]
    for ax in range(k):
        for g in (-1, 1):
            dg = tuple(g if a == ax else 0 for a in range(k))
            arms.append((dg, float(off), g * strides[ax]))
    arms.sort(key=lambda a: a[2])
    offsets = tuple(a[2] for a in arms)
    digits = tuple(a[0] for a in arms)
    coeffs = tuple(a[1] for a in arms)
    return offsets, digits, coeffs


def _probe_stencil_group(interpret: bool = False) -> bool:
    """Compile-and-match the padded stencil kernel (matvec + fused dot)
    and its batched twin against the jnp grid-shift oracle, including the
    zero-halo invariant — the same discipline as the DIA padded probes."""
    rng = np.random.default_rng(5)
    ok = True
    for grid, rt in _probe_shapes():
        n = int(np.prod(grid))
        npad = -(-n // LANES) * LANES
        offsets, digits, coeffs = _probe_grid_spec(grid)
        xv = jnp.asarray(np.pad(
            rng.standard_normal(n).astype(np.float32), (0, npad - n)))
        want = stencil_matvec(xv, grid, digits, coeffs)
        want_dot = jnp.vdot(xv, want)
        (xp,), front = pad_dia_vectors((xv,), npad, rt, offsets)
        got, gd = stencil_matvec_pallas_padded(
            grid, offsets, digits, coeffs, xp, rows_tile=rt, n=n,
            with_dot=True, interpret=interpret)
        mid = got[front: front + npad]
        yscale = float(jnp.max(jnp.abs(want))) or 1.0
        dscale = float(jnp.linalg.norm(xv) * jnp.linalg.norm(want)) or 1.0
        ok = ok and bool(jnp.max(jnp.abs(mid - want)) < 1e-5 * yscale)
        ok = ok and bool(jnp.abs(gd - want_dot) < 1e-5 * dscale)
        ok = ok and bool(jnp.all(got[:front] == 0.0))
        ok = ok and bool(jnp.all(got[front + npad:] == 0.0))
        # batched twin, per-system dot + per-system halo invariant
        B = 3
        xb = jnp.asarray(np.pad(
            rng.standard_normal((B, n)).astype(np.float32),
            ((0, 0), (0, npad - n))))
        wantb = stencil_matvec(xb, grid, digits, coeffs)
        wantb_dot = jnp.sum(xb * wantb, axis=-1)
        (xbp,), front = pad_dia_vectors((xb,), npad, rt, offsets)
        gotb, gbd = stencil_matvec_pallas_padded_batched(
            grid, offsets, digits, coeffs, xbp, rows_tile=rt, n=n,
            with_dot=True, interpret=interpret)
        midb = gotb[:, front: front + npad]
        yscale = float(jnp.max(jnp.abs(wantb))) or 1.0
        dscale = float(jnp.max(jnp.linalg.norm(xb, axis=-1)
                               * jnp.linalg.norm(wantb, axis=-1))) or 1.0
        ok = ok and bool(jnp.max(jnp.abs(midb - wantb)) < 1e-5 * yscale)
        ok = ok and bool(jnp.max(jnp.abs(gbd - wantb_dot))
                         < 1e-4 * dscale)
        ok = ok and bool(jnp.all(gotb[:, :front] == 0.0))
        ok = ok and bool(jnp.all(gotb[:, front + npad:] == 0.0))
    return ok


def _probe_stpipe_group(interpret: bool = False) -> bool:
    """Compile-and-match the matrix-free single-kernel pipelined
    iteration against the open-coded recurrence (the
    ``_probe_pipe2d_group`` discipline: per-vector parity, zero-halo
    invariant, accumulation-order-tolerant dot bounds)."""
    rng = np.random.default_rng(6)
    ok = True
    for grid, rt in _probe_shapes():
        n = int(np.prod(grid))
        npad = -(-n // LANES) * LANES
        offsets, digits, coeffs = _probe_grid_spec(grid)
        vecs = [jnp.asarray(np.pad(
            rng.standard_normal(n).astype(np.float32), (0, npad - n)))
            for _ in range(6)]
        alpha = jnp.float32(0.37)
        beta = jnp.float32(1.21)
        w, z, r, p, s, x = vecs
        q = stencil_matvec(w, grid, digits, coeffs)
        z2 = q + beta * z
        p2 = r + beta * p
        s2 = w + beta * s
        x2 = x + alpha * p2
        r2 = r - alpha * s2
        w2 = w - alpha * z2
        want = (z2, p2, s2, x2, r2, w2)
        gexp, dexp = jnp.vdot(r2, r2), jnp.vdot(w2, r2)
        padded, front = pad_dia_vectors(tuple(vecs), npad, rt, offsets)
        wp, zp, rp, pp, sp, xp = padded
        got = cg_pipelined_iter_stencil(grid, offsets, digits, coeffs,
                                        wp, zp, rp, pp, sp, xp, alpha,
                                        beta, rows_tile=rt, n=n,
                                        interpret=interpret)
        for gv, wv in zip(got[:6], want):
            scale = float(jnp.max(jnp.abs(wv))) or 1.0
            ok = ok and bool(
                jnp.max(jnp.abs(gv[front: front + npad] - wv))
                < 1e-5 * scale)
            ok = ok and bool(jnp.all(gv[:front] == 0.0))
            ok = ok and bool(jnp.all(gv[front + npad:] == 0.0))
        gs = float(jnp.vdot(r2, r2)) or 1.0
        ds = float(jnp.linalg.norm(w2) * jnp.linalg.norm(r2)) or 1.0
        ok = ok and bool(jnp.abs(got[6] - gexp) < 1e-4 * gs)
        ok = ok and bool(jnp.abs(got[7] - dexp) < 1e-4 * ds)
    return ok
