"""Pallas TPU kernels for the CG hot ops.

The reference's CUDA kernel inventory (reference acg/cg-kernels-cuda.cu):
merge-based CSR SpMV (:340-441), fused scalar/AXPY kernels with
device-resident scalars (:78-269), device dot with grid reduction
(:495-530).  The TPU equivalents here:

- :func:`dia_matvec_pallas_2d` / :func:`dia_matvec_pallas_2d_padded` —
  DIA SpMV as one kernel over a 2-D (rows, 128) layout of x held in VMEM:
  one pass over the bands, no materialized shifted copies of x, full
  (8, 128) vreg density; the padded variant additionally fuses the p'Ap
  reduction into the pass (CG's coupled_step, acg_tpu/solvers/loops.py).
- :func:`dia_matvec_pallas_hbm2d_ring` — the HBM-resident-x kernel for
  operators past the VMEM bound (the 100M-DOF regime): a VMEM ring of
  consecutive x tiles spanning the offset reach, ONE x-tile DMA per grid
  step (1.0x x stream), same padded contract and fused dot.
- :func:`dia_matvec_pallas_hbm2d` — the clustered-window HBM variant
  (one double-buffered window DMA per offset cluster, see
  :func:`_cluster_windows`): the fallback when the offset span exceeds
  the VMEM ring budget; ~one x re-fetch per cluster.
The fused pipelined-CG vector update (reference ``pipelined_daxpy_fused``
acg/cg-kernels-cuda.cu:187-269) needs no hand-written kernel on TPU: XLA
fuses the 7-stream/6-output update into one pass inside the jitted solver
loop, measured at parity with a dedicated Pallas kernel (PERF.md
"wire-or-delete decisions").

All kernels are correctness-tested in interpret mode on CPU.  On real
hardware the DIA kernels activate automatically via
:func:`pallas_spmv_available` — a once-per-process probe that compiles
every storage tier and verifies it against the XLA path.  A group whose
probe fails is left out of routing; the failure is reported on stderr
and kept for :func:`probe_errors` (``ACG_TPU_PALLAS=0`` skips the probes
entirely).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE_ROWS = 8          # float32 min sublane tile


def pallas_call(kernel, *, name: str, **kw):
    """``pl.pallas_call`` traced with 64-bit types off.  Under
    ``jax_enable_x64`` the kernels' index arithmetic traces as int64,
    and Mosaic's lowering of the int64 -> int32 conversion recurses
    without end (jax 0.9); every kernel in this package goes through
    here so an f64 solve elsewhere in the process cannot break them.
    ``name`` (``acg_<kernel>``) is the kernel's stable name in profiler
    traces and compiled programs (acg_tpu/obs/trace.py)."""
    call = pl.pallas_call(kernel, name=name, **kw)
    if kw.get("interpret"):
        return call               # the interpreter runs any dtype

    def run(*args):
        with jax.enable_x64(False):
            return call(*args)

    return run


# The original 1-D resident kernel (``dia_matvec_pallas``: (1, tile)
# blocks over a flat x) was DELETED: its unaligned lane-dimension window
# loads are rejected by current Mosaic ("cannot statically prove that
# index in dimension 1 is a multiple of 128"), and the 2-D kernel below
# dominates it by design (full (8, 128) vreg density vs 1/8).


def _window_2d(load, q: int, r: int, lane):
    """(rows, 128) window of a 2-D x shifted by ``off = q*128 + r``:
    a sublane shift (row slice via ``load``) plus, for r != 0, a lane
    rotation realized as two row-shifted loads rotated with the native
    ``pltpu.roll`` and blended by lane index (a lane-dim concatenate of
    misaligned slices is NOT supported by Mosaic: "result/input offset
    mismatch on non-concat dimension").  ``load(q)`` returns the row block
    starting q rows below the tile's base."""
    if r == 0:
        return load(q)
    lo = pltpu.roll(load(q), LANES - r, 1)
    hi = pltpu.roll(load(q + 1), LANES - r, 1)
    return jnp.where(lane < LANES - r, lo, hi)


def _dia2d_kernel(offsets, rows_tile, scaled, x_ref, bands_ref, scales_ref,
                  y_ref):
    """One grid step = one (rows_tile, 128) tile of y, x viewed 2-D.

    x is laid out as (rows, 128): a diagonal offset decomposes as
    ``off = q*128 + r`` into a SUBLANE shift q (a plain row slice, always
    lane-aligned) plus a LANE rotation r (see :func:`_window_2d`).
    Stencil offsets that are multiples of 128 (the ±nx, ±nx·ny bands of
    natural-order grids with lane-aligned nx) need no lane work at all."""
    i = pl.program_id(0)
    Wr = (x_ref.shape[0] - pl.num_programs(0) * rows_tile) // 2
    base = i * rows_tile + Wr
    acc = jnp.zeros((rows_tile, LANES), dtype=y_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_tile, LANES), 1)
    load = lambda q: x_ref[pl.ds(base + q, rows_tile), :]
    for d, off in enumerate(offsets):
        q, r = divmod(off, LANES)
        b = bands_ref[d].astype(y_ref.dtype)
        if scaled:
            b = b * scales_ref[d]
        acc = acc + b * _window_2d(load, q, r, lane)
    y_ref[:, :] = acc


@functools.partial(jax.jit,
                   static_argnames=("offsets", "rows_tile", "interpret"))
def dia_matvec_pallas_2d(bands, offsets: tuple, x, rows_tile: int = 512,
                         interpret: bool = False, scales=None):
    """y = DIA(bands, offsets) @ x via the 2-D resident-x kernel.

    ``bands``: (D, n_pad); ``x``: (n_pad,), n_pad a multiple of
    ``rows_tile * 128``; ``scales``: per-band scales for the int8
    two-value compression tier (None for direct bands).  x is held in
    VMEM as (rows, 128) with ``Wr`` zero rows of halo above and below
    (see :func:`_dia2d_kernel`).  Returns (n_pad,).
    """
    D, n = bands.shape
    assert n % LANES == 0 and n % (rows_tile * LANES) == 0
    R = n // LANES
    Wr = max(abs(o) for o in offsets) // LANES + 1
    xp = jnp.zeros((R + 2 * Wr, LANES), dtype=x.dtype)
    xp = jax.lax.dynamic_update_slice(xp, x.reshape(R, LANES), (Wr, 0))
    scaled = scales is not None
    sc = (scales.astype(x.dtype) if scaled
          else jnp.zeros((D,), dtype=x.dtype))
    y = pallas_call(
        functools.partial(_dia2d_kernel, offsets, rows_tile, scaled),
        name="acg_dia2d",
        out_shape=jax.ShapeDtypeStruct((R, LANES), x.dtype),
        grid=(R // rows_tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((D, rows_tile, LANES), lambda i: (0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((rows_tile, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(xp, bands.reshape(D, R, LANES), sc)
    return y.reshape(n)


def _padded_band_rows(bands_pad, rows_tile: int) -> tuple[int, int]:
    """(D, Rp) of padded bands, refusing any layout but (D, Rp, 128).

    The padded kernels read the bands as (D, Rp, 128) blocks.  A 2-D
    (D, Rp*128) operand would need a reshape here, and XLA's tiling of
    the two forms differs (the band axis of the 2-D form is tiled by 8),
    so that reshape is a full copy of the bands on every call — inside a
    solver loop, on every iteration.  Hence one layout, checked."""
    if bands_pad.ndim != 3 or bands_pad.shape[-1] != LANES:
        raise ValueError(f"padded bands must be (D, Rp, {LANES}) "
                         f"(pad_dia_operands), got {bands_pad.shape}")
    D, Rp, _ = bands_pad.shape
    assert Rp % rows_tile == 0
    return D, Rp


def _banded_tile_acc(offsets, rows_tile, scaled, src_ref, bands_ref,
                     scales_ref, base, dt):
    """One (rows_tile, 128) tile of DIA(bands) @ src on the padded layout:
    the clamped-window band accumulation shared by every padded kernel
    (_dia2d_padded_kernel, _pipe2d_kernel) — window starts are clamped
    into bounds; the clamp only actually displaces reads on halo tiles,
    where the band factor is zero."""
    Rp = src_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_tile, LANES), 1)
    hi_cap = Rp - rows_tile
    load = lambda q: src_ref[pl.ds(jnp.clip(base + q, 0, hi_cap),
                                   rows_tile), :]
    acc = jnp.zeros((rows_tile, LANES), dtype=dt)
    for d, off in enumerate(offsets):
        q, r = divmod(off, LANES)
        b = bands_ref[d].astype(dt)
        if scaled:
            b = b * scales_ref[d]
        acc = acc + b * _window_2d(load, q, r, lane)
    return acc


def _dia2d_padded_kernel(offsets, rows_tile, scaled, with_dot,
                         x_ref, bands_ref, scales_ref, y_ref, *dot_ref):
    """Variant of :func:`_dia2d_kernel` for PERMANENTLY padded operands.

    ``x_ref`` is the full (Rp, 128) vector with ``H = rows_tile`` zero halo
    rows built in on each side, resident in VMEM; the grid covers ALL Rp
    rows (the halo tiles carry zero bands, so they compute — and write —
    exact zeros, preserving the zero-halo invariant of the padded vector
    layout without any masking).  Window starts are clamped into bounds:
    the clamp only actually displaces reads on halo tiles, where the band
    factor is zero.  With ``with_dot``, each tile also emits the partial
    <x_tile, y_tile> (one SMEM scalar per tile), fusing the p'Ap reduction
    of CG into the SpMV pass — the traffic the reference saves by running
    cublasDdot back-to-back with SpMV on one stream (acg/cgcuda.c:858-894)
    is here never re-read from HBM at all."""
    i = pl.program_id(0)
    base = i * rows_tile
    acc = _banded_tile_acc(offsets, rows_tile, scaled, x_ref, bands_ref,
                           scales_ref, base, y_ref.dtype)
    y_ref[:, :] = acc
    if with_dot:
        # single SMEM accumulator revisited by every (sequential) grid
        # step: zeroed on the first tile, summed in tile order — the
        # deterministic on-chip reduction the reference gets from its
        # grid-wide atomics ddot (acg/cg-kernels-cuda.cu:495-530)
        @pl.when(i == 0)
        def _zero():
            dot_ref[0][0, 0] = jnp.asarray(0.0, y_ref.dtype)

        dot_ref[0][0, 0] += jnp.sum(x_ref[pl.ds(base, rows_tile), :] * acc)


@functools.partial(jax.jit, static_argnames=("offsets", "rows_tile",
                                             "with_dot", "interpret"))
def dia_matvec_pallas_2d_padded(bands_pad, offsets: tuple, x_pad,
                                rows_tile: int = 512,
                                with_dot: bool = False,
                                interpret: bool = False, scales=None):
    """y = DIA(bands) @ x on the padded layout (see kernel docstring).

    ``bands_pad``: (D, Rp, 128) with ``H = padded_halo_rows(offsets,
    rows_tile)`` zero halo rows in front and H + tail-rounding behind
    (build with :func:`pad_dia_operands`; any other layout is refused,
    see :func:`_padded_band_rows`); ``x_pad``: (Rp*128,) with the same
    halo, zeros there.  Returns y in the SAME padded layout (zero
    halo preserved), plus the scalar <x, y> when ``with_dot`` — which for
    CG's t = Ap is exactly p'Ap.
    """
    D, Rp = _padded_band_rows(bands_pad, rows_tile)
    npad = Rp * LANES
    ntiles = Rp // rows_tile
    scaled = scales is not None
    sc = (scales.astype(x_pad.dtype) if scaled
          else jnp.zeros((D,), dtype=x_pad.dtype))
    out_shape = [jax.ShapeDtypeStruct((Rp, LANES), x_pad.dtype)]
    out_specs = [pl.BlockSpec((rows_tile, LANES), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)]
    if with_dot:
        out_shape.append(jax.ShapeDtypeStruct((1, 1), x_pad.dtype))
        out_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0),
                                      memory_space=pltpu.SMEM))
    outs = pallas_call(
        functools.partial(_dia2d_padded_kernel, offsets, rows_tile, scaled,
                          with_dot),
        name="acg_dia2d_padded",
        out_shape=tuple(out_shape),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((D, rows_tile, LANES), lambda i: (0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=tuple(out_specs),
        interpret=interpret,
    )(x_pad.reshape(Rp, LANES), bands_pad, sc)
    y = outs[0].reshape(npad)
    if with_dot:
        return y, outs[1][0, 0]
    return y


def _pipe2d_kernel(offsets, rows_tile, scaled,
                   w_ref, bands_ref, scales_ref, ab_ref,
                   z_ref, r_ref, p_ref, s_ref, x_ref,
                   z_o, p_o, s_o, x_o, r_o, w_o, gd_o):
    """One WHOLE pipelined-CG iteration per grid sweep (padded layout).

    Per (rows_tile, 128) tile: q = (A w)_tile via the windowed band
    machinery of :func:`_dia2d_padded_kernel` (w resident in VMEM), then
    the Ghysels/Vanroose 6-vector update

        z' = q + beta z;  p' = r + beta p;  s' = w + beta s
        x' = x + alpha p';  r' = r - alpha s';  w' = w - alpha z'

    and the next reduction pair gamma = <r', r'>, delta = <w', r'> as
    sequentially-accumulated SMEM partials.  q never exists in HBM, w is
    read ONCE, and the dot operands are never re-read — the iteration's
    whole HBM traffic is bands + 5 tile reads + 6 tile writes, the
    minimal stream set (the role of the reference's fused
    pipelined_daxpy_fused + back-to-back dots on one stream,
    acg/cg-kernels-cuda.cu:187-269, taken one step further: SpMV, update
    and both dots in ONE kernel).  Halo tiles carry zero bands and zero
    vectors; every update above is linear, so they write exact zeros and
    the padded-layout invariant survives without masking."""
    i = pl.program_id(0)
    base = i * rows_tile
    dt = z_o.dtype
    alpha = ab_ref[0]
    beta = ab_ref[1]
    acc = _banded_tile_acc(offsets, rows_tile, scaled, w_ref, bands_ref,
                           scales_ref, base, dt)
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


@functools.partial(jax.jit, static_argnames=("offsets", "rows_tile",
                                             "interpret"))
def cg_pipelined_iter_pallas(bands_pad, offsets: tuple, w_pad, z_pad,
                             r_pad, p_pad, s_pad, x_pad, alpha, beta,
                             rows_tile: int = 512,
                             interpret: bool = False, scales=None):
    """One pipelined-CG iteration on the padded layout (see
    :func:`_pipe2d_kernel`): returns (z', p', s', x', r', w', gamma,
    delta).  All vectors share the padded zero-halo layout of
    :func:`dia_matvec_pallas_2d_padded`; ``alpha``/``beta`` are device
    scalars (this iteration's coefficients, derived from the PREVIOUS
    iteration's (gamma, delta) by the solver loop)."""
    D, Rp = _padded_band_rows(bands_pad, rows_tile)
    npad = Rp * LANES
    ntiles = Rp // rows_tile
    dt = w_pad.dtype
    scaled = scales is not None
    sc = (scales.astype(dt) if scaled else jnp.zeros((D,), dtype=dt))
    ab = jnp.stack([alpha.astype(dt), beta.astype(dt)])
    tile_spec = pl.BlockSpec((rows_tile, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    vec = jax.ShapeDtypeStruct((Rp, LANES), dt)
    outs = pallas_call(
        functools.partial(_pipe2d_kernel, offsets, rows_tile, scaled),
        name="acg_pipe2d",
        out_shape=(vec,) * 6 + (jax.ShapeDtypeStruct((1, 2), dt),),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),          # w (resident)
            pl.BlockSpec((D, rows_tile, LANES), lambda i: (0, i, 0),
                         memory_space=pltpu.VMEM),           # bands
            pl.BlockSpec(memory_space=pltpu.SMEM),           # scales
            pl.BlockSpec(memory_space=pltpu.SMEM),           # (alpha, beta)
            tile_spec, tile_spec, tile_spec, tile_spec, tile_spec,
        ],
        out_specs=(tile_spec,) * 6 + (
            pl.BlockSpec((1, 2), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),),
        interpret=interpret,
    )(w_pad.reshape(Rp, LANES), bands_pad, sc, ab,
      z_pad.reshape(Rp, LANES), r_pad.reshape(Rp, LANES),
      p_pad.reshape(Rp, LANES), s_pad.reshape(Rp, LANES),
      x_pad.reshape(Rp, LANES))
    z2, p2, s2, x2, r2, w2, gd = outs
    return (z2.reshape(npad), p2.reshape(npad), s2.reshape(npad),
            x2.reshape(npad), r2.reshape(npad), w2.reshape(npad),
            gd[0, 0], gd[0, 1])


def _dia2d_padded_batched_kernel(offsets, rows_tile, scaled, with_dot,
                                 x_ref, bands_ref, scales_ref, y_ref,
                                 *dot_ref):
    """Multi-RHS variant of :func:`_dia2d_padded_kernel`: the grid gains a
    BATCH dimension — grid (ntiles, B), batch fastest — so each band tile
    is DMA'd into VMEM once per row tile and then reused by all B systems
    (the band-block index map ignores the batch coordinate; Pallas skips
    the re-fetch while it is unchanged).  That is the whole point of
    multi-RHS batching: the band stream, the dominant HBM traffic of the
    CG iteration, is amortized across B right-hand sides, multiplying
    arithmetic intensity by ~B on the operator stream (the data-locality
    argument of Kronbichler et al., arXiv 2205.08909).  x is resident in
    VMEM as (B, Rp, 128); ``with_dot`` accumulates a PER-SYSTEM
    <x_s, y_s> partial into a (1, B) SMEM block (CG's p'Ap vector)."""
    i = pl.program_id(0)
    s = pl.program_id(1)
    base = i * rows_tile
    dt = y_ref.dtype
    Rp = x_ref.shape[1]
    hi_cap = Rp - rows_tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_tile, LANES), 1)
    load = lambda q: x_ref[s, pl.ds(jnp.clip(base + q, 0, hi_cap),
                                    rows_tile), :]
    acc = jnp.zeros((rows_tile, LANES), dtype=dt)
    for d, off in enumerate(offsets):
        q, r = divmod(off, LANES)
        bt = bands_ref[d].astype(dt)
        if scaled:
            bt = bt * scales_ref[d]
        acc = acc + bt * _window_2d(load, q, r, lane)
    y_ref[0, :, :] = acc
    if with_dot:
        # per-system SMEM accumulator, zeroed on that system's first tile
        # (batch is the fastest grid dim, so (0, s) precedes every (i, s))
        @pl.when(i == 0)
        def _zero():
            dot_ref[0][0, s] = jnp.asarray(0.0, dt)

        dot_ref[0][0, s] += jnp.sum(x_ref[s, pl.ds(base, rows_tile), :]
                                    * acc)


@functools.partial(jax.jit, static_argnames=("offsets", "rows_tile",
                                             "with_dot", "interpret"))
def dia_matvec_pallas_2d_padded_batched(bands_pad, offsets: tuple, x_pad,
                                        rows_tile: int = 512,
                                        with_dot: bool = False,
                                        interpret: bool = False,
                                        scales=None):
    """Multi-RHS y = DIA(bands) @ x on the padded layout: ``x_pad`` is
    (B, npad) (same per-system halo contract as
    :func:`dia_matvec_pallas_2d_padded`); returns (B, npad) — plus the
    per-system <x_s, y_s> vector of shape (B,) when ``with_dot`` (for
    CG's t = Ap this is the per-system p'Ap the batched loop carries)."""
    D, Rp = _padded_band_rows(bands_pad, rows_tile)
    npad = Rp * LANES
    B = x_pad.shape[0]
    assert x_pad.shape[-1] == npad
    ntiles = Rp // rows_tile
    scaled = scales is not None
    sc = (scales.astype(x_pad.dtype) if scaled
          else jnp.zeros((D,), dtype=x_pad.dtype))
    out_shape = [jax.ShapeDtypeStruct((B, Rp, LANES), x_pad.dtype)]
    out_specs = [pl.BlockSpec((1, rows_tile, LANES), lambda i, s: (s, i, 0),
                              memory_space=pltpu.VMEM)]
    if with_dot:
        out_shape.append(jax.ShapeDtypeStruct((1, B), x_pad.dtype))
        out_specs.append(pl.BlockSpec((1, B), lambda i, s: (0, 0),
                                      memory_space=pltpu.SMEM))
    outs = pallas_call(
        functools.partial(_dia2d_padded_batched_kernel, offsets, rows_tile,
                          scaled, with_dot),
        name="acg_dia2d_padded_batched",
        out_shape=tuple(out_shape),
        grid=(ntiles, B),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),        # x, resident
            # the band-tile block ignores the batch coordinate: fetched
            # once per row tile, reused across all B systems
            pl.BlockSpec((D, rows_tile, LANES), lambda i, s: (0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=tuple(out_specs),
        interpret=interpret,
    )(x_pad.reshape(B, Rp, LANES), bands_pad, sc)
    y = outs[0].reshape(B, npad)
    if with_dot:
        return y, outs[1][0]
    return y


@functools.partial(jax.jit, static_argnames=("offsets", "rows_tile",
                                             "interpret"))
def dia_matvec_pallas_2d_batched(bands, offsets: tuple, x,
                                 rows_tile: int = 512,
                                 interpret: bool = False, scales=None):
    """Eager-contract wrapper for (B, n) multi-RHS SpMV: pads operands
    into the padded layout and runs the batched resident kernel.  The
    bands are padded on every call; a solver loop pads them once, outside
    the loop (acg_tpu/solvers/cg.py ``_pad_fused``), and calls
    :func:`dia_matvec_pallas_2d_padded_batched` itself."""
    n = x.shape[-1]
    bp, (xp,) = pad_dia_operands(bands, (x,), rows_tile, offsets)
    hp = padded_halo_rows(offsets, rows_tile) * LANES
    y = dia_matvec_pallas_2d_padded_batched(bp, offsets, xp,
                                            rows_tile=rows_tile,
                                            interpret=interpret,
                                            scales=scales)
    return jax.lax.slice_in_dim(y, hp, hp + n, axis=-1)


def pallas_2d_batched_plan(nrhs: int, n: int, offsets: tuple, vec_dtype,
                           band_dtype) -> int | None:
    """rows_tile for the batched resident kernel, or None — the batched
    face of the resident VMEM plan: ALL B padded systems must fit VMEM
    (x is (B, Rp, 128) resident), plus double-buffered band tiles and B
    output tiles.  Shared by the batched fused solver plan
    (acg_tpu/solvers/cg.py ``_fused_plan_batched``) and dia_matvec_best's
    batched route, so the two can never pick different kernels."""
    vb = np.dtype(vec_dtype).itemsize
    mb = np.dtype(band_dtype).itemsize
    if nrhs < 1 or n % LANES or vb > 4 or mb > 4:
        return None
    R = n // LANES
    for rt in (512, 256, 128, 64, 32, 16, 8):
        H = padded_halo_rows(offsets, rt)
        Rp = R + 2 * H + (-R) % rt           # pad_dia_operands geometry
        x_bytes = nrhs * Rp * LANES * vb
        tile_bytes = rt * LANES * (len(offsets) * mb + vb)
        if x_bytes + 2 * tile_bytes <= _VMEM_BUDGET:
            return rt
    return None


def padded_halo_rows(offsets: tuple, rows_tile: int) -> int:
    """Zero-halo rows per side for the padded kernels: the offsets' row
    reach, rounded up to whole tiles so the grid stays uniform (464³'s
    z-band reaches 1682 rows — beyond any single admissible tile, hence
    multiple all-zero halo TILES per side rather than a halo-within-one-
    tile constraint)."""
    need = max(abs(o) for o in offsets) // LANES + 1
    return -(-need // rows_tile) * rows_tile


def pad_dia_vectors(x_vecs, n: int, rows_tile: int, offsets: tuple):
    """Vector half of :func:`pad_dia_operands`: pad length-``n`` vectors
    (last axis; a leading (B,) batch axis passes through) into the
    padded-kernel layout.  Returns ``(padded_vecs, front)`` with
    ``front`` the element count of the leading halo (slice
    ``y[..., front: front + n]`` recovers the logical vector) — the ONE
    owner of the halo/tail arithmetic shared by eager and solver
    callers."""
    R = n // LANES
    H = padded_halo_rows(offsets, rows_tile)
    back = H + (-R) % rows_tile
    return (tuple(jnp.pad(v, [(0, 0)] * (v.ndim - 1)
                          + [(H * LANES, back * LANES)]) for v in x_vecs),
            H * LANES)


def pad_dia_operands(bands, x_vecs, rows_tile: int, offsets: tuple):
    """Pad bands and vectors into the layout the padded kernels consume:
    ``H = padded_halo_rows(offsets, rows_tile)`` zero halo rows in front,
    and ``H`` plus whatever tail rounds the total row count to a
    rows_tile multiple behind (so ANY lane-aligned n admits any tile —
    464³'s row count is 2⁵·29³ and divides nothing useful).  The bands
    come back as (D, Rp, 128), the kernels' block layout, and are passed
    to them as they are; the vectors stay flat (Rp*128,), whose
    (Rp, 128) view is a bitcast.  Traced (jnp) ops — call inside jit,
    once per solve and outside the solver loop."""
    D, n = bands.shape
    R = n // LANES
    H = padded_halo_rows(offsets, rows_tile)
    back = H + (-R) % rows_tile
    bp = jnp.pad(bands.reshape(D, R, LANES),
                 ((0, 0), (H, back), (0, 0)))
    return bp, pad_dia_vectors(x_vecs, n, rows_tile, offsets)[0]


def _cluster_windows(offsets: tuple, slack: int = 8):
    """Group diagonals into DMA windows by their row shift q: nearby q's
    (within ``slack`` rows) share one window, so a 3-D stencil's
    {0, ±1, ±nx} cluster costs ONE window DMA per tile instead of five.
    Returns a tuple of (qmin, extra_rows, diags) with diags a tuple of
    (band_index, q, r); a window's scratch holds rows_tile + extra_rows
    rows starting at tile_base + qmin."""
    items = sorted(((off // LANES, off % LANES, d)
                    for d, off in enumerate(offsets)))
    windows = []
    for q, r, d in items:
        hi = q + (1 if r else 0)
        if windows and hi - windows[-1][0] <= slack:
            qmin, ext, diags = windows[-1]
            windows[-1] = (qmin, max(ext, hi - qmin), diags + ((d, q, r),))
        else:
            windows.append((q, hi - q, ((d, q, r),)))
    return tuple(windows)


def _dia_hbm2d_kernel(windows, rows_tile, scaled, with_dot, Rp, nbuf,
                      x_hbm, bands_ref, scales_ref, y_ref, *rest):
    """HBM-resident-x variant of :func:`_dia2d_padded_kernel`: x never
    enters VMEM whole; each grid step DMAs one (rows_tile + extra, 128)
    row slab per offset WINDOW (see :func:`_cluster_windows`) into
    double-buffered scratch, prefetching the next tile's slabs behind this
    tile's compute — the size-independent single-chip road to 100M-DOF
    operators.  In-window row offsets are STATIC (q - qmin), so loads stay
    aligned slices + the shared roll/blend lane rotation."""
    nwin = len(windows)
    if with_dot:
        dot_ref, xwins, sems = rest[0], rest[1:1 + nwin], rest[1 + nwin:]
    else:
        xwins, sems = rest[:nwin], rest[nwin:]
    i = pl.program_id(0)
    nsteps = pl.num_programs(0)

    def copies(step):
        # cast nbuf to step's dtype: under x64 a python int
        # promotes to int64 while program_id is int32
        buf = jax.lax.rem(step, jnp.asarray(nbuf, step.dtype))
        base = step * rows_tile
        return [pltpu.make_async_copy(
                    x_hbm.at[pl.ds(jnp.clip(base + qmin, 0,
                                            Rp - (rows_tile + ext)),
                                   rows_tile + ext), :],
                    xwins[w].at[buf], sems[w].at[buf])
                for w, (qmin, ext, _) in enumerate(windows)]

    @pl.when(i == 0)
    def _prologue():
        for c in copies(i):
            c.start()

    @pl.when(i + 1 < nsteps)
    def _prefetch():
        for c in copies(i + 1):
            c.start()

    for c in copies(i):
        c.wait()
    slot = jax.lax.rem(i, jnp.asarray(nbuf, i.dtype))
    acc = jnp.zeros((rows_tile, LANES), dtype=y_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_tile, LANES), 1)
    x_tile = None
    for w, (qmin, ext, diags) in enumerate(windows):
        for d, q, r in diags:
            b = bands_ref[d].astype(y_ref.dtype)
            if scaled:
                b = b * scales_ref[d]
            load = lambda qq, w=w: xwins[w][slot,
                                            pl.ds(qq - qmin, rows_tile), :]
            acc = acc + b * _window_2d(load, q, r, lane)
            if with_dot and q == 0 and r == 0:
                x_tile = load(0)
    y_ref[:, :] = acc
    if with_dot:
        @pl.when(i == 0)
        def _zero():
            dot_ref[0, 0] = jnp.asarray(0.0, y_ref.dtype)

        dot_ref[0, 0] += jnp.sum(x_tile * acc)


@functools.partial(jax.jit, static_argnames=("offsets", "rows_tile",
                                             "with_dot", "interpret"))
def dia_matvec_pallas_hbm2d(bands_pad, offsets: tuple, x_pad,
                            rows_tile: int = 512, with_dot: bool = False,
                            interpret: bool = False, scales=None):
    """Same contract as :func:`dia_matvec_pallas_2d_padded` (padded
    layout in and out, optional fused <x, y>), with x HBM-resident —
    for operators past the resident kernel's VMEM bound.  ``with_dot``
    requires a main diagonal (offset 0) — always present for SPD."""
    D, Rp = _padded_band_rows(bands_pad, rows_tile)
    npad = Rp * LANES
    ntiles = Rp // rows_tile
    assert not with_dot or 0 in offsets
    windows = _cluster_windows(offsets)
    nbuf = 2
    scaled = scales is not None
    sc = (scales.astype(x_pad.dtype) if scaled
          else jnp.zeros((D,), dtype=x_pad.dtype))
    out_shape = [jax.ShapeDtypeStruct((Rp, LANES), x_pad.dtype)]
    out_specs = [pl.BlockSpec((rows_tile, LANES), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)]
    if with_dot:
        out_shape.append(jax.ShapeDtypeStruct((1, 1), x_pad.dtype))
        out_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0),
                                      memory_space=pltpu.SMEM))
    scratch = ([pltpu.VMEM((nbuf, rows_tile + ext, LANES), x_pad.dtype)
                for _, ext, _ in windows]
               + [pltpu.SemaphoreType.DMA((nbuf,)) for _ in windows])
    outs = pallas_call(
        functools.partial(_dia_hbm2d_kernel, windows, rows_tile, scaled,
                          with_dot, Rp, nbuf),
        name="acg_dia_hbm2d",
        out_shape=tuple(out_shape),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),       # x stays in HBM
            pl.BlockSpec((D, rows_tile, LANES), lambda i: (0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x_pad.reshape(Rp, LANES), bands_pad, sc)
    y = outs[0].reshape(npad)
    if with_dot:
        return y, outs[1][0, 0]
    return y


def pallas_hbm2d_plan(n: int, offsets: tuple, vec_dtype,
                      band_dtype) -> int | None:
    """rows_tile for the HBM-resident 2-D kernel, or None.  Applies where
    the resident plan does not (x past the VMEM budget); any lane-aligned
    n works (the padded layout rounds the row count up)."""
    vb = np.dtype(vec_dtype).itemsize
    mb = np.dtype(band_dtype).itemsize
    if n % LANES or vb > 4 or mb > 4:
        return None
    windows = _cluster_windows(offsets)
    for rt in (1024, 512, 256):
        xbuf = sum(2 * (rt + ext) * LANES * vb for _, ext, _ in windows)
        tile_bytes = rt * LANES * (len(offsets) * mb + vb)
        if xbuf + 2 * tile_bytes <= _VMEM_BUDGET:
            return rt
    return None


def _ring_span(offsets: tuple, rows_tile: int) -> tuple[int, int]:
    """(qmin_t, qmax_t): the relative x-TILE offsets the diagonals reach
    — each diag's (rows_tile[+1], 128) load spans abs tiles
    floor(qq/rt) .. floor((qq + rt - 1)/rt) for qq in {q, q+1 if r}."""
    lo, hi = 0, 0
    for off in offsets:
        q, r = divmod(off, LANES)
        for qq in ((q, q + 1) if r else (q,)):
            lo = min(lo, qq // rows_tile)
            hi = max(hi, (qq + rows_tile - 1) // rows_tile)
    return lo, hi


def _dia_hbm2d_ring_kernel(offsets, rows_tile, T_ring, qmin_t, qmax_t,
                           scaled, with_dot, ntiles, x_hbm, bands_ref,
                           scales_ref, y_ref, *rest):
    """Ring-buffer variant of :func:`_dia_hbm2d_kernel`: instead of one
    window DMA per offset CLUSTER per tile (which re-fetches every x row
    once per cluster — the measured ~3x overfetch at 464³, PERF.md), a
    single VMEM ring holds the T_ring consecutive x tiles spanning the
    whole offset reach, and each grid step DMAs exactly ONE new x tile —
    the x stream drops to 1.0x.  Ring slot of abs tile j is j % T_ring;
    a diagonal's (rows_tile[+1]) row span crosses at most two ring slots
    (consecutive abs tiles), loaded as two statically-sized dynamic
    slices and concatenated on the sublane dim."""
    if with_dot:
        dot_ref, xring, sems = rest[0], rest[1], rest[2]
    else:
        xring, sems = rest[0], rest[1]
    i = pl.program_id(0)
    nsteps = pl.num_programs(0)
    # T_slots = T_ring + 1: one extra slot so the NEXT step's tile can
    # stream in behind this step's compute without touching a live slot
    T_slots = T_ring + 1
    tsl = jnp.asarray(T_slots, i.dtype)

    def slot_of(j_abs):
        # abs tile j lives in slot (j - qmin_t) mod T_slots; j - qmin_t
        # >= 0 for every fetched tile (j >= i + qmin_t >= qmin_t... may
        # still be negative for i = 0 halo reach), so bias by a T_slots
        # multiple before rem to keep it non-negative
        return jax.lax.rem(j_abs - qmin_t + 8 * tsl, tsl)

    def fetch(j_abs):
        jc = jnp.clip(j_abs, 0, ntiles - 1)   # out-of-range tiles are
        # read only by zero-band halo tiles — data is irrelevant there
        s = slot_of(j_abs)
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(jc * rows_tile, rows_tile), :],
            xring.at[pl.ds(s * rows_tile, rows_tile), :],
            sems.at[s])

    @pl.when(i == 0)
    def _prologue():
        for d in range(qmin_t, qmax_t + 1):   # this step's full span
            fetch(i + d).start()

    @pl.when(i + 1 < nsteps)
    def _prefetch():
        fetch(i + 1 + qmax_t).start()

    @pl.when(i == 0)
    def _wait_prologue():
        for d in range(qmin_t, qmax_t):
            fetch(i + d).wait()

    fetch(i + qmax_t).wait()    # newest tile of THIS step (issued by the
    #                             previous step's prefetch, or prologue)

    def load(qq):
        jt, o = divmod(qq, rows_tile)        # both static
        slot_a = slot_of(i + jt)
        if o == 0:
            return xring[pl.ds(slot_a * rows_tile, rows_tile), :]
        slot_b = slot_of(i + jt + 1)
        a = xring[pl.ds(slot_a * rows_tile + o, rows_tile - o), :]
        b = xring[pl.ds(slot_b * rows_tile, o), :]
        return jnp.concatenate([a, b], axis=0)

    acc = jnp.zeros((rows_tile, LANES), dtype=y_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_tile, LANES), 1)
    x_tile = None
    for d, off in enumerate(offsets):
        q, r = divmod(off, LANES)
        b = bands_ref[d].astype(y_ref.dtype)
        if scaled:
            b = b * scales_ref[d]
        acc = acc + b * _window_2d(load, q, r, lane)
        if with_dot and q == 0 and r == 0:
            x_tile = load(0)
    y_ref[:, :] = acc
    if with_dot:
        @pl.when(i == 0)
        def _zero():
            dot_ref[0, 0] = jnp.asarray(0.0, y_ref.dtype)

        dot_ref[0, 0] += jnp.sum(x_tile * acc)


@functools.partial(jax.jit, static_argnames=("offsets", "rows_tile",
                                             "with_dot", "interpret"))
def dia_matvec_pallas_hbm2d_ring(bands_pad, offsets: tuple, x_pad,
                                 rows_tile: int = 1024,
                                 with_dot: bool = False,
                                 interpret: bool = False, scales=None):
    """Same contract as :func:`dia_matvec_pallas_hbm2d` (padded layout in
    and out, optional fused <x, y>), with the ring-buffer x stream (1.0x
    fetch instead of one fetch per offset cluster)."""
    D, Rp = _padded_band_rows(bands_pad, rows_tile)
    npad = Rp * LANES
    ntiles = Rp // rows_tile
    assert not with_dot or 0 in offsets
    qmin_t, qmax_t = _ring_span(offsets, rows_tile)
    T_ring = qmax_t - qmin_t + 1
    scaled = scales is not None
    sc = (scales.astype(x_pad.dtype) if scaled
          else jnp.zeros((D,), dtype=x_pad.dtype))
    out_shape = [jax.ShapeDtypeStruct((Rp, LANES), x_pad.dtype)]
    out_specs = [pl.BlockSpec((rows_tile, LANES), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)]
    if with_dot:
        out_shape.append(jax.ShapeDtypeStruct((1, 1), x_pad.dtype))
        out_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0),
                                      memory_space=pltpu.SMEM))
    scratch = [pltpu.VMEM(((T_ring + 1) * rows_tile, LANES), x_pad.dtype),
               pltpu.SemaphoreType.DMA((T_ring + 1,))]
    outs = pallas_call(
        functools.partial(_dia_hbm2d_ring_kernel, offsets, rows_tile,
                          T_ring, qmin_t, qmax_t, scaled, with_dot,
                          ntiles),
        name="acg_dia_hbm2d_ring",
        out_shape=tuple(out_shape),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),       # x stays in HBM
            pl.BlockSpec((D, rows_tile, LANES), lambda i: (0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x_pad.reshape(Rp, LANES), bands_pad, sc)
    y = outs[0].reshape(npad)
    if with_dot:
        return y, outs[1][0, 0]
    return y


def pallas_hbm2d_ring_plan(n: int, offsets: tuple, vec_dtype,
                           band_dtype) -> int | None:
    """rows_tile for the ring kernel, or None (lane-misaligned, f64, or
    a ring too large for VMEM — very wide offset spans fall back to the
    clustered-window kernel, which has no span-proportional footprint).
    Besides the ring and the double-buffered tiles, the kernel keeps each
    band tile upcast to the vector dtype on its stack: 27 bf16 bands at
    rows_tile 512 need 16.3 MB of VMEM on v5e (the compiler's count),
    which the estimate without them put at 9.1."""
    vb = np.dtype(vec_dtype).itemsize
    mb = np.dtype(band_dtype).itemsize
    if n % LANES or vb > 4 or mb > 4:
        return None
    for rt in (1024, 512, 256):
        qmin_t, qmax_t = _ring_span(offsets, rt)
        ring = (qmax_t - qmin_t + 2) * rt * LANES * vb  # +1 prefetch slot
        tile_bytes = rt * LANES * (len(offsets) * mb + vb)
        upcast = len(offsets) * rt * LANES * vb
        if ring + 2 * tile_bytes + upcast <= _VMEM_BUDGET:
            return rt
    return None


def pallas_2d_plan(n: int, offsets: tuple, vec_dtype,
                   band_dtype) -> int | None:
    """rows_tile for the resident 2-D kernels, or None when the
    shape/dtype is outside their bounds (lane-misaligned n, f64, padded x
    exceeding the VMEM budget).  The VMEM estimate charges the REAL halo
    (ceil(need/rt)·rt rows per side — covers both the plain kernel's Wr
    and the padded layout's multi-tile H), so wide-offset thin-slab
    operators correctly fall through to the HBM kernel instead of blowing
    VMEM at compile time."""
    vb = np.dtype(vec_dtype).itemsize
    mb = np.dtype(band_dtype).itemsize
    if n % LANES or vb > 4 or mb > 4:
        return None
    R = n // LANES
    for rt in (512, 256, 128, 64, 32, 16, 8):
        if R % rt:
            continue
        H = padded_halo_rows(offsets, rt)
        x_bytes = (R + 2 * H) * LANES * vb
        tile_bytes = rt * LANES * (len(offsets) * mb + vb)
        if x_bytes + 2 * tile_bytes <= _VMEM_BUDGET:
            return rt
    return None


def pipe2d_plan(npad: int, offsets: tuple, vec_dtype, band_dtype,
                rows_tile_resident: int) -> int | None:
    """rows_tile for the single-kernel pipelined iteration
    (:func:`cg_pipelined_iter_pallas`), or None when it cannot fit.

    The pipe2d kernel pipelines 11 double-buffered vector tile streams
    (5 in + 6 out) ON TOP of the resident w and the band tiles — far more
    than the SpMV kernels the "resident" gate budgets for — so it needs
    its OWN VMEM check; reusing the resident plan's rows_tile can exceed
    physical VMEM at the flagship shape (review finding, round 5).  The
    tile must DIVIDE the resident plan's rows_tile: the operand padding
    (halo = whole rows_tile_resident tiles) was built for that layout,
    and any divisor keeps the grid uniform over it.  ``npad`` is the
    already-padded length."""
    vb = np.dtype(vec_dtype).itemsize
    mb = np.dtype(band_dtype).itemsize
    if npad % LANES or vb > 4 or mb > 4:
        return None
    Rp = npad // LANES
    w_bytes = Rp * LANES * vb
    for rt in (512, 256, 128, 64, 32, 16, 8):
        if rows_tile_resident % rt or Rp % rt:
            continue
        band_tile = rt * LANES * len(offsets) * mb
        vec_tiles = 11 * rt * LANES * vb
        if w_bytes + 2 * (band_tile + vec_tiles) <= _VMEM_BUDGET:
            return rt
    return None


def pipe2d_rt_for(nrows_padded: int, offsets: tuple, vec_dtype,
                  band_dtype, plan) -> int | None:
    """THE pipe2d gate, shared by the single-chip and distributed
    pipelined solvers (their selection must never diverge): rows_tile for
    the single-kernel iteration, or None.  ``plan`` is the fused-plan
    result; the kernel applies only on the resident tier, after its
    probe passes, and within its own VMEM
    plan.  Call OUTSIDE jit (probes must not run inside a trace; the
    result must be part of the jit cache key)."""
    if plan is None or plan[0] != "resident":
        return None
    if not pallas_spmv_available("pipe2d"):
        return None
    rt = plan[1]
    R = nrows_padded // LANES
    H = padded_halo_rows(offsets, rt)
    Rp = -(-(R + 2 * H) // rt) * rt          # pad_dia_operands geometry
    return pipe2d_plan(Rp * LANES, offsets, vec_dtype, band_dtype, rt)


def hbm_kernel_plan(n: int, offsets: tuple, vec_dtype, band_dtype):
    """(kind, kernel, rows_tile) for the HBM regime — the ONE owner of
    the ring-before-windows priority (ring: 1.0x x stream; clustered
    windows: the fallback for offset spans too wide for a VMEM ring) —
    or (None, None, None).  Shared by :func:`fused_plan_for` and the
    plain-matvec selector (acg_tpu/ops/dia.py)."""
    rt = pallas_hbm2d_ring_plan(n, offsets, vec_dtype, band_dtype)
    if rt is not None and pallas_spmv_available("hbm2dr"):
        return "hbm-ring", dia_matvec_pallas_hbm2d_ring, rt
    rt = pallas_hbm2d_plan(n, offsets, vec_dtype, band_dtype)
    if rt is not None and pallas_spmv_available("hbm2d"):
        return "hbm", dia_matvec_pallas_hbm2d, rt
    return None, None, None


def fused_kernels() -> dict:
    """kind -> padded-contract kernel, for every kind
    :func:`fused_plan_for` can return — the one map the solvers dispatch
    through (acg_tpu/solvers/cg.py ``_fused_ops``, cg_dist.py)."""
    return {"resident": dia_matvec_pallas_2d_padded,
            "resident-batched": dia_matvec_pallas_2d_padded_batched,
            "hbm-ring": dia_matvec_pallas_hbm2d_ring,
            "hbm": dia_matvec_pallas_hbm2d}


def fused_plan_for(n: int, offsets: tuple, vec_dtype,
                   band_dtype) -> tuple[str, int] | None:
    """THE fused padded-path gate, shared by the single-chip solver
    (acg_tpu/solvers/cg.py ``_fused_plan``) and the distributed per-shard
    plan (acg_tpu/solvers/cg_dist.py ``_dist_fused_plan``): ("resident" |
    "hbm-ring" | "hbm", rows_tile) — a :func:`fused_kernels` key — when a
    padded Pallas kernel is the right path for
    this (n, offsets, dtypes), else None.  The fused LOOP takes every
    storage width including f32: its win is structural (padded carries +
    in-kernel p'Ap), and the A/B measured it directly — p3d-var-96 f32
    full-width 25,578 it/s fused vs 19,448 XLA, 2026-07-31 (a
    pre-bring-up record, deleted at PR 21), even though the bare
    chained-marginal f32 SpMV loses to XLA (dia_matvec_best keeps plain
    f32 matvecs on XLA).
    ACG_TPU_FUSED_F32=0 restores the narrow-tiers-only gate for
    re-measurement.  HBM: any width past the resident VMEM bound."""
    import os

    if 0 not in offsets:
        return None
    bdt = np.dtype(band_dtype)
    rt = pallas_2d_plan(n, offsets, vec_dtype, bdt)
    if rt is not None:
        wide_ok = os.environ.get("ACG_TPU_FUSED_F32", "") != "0"
        if ((bdt.itemsize <= 2 or wide_ok)
                and pallas_spmv_available("fused2d")):
            return "resident", rt
        return None
    kind, _, rt = hbm_kernel_plan(n, offsets, vec_dtype, bdt)
    return (kind, rt) if kind is not None else None


def _pick_rows_tile(n: int) -> int | None:
    """Largest row-tile (in 128-lane rows) dividing n's row count, or None
    when n is not lane-aligned."""
    if n % LANES:
        return None
    R = n // LANES
    for t in (512, 256, 128, 64, 32, 16, 8):
        if R % t == 0:
            return t
    return None


# The 1-D HBM kernels (windowed/streamed) were DELETED with the rest
# of the (1, tile) family: rejected by current Mosaic (unaligned
# lane-dimension loads) and superseded by dia_matvec_pallas_hbm2d
# (full vreg density, clustered window DMAs, fused dot).


_VMEM_BUDGET = 12 * 2**20   # leave headroom below the ~16 MB/core VMEM


_SPMV_PROBE: dict = {}   # probe group -> passed
_PROBE_ERRORS: dict = {}  # probe group -> failure text, None if passed


def _probe_dia_group(kernels, n: int = 2048,
                     offsets: tuple = (-128, -1, 0, 1, 128)) -> bool:
    """Compile-and-match every DIA storage tier through each kernel of a
    group against the XLA path.  The bound is RELATIVE to the result scale
    (an absolute bound would bless a broken kernel on ill-scaled bands);
    the reference path reads the SAME narrowed band values, so all tiers
    compare at f32 accumulation tightness."""
    from acg_tpu.ops.dia import dia_matvec

    rng = np.random.default_rng(0)
    b32 = rng.standard_normal((len(offsets), n)).astype(np.float32)
    xv = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    ok = True
    for bands, scales in (
            (jnp.asarray(b32), None),
            (jnp.asarray(b32).astype(jnp.bfloat16), None),
            (jnp.asarray((b32 > 0).astype(np.int8)),
             jnp.asarray(np.arange(1.0, 1.0 + len(offsets),
                                   dtype=np.float32)))):
        bref = (bands.astype(jnp.float32) if scales is None
                else bands.astype(jnp.float32) * scales[:, None])
        want = dia_matvec(bref, offsets, xv)
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        for fn, kw in kernels:
            got = fn(bands, offsets, xv, scales=scales, **kw)
            ok = ok and bool(jnp.max(jnp.abs(got - want)) < 1e-5 * scale)
    return ok


def _probe_ell_group() -> bool:
    """Compile-and-match the ELL gather kernel (acg_tpu/ops/pallas_spmv.py)
    for f32 and bf16 value storage against the XLA gather formulation, at
    EVERY tile size _pick_ell_tile can select."""
    from acg_tpu.ops.pallas_spmv import _ELL_TILES, ell_matvec_pallas
    from acg_tpu.ops.spmv import ell_matvec

    rng = np.random.default_rng(0)
    n, W = 1024, 9
    vals = rng.standard_normal((n, W)).astype(np.float32)
    cols = jnp.asarray(rng.integers(0, n, (n, W)).astype(np.int32))
    xv = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    ok = True
    for v in (jnp.asarray(vals), jnp.asarray(vals, jnp.bfloat16)):
        want = ell_matvec(v, cols, xv)
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        for tile in _ELL_TILES:
            got = ell_matvec_pallas(v, cols, xv, tile=tile)
            ok = ok and bool(jnp.max(jnp.abs(got - want)) < 1e-5 * scale)
    return ok


def _probe_padded_group(kernel, shapes) -> bool:
    """Compile-and-match a padded-contract kernel (matvec + fused dot) at
    production shapes across all three storage tiers, including the
    zero-halo invariant the CG loop relies on."""
    from acg_tpu.ops.dia import dia_matvec

    rng = np.random.default_rng(0)
    ok = True
    for n, offsets, rt in shapes:
        D = len(offsets)
        b32 = rng.standard_normal((D, n)).astype(np.float32)
        xv = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        for bands, scales in (
                (jnp.asarray(b32), None),
                (jnp.asarray(b32).astype(jnp.bfloat16), None),
                (jnp.asarray((b32 > 0).astype(np.int8)),
                 jnp.asarray(np.arange(1.0, 1.0 + D, dtype=np.float32)))):
            bref = (bands.astype(jnp.float32) if scales is None
                    else bands.astype(jnp.float32) * scales[:, None])
            want = dia_matvec(bref, offsets, xv)
            want_dot = jnp.vdot(xv, want)
            bp, (xp,) = pad_dia_operands(bands, (xv,), rt, offsets)
            hp = padded_halo_rows(offsets, rt) * LANES
            got, gd = kernel(bp, offsets, xp, rows_tile=rt,
                             with_dot=True, scales=scales)
            mid = got[hp: hp + n]
            yscale = float(jnp.max(jnp.abs(want))) or 1.0
            # cancellation-safe dot scale: |x|·|y|, not |x·y|
            dscale = float(jnp.linalg.norm(xv) * jnp.linalg.norm(want)) or 1.0
            ok = ok and bool(jnp.max(jnp.abs(mid - want)) < 1e-5 * yscale)
            ok = ok and bool(jnp.abs(gd - want_dot) < 1e-5 * dscale)
            # the halo must come back EXACTLY zero (the padded-layout
            # invariant the CG loop relies on)
            ok = ok and bool(jnp.all(got[:hp] == 0.0))
            ok = ok and bool(jnp.all(got[hp + n:] == 0.0))
    return ok


def _probe_batched_group(interpret: bool = False) -> bool:
    """Compile-and-match the multi-RHS padded kernel
    (:func:`dia_matvec_pallas_2d_padded_batched`) against the batched XLA
    shift formulation across all three storage tiers, at both rows_tile
    extremes, with the per-system fused dot and the zero-halo invariant
    (every system's halo must come back exactly 0)."""
    from acg_tpu.ops.dia import dia_matvec

    rng = np.random.default_rng(2)
    ok = True
    for B, n, offsets, rt in (
            (3, 16 * 128, (-128, -3, 0, 3, 128), 16),
            (2, 512 * 128, (-16384, -128, -1, 0, 1, 128, 16384), 512)):
        D = len(offsets)
        b32 = rng.standard_normal((D, n)).astype(np.float32)
        xv = jnp.asarray(rng.standard_normal((B, n)).astype(np.float32))
        for bands, scales in (
                (jnp.asarray(b32), None),
                (jnp.asarray(b32).astype(jnp.bfloat16), None),
                (jnp.asarray((b32 > 0).astype(np.int8)),
                 jnp.asarray(np.arange(1.0, 1.0 + D, dtype=np.float32)))):
            bref = (bands.astype(jnp.float32) if scales is None
                    else bands.astype(jnp.float32) * scales[:, None])
            want = dia_matvec(bref, offsets, xv)
            want_dot = jnp.sum(xv * want, axis=-1)
            bp, (xp,) = pad_dia_operands(bands, (xv,), rt, offsets)
            hp = padded_halo_rows(offsets, rt) * LANES
            got, gd = dia_matvec_pallas_2d_padded_batched(
                bp, offsets, xp, rows_tile=rt, with_dot=True,
                scales=scales, interpret=interpret)
            mid = got[:, hp: hp + n]
            yscale = float(jnp.max(jnp.abs(want))) or 1.0
            dscale = float(jnp.max(
                jnp.linalg.norm(xv, axis=-1)
                * jnp.linalg.norm(want, axis=-1))) or 1.0
            ok = ok and bool(jnp.max(jnp.abs(mid - want)) < 1e-5 * yscale)
            ok = ok and bool(jnp.max(jnp.abs(gd - want_dot))
                             < 1e-4 * dscale)
            ok = ok and bool(jnp.all(got[:, :hp] == 0.0))
            ok = ok and bool(jnp.all(got[:, hp + n:] == 0.0))
    return ok


def _probe_pipe2d_group(interpret: bool = False) -> bool:
    """Compile-and-match the single-kernel pipelined iteration
    (:func:`cg_pipelined_iter_pallas`) against the plain jnp formulation
    at production shapes across the storage tiers, including the
    zero-halo invariant (every output's halo must come back exactly 0)."""
    from acg_tpu.ops.dia import dia_matvec

    rng = np.random.default_rng(1)
    ok = True
    for n, offsets, rt in ((512 * 128, (-16384, -128, -1, 0, 1, 128,
                                        16384), 512),
                           (16 * 128, (-128, -3, 0, 3, 128), 16)):
        D = len(offsets)
        b32 = rng.standard_normal((D, n)).astype(np.float32)
        vecs = [jnp.asarray(rng.standard_normal(n).astype(np.float32))
                for _ in range(6)]
        alpha = jnp.float32(0.37)
        beta = jnp.float32(1.21)
        for bands, scales in (
                (jnp.asarray(b32), None),
                (jnp.asarray(b32).astype(jnp.bfloat16), None),
                (jnp.asarray((b32 > 0).astype(np.int8)),
                 jnp.asarray(np.arange(1.0, 1.0 + D, dtype=np.float32)))):
            bref = (bands.astype(jnp.float32) if scales is None
                    else bands.astype(jnp.float32) * scales[:, None])
            w, z, r, p, s, x = vecs
            q = dia_matvec(bref, offsets, w)
            z2 = q + beta * z
            p2 = r + beta * p
            s2 = w + beta * s
            x2 = x + alpha * p2
            r2 = r - alpha * s2
            w2 = w - alpha * z2
            want = (z2, p2, s2, x2, r2, w2)
            gexp, dexp = jnp.vdot(r2, r2), jnp.vdot(w2, r2)
            bp, padded = pad_dia_operands(bands, tuple(vecs), rt, offsets)
            wp, zp, rp, pp, sp, xp = padded
            hp = padded_halo_rows(offsets, rt) * LANES
            got = cg_pipelined_iter_pallas(bp, offsets, wp, zp, rp, pp,
                                           sp, xp, alpha, beta,
                                           rows_tile=rt, scales=scales,
                                           interpret=interpret)
            for gv, wv in zip(got[:6], want):
                scale = float(jnp.max(jnp.abs(wv))) or 1.0
                ok = ok and bool(
                    jnp.max(jnp.abs(gv[hp: hp + n] - wv)) < 1e-5 * scale)
                ok = ok and bool(jnp.all(gv[:hp] == 0.0))
                ok = ok and bool(jnp.all(gv[hp + n:] == 0.0))
            # gamma is an all-positive sum: accumulation ORDER alone moves
            # it ~1e-5 relative at 65k rows (measured in interpret mode),
            # so 1e-4 is the wrong-kernel detector, not a precision claim
            # (indexing bugs produce O(1) relative errors)
            gs = float(jnp.vdot(r2, r2)) or 1.0
            ds = float(jnp.linalg.norm(w2) * jnp.linalg.norm(r2)) or 1.0
            ok = ok and bool(jnp.abs(got[6] - gexp) < 1e-4 * gs)
            ok = ok and bool(jnp.abs(got[7] - dexp) < 1e-4 * ds)
    return ok


_PROBE_GROUPS = {
    # probe at PRODUCTION block shapes:
    # both rows_tile extremes the selector can pick, with a flagship-scale
    # offset (±16384 = 128³'s z-band ⇒ a 129-row halo slab) plus the
    # lane-rotation path — Mosaic accepting a tiny block but rejecting the
    # big one would otherwise crash dia_matvec_best at trace time
    "resident2d": lambda: _probe_dia_group(
        ((dia_matvec_pallas_2d, dict(rows_tile=512)),
         (dia_matvec_pallas_2d, dict(rows_tile=8)),),
        n=512 * 128,
        offsets=(-16384, -128, -1, 0, 1, 128, 16384)),
    "fused2d": lambda: _probe_padded_group(
        dia_matvec_pallas_2d_padded,
        ((512 * 128, (-16384, -128, -1, 0, 1, 128, 16384), 512),
         (16 * 128, (-128, -3, 0, 3, 128), 16))),
    # the HBM kernel probe covers clustered windows (the {0, ±1, ±nx}
    # group sharing one DMA), a lone far window, an odd row count
    # exercising the asymmetric tail pad, and all three storage tiers
    "hbm2d": lambda: _probe_padded_group(
        dia_matvec_pallas_hbm2d,
        ((520 * 128, (-16384, -464, -1, 0, 1, 464, 16384), 512),
         (24 * 128, (-128, -3, 0, 3, 128), 16))),
    # ring-buffer HBM kernel: the same production shapes as hbm2d PLUS a
    # multi-tile ring span (third shape: reach past 2 tiles at rt=16) —
    # the 464³ geometry class whose window overfetch the ring removes
    "hbm2dr": lambda: _probe_padded_group(
        dia_matvec_pallas_hbm2d_ring,
        ((520 * 128, (-16384, -464, -1, 0, 1, 464, 16384), 512),
         (24 * 128, (-128, -3, 0, 3, 128), 16),
         (40 * 128, (-2100, -130, -1, 0, 1, 130, 2100), 16))),
    # the single-kernel pipelined iteration (SpMV + 6-vector update +
    # both dots in one pass — see cg_pipelined_iter_pallas)
    "pipe2d": _probe_pipe2d_group,
    # the multi-RHS resident kernel (batch grid dimension; band tiles
    # fetched once per row tile across all B systems)
    "batched2d": _probe_batched_group,
    # the ELL gather kernel (acg_tpu/ops/pallas_spmv.py): OUT_OF_AUTO
    "ell": _probe_ell_group,
    # matrix-free stencil kernels (acg_tpu/ops/stencil.py): bands
    # synthesized in-register, zero operator HBM stream
    "stencil2d": lambda: __import__(
        "acg_tpu.ops.stencil", fromlist=["_probe_stencil_group"]
    )._probe_stencil_group(),
    # its single-kernel pipelined iteration (the matrix-free pipe2d)
    "stpipe2d": lambda: __import__(
        "acg_tpu.ops.stencil", fromlist=["_probe_stpipe_group"]
    )._probe_stpipe_group(),
    # segmented-gather ELL (acg_tpu/ops/sgell.py): the unstructured tier
    "sgell": lambda: __import__(
        "acg_tpu.ops.sgell", fromlist=["_probe_sgell_group"]
    )._probe_sgell_group(),
    # its int8 lane-index storage tier (independent: a Mosaic rejecting
    # int8 blocks must degrade to int32 without killing the tier)
    "sgell8": lambda: __import__(
        "acg_tpu.ops.sgell", fromlist=["_probe_sgell8_group"]
    )._probe_sgell8_group(),
}


# Probe groups no automatic routing selects: their kernels run only when a
# caller forces them, and a forced call raises ERR_NOT_SUPPORTED when the
# probe failed.  "ell": Mosaic on TPU v5e refuses its in-kernel 1-D
# vector gather ("Only 2D gather is supported", PR 21 chip run); the
# unstructured tier on the chip is sgell.
OUT_OF_AUTO = frozenset({"ell"})


def pallas_spmv_available(kind: str = "resident2d") -> bool:
    """Probe once per KERNEL GROUP whether the Pallas kernels compile AND
    match the XLA path on this backend.  False off TPU, with
    ``ACG_TPU_PALLAS=0``, or when the probe raises or mismatches — a
    group that is False is left out of routing, so enabling a kernel can
    never change results.  A failure on a TPU is reported on stderr once
    and kept for :func:`probe_errors`.  Groups probe independently: a
    Mosaic regression in one group must not disable a proven group."""
    if kind in _SPMV_PROBE:
        return _SPMV_PROBE[kind]
    import os
    import sys

    platform = jax.devices()[0].platform
    if os.environ.get("ACG_TPU_PALLAS", "").strip() == "0":
        ok, err = False, "disabled by ACG_TPU_PALLAS=0"
    elif platform != "tpu":
        ok, err = False, f"no TPU (platform {platform})"
    else:
        try:
            ok = bool(_PROBE_GROUPS[kind]())
            err = None if ok else "result differs from the XLA reference"
        except Exception as e:  # noqa: BLE001 - kept and reported below
            ok, err = False, f"{type(e).__name__}: {str(e)[:2000]}"
        if not ok:
            print(f"acg_tpu: Pallas probe group {kind!r} failed; its "
                  f"kernels are not used: {err}", file=sys.stderr)
    _SPMV_PROBE[kind] = ok
    _PROBE_ERRORS[kind] = err
    return ok


def probe_errors() -> dict:
    """group -> why its probe failed (None when it passed), for every
    group probed so far in this process."""
    return dict(_PROBE_ERRORS)


def probe_all() -> dict:
    """Run every probe group; group -> None (passed) or the reason."""
    for kind in _PROBE_GROUPS:
        pallas_spmv_available(kind)
    return {k: _PROBE_ERRORS[k] for k in _PROBE_GROUPS}


# pipelined_update_pallas (the 6-vector fused pipelined-CG update as one
# Pallas kernel, the analog of reference acg/cg-kernels-cuda.cu:187-269)
# was DELETED after measurement: on v5e at 128^3 the XLA-fused update is
# marginally faster (2826 us vs 2882 us, speedup 0.981 — a pre-bring-up
# record, deleted at PR 21), i.e. XLA already emits the single fused pass
# over the 7 streams inside the jitted solver loop, so the hand-written kernel
# bought nothing.  See PERF.md "wire-or-delete decisions".
