"""AOT compiles of the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers one kernel at its production shape for a
``v5e:2x2`` topology described (not attached) and compiles it with the
TPU compiler, which refuses what Mosaic would refuse on the chip (VMEM
over the limit, slices not aligned to the tiling).  The topology is
described inside a fixture, never at import: only one process may hold
the TPU library, and the driver's test workers all import this file.
"""

import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from acg_tpu.ops import pallas_kernels as pk
from acg_tpu.ops import sgell as sg
from acg_tpu.ops import stencil as st

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    return txt


def _p3d(g):
    """7-pt offsets of a g³ grid and its row count."""
    return (-g * g, -g, -1, 0, 1, g, g * g), g ** 3


def _padded_rows(n, offsets, rt):
    R = n // pk.LANES
    H = pk.padded_halo_rows(offsets, rt)
    return R + 2 * H + (-R) % rt


def _padded_len(n, offsets, rt):
    return _padded_rows(n, offsets, rt) * pk.LANES


def _p27(g):
    """HPCG's 27-pt offsets of a g³ grid and its row count."""
    return tuple(sorted(i * g * g + j * g + k for i in (-1, 0, 1)
                        for j in (-1, 0, 1) for k in (-1, 0, 1))), g ** 3


def test_fused_resident_dia_128(one_chip):
    offsets, n = _p3d(128)
    rt = pk.pallas_2d_plan(n, offsets, np.float32, np.dtype("bfloat16"))
    assert rt is not None

    def step(bands, x):
        bp, (xp,) = pk.pad_dia_operands(bands, (x,), rt, offsets)
        return pk.dia_matvec_pallas_2d_padded(bp, offsets, xp,
                                              rows_tile=rt, with_dot=True)

    _compile(step, one_chip, ((7, n), BF16), ((n,), F32))


def test_hbm_ring_dia_256(one_chip):
    offsets, n = _p3d(256)
    rt = pk.pallas_hbm2d_ring_plan(n, offsets, np.float32,
                                   np.dtype("bfloat16"))
    assert rt is not None
    Rp = _padded_rows(n, offsets, rt)

    def step(bands_pad, x_pad):
        return pk.dia_matvec_pallas_hbm2d_ring(bands_pad, offsets, x_pad,
                                               rows_tile=rt, with_dot=True)

    _compile(step, one_chip, ((7, Rp, pk.LANES), BF16),
             ((Rp * pk.LANES,), F32))


def test_hbm_ring_dia_27pt_256(one_chip):
    """HPCG's 27 bands at 256³: the plan picks a rows_tile whose VMEM
    stack fits (512 did not: 16.3 MB on the chip)."""
    offsets, n = _p27(256)
    rt = pk.pallas_hbm2d_ring_plan(n, offsets, np.float32,
                                   np.dtype("bfloat16"))
    assert rt == 256
    Rp = _padded_rows(n, offsets, rt)

    def step(bands_pad, x_pad):
        return pk.dia_matvec_pallas_hbm2d_ring(bands_pad, offsets, x_pad,
                                               rows_tile=rt, with_dot=True)

    _compile(step, one_chip, ((27, Rp, pk.LANES), BF16),
             ((Rp * pk.LANES,), F32))


def test_pipe2d_iteration_128(one_chip):
    offsets, n = _p3d(128)
    rt = pk.pallas_2d_plan(n, offsets, np.float32, np.dtype("bfloat16"))
    npad = _padded_len(n, offsets, rt)
    prt = pk.pipe2d_plan(npad, offsets, np.float32, np.dtype("bfloat16"),
                         rt)
    assert prt is not None

    def step(bands_pad, w, z, r, p, s, x, ab):
        return pk.cg_pipelined_iter_pallas(bands_pad, offsets, w, z, r, p,
                                           s, x, ab[0], ab[1],
                                           rows_tile=prt)

    _compile(step, one_chip, ((7, npad // pk.LANES, pk.LANES), BF16),
             *[((npad,), F32)] * 6, ((2,), F32))


def _while_body_lines(txt):
    """The instructions of every computation a while body of the
    compiled module reaches (fusions and branches included)."""
    comps, cur = {}, None
    for line in txt.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            cur = cur.lstrip("%")
            comps[cur] = []
        elif cur is not None and line.startswith(" "):
            comps[cur].append(line)
    todo = [c for c in re.findall(r"body=%?([\w.\-]+)", txt) if c in comps]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += [r for r in re.findall(r"%([\w.\-]+)", line)
                     if r in comps]
    assert seen, "no while loop in the compiled module"
    return [line for c in seen for line in comps[c]]


def _band_ops_in_loop(txt, D, n):
    """Ops in the loop that produce a bf16 array of at least the bands'
    element count — a copy, slice, pad or fusion of the stored bands.
    Reading the loop-carried bands (get-tuple-element, a bitcast, the
    kernel's custom call) moves nothing and is not listed."""
    free = {"parameter", "get-tuple-element", "bitcast", "tuple",
            "custom-call"}
    hits = []
    for line in _while_body_lines(txt):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m is None or m.group(2) in free:
            continue
        for dims in re.findall(r"bf16\[([\d,]*)\]", m.group(1)):
            if dims and np.prod([int(v) for v in dims.split(",")]) >= D * n:
                hits.append(line.strip()[:160])
    return hits


def _fused_loop_text(one_chip, offsets, n, solver):
    """Optimized HLO of the fused solve loop that ``solver`` ("classic":
    ``_cg_device_fused``; "pipelined": ``_cg_pipelined_device_fused``)
    jits, at ``n`` unknowns on the HBM-ring tier with bf16 bands."""
    from acg_tpu.ops.dia import DeviceDia

    cgm = importlib.import_module("acg_tpu.solvers.cg")
    D = len(offsets)
    rt = pk.pallas_hbm2d_ring_plan(n, offsets, np.float32,
                                   np.dtype("bfloat16"))
    assert rt is not None
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    op = DeviceDia(bands=sds((D, n), BF16), offsets=offsets, nrows=n,
                   ncols=n, nnz=D * n, vec_dtype="float32")
    b = sds((n,), F32)
    stop2 = (sds((), F32), sds((), F32))
    if solver == "classic":
        def solve(op, b, x0, stop2, diffstop):
            return cgm._cg_device_fused(op, b, x0, stop2, diffstop,
                                        maxits=100, track_diff=False,
                                        check_every=1, rows_tile=rt,
                                        kind="hbm-ring")

        low = jax.jit(solve).lower(op, b, b, stop2, sds((), F32))
    else:
        def solve(op, b, x0, stop2):
            return cgm._cg_pipelined_device_fused(
                op, b, x0, stop2, maxits=100, check_every=1,
                replace_every=50, rows_tile=rt, kind="hbm-ring")

        low = jax.jit(solve).lower(op, b, b, stop2)
    txt = low.compile().as_text()
    assert "tpu_custom_call" in txt
    return txt


@pytest.mark.parametrize("pts,solver", [(7, "classic"), (27, "classic"),
                                        (7, "pipelined")],
                         ids=["cg-7pt", "cg-27pt", "pipelined-7pt"])
def test_fused_loop_carries_bands_without_relayout_256(one_chip, pts,
                                                       solver):
    """The 256³ fused solve loops read the padded bands straight from
    the loop carry: no copy, slice or fusion of the bands in the while
    body.  A (D, Rp*128) carry reshaped to the kernels' (D, Rp, 128) in
    the body costs a relayout of every band on every iteration (7 bands:
    a copy fusion; 27: a copy to 32 padded band rows and a slice back)."""
    offsets, n = (_p3d if pts == 7 else _p27)(256)
    txt = _fused_loop_text(one_chip, offsets, n, solver)
    assert _band_ops_in_loop(txt, len(offsets), n) == []


def test_band_scan_sees_a_2d_band_carry(one_chip):
    """Control for the test above: a loop that carries the bands flat and
    reshapes them to the kernel layout in its body shows the relayout."""
    offsets, n = _p3d(256)
    rt = pk.pallas_hbm2d_ring_plan(n, offsets, np.float32,
                                   np.dtype("bfloat16"))
    Rp = _padded_rows(n, offsets, rt)

    def loop(bands_flat, x_pad):
        def body(_, x):
            return pk.dia_matvec_pallas_hbm2d_ring(
                bands_flat.reshape(7, Rp, pk.LANES), offsets, x,
                rows_tile=rt)

        return jax.lax.fori_loop(0, 100, body, x_pad)

    txt = _compile(loop, one_chip, ((7, Rp * pk.LANES), BF16),
                   ((Rp * pk.LANES,), F32))
    assert _band_ops_in_loop(txt, 7, n) != []


def _stencil_128():
    grid = (128, 128, 128)
    offsets, digits, coeffs = st._probe_grid_spec(grid)
    return grid, offsets, digits, coeffs, 128 ** 3


def test_stencil_matvec_128(one_chip):
    grid, offsets, digits, coeffs, n = _stencil_128()
    rt = st.stencil_plan(n, offsets, np.float32)
    assert rt is not None
    npad = _padded_len(n, offsets, rt)

    def step(x_pad):
        return st.stencil_matvec_pallas_padded(grid, offsets, digits,
                                               coeffs, x_pad, rows_tile=rt,
                                               n=n, with_dot=True)

    _compile(step, one_chip, ((npad,), F32))


def test_stencil_pipelined_iteration_128(one_chip):
    grid, offsets, digits, coeffs, n = _stencil_128()
    rt = st.stencil_pipe_plan(n, offsets, np.float32)
    assert rt is not None
    npad = _padded_len(n, offsets, rt)

    def step(w, z, r, p, s, x, ab):
        return st.cg_pipelined_iter_stencil(grid, offsets, digits, coeffs,
                                            w, z, r, p, s, x, ab[0], ab[1],
                                            rows_tile=rt, n=n)

    _compile(step, one_chip, *[((npad,), F32)] * 6, ((2,), F32))


def test_stencil_pipelined_solve_loop_128(one_chip):
    """The whole pipelined solve loop around the mega-kernel, with the
    fp32 default residual replacement (PR 21) and exit certification:
    what cg_pipelined runs at 128³ (_cg_pipelined_stencil_fused)."""
    from acg_tpu.solvers.loops import cg_pipelined_while

    grid, offsets, digits, coeffs, n = _stencil_128()
    rt = st.stencil_pipe_plan(n, offsets, np.float32)
    npad = _padded_len(n, offsets, rt)
    front = pk.padded_halo_rows(offsets, rt) * pk.LANES

    def mv(v):
        core = jax.lax.slice_in_dim(v, front, front + n)
        y = st.stencil_matvec(core, grid, digits, coeffs)
        return jnp.pad(y, [(front, npad - front - n)])

    def solve(b, x0, stop2):
        def iter_step(z, r, p, w, s, x, alpha, beta):
            return st.cg_pipelined_iter_stencil(
                grid, offsets, digits, coeffs, w, z, r, p, s, x, alpha,
                beta, rows_tile=rt, n=n)

        return cg_pipelined_while(
            mv, lambda a, b_, c, d: (jnp.vdot(a, b_), jnp.vdot(c, d)),
            b, x0, (stop2[0], stop2[1]), 500, replace_every=None,
            iter_step=iter_step)[:2]

    _compile(solve, one_chip, ((npad,), F32), ((npad,), F32), ((2,), F32))


@pytest.mark.parametrize("idx_dtype", [jnp.int32, jnp.int8],
                         ids=["sgell", "sgell8"])
def test_sgell_slot_kernel(one_chip, idx_dtype):
    # a 1M-row unstructured operator: 1024 row tiles, ~6 slots per tile
    ntiles = 1024
    S = 6 * ntiles
    rows = S * sg.SUBL

    def step(vals, idx, seg, tile, first, x):
        return sg.sgell_matvec_pallas(vals, idx, seg, tile, first, x,
                                      S=S, ntiles=ntiles)

    _compile(step, one_chip, ((rows, sg.LANES), BF16),
             ((rows, sg.LANES), idx_dtype), ((S, sg.SUBL), jnp.int32),
             ((S,), jnp.int32), ((S,), jnp.int32),
             ((ntiles * sg.SUBL * sg.LANES,), F32))
