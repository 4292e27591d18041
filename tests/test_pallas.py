"""Pallas kernel correctness tests (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from acg_tpu.ops.dia import DiaMatrix
from acg_tpu.ops.pallas_kernels import dia_matvec_pallas_2d
from acg_tpu.sparse import poisson2d_5pt, poisson3d_7pt


@pytest.mark.parametrize("gen,n", [(poisson2d_5pt, 32), (poisson3d_7pt, 10)])
def test_dia_matvec_pallas_2d_f64_interpret(gen, n):
    """f64 through interpret mode (real Mosaic has no f64 — the selection
    layer never routes f64 to the kernel, but interpret-mode correctness
    pins the kernel math at full precision)."""
    A = gen(n)
    D = DiaMatrix.from_csr(A, row_align=1024)
    x = np.random.default_rng(0).standard_normal(D.nrows_padded)
    y = dia_matvec_pallas_2d(jnp.asarray(D.bands), D.offsets,
                             jnp.asarray(x), rows_tile=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y)[: A.nrows],
                               A.matvec(x[: A.nrows]), rtol=1e-12)


def test_dia_matvec_pallas_2d_matches_oracle():
    """2-D layout kernel: general offsets exercising both the pure
    sublane-shift path (off % 128 == 0) and the lane-rotation path."""
    from acg_tpu.ops.dia import dia_matvec
    from acg_tpu.ops.pallas_kernels import dia_matvec_pallas_2d

    n, rows_tile = 8192, 16
    offsets = (-1024, -257, -128, -1, 0, 1, 128, 300, 1024)
    rng = np.random.default_rng(51)
    bands = rng.standard_normal((len(offsets), n)).astype(np.float32)
    x = rng.standard_normal(n).astype(np.float32)
    y = dia_matvec_pallas_2d(jnp.asarray(bands), offsets, jnp.asarray(x),
                             rows_tile=rows_tile, interpret=True)
    want = dia_matvec(jnp.asarray(bands), offsets, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("gen,n", [(poisson2d_5pt, 32), (poisson3d_7pt, 16)])
def test_dia_matvec_pallas_2d_stencils(gen, n):
    A = gen(n, dtype=np.float32)
    D = DiaMatrix.from_csr(A, row_align=1024)
    from acg_tpu.ops.pallas_kernels import dia_matvec_pallas_2d

    x = np.random.default_rng(52).standard_normal(
        D.nrows_padded).astype(np.float32)
    y = dia_matvec_pallas_2d(jnp.asarray(D.bands.astype(np.float32)),
                             D.offsets, jnp.asarray(x), rows_tile=8,
                             interpret=True)
    np.testing.assert_allclose(
        np.asarray(y)[: A.nrows],
        A.matvec(x[: A.nrows].astype(np.float64)), rtol=1e-5, atol=1e-5)


def test_dia_matvec_pallas_2d_int8_scales():
    A = poisson3d_7pt(8, dtype=np.float32)
    D = DiaMatrix.from_csr(A, row_align=1024)
    from acg_tpu.ops.dia import two_value_scales
    from acg_tpu.ops.pallas_kernels import dia_matvec_pallas_2d

    sc = two_value_scales(D.bands)
    assert sc is not None
    mask = (D.bands != 0).astype(np.int8)
    x = np.random.default_rng(53).standard_normal(
        D.nrows_padded).astype(np.float32)
    y = dia_matvec_pallas_2d(jnp.asarray(mask), D.offsets, jnp.asarray(x),
                             rows_tile=8, interpret=True,
                             scales=jnp.asarray(sc.astype(np.float32)))
    np.testing.assert_allclose(
        np.asarray(y)[: A.nrows],
        A.matvec(x[: A.nrows].astype(np.float64)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("scales_on", [False, True])
def test_dia_matvec_pallas_2d_padded_fused_dot(scales_on):
    """Padded-layout kernel: matvec + fused p'Ap partial match the oracle,
    the halo comes back exactly zero, and the plain (no-dot) variant
    agrees."""
    import jax.numpy as jnp

    from acg_tpu.ops.dia import dia_matvec, two_value_scales
    from acg_tpu.ops.pallas_kernels import (LANES,
                                            dia_matvec_pallas_2d_padded,
                                            pad_dia_operands,
                                            padded_halo_rows)

    A = poisson3d_7pt(16, dtype=np.float32)       # offsets ±256
    D = DiaMatrix.from_csr(A, row_align=1024)
    rt = 8
    rng = np.random.default_rng(61)
    x = rng.standard_normal(D.nrows_padded).astype(np.float32)
    if scales_on:
        sc = two_value_scales(D.bands)
        bands = jnp.asarray((D.bands != 0).astype(np.int8))
        scales = jnp.asarray(sc.astype(np.float32))
        bref = bands.astype(jnp.float32) * scales[:, None]
    else:
        bands = jnp.asarray(D.bands.astype(np.float32))
        scales = None
        bref = bands
    want = dia_matvec(bref, D.offsets, jnp.asarray(x))
    bp, (xp,) = pad_dia_operands(bands, (jnp.asarray(x),), rt, D.offsets)
    assert bp.shape == (len(D.offsets), xp.size // LANES, LANES)
    y, pd = dia_matvec_pallas_2d_padded(bp, D.offsets, xp, rows_tile=rt,
                                        with_dot=True, interpret=True,
                                        scales=scales)
    hpad = padded_halo_rows(D.offsets, rt) * LANES
    mid = np.asarray(y)[hpad: hpad + D.nrows_padded]
    np.testing.assert_allclose(mid, np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(y)[:hpad] == 0.0)
    assert np.all(np.asarray(y)[hpad + D.nrows_padded:] == 0.0)
    np.testing.assert_allclose(float(pd),
                               float(jnp.vdot(jnp.asarray(x), want)),
                               rtol=1e-4)
    y2 = dia_matvec_pallas_2d_padded(bp, D.offsets, xp, rows_tile=rt,
                                     interpret=True, scales=scales)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-6)


@pytest.mark.parametrize("name", ["dia_matvec_pallas_2d_padded",
                                  "dia_matvec_pallas_2d_padded_batched",
                                  "dia_matvec_pallas_hbm2d",
                                  "dia_matvec_pallas_hbm2d_ring",
                                  "cg_pipelined_iter_pallas"])
def test_padded_kernels_refuse_flat_bands(name):
    """Every padded-contract kernel takes the bands as (D, Rp, 128), the
    layout pad_dia_operands builds, and refuses the flat (D, Rp*128)
    form: reshaping it inside a solver loop relayouts every band on
    every iteration."""
    from acg_tpu.ops import pallas_kernels as pk

    offsets = (-128, -1, 0, 1, 128)
    n, rt = 16 * pk.LANES, 8
    rng = np.random.default_rng(5)
    bands = jnp.asarray(rng.standard_normal((5, n)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    bp, (xp,) = pk.pad_dia_operands(bands, (x,), rt, offsets)
    assert bp.shape == (5, xp.size // pk.LANES, pk.LANES)
    if name == "cg_pipelined_iter_pallas":
        args = (xp,) * 6 + (jnp.float32(0.5), jnp.float32(0.25))
    elif name.endswith("_batched"):
        args = (xp[None],)
    else:
        args = (xp,)
    kernel = getattr(pk, name)
    with pytest.raises(ValueError, match=r"\(D, Rp, 128\)"):
        kernel(bp.reshape(5, -1), offsets, *args, rows_tile=rt,
               interpret=True)


def test_pallas_2d_plan_bounds():
    from acg_tpu.ops.pallas_kernels import pallas_2d_plan

    # flagship 128^3 bf16: fits at some tile
    offs = (-16384, -128, -1, 0, 1, 128, 16384)
    rt = pallas_2d_plan(128 ** 3, offs, np.float32, jnp.bfloat16)
    assert rt is not None and rt >= 129      # halo must fit in one tile
    # f32 bands at 128^3: larger stream, still must yield SOME tile or None
    # without crashing
    pallas_2d_plan(128 ** 3, offs, np.float32, np.float32)
    # f64 rejected (no Mosaic f64)
    assert pallas_2d_plan(128 ** 3, offs, np.float64, np.float64) is None
    # lane-misaligned n rejected
    assert pallas_2d_plan(1000, (-1, 0, 1), np.float32, np.float32) is None
    # offsets wider than the tile are FINE (multi-tile halo): R=24 only
    # admits rt=8, ±1152 needs a 10-row halo => 16 halo rows per side
    from acg_tpu.ops.pallas_kernels import (padded_halo_rows,
                                            pallas_hbm2d_plan)

    assert pallas_2d_plan(24 * 128, (-1152, 0, 1152),
                          np.float32, np.float32) == 8
    assert padded_halo_rows((-1152, 0, 1152), 8) == 16
    # the 100M-DOF north-star shape (z-band reach 1682 rows) now plans
    # the HBM kernel — the round-3 gap that kept 464³ on the XLA path
    n100m = 464 ** 3
    offs = (-464 * 464, -464, -1, 0, 1, 464, 464 * 464)
    assert pallas_2d_plan(n100m, offs, np.float32, jnp.bfloat16) is None
    assert pallas_hbm2d_plan(n100m, offs, np.float32, jnp.bfloat16) == 1024
    assert padded_halo_rows(offs, 1024) == 2048


def test_cg_fused_path_matches_generic():
    """The fused coupled_step path (padded layout + in-kernel dot) must
    produce the same solve as the generic path — forced through interpret
    mode on CPU by monkeypatching the probe."""
    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.solvers.cg import cg
    from acg_tpu.sparse.csr import manufactured_rhs
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    Dm = poisson3d_7pt_dia(8, dtype=np.float32, row_align=1024)
    dev = DeviceDia.from_dia(Dm, dtype=np.float32, mat_dtype="auto")
    assert dev.bands.dtype.itemsize <= 2
    from acg_tpu.sparse import poisson3d_7pt

    A = poisson3d_7pt(8, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=7)
    opts = SolverOptions(maxits=200, residual_rtol=1e-6)
    res_generic = cg(dev, jnp.asarray(np.pad(b, (0, dev.nrows_padded - A.nrows))),
                     options=opts)

    from acg_tpu.ops import pallas_kernels as pk

    orig = pk.dia_matvec_pallas_2d_padded

    def interp(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    try:
        pk._SPMV_PROBE["fused2d"] = True
        import unittest.mock as mock

        with mock.patch.object(pk, "dia_matvec_pallas_2d_padded", interp):
            # the solver imports the symbol inside the jitted function, so
            # patching the module attribute is enough
            bp = jnp.asarray(np.pad(b, (0, dev.nrows_padded - A.nrows)))
            res_fused = cg(dev, bp, options=opts)
            # the fused path must honor segment_iters with identical
            # results (the review finding: segmentation silently dropped)
            from dataclasses import replace

            res_seg = cg(dev, bp, options=replace(opts, segment_iters=17))
            # pipelined CG through the same padded fused matvec
            from acg_tpu.solvers.cg import cg_pipelined

            res_pipe = cg_pipelined(dev, bp, options=opts)
    finally:
        pk._SPMV_PROBE.pop("fused2d", None)
    assert res_seg.niterations == res_fused.niterations
    np.testing.assert_array_equal(np.asarray(res_seg.x),
                                  np.asarray(res_fused.x))
    # generic pipelined baseline OUTSIDE the probe (XLA path): the fused
    # pipelined path must reproduce it, not merely converge
    from acg_tpu.solvers.cg import cg_pipelined as _cgp

    res_pipe_gen = _cgp(dev, jnp.asarray(np.pad(b, (0, dev.nrows_padded
                                                    - A.nrows))),
                        options=opts)
    assert res_pipe.converged and res_pipe_gen.converged
    # kernel vs XLA accumulation order differs in final ulps, which can
    # flip the iteration the threshold is crossed on
    assert abs(res_pipe.niterations - res_pipe_gen.niterations) <= 1
    np.testing.assert_allclose(np.asarray(res_pipe.x),
                               np.asarray(res_pipe_gen.x),
                               rtol=5e-4, atol=5e-5)
    errp = (np.linalg.norm(res_pipe.x[: A.nrows] - xstar)
            / np.linalg.norm(xstar))
    assert errp < 1e-3
    assert res_fused.converged and res_generic.converged
    np.testing.assert_allclose(res_fused.x[: A.nrows],
                               res_generic.x[: A.nrows],
                               rtol=5e-4, atol=5e-5)
    err = (np.linalg.norm(res_fused.x[: A.nrows] - xstar)
           / np.linalg.norm(xstar))
    assert err < 1e-3


def test_pallas_probe_false_on_cpu():
    from acg_tpu.ops import pallas_kernels as pk

    pk._SPMV_PROBE.clear()
    try:
        # cpu backend in tests; groups probe independently
        assert pk.pallas_spmv_available("resident2d") is False
        assert pk.pallas_spmv_available("fused2d") is False
        assert pk.pallas_spmv_available("hbm2d") is False
        # the reason is kept, not swallowed
        assert pk.probe_errors()["fused2d"].startswith("no TPU")
    finally:
        pk._SPMV_PROBE.clear()


# ── ELL gather kernel (acg_tpu/ops/pallas_spmv.py; out of auto routing) ───

def test_ell_matvec_pallas_matches_oracle():
    from acg_tpu.ops.pallas_spmv import ell_matvec_pallas
    from acg_tpu.ops.spmv import ell_matvec
    from acg_tpu.sparse.ell import EllMatrix

    A = poisson3d_7pt(8)                       # 512 rows, W=7
    E = EllMatrix.from_csr(A, row_align=256)
    vals = jnp.asarray(E.vals.astype(np.float32))
    cols = jnp.asarray(E.colidx)
    x = jnp.asarray(np.random.default_rng(21)
                    .standard_normal(E.vals.shape[0]).astype(np.float32))
    y = ell_matvec_pallas(vals, cols, x, tile=256, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ell_matvec(vals, cols, x)),
                               rtol=1e-6)


def test_ell_matvec_pallas_scattered_bf16():
    from acg_tpu.ops.pallas_spmv import ell_matvec_pallas
    from acg_tpu.ops.spmv import ell_matvec

    rng = np.random.default_rng(22)
    n, W = 512, 11
    vals = jnp.asarray(rng.standard_normal((n, W)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, n, (n, W)).astype(np.int32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    for v in (vals, vals.astype(jnp.bfloat16)):
        y = ell_matvec_pallas(v, cols, x, tile=128, interpret=True)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ell_matvec(v, cols, x)),
                                   rtol=1e-5, atol=1e-6)


def test_ell_forced_raises_without_probe_and_runs_interpreted():
    """The ELL kernel is out of automatic routing (OUT_OF_AUTO): a forced
    call raises ERR_NOT_SUPPORTED where its probe fails (here: no TPU),
    never falling back quietly; interpreted, it matches the oracle."""
    from acg_tpu.errors import AcgError, Status
    from acg_tpu.ops import pallas_kernels as pk
    from acg_tpu.ops.pallas_spmv import ell_matvec_forced
    from acg_tpu.ops.spmv import ell_matvec

    assert "ell" in pk.OUT_OF_AUTO and "ell" in pk._PROBE_GROUPS
    rng = np.random.default_rng(23)
    n, W = 256, 5
    vals = jnp.asarray(rng.standard_normal((n, W)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, n, (n, W)).astype(np.int32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    pk._SPMV_PROBE.pop("ell", None)
    try:
        with pytest.raises(AcgError) as ei:
            ell_matvec_forced(vals, cols, x)
        assert ei.value.status == Status.ERR_NOT_SUPPORTED
        assert "no TPU" in str(ei.value)
    finally:
        pk._SPMV_PROBE.pop("ell", None)
    y = ell_matvec_forced(vals, cols, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ell_matvec(vals, cols, x)),
                               rtol=1e-5, atol=1e-6)


def test_dia_matvec_best_routes_to_hbm2d(monkeypatch):
    """dia_matvec_best must select the HBM-resident 2-D kernel when the
    resident plan refuses (the round-2 'HBM kernel selected by nothing'
    class of bug, re-pinned for the hbm2d generation)."""
    import jax.numpy as jnp

    from acg_tpu.ops import dia as dia_mod
    from acg_tpu.ops import pallas_kernels as pk

    calls = {}
    orig = pk.dia_matvec_pallas_hbm2d

    def spy(bands_pad, offsets, x_pad, rows_tile, with_dot=False,
            scales=None, **kw):
        calls["rt"] = rows_tile
        return orig(bands_pad, offsets, x_pad, rows_tile=rows_tile,
                    with_dot=with_dot, scales=scales, interpret=True)

    monkeypatch.setattr(pk, "dia_matvec_pallas_hbm2d", spy)
    monkeypatch.setattr(pk, "pallas_2d_plan", lambda *a, **k: None)
    monkeypatch.setattr(pk, "pallas_spmv_available",
                        lambda kind="resident2d": kind == "hbm2d")
    n = 4096
    offsets = (-512, -1, 0, 1, 512)
    rng = np.random.default_rng(71)
    bands = jnp.asarray(rng.standard_normal((5, n)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    y = dia_mod.dia_matvec_best(bands, offsets, x)
    assert calls, "hbm2d kernel was not selected"
    want = dia_mod.dia_matvec(bands, offsets, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_device_dia_eager_hbm2d_cache(monkeypatch):
    """DeviceDia.matvec's eager HBM-regime path: the padded band stack is
    built ONCE, cached on the instance, reused across calls, and the
    result matches the XLA oracle (interpret-mode kernel — the branch is
    probe-gated off on CPU otherwise, so this is its only coverage)."""
    import jax.numpy as jnp

    from acg_tpu.ops import pallas_kernels as pk
    from acg_tpu.ops.dia import DeviceDia, dia_matvec

    kernel_calls = []
    pad_calls = []
    orig_kernel = pk.dia_matvec_pallas_hbm2d
    orig_pad = pk.pad_dia_operands

    def spy_kernel(bands_pad, offsets, x_pad, rows_tile, with_dot=False,
                   scales=None, **kw):
        kernel_calls.append(rows_tile)
        return orig_kernel(bands_pad, offsets, x_pad, rows_tile=rows_tile,
                           with_dot=with_dot, scales=scales, interpret=True)

    def spy_pad(bands, x_vecs, rows_tile, offsets):
        pad_calls.append(rows_tile)
        return orig_pad(bands, x_vecs, rows_tile, offsets)

    monkeypatch.setattr(pk, "dia_matvec_pallas_hbm2d", spy_kernel)
    monkeypatch.setattr(pk, "pad_dia_operands", spy_pad)
    monkeypatch.setattr(pk, "pallas_2d_plan", lambda *a, **k: None)
    monkeypatch.setattr(pk, "pallas_hbm2d_plan", lambda *a, **k: 8)
    monkeypatch.setattr(pk, "pallas_spmv_available",
                        lambda kind="resident2d": kind == "hbm2d")
    n = 4096
    offsets = (-512, -1, 0, 1, 512)
    rng = np.random.default_rng(72)
    bands = jnp.asarray(rng.standard_normal((5, n)).astype(np.float32))
    dev = DeviceDia(bands=bands, offsets=offsets, nrows=n, ncols=n,
                    nnz=5 * n, vec_dtype="float32")
    for seed in (1, 2):
        x = jnp.asarray(np.random.default_rng(seed)
                        .standard_normal(n).astype(np.float32))
        y = dev.matvec(x)
        want = dia_matvec(bands, offsets, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert len(kernel_calls) == 2, kernel_calls
    assert len(pad_calls) == 1, "padded band stack must be cached"
    assert dev.__dict__.get("_hbm2d_pad") is not None


# ── ring-buffer HBM kernel (hbm2dr) ──────────────────────────────────────

@pytest.mark.parametrize("case", [
    (520 * 128, (-16384, -464, -1, 0, 1, 464, 16384), 256),
    (24 * 128, (-128, -3, 0, 3, 128), 8),
    # reach past 2 tiles: the multi-slot ring span (464³'s geometry class)
    (40 * 128, (-2100, -130, -1, 0, 1, 130, 2100), 16),
])
def test_hbm2d_ring_matches_oracle(case):
    """Ring-buffer HBM kernel: matvec + fused dot + int8 tier match the
    XLA oracle in interpret mode, across single- and multi-tile ring
    spans (the kernel replaces one window DMA per offset cluster with
    ONE x-tile fetch per grid step — 1.0x x stream)."""
    import jax.numpy as jnp

    from acg_tpu.ops.dia import dia_matvec
    from acg_tpu.ops.pallas_kernels import (LANES,
                                            dia_matvec_pallas_hbm2d_ring,
                                            pad_dia_operands,
                                            padded_halo_rows)

    n, offsets, rt = case
    rng = np.random.default_rng(3)
    D = len(offsets)
    bands = jnp.asarray(rng.standard_normal((D, n)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    bp, (xp,) = pad_dia_operands(bands, (x,), rt, offsets)
    hp = padded_halo_rows(offsets, rt) * LANES
    y, dot = dia_matvec_pallas_hbm2d_ring(bp, offsets, xp, rows_tile=rt,
                                          with_dot=True, interpret=True)
    want = dia_matvec(bands, offsets, x)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(y[hp: hp + n]),
                               np.asarray(want), atol=1e-5 * scale)
    dw = float(jnp.vdot(x, want))
    assert abs(float(dot) - dw) <= 1e-4 * max(abs(dw), 1.0)
    # int8 mask tier
    sc = jnp.asarray(np.arange(1.0, 1.0 + D, dtype=np.float32))
    mask = jnp.asarray((np.asarray(bands) > 0).astype(np.int8))
    bp2, _ = pad_dia_operands(mask, (), rt, offsets)
    y2 = dia_matvec_pallas_hbm2d_ring(bp2, offsets, xp, rows_tile=rt,
                                      scales=sc, interpret=True)
    want2 = dia_matvec(mask.astype(jnp.float32) * sc[:, None], offsets, x)
    np.testing.assert_allclose(
        np.asarray(y2[hp: hp + n]), np.asarray(want2),
        atol=1e-5 * float(jnp.max(jnp.abs(want2))))


def test_fused_plan_prefers_ring_over_windows(monkeypatch):
    """Past the resident bound, fused_plan_for selects the ring kernel
    when its probe passes, the clustered-window kernel otherwise."""
    from acg_tpu.ops import pallas_kernels as pk

    n464 = 464 ** 3
    offs = (-215296, -464, -1, 0, 1, 464, 215296)
    monkeypatch.setattr(pk, "pallas_spmv_available",
                        lambda kind="resident2d": kind in ("hbm2dr",
                                                           "hbm2d",
                                                           "fused2d"))
    kind, rt = pk.fused_plan_for(n464, offs, np.float32, jnp.bfloat16)
    assert kind == "hbm-ring" and rt in (1024, 512, 256)
    # ring probe failing -> windows fallback
    monkeypatch.setattr(pk, "pallas_spmv_available",
                        lambda kind="resident2d": kind in ("hbm2d",
                                                           "fused2d"))
    kind, rt = pk.fused_plan_for(n464, offs, np.float32, jnp.bfloat16)
    assert kind == "hbm"


def test_ring_span_and_plan_geometry():
    from acg_tpu.ops.pallas_kernels import (_ring_span,
                                            pallas_hbm2d_ring_plan)

    # 464³ at rt=1024: z-band q=±1682(+rot) -> tiles [-2, 2], 5-tile ring
    offs = (-215296, -464, -1, 0, 1, 464, 215296)
    assert _ring_span(offs, 1024) == (-2, 2)
    rt = pallas_hbm2d_ring_plan(464 ** 3, offs, np.float32, jnp.bfloat16)
    assert rt == 1024


def test_cg_fused_ring_path_matches_generic(monkeypatch):
    """The fused solve through the ring HBM kernel (kind "hbm-ring") must
    reproduce the generic-path solve — interpret-forced on CPU."""
    import unittest.mock as mock

    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.ops import pallas_kernels as pk
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.solvers.cg import cg
    from acg_tpu.sparse import poisson3d_7pt
    from acg_tpu.sparse.csr import manufactured_rhs
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    Dm = poisson3d_7pt_dia(16, dtype=np.float32, row_align=1024)
    dev = DeviceDia.from_dia(Dm, dtype=np.float32, mat_dtype="auto")
    A = poisson3d_7pt(16, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=9)
    bp = jnp.asarray(np.pad(b, (0, dev.nrows_padded - A.nrows)))
    opts = SolverOptions(maxits=300, residual_rtol=1e-6)
    res_generic = cg(dev, bp, options=opts)

    orig = pk.dia_matvec_pallas_hbm2d_ring

    def interp(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setitem(pk._SPMV_PROBE, "hbm2dr", True)
    monkeypatch.setitem(pk._SPMV_PROBE, "fused2d", False)
    monkeypatch.setattr(pk, "pallas_2d_plan", lambda *a, **k: None)
    with mock.patch.object(pk, "dia_matvec_pallas_hbm2d_ring", interp):
        res_ring = cg(dev, bp, options=opts)
        res_seg = cg(dev, bp, options=SolverOptions(
            maxits=300, residual_rtol=1e-6, segment_iters=37))
    assert res_ring.converged
    assert abs(res_ring.niterations - res_generic.niterations) <= 2
    np.testing.assert_allclose(res_ring.x[: A.nrows], xstar,
                               atol=1e-4 * np.abs(xstar).max())
    assert res_seg.niterations == res_ring.niterations
    np.testing.assert_array_equal(np.asarray(res_seg.x),
                                  np.asarray(res_ring.x))


def test_hbm_kernels_random_geometry():
    """Bounded geometry fuzz for BOTH HBM kernels (ring + windows):
    random offset sets / tile sizes / row counts in interpret mode vs
    the XLA oracle — the full 60-geometry campaign ran clean 2026-07-31;
    this keeps a 10-case slice in CI."""
    import jax.numpy as jnp

    from acg_tpu.ops.dia import dia_matvec
    from acg_tpu.ops.pallas_kernels import (LANES, dia_matvec_pallas_hbm2d,
                                            dia_matvec_pallas_hbm2d_ring,
                                            pad_dia_operands,
                                            padded_halo_rows)

    rng = np.random.default_rng(17)
    for _ in range(10):
        R = int(rng.integers(2, 30)) * 8
        n = R * LANES
        rt = int(rng.choice([8, 16, 32]))
        D = int(rng.integers(1, 7))
        maxoff = max(n // 2 - 1, 2)
        offs = {0}
        while len(offs) < D:
            offs.add(int(rng.integers(-maxoff, maxoff + 1)))
        offsets = tuple(sorted(offs))
        bands = jnp.asarray(rng.standard_normal(
            (len(offsets), n)).astype(np.float32))
        x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        bp, (xp,) = pad_dia_operands(bands, (x,), rt, offsets)
        hp = padded_halo_rows(offsets, rt) * LANES
        want = dia_matvec(bands, offsets, x)
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        for kern in (dia_matvec_pallas_hbm2d_ring, dia_matvec_pallas_hbm2d):
            y = kern(bp, offsets, xp, rows_tile=rt,
                     interpret=True)[hp: hp + n]
            err = float(jnp.max(jnp.abs(y - want))) / scale
            assert err < 1e-5, (kern.__name__, R, rt, offsets, err)


def test_pipe2d_kernel_probe_interpret():
    """The single-kernel pipelined iteration (cg_pipelined_iter_pallas)
    matches the plain jnp formulation at production shapes — the probe's
    own oracle, run through interpret mode on CPU."""
    from acg_tpu.ops.pallas_kernels import _probe_pipe2d_group

    assert _probe_pipe2d_group(interpret=True)


def test_cg_pipelined_iter_kernel_matches_generic():
    """Pipelined CG through the single-kernel iteration (pipe2d) must
    reproduce the generic pipelined solve — interpret-forced on CPU."""
    import unittest.mock as mock

    import jax.numpy as jnp

    from acg_tpu.config import SolverOptions
    from acg_tpu.ops import pallas_kernels as pk
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.solvers.cg import cg_pipelined
    from acg_tpu.sparse import poisson3d_7pt
    from acg_tpu.sparse.csr import manufactured_rhs
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    Dm = poisson3d_7pt_dia(8, dtype=np.float32, row_align=1024)
    dev = DeviceDia.from_dia(Dm, dtype=np.float32, mat_dtype="auto")
    A = poisson3d_7pt(8, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=9)
    bp = jnp.asarray(np.pad(b, (0, dev.nrows_padded - A.nrows)))
    opts = SolverOptions(maxits=200, residual_rtol=1e-6)
    res_generic = cg_pipelined(dev, bp, options=opts)

    orig_pad = pk.dia_matvec_pallas_2d_padded
    orig_iter = pk.cg_pipelined_iter_pallas

    def interp_pad(*a, **k):
        k["interpret"] = True
        return orig_pad(*a, **k)

    used = {}

    def interp_iter(*a, **k):
        used["pipe2d"] = True
        k["interpret"] = True
        return orig_iter(*a, **k)

    import importlib

    # the package eagerly exports the cg FUNCTION, which shadows the
    # submodule in `import ... as` resolution — go through sys.modules
    cg_mod = importlib.import_module("acg_tpu.solvers.cg")

    try:
        pk._SPMV_PROBE["fused2d"] = True
        pk._SPMV_PROBE["pipe2d"] = True
        # an earlier test may have traced the same static signature with
        # the pipe2d probe OFF — the cached executable would silently
        # bypass the kernel under test (and ours must not leak back)
        cg_mod._cg_pipelined_device_fused.clear_cache()
        with mock.patch.object(pk, "dia_matvec_pallas_2d_padded",
                               interp_pad), \
             mock.patch.object(pk, "cg_pipelined_iter_pallas", interp_iter):
            res_kernel = cg_pipelined(dev, bp, options=opts)
    finally:
        pk._SPMV_PROBE.pop("fused2d", None)
        pk._SPMV_PROBE.pop("pipe2d", None)
        cg_mod._cg_pipelined_device_fused.clear_cache()
    assert used.get("pipe2d"), "pipe2d kernel was not selected"
    assert res_kernel.converged
    assert abs(res_kernel.niterations - res_generic.niterations) <= 2
    np.testing.assert_allclose(res_kernel.x[: A.nrows], xstar,
                               atol=1e-3 * np.abs(xstar).max())
    np.testing.assert_allclose(res_kernel.x, res_generic.x,
                               atol=1e-4 * np.abs(res_generic.x).max())
