"""DIA format + RCM tests: the gather-free TPU SpMV path."""

import numpy as np
import pytest

from acg_tpu.config import SolverOptions
from acg_tpu.ops.dia import DeviceDia, DiaMatrix, dia_efficiency, dia_matvec
from acg_tpu.solvers.cg import cg, cg_pipelined
from acg_tpu.sparse import coo_to_csr, poisson2d_5pt, poisson3d_7pt
from acg_tpu.sparse.csr import manufactured_rhs
from acg_tpu.sparse.rcm import bandwidth, permute_symmetric, rcm_order


def test_dia_from_csr_poisson():
    A = poisson3d_7pt(4)
    D = DiaMatrix.from_csr(A)
    assert len(D.offsets) == 7
    assert D.offsets == (-16, -4, -1, 0, 1, 4, 16)
    assert dia_efficiency(A) > 0.7


def test_dia_matvec_host_oracle():
    A = poisson2d_5pt(6)
    D = DiaMatrix.from_csr(A)
    x = np.random.default_rng(0).standard_normal(A.nrows)
    np.testing.assert_allclose(D.matvec(x), A.matvec(x), rtol=1e-14)


def test_dia_matvec_device():
    import jax.numpy as jnp

    A = poisson3d_7pt(5)
    D = DiaMatrix.from_csr(A)
    dev = DeviceDia.from_dia(D)
    x = np.random.default_rng(1).standard_normal(A.nrows)
    xp = np.zeros(dev.nrows_padded)
    xp[: A.nrows] = x
    y = dev.matvec(jnp.asarray(xp))
    np.testing.assert_allclose(np.asarray(y)[: A.nrows], A.matvec(x),
                               rtol=1e-12)


def test_dia_asymmetric_offsets():
    # non-symmetric structure: band above only
    A = coo_to_csr([0, 0, 1, 2], [0, 2, 1, 2], [1.0, 5.0, 2.0, 3.0], 3, 3)
    D = DiaMatrix.from_csr(A)
    x = np.array([1.0, 10.0, 100.0])
    np.testing.assert_allclose(D.matvec(x), A.matvec(x))


def test_cg_dia_format():
    A = poisson3d_7pt(5)
    xstar, b = manufactured_rhs(A, seed=2)
    res = cg(A, b, fmt="dia",
             options=SolverOptions(maxits=1000, residual_rtol=1e-10))
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)
    res_p = cg_pipelined(A, b, fmt="dia",
                         options=SolverOptions(maxits=1000,
                                               residual_rtol=1e-10))
    np.testing.assert_allclose(res_p.x, xstar, atol=1e-7)


def test_cg_auto_picks_dia_for_stencil():
    from acg_tpu.ops.dia import DeviceDia as DD
    from acg_tpu.solvers.cg import _prepare

    A = poisson2d_5pt(6)
    dev, _, _, perm = _prepare(A, np.ones(A.nrows), None, None, "auto")
    assert isinstance(dev, DD)
    assert perm is None


def test_cg_auto_picks_ell_for_scattered():
    from acg_tpu.ops.spmv import DeviceEll as DE
    from acg_tpu.solvers.cg import _prepare

    rng = np.random.default_rng(3)
    n, nnz = 200, 600
    r = rng.integers(0, n, nnz)
    c = rng.integers(0, n, nnz)
    A = coo_to_csr(np.r_[r, np.arange(n)], np.r_[c, np.arange(n)],
                   np.r_[rng.standard_normal(nnz) * 0.01, np.full(n, 10.0)],
                   n, n, symmetrize=True)
    dev, _, _, perm = _prepare(A, np.ones(n), None, None, "auto")
    assert isinstance(dev, DE)
    assert perm is None


def test_rcm_reduces_bandwidth():
    # random permutation of a banded matrix; RCM should recover a small band
    A = poisson2d_5pt(12)
    rng = np.random.default_rng(4)
    scramble = rng.permutation(A.nrows)
    As = permute_symmetric(A, scramble)
    assert bandwidth(As) > 3 * bandwidth(A)
    perm = rcm_order(As)
    Ar = permute_symmetric(As, perm)
    assert bandwidth(Ar) <= 2 * bandwidth(A)


def test_rcm_preserves_operator():
    A = poisson2d_5pt(5)
    perm = rcm_order(A)
    Ar = permute_symmetric(A, perm)
    x = np.random.default_rng(5).standard_normal(A.nrows)
    # y_r = P A P' (P x) == P (A x)
    old_to_new = np.empty_like(perm)
    old_to_new[perm] = np.arange(len(perm))
    np.testing.assert_allclose(Ar.matvec(x[perm]), A.matvec(x)[perm],
                               rtol=1e-13)


def _scrambled_tridiag(n=400, seed=7):
    """SPD tridiagonal under a random row/col scramble: dia_efficiency of
    the scrambled matrix is tiny, but RCM recovers the band — exercises the
    fmt="auto" RCM branch (the round-2 crash repro)."""
    i = np.arange(n - 1)
    r = np.r_[np.arange(n), i, i + 1]
    c = np.r_[np.arange(n), i + 1, i]
    v = np.r_[np.full(n, 4.0), np.full(n - 1, -1.0), np.full(n - 1, -1.0)]
    A = coo_to_csr(r, c, v, n, n)
    scramble = np.random.default_rng(seed).permutation(n)
    return permute_symmetric(A, scramble)


def test_cg_auto_rcm_branch_converges():
    from acg_tpu.ops.dia import dia_efficiency
    from acg_tpu.solvers.cg import PermutedOperator, build_device_operator

    As = _scrambled_tridiag()
    assert dia_efficiency(As) < 0.25      # would not pick DIA directly
    dev = build_device_operator(As, dtype=np.float64, fmt="auto")
    assert isinstance(dev, PermutedOperator)
    b = np.random.default_rng(8).standard_normal(As.nrows)
    res = cg(As, b, fmt="auto", dtype=np.float64,
             options=SolverOptions(maxits=2000, residual_rtol=1e-10))
    assert res.converged
    # the TRUE residual in the caller's ordering, not the solver's
    true_res = np.linalg.norm(As.matvec(res.x) - b) / np.linalg.norm(b)
    assert true_res < 1e-9
    # same through a prebuilt PermutedOperator (the CLI path)
    res2 = cg(dev, b, options=SolverOptions(maxits=2000,
                                            residual_rtol=1e-10))
    np.testing.assert_allclose(res2.x, res.x, atol=1e-10)


def test_cg_auto_rcm_pipelined():
    from acg_tpu.solvers.cg import cg_pipelined as cgp

    As = _scrambled_tridiag(n=300, seed=9)
    b = np.random.default_rng(10).standard_normal(As.nrows)
    res = cgp(As, b, fmt="auto", dtype=np.float64,
              options=SolverOptions(maxits=2000, residual_rtol=1e-10))
    true_res = np.linalg.norm(As.matvec(res.x) - b) / np.linalg.norm(b)
    assert true_res < 1e-8


# ── mixed-precision operator storage (mat_dtype) ─────────────────────────

def test_lossless_cast_detection():
    import jax.numpy as jnp

    from acg_tpu.ops.dia import lossless_cast, resolve_mat_dtype

    ints = np.array([[-1.0, 0.0, 6.0, 2.5]])       # bf16-exact values
    assert lossless_cast(ints, jnp.bfloat16)
    gen = np.array([[1.0 / 3.0, 0.1]])             # not representable
    assert not lossless_cast(gen, jnp.bfloat16)
    assert resolve_mat_dtype(ints, "auto", np.float32) == jnp.bfloat16
    assert resolve_mat_dtype(gen, "auto", np.float32) == np.float32
    assert resolve_mat_dtype(ints, None, np.float64) == np.float64


def test_dia_auto_narrows_bf16_bitexact():
    """Bands with several bf16-exact values (not two-valued, so the int8
    tier is skipped) must narrow to bf16 storage with an SpMV that is
    bit-identical to f32 storage."""
    import jax.numpy as jnp

    A = poisson3d_7pt(6, dtype=np.float32)
    D = DiaMatrix.from_csr(A)
    bands = D.bands.copy()
    diag = D.offsets.index(0)
    nz = bands[diag] != 0                  # diagonal: alternate 6.0 / 8.0
    bands[diag, nz] = np.where(np.arange(nz.sum()) % 2 == 0, 6.0, 8.0)
    D = DiaMatrix(D.nrows, D.ncols, D.offsets, bands, D.nnz)
    d32 = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype=None)
    dauto = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype="auto")
    assert dauto.scales is None
    assert dauto.bands.dtype == jnp.bfloat16
    assert dauto.vec_dtype == "float32"
    assert dauto.mat_itemsize == 2
    x = jnp.asarray(np.random.default_rng(3)
                    .standard_normal(d32.nrows_padded).astype(np.float32))
    y32 = np.asarray(d32.matvec(x))
    yauto = np.asarray(dauto.matvec(x))
    np.testing.assert_array_equal(y32, yauto)


def test_dia_auto_keeps_f64_for_general_values():
    """Varying irrational band values: neither the two-value tier nor the
    bf16 tier applies — storage stays at the full vector dtype."""
    A = poisson3d_7pt(4, dtype=np.float64)
    D = DiaMatrix.from_csr(A)
    bands = D.bands * np.pi
    nz = bands != 0                        # make values vary within bands
    bands[nz] *= (1.0 + 0.001 * np.arange(nz.sum()))
    D = DiaMatrix(D.nrows, D.ncols, D.offsets, bands, D.nnz)
    dev = DeviceDia.from_dia(D, dtype=np.float64, mat_dtype="auto")
    assert dev.scales is None
    assert dev.bands.dtype == np.float64


def test_cg_with_auto_mat_dtype_matches_f32():
    """Solver-level: identical iteration count and solution with auto
    (bf16) vs full-width operator storage on a bf16-exact matrix."""
    A = poisson3d_7pt(8, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=0)
    opts = SolverOptions(maxits=500, residual_rtol=1e-6)
    r32 = cg(A, b, options=opts, dtype=np.float32, mat_dtype=None)
    rauto = cg(A, b, options=opts, dtype=np.float32, mat_dtype="auto")
    assert r32.niterations == rauto.niterations
    np.testing.assert_array_equal(r32.x, rauto.x)


def test_ell_auto_mat_dtype():
    import jax.numpy as jnp

    from acg_tpu.ops.spmv import DeviceEll, pad_vector
    from acg_tpu.sparse import EllMatrix

    A = poisson3d_7pt(5, dtype=np.float32)
    E = EllMatrix.from_csr(A)
    dev = DeviceEll.from_ell(E, dtype=np.float32, mat_dtype="auto")
    assert dev.vals.dtype == jnp.bfloat16
    x = np.random.default_rng(5).standard_normal(A.nrows).astype(np.float32)
    xp = jnp.asarray(pad_vector(x, dev.nrows_padded))
    y = np.asarray(dev.matvec(xp))[: A.nrows]
    np.testing.assert_allclose(y, A.matvec(x), rtol=1e-6, atol=1e-5)


def test_auto_tier_order_bf16_first_then_int8():
    """Tier preference under mat_dtype="auto" (BENCH_r02: bf16 beat the
    int8 tier end-to-end on v5e): bf16-exact bands take bf16 even when
    two-valued (Poisson); two-valued bands that are NOT bf16-exact (e.g.
    {0, 1/3}) take the exact int8 mask tier.  Both are bit-identical to
    full storage."""
    import jax.numpy as jnp

    A = poisson3d_7pt(6, dtype=np.float32)
    D = DiaMatrix.from_csr(A)
    dauto = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype="auto")
    assert dauto.scales is None                  # bf16 won over int8
    assert dauto.bands.dtype == jnp.bfloat16
    assert dauto.mat_itemsize == 2
    dfull = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype=None)
    x = jnp.asarray(np.random.default_rng(7)
                    .standard_normal(dfull.nrows_padded).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(dfull.matvec(x)),
                                  np.asarray(dauto.matvec(x)))

    # {0, c}-valued with c not bf16-representable -> int8 mask tier
    third = DiaMatrix(D.nrows, D.ncols, D.offsets,
                      np.where(D.bands != 0, 1.0 / 3.0, 0.0), D.nnz)
    d8 = DeviceDia.from_dia(third, dtype=np.float32, mat_dtype="auto")
    assert d8.scales is not None
    assert d8.bands.dtype == jnp.int8
    assert d8.mat_itemsize == 1
    t8full = DeviceDia.from_dia(third, dtype=np.float32, mat_dtype=None)
    np.testing.assert_array_equal(np.asarray(t8full.matvec(x)),
                                  np.asarray(d8.matvec(x)))


def test_two_value_rejects_varying_bands():
    from acg_tpu.ops.dia import two_value_scales

    A = poisson3d_7pt(4, dtype=np.float64)
    D = DiaMatrix.from_csr(A)
    assert two_value_scales(D.bands) is not None
    varying = D.bands.copy()
    varying[0, varying[0] != 0] = np.arange(
        1, (varying[0] != 0).sum() + 1, dtype=np.float64)
    assert two_value_scales(varying) is None


def test_cg_with_two_value_compression_matches():
    A = poisson3d_7pt(8, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=0)
    opts = SolverOptions(maxits=500, residual_rtol=1e-6)
    rfull = cg(A, b, options=opts, dtype=np.float32, mat_dtype=None,
               fmt="dia")
    rauto = cg(A, b, options=opts, dtype=np.float32, mat_dtype="auto",
               fmt="dia")
    assert rfull.niterations == rauto.niterations
    np.testing.assert_array_equal(rfull.x, rauto.x)


def test_two_value_mask_respects_cast_underflow():
    """A value that underflows in the requested cast must become a mask
    zero (mask and scales derive from the same cast array).  Bands use a
    non-bf16-exact value so the int8 tier (not bf16) is exercised."""
    A = poisson3d_7pt(4, dtype=np.float64)
    D = DiaMatrix.from_csr(A)
    bands = np.where(DiaMatrix.from_csr(A).bands != 0, 1.0 / 3.0, 0.0)
    diag = D.offsets.index(0)
    nzpos = np.flatnonzero(bands[diag] != 0)
    bands[diag, nzpos[1]] = 1e-50          # underflows to 0 in float32
    D = DiaMatrix(D.nrows, D.ncols, D.offsets, bands, D.nnz)
    dauto = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype="auto")
    assert dauto.scales is not None        # int8 tier engaged
    dfull = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype=None)
    import jax.numpy as jnp
    x = jnp.asarray(np.random.default_rng(9)
                    .standard_normal(dfull.nrows_padded).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(dfull.matvec(x)),
                                  np.asarray(dauto.matvec(x)))


def test_auto_tier_decides_on_cast_bands():
    """Tier decisions must look at the vdt-CAST bands: f64 bands holding a
    1e-50 entry (underflows to 0 in f32) are bf16-exact AFTER the cast, so
    dtype=float32 auto storage is bf16 — not full width (the round-3
    review regression)."""
    import jax.numpy as jnp

    A = poisson3d_7pt(4, dtype=np.float64)
    D = DiaMatrix.from_csr(A)
    bands = D.bands.copy()
    diag = D.offsets.index(0)
    nzpos = np.flatnonzero(bands[diag] != 0)
    bands[diag, nzpos[1]] = 1e-50
    D = DiaMatrix(D.nrows, D.ncols, D.offsets, bands, D.nnz)
    dev = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype="auto")
    assert dev.bands.dtype == jnp.bfloat16 and dev.scales is None
    dfull = DeviceDia.from_dia(D, dtype=np.float32, mat_dtype=None)
    x = jnp.asarray(np.random.default_rng(11)
                    .standard_normal(dev.nrows_padded).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(dfull.matvec(x)),
                                  np.asarray(dev.matvec(x)))


def test_mat_dtype_int8_explicit():
    """mat_dtype='int8' forces the exact two-value mask tier; non-two-
    valued bands are rejected rather than lossily narrowed."""
    import jax.numpy as jnp

    from acg_tpu.errors import AcgError
    from acg_tpu.ops.dia import DeviceDia, DiaMatrix
    from acg_tpu.sparse import poisson3d_7pt

    A = poisson3d_7pt(8, dtype=np.float32)
    dev = DeviceDia.from_dia(DiaMatrix.from_csr(A), dtype=np.float32,
                             mat_dtype="int8")
    assert dev.bands.dtype == jnp.int8 and dev.scales is not None
    x = np.random.default_rng(0).standard_normal(
        dev.nrows_padded).astype(np.float32)
    got = np.asarray(dev.matvec(jnp.asarray(x)))[: A.nrows]
    np.testing.assert_allclose(
        got, A.matvec(x[: A.nrows].astype(np.float64)), rtol=1e-5)

    from acg_tpu.sparse.poisson import poisson3d_7pt_varcoef

    V = poisson3d_7pt_varcoef(8, dtype=np.float32)
    with pytest.raises(AcgError):
        DeviceDia.from_dia(DiaMatrix.from_csr(V), dtype=np.float32,
                           mat_dtype="int8")


def test_mat_dtype_int8_rejected_off_dia_band_path():
    """mat_dtype='int8' must never silently truncate values: the non-DIA
    storage builders (ELL) reject it instead of lossily narrowing."""
    from acg_tpu.errors import AcgError
    from acg_tpu.ops.spmv import DeviceEll
    from acg_tpu.sparse import poisson3d_7pt
    from acg_tpu.sparse.ell import EllMatrix

    E = EllMatrix.from_csr(poisson3d_7pt(6, dtype=np.float32))
    with pytest.raises(AcgError):
        DeviceEll.from_ell(E, dtype=np.float32, mat_dtype="int8")


def test_release_matvec_cache_drops_the_eager_pad(monkeypatch):
    """The eager HBM-regime matvec caches a second padded band copy on
    the instance; release_matvec_cache must drop exactly the attribute
    matvec writes (pins the name coupling — a rename that silently turns
    the release into a no-op fails here)."""
    import jax.numpy as jnp

    from acg_tpu.ops import dia as dia_mod
    from acg_tpu.ops.dia import DeviceDia, DiaMatrix

    A = poisson2d_5pt(16)          # 256 rows: n % 128 == 0
    dev = DeviceDia.from_dia(DiaMatrix.from_csr(A), dtype=np.float32)

    def fake_kernel(bands_pad, offsets, xp, rows_tile=None, scales=None,
                    **kw):
        assert bands_pad.ndim == 3      # the padded kernels' contract
        return jnp.zeros_like(xp)

    # force the eager HBM route: no resident 2-D plan, HBM kernel "found"
    from acg_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "pallas_2d_plan", lambda *a, **k: None)
    monkeypatch.setattr(dia_mod, "_hbm_kernel_for",
                        lambda *a, **k: (fake_kernel, 2))
    x = jnp.zeros(dev.nrows_padded, dtype=jnp.float32)
    dev.matvec(x)
    assert "_hbm2d_pad" in dev.__dict__, \
        "matvec no longer populates the cache this test pins"
    dev.release_matvec_cache()
    assert "_hbm2d_pad" not in dev.__dict__
    # idempotent on an empty cache
    dev.release_matvec_cache()
