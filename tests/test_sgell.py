"""Segmented-gather ELL (acg_tpu/ops/sgell.py): packing, kernel, routing.

The kernel is probe-gated off on CPU, so these tests drive it through
interpret mode (``interpret=True`` skips the probe) — the same discipline
as the other Pallas kernels' CPU coverage (tests/test_pallas.py)."""

import numpy as np
import jax.numpy as jnp
import pytest

from acg_tpu.ops.sgell import (MIN_FILL, TILE, DeviceSgell,
                               build_device_sgell, pack_sgell)
from acg_tpu.sparse.csr import CsrMatrix


def _random_local_csr(n, W, spread, seed=0, drop_tile=None):
    """Unstructured but local: W entries/row within +-spread columns."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n), W)
    cols = np.clip(rows + rng.integers(-spread, spread + 1, size=n * W),
                   0, n - 1)
    if drop_tile is not None:
        keep = (rows // TILE) != drop_tile
        rows, cols = rows[keep], cols[keep]
    uniq = np.unique(rows * np.int64(n) + cols)
    rows, cols = (uniq // n).astype(np.int64), (uniq % n).astype(np.int64)
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    rowptr = np.searchsorted(rows, np.arange(n + 1)).astype(np.int64)
    return CsrMatrix(n, n, rowptr, cols.astype(np.int32), vals), rows, cols


def _coo_oracle(rows, cols, vals, x, n):
    y = np.zeros(n, dtype=np.float64)
    np.add.at(y, rows, vals.astype(np.float64) * x[cols])
    return y


def test_pack_sgell_cell_uniqueness_and_constraints():
    """Packing invariants: every entry lands in exactly one cell, cells
    within a sublane of a slot share one x segment, and every tile owns at
    least one slot (empty tiles included)."""
    A, rows, cols = _random_local_csr(2600, 7, 350, seed=3, drop_tile=1)
    packed = pack_sgell(rows, cols, A.vals, A.nrows)
    S, ntiles = packed["S"], packed["ntiles"]
    assert ntiles == 3
    # every tile has >= 1 slot and tile ids are non-decreasing
    tiles, counts = np.unique(packed["tile"], return_counts=True)
    assert list(tiles) == list(range(ntiles))
    assert np.all(np.diff(packed["tile"]) >= 0)
    assert packed["first"].sum() == ntiles
    # reconstruct entries from cells: value-weighted check against oracle
    vals2 = packed["vals"].reshape(S, 8, 128)
    idx2 = packed["idx"].reshape(S, 8, 128)
    seg = packed["seg"]
    x = np.random.default_rng(0).standard_normal(A.nrows).astype(np.float64)
    xp = np.zeros(packed["n_pad"])
    xp[: A.nrows] = x
    y = np.zeros(packed["n_pad"])
    for s_id in range(S):
        t = packed["tile"][s_id]
        for sub in range(8):
            src = xp[seg[s_id, sub] * 128:(seg[s_id, sub] + 1) * 128]
            contrib = vals2[s_id, sub] * src[idx2[s_id, sub]]
            y[t * TILE + sub * 128:(t * TILE + (sub + 1) * 128)] += contrib
    want = _coo_oracle(rows, cols, A.vals, x, A.nrows)
    np.testing.assert_allclose(y[: A.nrows], want, rtol=1e-5, atol=1e-8)


def test_fill_only_metadata_matches_full_layout():
    """The ISSUE 14 fill-only fast path (one linear sweep, native or
    NumPy) must report the EXACT S/fill of the full two-lexsort layout
    — on structured, unstructured, multi-tile and padded-shard
    inputs, with and without the native library."""
    from acg_tpu import native
    from acg_tpu.ops.sgell import pack_csr
    from acg_tpu.sparse import poisson2d_5pt

    cases = [poisson2d_5pt(9), poisson2d_5pt(40),
             _random_local_csr(3 * TILE, 6, 700, seed=4)[0],
             _random_local_csr(TILE, 3, 50, seed=5, drop_tile=0)[0]]
    for M in cases:
        for nrows in (None, -(-M.nrows // TILE) * TILE + TILE):
            full = pack_csr(M, np.float32, nrows=nrows, min_fill=0.0)
            meta = pack_csr(M, np.float32, nrows=nrows, min_fill=2.0)
            assert meta["vals"] is None          # metadata only
            assert meta["S"] == full["S"]
            assert meta["fill"] == pytest.approx(full["fill"], abs=0)
            saved = native._lib
            native._lib = False                  # NumPy fallback sweep
            try:
                meta2 = pack_csr(M, np.float32, nrows=nrows,
                                 min_fill=2.0)
            finally:
                native._lib = saved
            assert meta2["S"] == full["S"]
            # the CSR-direct metadata entry (no pack expansions at all)
            from acg_tpu.ops.sgell import sgell_fill_metadata

            meta3 = sgell_fill_metadata(M, nrows=nrows)
            assert meta3["vals"] is None
            assert meta3["S"] == full["S"]
            assert meta3["fill"] == pytest.approx(full["fill"], abs=0)
            assert meta3["n_pad"] == full["n_pad"]


def test_fill_only_unsorted_input_falls_back():
    """Non-CSR-ordered COO input cannot take the run-length sweep; the
    metadata call must still report the exact layout fill."""
    rng = np.random.default_rng(7)
    n = TILE
    rows = rng.integers(0, n, 900)
    cols = rng.integers(0, n, 900)
    uniq = np.unique(rows * np.int64(n) + cols)
    rows, cols = (uniq // n), (uniq % n)
    shuf = rng.permutation(len(rows))
    rows, cols = rows[shuf], cols[shuf]
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    full = pack_sgell(rows, cols, vals, n, min_fill=0.0)
    meta = pack_sgell(rows, cols, vals, n, min_fill=2.0)
    assert meta["vals"] is None
    assert meta["S"] == full["S"]


def test_sgell_matvec_interpret_matches_oracle():
    A, rows, cols = _random_local_csr(3000, 9, 400, seed=5)
    dev = build_device_sgell(A, interpret=True, min_fill=0.0)
    assert isinstance(dev, DeviceSgell)
    x = np.random.default_rng(1).standard_normal(A.nrows).astype(np.float32)
    xp = jnp.pad(jnp.asarray(x), (0, dev.nrows_padded - A.nrows))
    y = np.asarray(dev.matvec(xp))
    want = _coo_oracle(rows, cols, A.vals, x.astype(np.float64), A.nrows)
    scale = np.abs(want).max()
    np.testing.assert_allclose(y[: A.nrows], want, atol=1e-5 * scale)
    # padding rows stay exactly zero (the CG padded-vector invariant)
    assert np.all(y[A.nrows:] == 0)


def test_sgell_empty_tile_zeroed():
    """A tile with no entries still gets its forced slot and a zeroed
    output block (an unvisited Pallas output block is garbage)."""
    A, rows, cols = _random_local_csr(3000, 9, 400, seed=7, drop_tile=1)
    dev = build_device_sgell(A, interpret=True, min_fill=0.0)
    x = np.random.default_rng(2).standard_normal(A.nrows).astype(np.float32)
    y = np.asarray(dev.matvec(
        jnp.pad(jnp.asarray(x), (0, dev.nrows_padded - A.nrows))))
    assert np.all(y[TILE:2 * TILE] == 0)
    want = _coo_oracle(rows, cols, A.vals, x.astype(np.float64), A.nrows)
    np.testing.assert_allclose(y[: A.nrows], want,
                               atol=1e-5 * (np.abs(want).max() or 1.0))


def test_sgell_bf16_storage_tier():
    A, rows, cols = _random_local_csr(2048, 6, 300, seed=9)
    dev = build_device_sgell(A, mat_dtype="bfloat16", interpret=True,
                             min_fill=0.0)
    assert dev.vals.dtype == jnp.bfloat16
    assert dev.mat_itemsize == 2
    x = np.random.default_rng(3).standard_normal(A.nrows).astype(np.float32)
    y = np.asarray(dev.matvec(
        jnp.pad(jnp.asarray(x), (0, dev.nrows_padded - A.nrows))))
    want = _coo_oracle(rows, cols, A.vals, x.astype(np.float64), A.nrows)
    scale = np.abs(want).max()
    np.testing.assert_allclose(y[: A.nrows], want, atol=2e-2 * scale)


def test_sgell_gating():
    """build_device_sgell returns None when the tier does not apply: f64
    vectors, sub-threshold fill, failed probe (the CPU default)."""
    A, _, _ = _random_local_csr(2048, 6, 300, seed=11)
    assert build_device_sgell(A, dtype=np.float64, interpret=True) is None
    # uniform random columns at this size -> fill far below MIN_FILL
    rng = np.random.default_rng(13)
    n = 4096
    rows = np.repeat(np.arange(n), 4)
    cols = rng.integers(0, n, size=4 * n)
    uniq = np.unique(rows * np.int64(n) + cols)
    rows, cols = (uniq // n).astype(np.int64), (uniq % n).astype(np.int64)
    rowptr = np.searchsorted(rows, np.arange(n + 1)).astype(np.int64)
    Ar = CsrMatrix(n, n, rowptr, cols.astype(np.int32),
                   rng.standard_normal(len(rows)).astype(np.float32))
    dev = build_device_sgell(Ar, interpret=True)
    if dev is not None:          # only if random happened to cluster
        assert dev.fill >= MIN_FILL
    # probe-gated off on CPU when interpret not forced
    assert build_device_sgell(A) is None


def test_sgell_end_to_end_cg():
    """A full CG solve through the DeviceSgell operator passthrough —
    the production wiring (build_device_operator returns the operator
    as-is), numerics vs the manufactured solution."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.solvers.cg import cg
    from acg_tpu.sparse import poisson3d_7pt
    from acg_tpu.sparse.csr import manufactured_rhs
    from acg_tpu.sparse.rcm import permute_symmetric

    P = poisson3d_7pt(12, dtype=np.float32)
    perm = np.random.default_rng(17).permutation(P.nrows)
    Pp = permute_symmetric(P, perm)          # scattered ordering
    dev = build_device_sgell(Pp, interpret=True, min_fill=0.0)
    assert isinstance(dev, DeviceSgell)
    xstar, b = manufactured_rhs(Pp, seed=2)
    res = cg(dev, b, options=SolverOptions(maxits=600, residual_rtol=1e-6))
    assert res.converged
    err = np.abs(np.asarray(res.x) - xstar).max() / np.abs(xstar).max()
    assert err < 1e-3, err


def test_build_device_operator_routes_to_sgell(monkeypatch):
    """fmt="auto" on a scattered matrix that neither DIA nor RCM->DIA can
    recover routes through the sgell tier when the probe passes (here:
    monkeypatched to the interpret kernel), before the XLA ELL
    fallback."""
    from acg_tpu.ops import sgell as sgell_mod
    from acg_tpu.solvers.cg import build_device_operator

    # scattered-but-local matrix with enough fill
    A, _, _ = _random_local_csr(3000, 9, 1200, seed=19)

    orig = sgell_mod.build_device_sgell

    def forced(mat, dtype=None, mat_dtype="auto", min_fill=MIN_FILL,
               interpret=False):
        return orig(mat, dtype=dtype, mat_dtype=mat_dtype,
                    min_fill=0.0, interpret=True)

    monkeypatch.setattr(sgell_mod, "build_device_sgell", forced)
    dev = build_device_operator(A, dtype=np.float32, fmt="auto")
    # this matrix is RCM-able (local spread), so the route of choice is
    # sgell on the RCM-permuted matrix; a plain DeviceSgell would mean
    # the bandwidth-reduction step was skipped
    from acg_tpu.solvers.cg import PermutedOperator

    assert isinstance(dev, PermutedOperator)
    assert isinstance(dev.dev, DeviceSgell)
    # the documented force contract survives: fmt="ell" pins the XLA
    # gather form even when the sgell tier is available
    from acg_tpu.ops.spmv import DeviceEll

    dev_forced = build_device_operator(A, dtype=np.float32, fmt="ell")
    assert isinstance(dev_forced, DeviceEll)


def test_sgell_int8_index_tier_interpret():
    """The int8 lane-index storage tier (indices < 128 by construction)
    must produce identical results through the interpret kernel."""
    from acg_tpu.ops.sgell import sgell_matvec_pallas

    A, rows, cols = _random_local_csr(3000, 9, 400, seed=5)
    dev = build_device_sgell(A, interpret=True, min_fill=0.0)
    x = np.random.default_rng(1).standard_normal(A.nrows).astype(np.float32)
    xp = jnp.pad(jnp.asarray(x), (0, dev.nrows_padded - A.nrows))
    y32 = np.asarray(dev.matvec(xp))
    assert np.asarray(dev.idx).max() < 128
    y8 = np.asarray(sgell_matvec_pallas(
        dev.vals, jnp.asarray(np.asarray(dev.idx).astype(np.int8)),
        dev.seg, dev.tile, dev.first, xp,
        S=dev.S, ntiles=dev.ntiles, interpret=True))
    np.testing.assert_array_equal(y8, y32)


def test_sgell_idx_narrow_gating(monkeypatch):
    from acg_tpu.ops import pallas_kernels as pk
    from acg_tpu.ops.sgell import sgell_idx_narrow

    idx = np.arange(12, dtype=np.int32).reshape(3, 4) % 128
    # probe off (CPU default): int32 kept
    assert sgell_idx_narrow(idx).dtype == np.int32
    monkeypatch.setitem(pk._SPMV_PROBE, "sgell8", True)
    assert sgell_idx_narrow(idx).dtype == np.int8
    # interpret mode always keeps int32
    assert sgell_idx_narrow(idx, interpret=True).dtype == np.int32


def test_pack_refuses_more_slots_than_smem_holds(monkeypatch):
    """The kernel's scalar-prefetch tables live in SMEM (1 MiB on v5e):
    a pack with more slots than MAX_SLOTS does not take the tier."""
    from acg_tpu.ops import sgell as sg
    from acg_tpu.sparse import poisson2d_5pt

    A = poisson2d_5pt(64, dtype=np.float32)
    full = sg.pack_csr(A, np.float32)
    assert full["vals"] is not None and full["S"] > 2
    monkeypatch.setattr(sg, "MAX_SLOTS", 2)
    assert sg.pack_csr(A, np.float32)["vals"] is None
    assert sg.build_device_sgell(A, dtype=np.float32, min_fill=0.0,
                                 interpret=True) is None
