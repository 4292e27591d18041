"""ctypes bindings for the native host library (native/acg_host.cpp).

The reference's host data layer is C (acg/sort.c, acg/prefixsum.c,
acg/mtxfile.c parsing, acg/graph.c traversals); acg_tpu mirrors that split
with a small C++ library for the host hot paths and exposes it here.  The
library is not committed: :func:`load` builds it from native/acg_host.cpp
(native/build.sh) the first time it is missing or older than its source.
Every entry point has a NumPy fallback; when the build or the load fails,
the reason goes to stderr once and the fallbacks run.

Accelerated paths (used automatically when the library is present):
- :func:`parse_mtx_body` — single-pass text parse of coordinate entries
  (feeds acg_tpu/io/mtxfile.py);
- :func:`coo_to_csr_native` — radix-sort CSR assembly with duplicate
  summing (feeds acg_tpu/sparse/csr.py);
- :func:`bfs_order_native` — level-set BFS (feeds the partitioner and RCM);
- :func:`hem_round_native` — one heavy-edge-matching proposal round
  (feeds partition/partitioner.py's multilevel coarsening);
- :func:`refine_weighted_sweep_native` — the KL-style weighted boundary
  refinement sweep (the V-cycle's coarse-level refinement inner loop);
- :func:`radix_argsort_native` — stable LSD radix argsort of uint64 keys
  (the reference's acgradixsortpair, acg/sort.c — shared by contraction
  edge aggregation and the partition-system edge grouping);
- :func:`sgell_fill_slots_native` — exact sgell pack slot count in one
  CSR sweep (the fill-only metadata path of the fast-tier diagnosis);
- :func:`csr_permute_sym_native` — sort-free symmetric CSR permutation
  (the per-part RCM relabel of rcm_localize).

The multilevel stages (matching proposals, contraction counting sort,
refinement gain scans) run over a portable std::thread pool sized by
``ACG_NATIVE_THREADS`` (default: hardware concurrency; see
:func:`native_threads`).  Threaded output is BIT-IDENTICAL to
single-threaded and to the NumPy fallbacks — chunks are contiguous
input ranges merged in chunk order — so the partition never depends on
the thread count (pinned by tests/test_native.py).

Every accelerated partitioner path is BIT-COMPATIBLE with its NumPy
fallback: the fallbacks compute the identical deterministic quantity
(per-row lexicographic argmax, stable sorts, first-max argmax
tie-breaks), and all randomness is generated host-side by the caller's
NumPy RNG and passed in — same seeds produce the same partition with or
without the library (pinned by tests/test_native.py).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys

import numpy as np

_LIB_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native", "libacg_host.so")
_lib = None


def build(verbose: bool = True) -> bool:
    """Compile the native library with g++ (native/build.sh)."""
    script = os.path.join(os.path.dirname(_LIB_PATH), "build.sh")
    try:
        out = subprocess.run(["sh", script], capture_output=True, text=True)
    except OSError as e:
        if verbose:
            print(f"native build failed: {e}", file=sys.stderr)
        return False
    if out.returncode != 0:
        if verbose:
            print(f"native build failed:\n{out.stderr}", file=sys.stderr)
        return False
    global _lib
    _lib = None
    return True


def _stale() -> bool:
    src = os.path.join(os.path.dirname(_LIB_PATH), "acg_host.cpp")
    return (not os.path.exists(_LIB_PATH)
            or os.path.getmtime(_LIB_PATH) < os.path.getmtime(src))


def load():
    """Load (and memoize) the shared library, building it first when it
    is missing or stale; None (reported on stderr) if that fails."""
    global _lib
    if _lib is not None:
        return _lib if _lib is not False else None
    built = not _stale() or build()
    _lib = False                  # memoize a failure: report it once
    if not built:
        print("acg_tpu: native library unavailable; using the NumPy "
              "fallbacks", file=sys.stderr)
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        print(f"acg_tpu: native library failed to load ({e}); using the "
              "NumPy fallbacks", file=sys.stderr)
        return None
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.acg_parse_mtx_body.restype = ctypes.c_int
    lib.acg_parse_mtx_body.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
        i64p, i64p, f64p]
    lib.acg_coo_to_csr.restype = ctypes.c_int64
    lib.acg_coo_to_csr.argtypes = [i64p, i64p, f64p, ctypes.c_int64,
                                   ctypes.c_int64, ctypes.c_int64,
                                   i64p, i64p, f64p]
    lib.acg_bfs_order.restype = ctypes.c_int64
    lib.acg_bfs_order.argtypes = [i64p, i64p, ctypes.c_int64, u8p,
                                  ctypes.c_int64, ctypes.c_int, i64p]
    lib.acg_rcm_order.restype = ctypes.c_int64
    lib.acg_rcm_order.argtypes = [i64p, i64p, ctypes.c_int64, i64p]
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.acg_hem_round.restype = ctypes.c_int64
    lib.acg_hem_round.argtypes = [i64p, i64p, f64p, u32p,
                                  ctypes.c_int64, ctypes.c_int64, i64p]
    lib.acg_hem_compact_live.restype = ctypes.c_int64
    lib.acg_hem_compact_live.argtypes = [i64p, i64p, f64p,
                                         ctypes.c_int64, i64p]
    lib.acg_contract_edges.restype = ctypes.c_int64
    lib.acg_contract_edges.argtypes = [i64p, i64p, f64p,
                                       ctypes.c_int64, i64p,
                                       ctypes.c_int64, i64p, i64p, f64p]
    lib.acg_refine_weighted_sweep.restype = ctypes.c_int64
    lib.acg_refine_weighted_sweep.argtypes = [
        i64p, i64p, f64p, i64p, ctypes.c_int64, i64p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, i64p,
        ctypes.c_int64, ctypes.c_int]
    lib.acg_radix_argsort_u64.restype = ctypes.c_int
    lib.acg_radix_argsort_u64.argtypes = [u64p, ctypes.c_int64, i64p]
    lib.acg_sgell_fill_slots.restype = ctypes.c_int64
    lib.acg_sgell_fill_slots.argtypes = [i64p, i64p, ctypes.c_int64,
                                         ctypes.c_int64]
    lib.acg_csr_permute_sym.restype = ctypes.c_int
    lib.acg_csr_permute_sym.argtypes = [i64p, i64p, ctypes.c_int64,
                                        i64p, i64p, i64p, i64p]
    lib.acg_native_threads.restype = ctypes.c_int
    lib.acg_native_threads.argtypes = []
    _lib = lib
    return lib


def available() -> bool:
    return load() is not None


def native_threads() -> int:
    """The thread count the native stages will use: the
    ``ACG_NATIVE_THREADS`` resolution (default: hardware concurrency).
    1 when the library is unavailable — the NumPy fallbacks are
    single-threaded."""
    lib = load()
    if lib is None:
        return 1
    return int(lib.acg_native_threads())


def _i64(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def parse_mtx_body(data: bytes, nnz: int, with_values: bool):
    """Parse nnz 'row col [val]' lines; returns (rowidx, colidx, vals).
    Returns None if the native library is unavailable (caller falls back).
    """
    lib = load()
    if lib is None:
        return None
    rowidx = np.empty(nnz, dtype=np.int64)
    colidx = np.empty(nnz, dtype=np.int64)
    vals = np.empty(nnz if with_values else 1, dtype=np.float64)
    rc = lib.acg_parse_mtx_body(
        data, len(data), nnz, int(with_values), _i64(rowidx), _i64(colidx),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if rc != 0:
        from acg_tpu.errors import AcgError, Status
        raise AcgError(Status.ERR_EOF if rc == -2 else
                       Status.ERR_INVALID_FORMAT,
                       "malformed matrix data (native parser)")
    if not with_values:
        vals = np.ones(nnz, dtype=np.float64)
    return rowidx, colidx, vals


def coo_to_csr_native(rowidx, colidx, vals, nrows: int, ncols: int):
    """Radix-sorted CSR assembly; returns (rowptr, colidx, vals) or None."""
    lib = load()
    if lib is None:
        return None
    rowidx = np.ascontiguousarray(rowidx, dtype=np.int64)
    colidx = np.ascontiguousarray(colidx, dtype=np.int64)
    vals64 = np.ascontiguousarray(vals, dtype=np.float64)
    nnz = len(rowidx)
    rowptr = np.zeros(nrows + 1, dtype=np.int64)
    outcol = np.empty(nnz, dtype=np.int64)
    outval = np.empty(nnz, dtype=np.float64)
    m = lib.acg_coo_to_csr(
        _i64(rowidx), _i64(colidx),
        vals64.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        nnz, nrows, ncols, _i64(rowptr), _i64(outcol),
        outval.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if m < 0:
        from acg_tpu.errors import AcgError, Status
        raise AcgError(Status.ERR_INDEX_OUT_OF_BOUNDS,
                       "COO index out of bounds (native)")
    return rowptr, outcol[:m].copy(), outval[:m].astype(vals.dtype)


def rcm_order_native(rowptr, colidx, nrows: int):
    """Whole-graph RCM ordering (new->old), or None if unavailable.
    Mirrors acg_tpu/sparse/rcm.py's rules (min-degree component starts,
    two-sweep pseudo-peripheral refinement, degree-sorted BFS, reversal)."""
    lib = load()
    if lib is None:
        return None
    rowptr = np.ascontiguousarray(rowptr, dtype=np.int64)
    colidx = np.ascontiguousarray(colidx, dtype=np.int64)
    order = np.empty(max(nrows, 1), dtype=np.int64)
    n = lib.acg_rcm_order(_i64(rowptr), _i64(colidx), nrows, _i64(order))
    if n != nrows:
        return None
    return order[:nrows]


def bfs_order_native(rowptr, colidx, nrows: int, allowed, seed: int,
                     sort_by_degree: bool):
    """Level-set BFS ordering; returns order array or None."""
    lib = load()
    if lib is None:
        return None
    rowptr = np.ascontiguousarray(rowptr, dtype=np.int64)
    colidx = np.ascontiguousarray(colidx, dtype=np.int64)
    order = np.empty(nrows, dtype=np.int64)
    if allowed is not None:
        allowed = np.ascontiguousarray(allowed, dtype=np.uint8)
        ap = allowed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    else:
        ap = None
    n = lib.acg_bfs_order(_i64(rowptr), _i64(colidx), nrows, ap,
                          seed, int(sort_by_degree), _i64(order))
    if n < 0:
        return None
    return order[:n]


def hem_round_native(rows, cols, w, jit, n: int, match) -> int | None:
    """One heavy-edge-matching proposal round over a LIVE edge list (see
    native/acg_host.cpp acg_hem_round): per-row lexicographic argmax of
    (weight, jitter, col) + mutual matching, updating ``match`` in place.
    Returns newly matched node count, or None if unavailable (caller runs
    the bit-compatible NumPy round)."""
    lib = load()
    if lib is None:
        return None
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    w = np.ascontiguousarray(w, dtype=np.float64)
    jit = np.ascontiguousarray(jit, dtype=np.uint32)
    assert match.dtype == np.int64 and match.flags.c_contiguous
    newly = lib.acg_hem_round(
        _i64(rows), _i64(cols),
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        jit.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        len(rows), n, _i64(match))
    if newly < 0:
        return None
    return int(newly)


def hem_compact_live_native(rows, cols, w, match) -> int | None:
    """Compact an edge list IN PLACE to the edges whose both endpoints
    are unmatched (see acg_hem_compact_live); returns the new count, or
    None if unavailable.  ``rows``/``cols`` int64 and ``w`` float64 must
    be C-contiguous and writable."""
    lib = load()
    if lib is None:
        return None
    for a, dt in ((rows, np.int64), (cols, np.int64), (w, np.float64)):
        if a.dtype != dt or not a.flags.c_contiguous or not a.flags.writeable:
            return None
    match = np.ascontiguousarray(match, dtype=np.int64)
    return int(lib.acg_hem_compact_live(
        _i64(rows), _i64(cols),
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(rows), _i64(match)))


def contract_edges_native(rows, cols, w, cmap, nc: int,
                          reuse_buffers: bool = False):
    """Contracted, aggregated coarse edge list (see acg_contract_edges):
    returns (ur, uc, agg) — bit-identical to the stable-argsort +
    reduceat NumPy path — or None if unavailable.

    ``reuse_buffers=True`` aliases the output buffers onto the INPUT
    arrays (which must then be C-contiguous, writable, at the exact
    dtypes, and dead to the caller afterwards): the native side runs
    its map phase in place, so no full-size edge-list copy is ever
    allocated — the finest level's 63M-edge contraction at 9M rows
    was the partitioner's peak-RSS moment."""
    lib = load()
    if lib is None:
        return None
    if reuse_buffers:
        for a, dt in ((rows, np.int64), (cols, np.int64),
                      (w, np.float64)):
            if (a.dtype != dt or not a.flags.c_contiguous
                    or not a.flags.writeable):
                reuse_buffers = False
                break
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    w = np.ascontiguousarray(w, dtype=np.float64)
    cmap = np.ascontiguousarray(cmap, dtype=np.int64)
    if reuse_buffers:
        out_r, out_c, out_w = rows, cols, w
    else:
        out_r = np.empty(len(rows), dtype=np.int64)
        out_c = np.empty(len(rows), dtype=np.int64)
        out_w = np.empty(len(rows), dtype=np.float64)
    m = lib.acg_contract_edges(
        _i64(rows), _i64(cols),
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(rows), _i64(cmap), nc, _i64(out_r), _i64(out_c),
        out_w.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if m < 0:
        return None
    # .copy() so the (possibly much larger) scratch buffers are freed
    return out_r[:m].copy(), out_c[:m].copy(), out_w[:m].copy()


def refine_weighted_sweep_native(ptr, adj_c, adj_w, nw, boundary, part,
                                 sizes, cap: int, mode: int) -> int | None:
    """One sequential weighted-refinement sweep (see native/acg_host.cpp
    acg_refine_weighted_sweep): visits ``boundary`` in order with
    immediate updates, mutating ``part`` (int32) and ``sizes`` (int64)
    in place.  mode 0 = gain sweep, 1 = balance repair.  Returns moves
    made, or None if unavailable."""
    lib = load()
    if lib is None:
        return None
    ptr = np.ascontiguousarray(ptr, dtype=np.int64)
    adj_c = np.ascontiguousarray(adj_c, dtype=np.int64)
    adj_w = np.ascontiguousarray(adj_w, dtype=np.float64)
    nw = np.ascontiguousarray(nw, dtype=np.int64)
    boundary = np.ascontiguousarray(boundary, dtype=np.int64)
    assert part.dtype == np.int32 and part.flags.c_contiguous
    assert sizes.dtype == np.int64 and sizes.flags.c_contiguous
    moved = lib.acg_refine_weighted_sweep(
        _i64(ptr), _i64(adj_c),
        adj_w.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _i64(nw), len(ptr) - 1, _i64(boundary), len(boundary),
        part.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        int(sizes.shape[0]), _i64(sizes), int(cap), int(mode))
    if moved < 0:
        return None
    return int(moved)


def sgell_fill_slots_native(rowptr, colidx, nrows: int,
                            n_pad: int) -> int | None:
    """Exact slot count S of the sgell pack layout in one CSR sweep
    (see native/acg_host.cpp acg_sgell_fill_slots) — the fill-only
    metadata path of the fast-tier diagnosis.  Requires in-row columns
    ascending (the CsrMatrix contract).  None if unavailable or on
    malformed input (caller falls back to the full layout)."""
    lib = load()
    if lib is None:
        return None
    rowptr = np.ascontiguousarray(rowptr, dtype=np.int64)
    colidx = np.ascontiguousarray(colidx, dtype=np.int64)
    S = lib.acg_sgell_fill_slots(_i64(rowptr), _i64(colidx),
                                 int(nrows), int(n_pad))
    return int(S) if S >= 0 else None


def csr_permute_sym_native(rowptr, colidx, nrows: int, perm):
    """Symmetric CSR permutation without a global sort (see
    acg_csr_permute_sym): returns (outrowptr, outcol, order) with
    ``order`` the per-entry source index, so the caller gathers values
    at their native dtype; None if unavailable."""
    lib = load()
    if lib is None:
        return None
    rowptr = np.ascontiguousarray(rowptr, dtype=np.int64)
    colidx = np.ascontiguousarray(colidx, dtype=np.int64)
    perm = np.ascontiguousarray(perm, dtype=np.int64)
    nnz = int(rowptr[-1])
    outrowptr = np.empty(nrows + 1, dtype=np.int64)
    outcol = np.empty(max(nnz, 1), dtype=np.int64)
    order = np.empty(max(nnz, 1), dtype=np.int64)
    rc = lib.acg_csr_permute_sym(_i64(rowptr), _i64(colidx), nrows,
                                 _i64(perm), _i64(outrowptr),
                                 _i64(outcol), _i64(order))
    if rc != 0:
        return None
    return outrowptr, outcol[:nnz], order[:nnz]


def radix_argsort_native(keys) -> np.ndarray | None:
    """Stable LSD radix argsort of uint64 keys (the reference's
    acgradixsortpair, acg/sort.c) — identical permutation to
    ``np.argsort(keys, kind="stable")``; None if unavailable."""
    lib = load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    perm = np.empty(len(keys), dtype=np.int64)
    lib.acg_radix_argsort_u64(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        len(keys), _i64(perm))
    return perm


def stable_argsort_u64(keys) -> np.ndarray:
    """Stable argsort of non-negative int64/uint64 keys through the
    native radix sorter when present, else ``np.argsort(kind="stable")``
    — the two produce the IDENTICAL permutation (LSD radix is stable),
    so consumers are bit-compatible either way."""
    if len(keys) > 1 << 14:         # below this numpy wins on constants
        perm = radix_argsort_native(keys)
        if perm is not None:
            return perm
    return np.argsort(keys, kind="stable")


if __name__ == "__main__":
    if "--build" in sys.argv:
        ok = build()
        print("native library:", "built" if ok else "build FAILED")
        sys.exit(0 if ok else 1)
    print("native library available:", available())
