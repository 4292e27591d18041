"""Segmented-gather ELL: the fast Pallas tier for unstructured SpMV.

The reference's answer to arbitrary sparsity is the merge-path CSR kernel
(reference acg/cg-kernels-cuda.cu:340-441 ``csrgemv_merge``): load-balance
rows across warps in-kernel and rely on the GPU cache hierarchy to absorb
the x gathers.  TPUs have no gather cache path — Mosaic's vector gather
support is exactly one shape: ``take_along_axis(src, idx, axis=1)`` on
``(R, 128)`` f32 blocks, i.e. each output element may gather from the
128-element x segment held in its OWN sublane row (measured compile
envelope, 2026-07-31: lane-dim gathers compile for any R with lane width
exactly 128; sublane-dim and wide-lane forms are rejected or crash
Mosaic).  So the load balancing moves to the host, like the rest of this
framework's kernels (SURVEY §7 design stance):

- Output rows are tiled 1024 at a time, viewed as an (8, 128) block:
  row i sits at sublane ``(i // 128) % 8``, lane ``i % 128``.
- x is viewed as 128-element SEGMENTS (``x3d[q] = x[128q : 128q+128]``).
- A **slot** is one (8, 128) pair of val/idx vregs for a tile, where all
  entries in sublane ``s`` read from ONE shared segment ``seg[slot, s]``.
  The 8 segment rows are DMA'd per slot through scalar-prefetched
  BlockSpec index maps (the grid's dynamic-fetch engine does the
  "gather" of segments; the in-kernel lane gather does the rest).
- Host packing buckets each row's entries by (segment, rank-within-row)
  and numbers the distinct buckets per (tile, sublane) — slot count per
  tile is the max over its sublanes, so cost adapts per tile instead of
  paying a global worst case (the same philosophy as merge-path's
  per-warp balancing, executed at preprocessing time).

Efficiency is ``nnz / (S * 1024)`` (the **fill factor**): high for any
matrix whose 128-row windows touch few distinct x segments (FEM meshes
and anything with locality, with or without an RCM pass), low only for
uniformly random sparsity — where every architecture is bandwidth-hostile
and the XLA gather fallback remains the honest answer.  Selection is by
fill threshold + the usual compile-and-match probe (group "sgell"), so
enabling the kernel can never change results.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from acg_tpu.ops.pallas_kernels import pallas_call

LANES = 128
SUBL = 8
# The kernel scalar-prefetches seg/tile/first (40 B per slot) into SMEM,
# which holds 1 MiB on v5e: 26,000 slots fail to compile there (AOT
# compile for a described v5e, PR 21).  Larger packs do not take the tier.
MAX_SLOTS = 24 * 1024
TILE = SUBL * LANES          # 1024 output rows per tile

# sgell wins over the XLA gather formulation down to ~0.002 fill on the
# traffic model (slot stream ~12 KB vs the measured ~7.6 ns/element XLA
# gather); 0.02 keeps a 10x margin until re-measured on each generation
MIN_FILL = 0.02


def sgell_fill_metadata(A, nrows: int | None = None) -> dict:
    """Metadata-only pack diagnosis straight from a CsrMatrix: the
    ``S``/``fill``/``n_pad`` a full :func:`pack_csr` would report, with
    NONE of its O(nnz) expansions (rowids repeat, colidx/vals casts) —
    the fast-tier report sweeps every part of a 9M-row system through
    this.  In-row column order is guaranteed by the CsrMatrix
    contract, so the run-length slot counter applies directly."""
    nnz = A.nnz
    base = A.nrows if nrows is None else nrows
    n_pad = -(-max(base, 1) // TILE) * TILE
    ntiles = n_pad // TILE
    meta = dict(vals=None, idx=None, seg=None, tile=None, first=None,
                ntiles=ntiles, n_pad=n_pad)
    if nnz == 0:
        # one mandatory slot per tile (every output block is zeroed)
        return dict(meta, S=ntiles, fill=0.0)
    from acg_tpu import native

    S = native.sgell_fill_slots_native(A.rowptr, A.colidx, A.nrows,
                                       n_pad)
    if S is None:
        rowids = np.repeat(np.arange(A.nrows), A.rowlens)
        S = _fill_slots_py(rowids, A.colidx.astype(np.int64), n_pad)
    return dict(meta, S=S, fill=nnz / (S * TILE))


def _fill_slots_py(rows: np.ndarray, cols: np.ndarray,
                   n_pad: int) -> int:
    """NumPy run-length slot counter for CSR-ordered (rows, cols)."""
    nnz = len(rows)
    q = cols // LANES
    dr = np.diff(rows)
    new_g = np.r_[True, (dr != 0) | (q[1:] != q[:-1])]
    starts = np.flatnonzero(new_g)
    cnt = np.diff(np.r_[starts, nnz])
    ts = rows[starts] // LANES           # (tile, sublane) id per group
    q_g = q[starts]
    order = np.lexsort((q_g, ts))
    k_ts, k_q, k_c = ts[order], q_g[order], cnt[order]
    new2 = np.r_[True, (k_ts[1:] != k_ts[:-1]) | (k_q[1:] != k_q[:-1])]
    s2 = np.flatnonzero(new2)
    gmax = np.maximum.reduceat(k_c, s2)
    slots_ts = np.zeros(n_pad // LANES, dtype=np.int64)
    np.add.at(slots_ts, k_ts[s2], gmax)
    return int(np.maximum(slots_ts.reshape(-1, SUBL).max(axis=1),
                          1).sum())


def _fill_slots_metadata(rows: np.ndarray, cols: np.ndarray,
                         nrows: int, n_pad: int) -> int | None:
    """Exact slot count S of the pack layout WITHOUT the layout: with
    row-major input and in-row columns ascending (the CSR expansion
    pack_csr feeds in), the per-(row, segment) entry count is a RUN
    LENGTH, and a (tile, sublane)'s slot count is the sum over segments
    of the max run across its 128 rows — so S falls out of one linear
    sweep instead of the two multi-key lexsorts of the full pack (the
    40 s metadata-only wall of the 9M-row fast-tier diagnosis).  None
    when the input is not row-major sorted (caller takes the full
    layout path)."""
    if len(rows) == 0:
        return None
    dr = np.diff(rows)
    if not bool(np.all((dr > 0) | ((dr == 0) & (np.diff(cols) > 0)))):
        return None                      # not CSR-ordered: full path
    from acg_tpu import native

    rowptr = np.searchsorted(rows, np.arange(nrows + 1)).astype(np.int64)
    S = native.sgell_fill_slots_native(rowptr, cols, nrows, n_pad)
    if S is not None:
        return S
    return _fill_slots_py(rows, cols, n_pad)


def pack_sgell(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
               nrows: int, min_fill: float = 0.0):
    """Pack COO entries (unique (row, col) pairs, any order) into the
    slot layout.  Returns a dict of numpy arrays:

    - ``vals``  (S*8, 128): entry values (slot-major)
    - ``idx``   (S*8, 128) int32: lane index of each entry within its
      sublane's segment
    - ``seg``   (S, 8) int32: x-segment id per (slot, sublane)
    - ``tile``  (S,) int32: output tile of each slot (non-decreasing)
    - ``first`` (S,) int32: 1 on the first slot of each tile (the kernel
      zero-initializes the output block there)
    - ``S``, ``ntiles``, ``n_pad``, ``fill``

    Every tile owns >= 1 slot even when empty, so every output block is
    visited and zeroed (an unvisited Pallas output block is garbage).

    When the computed fill lands below ``min_fill`` the slot arrays are
    NOT materialized (they can dwarf the matrix itself — S*12 KB for a
    low-fill pack) and the returned dict carries ``vals=None`` plus the
    metadata, so callers can report the fill without paying for it."""
    nnz = len(vals)
    n_pad = -(-max(nrows, 1) // TILE) * TILE
    ntiles = n_pad // TILE
    if min_fill > 1.0 and nnz:
        # metadata-only call (the fill can never clear a >1 gate): the
        # slot count comes from the linear-sweep path when the input is
        # CSR-ordered — same S, no layout, no lexsorts
        S = _fill_slots_metadata(rows, cols, nrows, n_pad)
        if S is not None:
            return dict(vals=None, idx=None, seg=None, tile=None,
                        first=None, S=S, ntiles=ntiles, n_pad=n_pad,
                        fill=nnz / (S * TILE))
    t = rows // TILE
    s = (rows // LANES) % SUBL
    lane = rows % LANES
    q = cols // LANES
    r = cols % LANES
    # rank of each entry within its (row, segment) group: same-row entries
    # hitting the same segment must land in different slots
    order = np.lexsort((r, q, rows))
    rows_o = rows[order]
    q_o = q[order]
    new_grp = np.r_[True, (rows_o[1:] != rows_o[:-1]) | (q_o[1:] != q_o[:-1])]
    grp_start_of = np.flatnonzero(new_grp)[np.cumsum(new_grp) - 1]
    rank_o = np.arange(nnz) - grp_start_of
    rank = np.empty(nnz, dtype=np.int64)
    rank[order] = rank_o
    # slot numbering per (tile, sublane): distinct (segment, rank) pairs
    # in sorted order ARE the slots of that sublane
    key = np.lexsort((lane, rank, q, s, t))
    t_k, s_k, l_k, q_k, r_k, v_k, rank_k = (
        a[key] for a in (t, s, lane, q, r, vals, rank))
    new_slot = np.r_[True, (t_k[1:] != t_k[:-1]) | (s_k[1:] != s_k[:-1])
                     | (q_k[1:] != q_k[:-1]) | (rank_k[1:] != rank_k[:-1])]
    new_ts = np.r_[True, (t_k[1:] != t_k[:-1]) | (s_k[1:] != s_k[:-1])]
    slot_counter = np.cumsum(new_slot) - 1
    ts_first_slot = slot_counter[np.flatnonzero(new_ts)]
    ts_id = np.cumsum(new_ts) - 1
    slot_in_ts = slot_counter - ts_first_slot[ts_id]
    # per-tile slot count = max over its sublanes, min 1 (empty tiles
    # still need their output block zeroed)
    nslots_ts = np.zeros((ntiles, SUBL), dtype=np.int64)
    if nnz:
        np.maximum.at(nslots_ts, (t_k, s_k), slot_in_ts + 1)
    nslots_t = np.maximum(nslots_ts.max(axis=1), 1)
    tile_slot0 = np.concatenate(([0], np.cumsum(nslots_t)))
    S = int(tile_slot0[-1])
    fill = nnz / (S * TILE)
    if fill < min_fill or S > MAX_SLOTS:
        return dict(vals=None, idx=None, seg=None, tile=None, first=None,
                    S=S, ntiles=ntiles, n_pad=n_pad, fill=fill)
    pv = np.zeros((S, SUBL, LANES), dtype=vals.dtype)
    pidx = np.zeros((S, SUBL, LANES), dtype=np.int32)
    seg = np.zeros((S, SUBL), dtype=np.int32)
    if nnz:
        gslot = tile_slot0[t_k] + slot_in_ts
        pv[gslot, s_k, l_k] = v_k
        pidx[gslot, s_k, l_k] = r_k
        seg[gslot, s_k] = q_k
    tile_of_slot = np.repeat(np.arange(ntiles, dtype=np.int32),
                             nslots_t).astype(np.int32)
    first = np.zeros(S, dtype=np.int32)
    first[tile_slot0[:-1]] = 1
    return dict(vals=pv.reshape(S * SUBL, LANES),
                idx=pidx.reshape(S * SUBL, LANES),
                seg=seg, tile=tile_of_slot, first=first,
                S=S, ntiles=ntiles, n_pad=n_pad, fill=fill)


def pack_csr(A, vec_dtype, nrows: int | None = None,
             min_fill: float = 0.0) -> dict:
    """Pack a CsrMatrix: the ONE rowids-expansion + cast + pack sequence
    shared by the single-chip builder (:func:`build_device_sgell`) and
    the per-shard distributed packer (parallel/sharded.py).  ``nrows``
    overrides the padded row count (distributed shards pack at the
    uniform padded shard length)."""
    rowids = np.repeat(np.arange(A.nrows), A.rowlens)
    return pack_sgell(rowids, A.colidx.astype(np.int64),
                      A.vals.astype(np.dtype(vec_dtype)),
                      A.nrows if nrows is None else nrows,
                      min_fill=min_fill)


def pad_pack(packed: dict, S_pad: int) -> dict:
    """Pad a materialized pack to ``S_pad`` slots (uniform-shape stacking
    across shards, parallel/sharded.py): padding slots carry zero values,
    segment 0, the LAST tile id, and first=0 — pure accumulate-zero
    no-ops on an already-initialized output block."""
    S, ntiles = packed["S"], packed["ntiles"]
    assert S_pad >= S
    if S_pad == S:
        return packed
    ext = S_pad - S
    out = dict(packed)
    out["vals"] = np.concatenate(
        [packed["vals"], np.zeros((ext * SUBL, LANES),
                                  dtype=packed["vals"].dtype)])
    out["idx"] = np.concatenate(
        [packed["idx"], np.zeros((ext * SUBL, LANES), dtype=np.int32)])
    out["seg"] = np.concatenate(
        [packed["seg"], np.zeros((ext, SUBL), dtype=np.int32)])
    out["tile"] = np.concatenate(
        [packed["tile"], np.full(ext, ntiles - 1, dtype=np.int32)])
    out["first"] = np.concatenate(
        [packed["first"], np.zeros(ext, dtype=np.int32)])
    out["S"] = S_pad
    return out


def _sgell_kernel(seg_ref, tile_ref, first_ref, *refs):
    """One grid step = one slot: 8 prefetched (1, 1, 128) x-segment rows,
    concatenated on the sublane dim, lane-gathered by idx, FMA'd into the
    revisited (8, 128) output block of the slot's tile."""
    x_refs = refs[:SUBL]
    v_ref, i_ref, o_ref = refs[SUBL], refs[SUBL + 1], refs[SUBL + 2]
    k = pl.program_id(0)
    xsrc = jnp.concatenate([xr[0, :, :] for xr in x_refs], axis=0)
    idx = i_ref[:, :]
    if idx.dtype != jnp.int32:       # int8 storage tier: lane index < 128
        idx = idx.astype(jnp.int32)
    g = jnp.take_along_axis(xsrc, idx, axis=1)
    contrib = v_ref[:, :].astype(o_ref.dtype) * g

    @pl.when(first_ref[k] == 1)
    def _():
        o_ref[:, :] = jnp.zeros_like(o_ref)

    o_ref[:, :] += contrib


@functools.partial(jax.jit, static_argnames=("S", "ntiles", "interpret"))
def sgell_matvec_pallas(vals, idx, seg, tile, first, x_pad,
                        S: int, ntiles: int, interpret: bool = False):
    """y_pad = A @ x_pad through the slot kernel.  ``x_pad``: (n_pad,)
    f32 (Mosaic's lane gather is f32-only; bf16 crashes the compiler).
    ``vals`` may be bf16 storage (upcast after load — values are streamed,
    not gathered).  Returns (n_pad,) f32 with padding rows zero."""
    x3d = x_pad.reshape(ntiles * SUBL, 1, LANES)

    x_specs = [
        pl.BlockSpec((1, 1, LANES),
                     (lambda s_cap: lambda k, seg_r, tile_r, first_r:
                      (seg_r[k * SUBL + s_cap], 0, 0))(s),
                     memory_space=pltpu.VMEM)
        for s in range(SUBL)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=x_specs + [
            pl.BlockSpec((SUBL, LANES),
                         lambda k, seg_r, tile_r, first_r: (k, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((SUBL, LANES),
                         lambda k, seg_r, tile_r, first_r: (k, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((SUBL, LANES),
                               lambda k, seg_r, tile_r, first_r:
                               (tile_r[k], 0),
                               memory_space=pltpu.VMEM),
    )
    y = pallas_call(
        _sgell_kernel, grid_spec=grid_spec,
        name="acg_sgell",
        out_shape=jax.ShapeDtypeStruct((ntiles * SUBL, LANES), x_pad.dtype),
        interpret=interpret,
    )(seg.reshape(-1), tile, first, *([x3d] * SUBL), vals, idx)
    return y.reshape(-1)


def sgell_matvec_any(vals, idx, seg, tile, first, x, S: int, ntiles: int,
                     interpret: bool = False):
    """:func:`sgell_matvec_pallas` for 1-D or batched ``(B, n_pad)`` x —
    the ONE owner of the multi-RHS fallback (DeviceSgell.matvec and the
    distributed per-shard closure both dispatch here, so a future true
    batched sgell kernel lands in exactly one place): the slot kernel is
    1-D (scalar-prefetch grid), so vmap re-invokes it per system — the
    pack streams once per system rather than once overall, but keeps the
    sgell tier available to batched solves without a second kernel."""
    if x.ndim == 2:
        return jax.vmap(lambda xi: sgell_matvec_pallas(
            vals, idx, seg, tile, first, xi, S=S, ntiles=ntiles,
            interpret=interpret))(x)
    return sgell_matvec_pallas(vals, idx, seg, tile, first, x,
                               S=S, ntiles=ntiles, interpret=interpret)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceSgell:
    """Device-resident segmented-gather ELL operator.  Duck-typed like
    DeviceEll/DeviceDia (nrows/nnz/vec_dtype/nrows_padded/matvec) so the
    solvers treat it as just another operator; built by
    :func:`build_device_sgell` only when the probe passes and the fill
    clears :data:`MIN_FILL`."""

    vals: jax.Array
    idx: jax.Array
    seg: jax.Array
    tile: jax.Array
    first: jax.Array
    S: int = dataclasses.field(metadata=dict(static=True), default=0)
    ntiles: int = dataclasses.field(metadata=dict(static=True), default=0)
    nrows: int = dataclasses.field(metadata=dict(static=True), default=0)
    ncols: int = dataclasses.field(metadata=dict(static=True), default=0)
    nnz: int = dataclasses.field(metadata=dict(static=True), default=0)
    vec_dtype: str = dataclasses.field(metadata=dict(static=True),
                                       default="float32")
    interpret: bool = dataclasses.field(metadata=dict(static=True),
                                        default=False)

    @property
    def nrows_padded(self) -> int:
        return self.ntiles * TILE

    @property
    def mat_itemsize(self) -> int:
        return self.vals.dtype.itemsize

    def operator_stream_bytes(self) -> int:
        """Per-SpMV HBM bytes of the operator stream: packed values plus
        every per-tile table (segment ids, tile descriptors, first-row
        offsets) the kernel walks each pass — charged once per iteration
        by the roofline model (acg_tpu/obs/roofline.py)."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in (self.vals, self.idx, self.seg,
                             self.tile, self.first))

    @property
    def fill(self) -> float:
        return self.nnz / (self.S * TILE)

    def matvec(self, x: jax.Array) -> jax.Array:
        return sgell_matvec_any(self.vals, self.idx, self.seg,
                                self.tile, self.first, x,
                                S=self.S, ntiles=self.ntiles,
                                interpret=self.interpret)


def sgell_supported(vec_dtype) -> bool:
    """The kernel gathers x as f32 — the only dtype Mosaic's lane gather
    accepts (bf16 crashes the compiler, f64 is unsupported)."""
    return np.dtype(vec_dtype) == np.float32


def sgell_available() -> bool:
    """Probe group "sgell" of the shared once-per-process registry."""
    from acg_tpu.ops.pallas_kernels import pallas_spmv_available

    return pallas_spmv_available("sgell")


def sgell_require_available(vec_dtype, interpret: bool = False) -> None:
    """The forced-tier gate, shared by every entry point that accepts an
    explicit fmt="sgell" (single-chip build_device_operator, distributed
    ShardedSystem.build): raise ERR_NOT_SUPPORTED when the tier cannot
    run, so a forced tier errors identically everywhere instead of two
    hand-maintained copies drifting.  ``interpret`` skips the Mosaic
    probe (CPU tests force the interpret kernel)."""
    from acg_tpu.errors import AcgError, Status

    vdt = np.dtype(vec_dtype)
    if not sgell_supported(vdt):
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       f"format 'sgell' does not support vector dtype "
                       f"{vdt.name}")
    if not interpret and not sgell_available():
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "format 'sgell' forced but the kernel probe failed "
                       "on this backend (Mosaic unavailable or rejected "
                       "the kernel)")


def sgell_idx_narrow(idx: np.ndarray, interpret: bool = False) -> np.ndarray:
    """Lane indices are < 128 by construction (c % 128), so int8 storage
    always fits and quarters the index stream (~25% of slot traffic).
    Gated on its OWN probe group ("sgell8") so a Mosaic rejecting int8
    blocks degrades to int32 without killing the tier.  Interpret mode
    keeps int32 — CPU tests pin the int8 kernel math separately."""
    from acg_tpu.ops.pallas_kernels import pallas_spmv_available

    if not interpret and pallas_spmv_available("sgell8"):
        return idx.astype(np.int8)
    return idx


def build_device_sgell(A, dtype=None, mat_dtype="auto",
                       min_fill: float = MIN_FILL,
                       interpret: bool = False,
                       _probing: bool = False) -> DeviceSgell | None:
    """Pack a CsrMatrix and build the device operator, or None when the
    tier does not apply (dtype unsupported, fill below threshold, probe
    failed).  ``interpret`` forces the interpret-mode kernel and skips the
    probe — CPU testing only.  ``_probing`` skips the availability check
    so the probe itself can build the operator it is validating (the
    check would otherwise re-enter the probe)."""
    from acg_tpu.ops.dia import resolve_mat_dtype

    vdt = np.dtype(dtype if dtype is not None else A.vals.dtype)
    if not sgell_supported(vdt):
        return None
    if not interpret and not _probing and not sgell_available():
        return None
    packed = pack_csr(A, vdt, min_fill=min_fill)
    if packed["vals"] is None:
        return None
    mdt = resolve_mat_dtype(packed["vals"], mat_dtype, vdt)
    # _probing must not consult the sgell8 probe: the probe thunks call
    # THIS function, and pallas_spmv_available caches only after the
    # thunk returns — narrowing here would re-enter the probe unboundedly
    # (the int8 probe casts its indices itself)
    idx_arr = (packed["idx"] if (_probing or interpret)
               else sgell_idx_narrow(packed["idx"]))
    return DeviceSgell(
        vals=jnp.asarray(packed["vals"].astype(np.dtype(mdt))),
        idx=jnp.asarray(idx_arr),
        seg=jnp.asarray(packed["seg"]),
        tile=jnp.asarray(packed["tile"]),
        first=jnp.asarray(packed["first"]),
        S=packed["S"], ntiles=packed["ntiles"],
        nrows=A.nrows, ncols=A.ncols, nnz=A.nnz,
        vec_dtype=vdt.name, interpret=interpret)


def _probe_oracle(A):
    """Shared probe oracle: (xv, want, scale) through the XLA ELL path."""
    from acg_tpu.ops.spmv import ell_matvec
    from acg_tpu.sparse.ell import EllMatrix

    E = EllMatrix.from_csr(A)
    rng = np.random.default_rng(0)
    xv = jnp.asarray(rng.standard_normal(A.nrows).astype(np.float32))
    want = ell_matvec(jnp.asarray(E.vals.astype(np.float32)),
                      jnp.asarray(E.colidx),
                      jnp.pad(xv, (0, E.nrows_padded - A.nrows)))[: A.nrows]
    return xv, want, float(jnp.max(jnp.abs(want))) or 1.0


def _probe_sgell8_group() -> bool:
    """Compile-and-match the int8-lane-index storage tier (see
    :func:`sgell_idx_narrow`) against the XLA oracle."""
    A = _probe_matrix()
    n = A.nrows
    xv, want, scale = _probe_oracle(A)
    dev = build_device_sgell(A, min_fill=0.0, _probing=True)
    if dev is None:
        return False
    got = sgell_matvec_pallas(
        dev.vals, jnp.asarray(np.asarray(dev.idx).astype(np.int8)),
        dev.seg, dev.tile, dev.first,
        jnp.pad(xv, (0, dev.nrows_padded - n)),
        S=dev.S, ntiles=dev.ntiles)[:n]
    return bool(jnp.max(jnp.abs(got - want)) <= 1e-5 * scale)


def _probe_matrix():
    """The shared probe workload: multi-tile local matrix with an empty
    interior tile (the forced-slot zeroing case)."""
    from acg_tpu.sparse.csr import CsrMatrix

    rng = np.random.default_rng(0)
    n, W = 4 * TILE, 6
    rows = np.repeat(np.arange(n), W)
    cols = np.clip(rows + rng.integers(-500, 501, size=n * W), 0, n - 1)
    keep = (rows // TILE) != 2
    rows, cols = rows[keep], cols[keep]
    uniq = np.unique(rows * np.int64(n) + cols)
    rows, cols = uniq // n, uniq % n
    vals32 = rng.standard_normal(len(rows)).astype(np.float32)
    order = np.lexsort((cols, rows))
    rows, cols, vals32 = rows[order], cols[order], vals32[order]
    rowptr = np.searchsorted(rows, np.arange(n + 1))
    return CsrMatrix(n, n, rowptr.astype(np.int64), cols.astype(np.int32),
                     vals32)


def _probe_sgell_group() -> bool:
    """Compile-and-match at production-ish shapes: a multi-tile local
    matrix (segments spread across the tile neighborhood), an empty
    interior tile, f32 and bf16 value storage."""
    A = _probe_matrix()
    n = A.nrows
    xv, want, scale = _probe_oracle(A)
    ok = True
    for mdt in (None, "bfloat16"):
        dev = build_device_sgell(A, mat_dtype=mdt, min_fill=0.0,
                                 _probing=True)
        if dev is None:
            return False
        got = dev.matvec(jnp.pad(xv, (0, dev.nrows_padded - n)))[:n]
        tol = 1e-5 if mdt is None else 2e-2
        ok = ok and bool(jnp.max(jnp.abs(got - want)) <= tol * scale)
    return ok
