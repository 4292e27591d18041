"""DIA (diagonal) sparse format: the gather-free TPU SpMV.

A matrix with D distinct nonzero diagonals multiplies as

    y = sum_d  band_d * shift(x, offset_d)

where ``shift`` is a static slice + zero-pad — D fused elementwise
multiply-adds streaming at HBM bandwidth on the VPU, with **no gathers**.
This is the TPU-shaped answer to the reference's merge-based CSR kernel
(reference acg/cg-kernels-cuda.cu:340-441): instead of load-balancing an
irregular access pattern inside the kernel, the access pattern is made
regular on the host (natural stencil ordering, or RCM + diagonal bucketing,
acg_tpu/sparse/rcm.py).

7-pt Poisson in natural order is exactly 7 diagonals; RCM-ordered FEM
matrices have a dense band.  ``DiaMatrix.from_csr`` stores every nonzero
diagonal; efficiency requires ndiags << n (use :func:`dia_efficiency` to
decide DIA vs ELL — the CLI does this automatically).

Storage: ``bands[D, n]`` aligned so ``bands[d, i] = A[i, i + offset[d]]``
(row-major alignment).  Entries whose column falls outside [0, n) are 0.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.sparse.csr import CsrMatrix


@dataclasses.dataclass(frozen=True)
class DiaMatrix:
    """Host-side DIA matrix; see module docstring for layout."""

    nrows: int
    ncols: int
    offsets: tuple          # static python ints, sorted
    bands: np.ndarray       # (D, nrows_padded)
    nnz: int

    @property
    def nrows_padded(self) -> int:
        return self.bands.shape[1]

    @classmethod
    def from_csr(cls, A: CsrMatrix, row_align: int = 8) -> "DiaMatrix":
        r, c, v = A.to_coo()
        offs = np.unique(c - r)
        nrp = -(-max(A.nrows, 1) // row_align) * row_align
        bands = np.zeros((len(offs), nrp), dtype=A.vals.dtype)
        d = np.searchsorted(offs, c - r)
        bands[d, r] = v
        return cls(A.nrows, A.ncols, tuple(int(o) for o in offs), bands,
                   A.nnz)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """Host oracle."""
        n = self.nrows_padded
        xp = np.zeros(n, dtype=x.dtype)
        xp[: len(x)] = x
        y = np.zeros(n, dtype=np.result_type(self.bands, x))
        for d, off in enumerate(self.offsets):
            if off >= 0:
                y[: n - off] += self.bands[d, : n - off] * xp[off:]
            else:
                y[-off:] += self.bands[d, -off:] * xp[: n + off]
        return y[: self.nrows]


def lossless_cast(a: np.ndarray, dtype, chunk: int = 1 << 22) -> bool:
    """True iff every value of ``a`` round-trips exactly through ``dtype``.

    Used by the ``mat_dtype="auto"`` policy: stencil/FEM matrices whose
    coefficients are small integers or dyadic rationals (e.g. the 7-pt
    Poisson bands, -1 and 6) are exactly representable in bfloat16, so
    storing the operator at half the width is a pure HBM-bandwidth win with
    bit-identical arithmetic (the bf16->f32 upcast before the multiply is
    exact).

    Scans in bounded chunks with early exit: the whole-array round-trip
    would transiently allocate ~2x the band storage at the peak-memory
    moment of a 100M-DOF build."""
    dt = np.dtype(dtype)
    flat = np.asarray(a).reshape(-1)
    for s in range(0, flat.size, chunk):
        piece = flat[s: s + chunk]
        rt = np.asarray(piece, dtype=dt)
        if not np.array_equal(np.asarray(rt, dtype=piece.dtype), piece):
            return False
    return True


def resolve_mat_dtype(vals: np.ndarray, mat_dtype, vec_dtype):
    """Resolve the operator-storage dtype.

    ``mat_dtype``: None → store at the vector dtype; "auto" → bfloat16 when
    the cast is exact (see :func:`lossless_cast`), else the vector dtype;
    "int8" → rejected HERE (the exact two-value mask tier is a DIA band
    feature handled in :meth:`DeviceDia.from_dia` before this resolver —
    every other storage builder must not silently truncate to int8);
    any other dtype → taken literally (lossy narrowing allowed, caller
    opts in — the mixed-precision-CG configuration)."""
    if mat_dtype is None:
        return vec_dtype
    if mat_dtype == "auto":
        if np.dtype(vec_dtype).itemsize > 2 and lossless_cast(vals, jnp.bfloat16):
            return jnp.bfloat16
        return vec_dtype
    if mat_dtype == "int8":
        from acg_tpu.errors import AcgError, Status

        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "mat_dtype='int8' (the exact two-value mask tier) "
                       "exists only for DIA band storage; use "
                       "mat_dtype='auto' to get it where applicable")
    return mat_dtype


def two_value_scales(bands: np.ndarray):
    """Per-band scale vector when every band is {0, c_d}-valued, else None.

    Constant-coefficient stencils (Poisson: off-diagonals -1, diagonal 6,
    with zeros where the neighbour crosses the domain boundary) have
    exactly two values per band, so the band compresses EXACTLY to an int8
    0/1 mask times a scalar — a 4x (f32) / 2x (bf16) shrink of the
    dominant HBM stream of the whole CG iteration, with bit-identical
    arithmetic (mask upcast and scalar multiply are exact).  This is the
    TPU-native counterpart of the reference hard-coding its flop/byte
    models around value streams (acg/cgcuda.c:885-890): here the value
    stream itself is compressed away.
    """
    scales = np.zeros(bands.shape[0], dtype=bands.dtype)
    for d in range(bands.shape[0]):
        nz = bands[d][bands[d] != 0]
        if nz.size == 0:
            continue
        c = nz[0]
        if not np.all(nz == c):
            return None
        scales[d] = c
    return scales


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceDia:
    """Device-resident DIA operator (offsets are static => the shift
    pattern compiles into the executable).

    ``bands`` may be stored narrower than the compute dtype (see
    :func:`resolve_mat_dtype`); ``vec_dtype`` is the dtype CG vectors and
    all arithmetic use — bands are upcast to it inside the fused SpMV, so
    narrow storage only changes HBM traffic, not the computation."""

    bands: jax.Array
    scales: jax.Array | None = None     # two-value tier: bands is an int8
    #                                     0/1 mask, true band = scales[d]*mask
    offsets: tuple = dataclasses.field(metadata=dict(static=True),
                                       default=())
    nrows: int = dataclasses.field(metadata=dict(static=True), default=0)
    ncols: int = dataclasses.field(metadata=dict(static=True), default=0)
    nnz: int = dataclasses.field(metadata=dict(static=True), default=0)
    vec_dtype: str = dataclasses.field(metadata=dict(static=True),
                                       default="float32")
    # the constant-coefficient stencil these bands store, when recognized
    # (acg_tpu/ops/stencil.py StencilSpec), else None: a multigrid
    # preconditioner is derived from it while the SpMV reads the bands
    stencil: object = dataclasses.field(metadata=dict(static=True),
                                        default=None)

    @classmethod
    def from_dia(cls, D: DiaMatrix, dtype=None, mat_dtype="auto") -> "DeviceDia":
        """Tier order under mat_dtype="auto": lossless bf16 FIRST, then
        exact two-value int8, then full width.  bf16 wins when both apply:
        measured end-to-end on v5e at 128³ Poisson, bf16 3836 it/s vs the
        int8 tier's 3771 (pre-bring-up record, deleted at PR 21 — the
        int8→f32 upcast + scales broadcast costs more than the smaller
        band stream saves).  int8
        remains the exact tier for two-valued bands that are NOT
        bf16-representable (e.g. {0, 1/3} coefficients).

        ``mat_dtype="int8"`` FORCES the exact mask tier (error when the
        bands are not two-valued — never a lossy truncation); any other
        concrete dtype is a caller-opted lossy narrowing
        (:func:`resolve_mat_dtype`)."""
        vdt = np.dtype(dtype if dtype is not None else D.bands.dtype)
        name = np.dtype(vdt).name
        # ALL tier decisions look at the vdt-cast bands (a value that
        # underflows in the cast must become a mask zero / a bf16 zero, or
        # the bit-identical guarantee breaks); bf16-losslessness is scanned
        # exactly once
        cast = np.asarray(D.bands, dtype=vdt)

        def int8_tier():
            sc = two_value_scales(cast)
            if sc is None:
                return None
            return cls(bands=jnp.asarray((cast != 0).astype(np.int8)),
                       scales=jnp.asarray(sc),
                       offsets=D.offsets, nrows=D.nrows, ncols=D.ncols,
                       nnz=D.nnz, vec_dtype=name)

        if mat_dtype == "int8":
            # explicit request for the two-value mask tier (benchmarking /
            # operators known two-valued); exactness is non-negotiable
            dev = int8_tier()
            if dev is None:
                from acg_tpu.errors import AcgError, Status

                raise AcgError(Status.ERR_INVALID_VALUE,
                               "mat_dtype='int8' requires two-valued "
                               "bands (the exact mask tier)")
            return dev
        if mat_dtype == "auto":
            bf16_ok = vdt.itemsize > 2 and lossless_cast(cast, jnp.bfloat16)
            if bf16_ok:
                mdt = jnp.bfloat16
            else:
                dev = int8_tier()
                if dev is not None:
                    return dev
                mdt = vdt
        else:
            mdt = resolve_mat_dtype(cast, mat_dtype, vdt)
        # narrow on host BEFORE upload: halves H2D transfer and avoids a
        # transient full-width device copy at large n
        host = cast.astype(np.dtype(mdt)) if np.dtype(mdt) != vdt else cast
        return cls(bands=jnp.asarray(host), scales=None,
                   offsets=D.offsets,
                   nrows=D.nrows, ncols=D.ncols, nnz=D.nnz,
                   vec_dtype=name)

    @property
    def nrows_padded(self) -> int:
        return self.bands.shape[1]

    @property
    def mat_itemsize(self) -> int:
        return self.bands.dtype.itemsize

    def operator_stream_bytes(self) -> int:
        """Per-SpMV HBM bytes of the operator stream itself, at its
        ACTUAL storage width (bf16-narrowed bands stream 2 B/value, the
        int8 mask tier 1 B/value + the D-scalar scales) — the number the
        roofline model (acg_tpu/obs/roofline.py) charges once per
        iteration regardless of the batch size."""
        nbytes = int(self.bands.size) * self.mat_itemsize
        if self.scales is not None:
            nbytes += int(self.scales.size) * self.scales.dtype.itemsize
        return nbytes

    def release_matvec_cache(self) -> None:
        """Drop the eager-path padded-band cache (see :meth:`matvec`).

        The cache holds a second full padded copy of the band stack on
        device (~GB-scale at 464³) for as long as the operator lives;
        long-lived processes that did a few eager matvecs in the HBM
        regime and moved on call this to hand the memory back."""
        self.__dict__.pop("_hbm2d_pad", None)

    def matvec(self, x: jax.Array) -> jax.Array:
        """SpMV through :func:`dia_matvec_best`.  In the HBM-resident
        regime (past the resident-x VMEM bound) that path pads the band
        stack per call: a ~GB-scale copy per call at e.g. 464³, and XLA
        does not hoist it out of a jitted loop either (the fused solver
        path pads once per solve instead, acg_tpu/solvers/cg.py
        _cg_device_fused).  Repeated eager matvecs
        therefore reuse a single-slot padded-band cache held on the
        instance (skipped when ``bands`` is a tracer, i.e. when the
        operator itself is a jit argument)."""
        from acg_tpu.ops import pallas_kernels as pk

        n = x.shape[-1]
        if (not isinstance(self.bands, jax.core.Tracer)
                and x.ndim == 1
                and n % pk.LANES == 0
                and pk.pallas_2d_plan(n, self.offsets, x.dtype,
                                      self.bands.dtype) is None):
            kernel, rt = _hbm_kernel_for(n, self.offsets, x.dtype,
                                         self.bands.dtype)
            if kernel is not None:
                cached = self.__dict__.get("_hbm2d_pad")
                if cached is None or cached[0] != rt:
                    bp, _ = pk.pad_dia_operands(self.bands, (), rt,
                                                self.offsets)
                    cached = (rt, jax.block_until_ready(bp))
                    object.__setattr__(self, "_hbm2d_pad", cached)
                (xp,), front = pk.pad_dia_vectors((x,), n, rt,
                                                  self.offsets)
                y = kernel(cached[1], self.offsets, xp, rows_tile=rt,
                           scales=self.scales)
                return y[front: front + n]
        return dia_matvec_best(self.bands, self.offsets, x,
                               scales=self.scales)


def _shift(x: jax.Array, off: int) -> jax.Array:
    """x shifted by ``off`` along its LAST axis with zero fill:
    out[..., i] = x[..., i+off] — the system axis is last, so a batched
    ``(B, n)`` x shifts every right-hand side in one static slice."""
    if off == 0:
        return x
    n = x.shape[-1]
    z = jnp.zeros(x.shape[:-1] + (abs(off),), dtype=x.dtype)
    # lax.slice_in_dim, NOT x[..., off:]: the ellipsis form lowers to a
    # stablehlo.gather (observed in the distributed local-SpMV HLO), and
    # gathers run two orders below HBM bandwidth on TPU — the exact cliff
    # this gather-free formulation exists to avoid
    if off > 0:
        return jnp.concatenate(
            [jax.lax.slice_in_dim(x, off, n, axis=-1), z], axis=-1)
    return jnp.concatenate(
        [z, jax.lax.slice_in_dim(x, 0, n + off, axis=-1)], axis=-1)


def dia_matvec(bands: jax.Array, offsets: tuple, x: jax.Array,
               scales: jax.Array | None = None) -> jax.Array:
    """y[i] = sum_d bands[d, i] * x[i + offsets[d]] — gather-free SpMV.

    ``x`` is ``(n,)`` or batched ``(B, n)`` (the multi-RHS form: every
    system multiplies against the SAME band stream, read once).
    XLA fuses the D multiply-adds into one pass; the shifts are static
    slices.  ``x`` has length nrows_padded.  Bands stored narrower than x
    (mixed-precision operator) are upcast in-register — the band stream is
    the dominant HBM traffic of the whole CG iteration, so bf16 storage is
    a ~1.7x measured speedup on v5e at 128^3 (see bench.py).  With
    ``scales`` the bands are int8 0/1 masks and the true band is
    ``scales[d] * mask`` (exact two-value compression, 1 B/value).
    """
    if scales is None and jnp.issubdtype(bands.dtype, jnp.integer):
        raise TypeError("bands are a compressed int mask; pass the scales "
                        "from DeviceDia (or call DeviceDia.matvec)")
    y = jnp.zeros_like(x)
    for d, off in enumerate(offsets):
        b = bands[d].astype(x.dtype)
        if scales is not None:
            b = b * scales[d].astype(x.dtype)
        y = y + b * _shift(x, off)
    return y


def dia_matvec_best(bands: jax.Array, offsets: tuple, x: jax.Array,
                    scales: jax.Array | None = None) -> jax.Array:
    """DIA SpMV through the best available path for this shape/backend.

    Selection, decided at trace time: the resident-x 2-D Pallas kernel
    (narrow band tiers) when the padded x fits the VMEM budget, the
    HBM-resident-x kernel (clustered window DMAs) when it does not, else
    the XLA fallback.  Kernels are probe-gated
    (compile-and-match once per process, acg_tpu/ops/pallas_kernels.py), so
    enabling them can never change results.  Callable both on full arrays
    (DeviceDia.matvec) and inside shard_map on per-shard blocks
    (acg_tpu/solvers/cg_dist.py)."""
    from acg_tpu.ops.pallas_kernels import (LANES, pallas_2d_plan,
                                            pallas_spmv_available)

    n = x.shape[-1]
    if x.ndim == 2:
        # multi-RHS: the batched resident kernel streams the band data
        # once per tile across all B systems (acg_tpu/ops/pallas_kernels.py
        # dia_matvec_pallas_2d_batched); outside its plan/probe the XLA
        # shift form broadcasts over the leading axis with the bands still
        # read once per fused pass
        from acg_tpu.ops.pallas_kernels import pallas_2d_batched_plan

        rt_b = pallas_2d_batched_plan(x.shape[0], n, offsets, x.dtype,
                                      bands.dtype)
        if rt_b is not None and pallas_spmv_available("batched2d"):
            from acg_tpu.ops.pallas_kernels import dia_matvec_pallas_2d_batched

            return dia_matvec_pallas_2d_batched(bands, offsets, x,
                                                rows_tile=rt_b,
                                                scales=scales)
        return dia_matvec(bands, offsets, x, scales=scales)
    if n % LANES == 0:
        rt_res = pallas_2d_plan(n, offsets, x.dtype, bands.dtype)
        # the resident 2-D layout kernel: full (8, 128) vreg density (see
        # _dia2d_kernel) — for the NARROW band tiers only: measured on
        # v5e at 128³ (chained marginal,
        # measurements/kernels-spmv2d-20260730), bf16 bands 43.9 µs vs
        # XLA 71.8 µs (1.64x), but f32 bands 86.3 µs vs XLA 75.5 µs —
        # the full-width stream is already roofline-bound on the XLA
        # path, so resident-sized f32 stays on XLA
        if (rt_res is not None and bands.dtype.itemsize <= 2
                and pallas_spmv_available("resident2d")):
            from acg_tpu.ops.pallas_kernels import dia_matvec_pallas_2d

            return dia_matvec_pallas_2d(bands, offsets, x,
                                        rows_tile=rt_res, scales=scales)
        # past the resident-x VMEM bound (the 100M-DOF regime): the
        # HBM-resident-x kernel, for EVERY storage width — at this scale
        # the XLA path's materialized shifted copies of x dominate.  The
        # pads below run on every call, bands included: XLA does not
        # hoist the band pad out of a while loop (a v5e compile of a loop
        # over this route keeps a relayout and a pad of the bands in the
        # body).  The solver's fused path (acg_tpu/solvers/cg.py
        # _cg_device_fused) pads the bands once per solve and carries
        # permanently padded vectors
        if rt_res is None:
            kernel, rt = _hbm_kernel_for(n, offsets, x.dtype, bands.dtype)
            if kernel is not None:
                from acg_tpu.ops.pallas_kernels import (pad_dia_operands,
                                                        padded_halo_rows)

                bp, (xp,) = pad_dia_operands(bands, (x,), rt, offsets)
                hp = padded_halo_rows(offsets, rt) * LANES
                y = kernel(bp, offsets, xp, rows_tile=rt, scales=scales)
                return y[hp: hp + n]
    return dia_matvec(bands, offsets, x, scales=scales)


def _hbm_kernel_for(n: int, offsets: tuple, vec_dtype, band_dtype):
    """(kernel, rows_tile) for the HBM regime, or (None, None) — thin
    face of the one routing owner (pallas_kernels.hbm_kernel_plan).
    Shared by dia_matvec_best and DeviceDia.matvec."""
    from acg_tpu.ops import pallas_kernels as pk

    _, kernel, rt = pk.hbm_kernel_plan(n, offsets, vec_dtype, band_dtype)
    return kernel, rt


def dia_efficiency(A: CsrMatrix, offsets=None) -> float:
    """nnz / (ndiags * n): fraction of DIA storage that is real nonzeros.
    Near 1 for stencils; tiny for scattered matrices (prefer ELL below
    ~0.25, the break-even where DIA streams 4x the useful data).  Pass
    precomputed unique ``offsets`` to avoid the O(nnz) sweep."""
    if offsets is None:
        r, c, _ = A.to_coo()
        offsets = np.unique(c - r)
    ndiags = len(offsets)
    if not A.nrows or not ndiags:
        return 0.0
    return A.nnz / (ndiags * A.nrows)
