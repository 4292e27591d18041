"""Analytic per-iteration HBM-traffic model and iteration-rate ceiling.

CG is bandwidth-bound (the reference hard-codes byte models per op class,
ref acg/cgcuda.c:885-890 "12-16 B/nnz"), so the honest performance
question for any solve is "what fraction of the memory-traffic ceiling
did it reach".  This module computes that ceiling analytically from the
device operator actually built (NOT from nominal nnz counts): the
operator stream is the real device arrays' byte size at their storage
width (bf16-narrowed bands, int8 masks, ELL value+index rectangles —
acg_tpu/ops/dia.py / spmv.py / sgell.py each export their own
``operator_stream_bytes()``), the vector traffic follows the per-variant
stream counts of acg_tpu/solvers/base.py, and multi-RHS solves multiply
only the vector half by B (the operator stream is read once per
iteration for ALL systems — the batching amortization of ISSUE 2).

The predicted ceiling is ``HBM_bandwidth / bytes_per_iteration`` (times
the mesh size for sharded solves, whose shards stream in parallel);
``--hbm-gbps`` overrides the per-chip table below.  Every solve can then
report measured-vs-predicted "% of roofline" — ``RooflineModel.frac``.

Model assumptions are documented in PERF.md ("Roofline methodology").
"""

from __future__ import annotations

import dataclasses

# HBM bandwidth by device kind (GB/s), the published per-chip figures
# (Google Cloud documentation, "TPU v4" / "TPU v5e" / "TPU v5p" /
# "TPU v6e": v5e is 16 GB of HBM at 819 GB/s).  Substring-matched
# against jax's device_kind, longest key first.  A kind that is not here
# is an error, never a default: bench.py and the CLI's --explain report
# share this one table.
CHIP_HBM_GBPS = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5p": 2765.0,
    "TPU v5": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}

# SpMV vector reads+writes per system per iteration by operator family:
# DIA streams x once (VMEM-resident across the shifted windows) + y;
# the gather families (ELL / sgell) pay the gathered x read + y, counted
# 3 streams like the reference's CSR model (solvers/base.py
# cg_bytes_per_iter).  The matrix-free stencil tier streams the same
# x + y pair as DIA — with operator_bytes == 0 those two streams ARE
# the whole SpMV traffic (the vector-only ceiling of ROADMAP item 2).
_SPMV_VEC_STREAMS = {"dia": 2, "ell": 3, "sgell": 3, "stencil": 2}


def hbm_gbps_for(device_kind: str | None = None,
                 override: float | None = None) -> float:
    """The HBM bandwidth to model against: an explicit override
    (``--hbm-gbps``) wins; else the chip table keyed by device kind.
    Raises for a kind the table does not hold (``cpu`` included)."""
    if override is not None and override > 0:
        return float(override)
    for k, bw in sorted(CHIP_HBM_GBPS.items(), key=lambda kv: -len(kv[0])):
        if device_kind and k in device_kind:
            return bw
    from acg_tpu.errors import AcgError, Status

    raise AcgError(Status.ERR_NOT_SUPPORTED,
                   f"no published HBM bandwidth for device kind "
                   f"{device_kind!r}; pass --hbm-gbps")


def detected_device_kind() -> str | None:
    """The first device's kind when it is a TPU, else None: off the chip
    the roofline has no bandwidth to model against ("not measured")."""
    import jax

    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else None


def _model_gbps(device_kind: str | None, override: float | None):
    """Bandwidth for a model, or None ("not measured") when there is
    neither an override nor a chip."""
    if device_kind is None and not (override and override > 0):
        return None
    return hbm_gbps_for(device_kind, override)


@dataclasses.dataclass(frozen=True)
class RooflineModel:
    """Analytic traffic model for one solver configuration.

    ``operator_bytes`` is streamed once per iteration regardless of
    ``nrhs``; ``vector_bytes`` already includes the ×nrhs factor.
    ``predicted_iters_per_sec`` is a CEILING (perfect overlap, zero
    dispatch cost): measured/predicted > 1 means the model is wrong,
    not the hardware fast."""

    operator_format: str
    solver: str                 # "cg" | "cg-pipelined" | "cg-sstep"
    nrhs: int
    nrows: int                  # padded rows the streams run over (global)
    nparts: int
    operator_bytes: int         # operator stream per iteration (×1)
    vector_bytes: int           # vector streams per iteration (×nrhs folded in)
    hbm_gbps: float | None      # None: no chip, the ceiling is not measured
    device_kind: str | None = None
    # s-step block size (0 = not an s-step solve).  The s-step traffic
    # table ("s-step methodology", PERF.md): per s-iteration block the
    # basis build pays 2s operator applications (s for the P block,
    # s-1 for the R block, one residual replacement), so the operator
    # stream factor per ITERATION is 2s/s = 2; the per-system vector
    # traffic is (8s+6)/s streams per iteration — 4s basis read+writes,
    # 2(2s+1) Gram + update reads of the basis block, and 4 x/p streams
    # per block — which UNDERCUTS classic CG's 15 streams for s >= 2
    # (the dot re-reads are gone; the basis is reused from the MXU
    # contraction).  operator_bytes below already carries the ×2.
    sstep: int = 0
    # halo wire traffic (distributed solves only; all zero/identity for
    # nparts == 1).  The on-wire halo payload per iteration across the
    # whole mesh, priced at the WIRE itemsize — the compressed formats
    # (SolverOptions.halo_wire, parallel/halo.py wire_encode) halve
    # this without changing the HBM streams above, so halo_bytes is
    # reported separately and does NOT enter bytes_per_iter (halo
    # messages ride ICI, not HBM; the compiled truth is CommAudit's
    # ppermute byte count).
    halo_wire: str = "f32"
    halo_wire_itemsize: int = 0      # bytes/value on the wire (0 = no halo)
    halo_base_itemsize: int = 0      # identity-wire bytes/value (vec dtype)
    halo_bytes: int = 0              # ghost payload per iteration, ×nrhs folded

    @property
    def bytes_per_iter(self) -> int:
        return self.operator_bytes + self.vector_bytes

    @property
    def halo_bytes_saved_ratio(self) -> float:
        """Fraction of the identity-wire halo payload the chosen wire
        format saves (0.0 at the identity wire; 0.5 at bf16/int16-delta
        for f32 vectors).  NaN when there is no halo at all."""
        if self.halo_wire_itemsize <= 0 or self.halo_base_itemsize <= 0:
            return float("nan")
        return 1.0 - self.halo_wire_itemsize / self.halo_base_itemsize

    @property
    def predicted_iters_per_sec(self) -> float:
        if self.hbm_gbps is None:
            return float("nan")
        if self.bytes_per_iter <= 0:
            return float("inf")
        return (self.hbm_gbps * 1e9 * max(self.nparts, 1)
                / self.bytes_per_iter)

    def frac(self, measured_iters_per_sec: float) -> float:
        """Measured-vs-predicted fraction of roofline ("% of roofline"
        as a ratio); NaN when the measurement is absent/non-finite."""
        ceil = self.predicted_iters_per_sec
        if not (measured_iters_per_sec == measured_iters_per_sec) \
                or ceil <= 0 or ceil != ceil or ceil == float("inf"):
            return float("nan")
        return measured_iters_per_sec / ceil

    def as_dict(self) -> dict:
        return {
            "operator_format": str(self.operator_format),
            "solver": str(self.solver),
            "nrhs": int(self.nrhs),
            "nrows": int(self.nrows),
            "nparts": int(self.nparts),
            "operator_bytes": int(self.operator_bytes),
            "vector_bytes": int(self.vector_bytes),
            "bytes_per_iter": int(self.bytes_per_iter),
            "hbm_gbps": self.hbm_gbps,
            "device_kind": self.device_kind,
            "predicted_iters_per_sec": (
                None if self.hbm_gbps is None
                else float(self.predicted_iters_per_sec)),
            "sstep": int(self.sstep),
            "halo_wire": str(self.halo_wire),
            "halo_wire_itemsize": int(self.halo_wire_itemsize),
            "halo_base_itemsize": int(self.halo_base_itemsize),
            "halo_bytes": int(self.halo_bytes),
            "halo_bytes_saved_ratio": float(self.halo_bytes_saved_ratio),
        }

    def report(self) -> str:
        """Human-readable roofline block (the ``--explain`` report)."""
        def mb(n):
            return f"{n / 1e6:.2f} MB"

        kind = self.device_kind or "unknown device"
        lines = [
            f"roofline model ({self.operator_format} operator, "
            f"{self.solver} solver, nrhs={self.nrhs}"
            + (f", s={self.sstep}" if self.sstep else "")
            + (f", {self.nparts} shards" if self.nparts > 1 else "") + "):",
            f"  operator stream : {mb(self.operator_bytes)}/iter "
            + ("(read once for all systems; x2 for the s-step basis "
               "build)" if self.sstep else "(read once for all systems)"),
            f"  vector streams  : {mb(self.vector_bytes)}/iter "
            f"(x{self.nrhs} system(s))",
            f"  total           : {mb(self.bytes_per_iter)}/iter",
            (f"  HBM bandwidth   : {self.hbm_gbps:.0f} GB/s ({kind})"
             + (f" x {self.nparts} chips" if self.nparts > 1 else "")
             if self.hbm_gbps is not None else
             "  HBM bandwidth   : not measured (no TPU)"),
            ("  predicted ceiling: "
             + (f"{self.predicted_iters_per_sec:.1f} iterations/sec"
                if self.hbm_gbps is not None else "not measured")),
        ]
        if self.halo_bytes > 0:
            saved = self.halo_bytes_saved_ratio
            lines.insert(3, (
                f"  halo wire       : {self.halo_bytes / 1e3:.2f} KB/iter "
                f"on ICI ({self.halo_wire}, "
                f"{self.halo_wire_itemsize} B/value"
                + (f", {saved:.0%} off the identity wire"
                   if saved == saved and saved > 0 else "") + ")"))
        return "\n".join(lines)


def _vec_bytes_per_system(fmt: str, nrows: int, val_bytes: int,
                          pipelined: bool, sstep: int = 0) -> int:
    """Per-system per-iteration vector traffic: the SpMV's x/y streams
    for this operator family plus the BLAS-1 streams of the solver
    variant (solvers/base.py is the one owner of the BLAS-1 model).
    s-step solves replace both with the block model documented on
    :class:`RooflineModel`: (8s+6)/s streams per iteration."""
    from acg_tpu.solvers.base import _cg_blas1_bytes

    if sstep:
        return int((8 * sstep + 6) * nrows * val_bytes / sstep)
    base_fmt = fmt.split("+")[-1]           # "rcm+sgell" -> "sgell"
    streams = _SPMV_VEC_STREAMS.get(base_fmt, 3)
    return (streams * nrows * val_bytes
            + _cg_blas1_bytes(nrows, val_bytes, pipelined))


def roofline_for_operator(dev, *, solver: str = "cg", nrhs: int = 1,
                          hbm_gbps: float | None = None,
                          device_kind: str | None = None,
                          operator_format: str | None = None,
                          sstep: int = 0) -> RooflineModel:
    """Model a single-chip solve over a device operator (DeviceDia /
    DeviceEll / DeviceSgell — anything exporting
    ``operator_stream_bytes()`` + nrows_padded/vec_dtype).  ``sstep``
    selects the s-step traffic table (×2 operator stream, block-
    amortized vector streams — RooflineModel field docs)."""
    import numpy as np

    if device_kind is None:
        device_kind = detected_device_kind()
    fmt = operator_format if operator_format is not None \
        else _format_name(dev)
    n = int(dev.nrows_padded)
    vb = np.dtype(dev.vec_dtype).itemsize
    pipelined = "pipelined" in solver
    vec = nrhs * _vec_bytes_per_system(fmt, n, vb, pipelined,
                                       sstep=sstep)
    op = int(dev.operator_stream_bytes()) * (2 if sstep else 1)
    return RooflineModel(
        operator_format=fmt, solver=solver, nrhs=int(nrhs), nrows=n,
        nparts=1, operator_bytes=op,
        vector_bytes=int(vec),
        hbm_gbps=_model_gbps(device_kind, hbm_gbps),
        device_kind=device_kind, sstep=int(sstep))


def roofline_for_sharded(ss, *, solver: str = "cg", nrhs: int = 1,
                         hbm_gbps: float | None = None,
                         device_kind: str | None = None,
                         sstep: int = 0,
                         halo_wire: str = "f32") -> RooflineModel:
    """Model a distributed solve over a ShardedSystem: the operator
    stream is every shard's local block plus the interface operator in
    the form the shard program reads (their actual uploaded byte sizes),
    vectors run over the padded shard rows;
    the ceiling scales by the mesh size (shards stream in parallel —
    collectives ride ICI, not HBM, and are audited separately by
    obs/hlo.py).  ``halo_wire`` prices the per-iteration ghost payload
    at its on-wire itemsize (``SolverOptions.halo_wire``): the
    ``halo_bytes``/``halo_bytes_saved_ratio`` fields of the model, kept
    OUT of the HBM ceiling."""
    if device_kind is None:
        device_kind = detected_device_kind()
    import numpy as np

    from acg_tpu.parallel.halo import wire_itemsize

    op_bytes = sum(int(a.nbytes) for a in ss.local_op_arrays()
                   if a is not None)
    op_bytes += sum(int(a.nbytes) for a in ss.iface_op_arrays())
    if sstep:
        op_bytes *= 2
    n = int(ss.nparts) * int(ss.nown_max)
    vb = np.dtype(ss.vec_dtype).itemsize
    pipelined = "pipelined" in solver
    vec = nrhs * _vec_bytes_per_system(ss.local_fmt, n, vb, pipelined,
                                       sstep=sstep)
    wi = wire_itemsize(halo_wire, np.dtype(ss.vec_dtype))
    halo_bytes = int(ss.nparts) * int(ss.nghost_max) * wi * int(nrhs)
    return RooflineModel(
        operator_format=ss.local_fmt, solver=solver, nrhs=int(nrhs),
        nrows=n, nparts=int(ss.nparts), operator_bytes=int(op_bytes),
        vector_bytes=int(vec),
        hbm_gbps=_model_gbps(device_kind, hbm_gbps),
        device_kind=device_kind, sstep=int(sstep),
        halo_wire=str(halo_wire), halo_wire_itemsize=int(wi),
        halo_base_itemsize=int(vb), halo_bytes=int(halo_bytes))


def _format_name(dev) -> str:
    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.ops.sgell import DeviceSgell
    from acg_tpu.ops.stencil import DeviceStencil

    inner = getattr(dev, "dev", dev)    # unwrap PermutedOperator
    if isinstance(inner, DeviceStencil):
        return "stencil"
    if isinstance(inner, DeviceDia):
        return "dia"
    if isinstance(inner, DeviceSgell):
        return "sgell"
    return "ell"
