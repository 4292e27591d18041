"""Device-resident sharded system: uniform padded shards over the mesh.

Bridges the irregular host-side partition (acg_tpu/partition/graph.py) to
SPMD execution: every per-part quantity is padded to the global maximum and
stacked on a leading "parts" axis sharded over the 1-D mesh, so all shards
run the same static-shape program — the SPMD analog of the reference's
per-rank locally-sized buffers (symmetric-heap buffers there are *also*
sized to the global max, reference acg/halo.c:883-891; on TPU uniformity is
simply the programming model).

Padding invariants (why no masks are needed in the solve loop):
- owned vectors are (NOWN,) with zeros beyond the shard's true ``nown``;
  padded matrix rows are all-zero, so pad entries stay exactly zero through
  every CG update and contribute nothing to dots;
- ``A_local`` columns index owned slots only; ``A_iface`` columns index the
  ghost vector (length G); ELL pad lanes have value 0 / column 0;
- halo tables pad with -1 (dropped on scatter) or 0 (gathered but unused).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.config import HaloMethod
from acg_tpu.parallel.halo import (HaloTables, build_halo_tables,
                                   halo_allgather, halo_ppermute)
from acg_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from acg_tpu.parallel.multihost import gather_to_host, make_global_array
from acg_tpu.partition.graph import PartitionedSystem
from acg_tpu.sparse.ell import EllMatrix


def _pad8(n: int) -> int:
    return max(-(-n // 8) * 8, 8)


# The most contiguous border-row runs a shard's interface may split into
# for the slice form of the interface correction (ShardedSystem.iruns):
# a z-slab shard has 2 (its first and last plane), a box of a block
# partition at most one per face.
MAX_IFACE_RUNS = 6


def _iface_part_runs(A, max_lanes: int):
    """One part's interface block cut into maximal runs of consecutive
    rows that have entries, or None when it is not a few such runs.

    In a run starting at row ``r0`` every entry lies on one of a few
    diagonals ``col - row`` of the (owned row, ghost slot) block, so each
    diagonal's ghost columns are a unit-stride window: ``row + c``.
    Returns ``[(r0, n, diags, vals)]`` with ``vals`` (n, len(diags))
    holding each row's entry on each diagonal (0 where it has none).
    A run with more diagonals than ``max_lanes`` (the ELL width) is not
    worth slicing: None."""
    if not A.nnz:
        return []
    rl = A.rowlens
    rows = np.flatnonzero(rl)
    cuts = np.flatnonzero(np.diff(rows) != 1) + 1
    if len(cuts) + 1 > MAX_IFACE_RUNS:
        return None
    rowids = np.repeat(np.arange(A.nrows, dtype=np.int64), rl)
    diag = A.colidx.astype(np.int64) - rowids
    runs = []
    for seg in np.split(rows, cuts):
        r0, n = int(seg[0]), len(seg)
        lo, hi = int(A.rowptr[r0]), int(A.rowptr[r0 + n])
        d = diag[lo:hi]
        diags = np.unique(d)
        if len(diags) > max_lanes:
            return None
        vals = np.zeros((n, len(diags)), dtype=A.vals.dtype)
        vals[rowids[lo:hi] - r0, np.searchsorted(diags, d)] = A.vals[lo:hi]
        runs.append((r0, n, diags, vals))
    return runs


def stack_iface_runs(ps: PartitionedSystem, nown: int, nghost: int,
                     max_lanes: int, dtype):
    """Every part's interface as contiguous border-row runs, stacked and
    padded to one static shape, or None when some part's interface is
    not at most :data:`MAX_IFACE_RUNS` runs fitting a padded shard.

    Returns ``(rows, cols, lens, vals, pad)``: run row starts (P, R),
    ghost-window starts per diagonal lane (P, R, K), run lengths (P, R),
    values (P, R, L, K), and the static (front, back) zero margin the
    ghost vector needs so every window lies inside it.  R, L and K are
    maxima over parts; a part with fewer runs (an end slab has one
    neighbour) is padded with empty runs, a run with fewer diagonals
    with zero-valued lanes that keep an in-range window.  A run's
    length-L window starts at ``min(row start, nown - L)`` so it stays
    inside the shard; values and ghost windows are laid out from there."""
    per = []
    for p in ps.parts:
        runs = _iface_part_runs(p.A_iface, max_lanes)
        if runs is None:
            return None
        per.append(runs)
    P = ps.nparts
    R = max(max(len(runs) for runs in per), 1)
    L = max((n for runs in per for _, n, _, _ in runs), default=1)
    K = max((len(d) for runs in per for _, _, d, _ in runs), default=1)
    rows = np.zeros((P, R), dtype=np.int32)
    lens = np.zeros((P, R), dtype=np.int32)
    cols = np.zeros((P, R, K), dtype=np.int64)
    vals = np.zeros((P, R, L, K), dtype=dtype)
    for i, runs in enumerate(per):
        for j, (r0, n, diags, v) in enumerate(runs):
            rows[i, j], lens[i, j] = r0, n
            ws = min(r0, nown - L)
            cols[i, j, :] = ws + diags[0]
            cols[i, j, :len(diags)] = ws + diags
            vals[i, j, r0 - ws:r0 - ws + n, :len(diags)] = v
    front = max(0, -int(cols.min()))
    back = max(0, int(cols.max()) + L - nghost)
    return (rows, (cols + front).astype(np.int32), lens, vals,
            (front, back))


def _dia_padded_nown(maxnown: int) -> int:
    """The DIA shard padding rule — 256-lane alignment above 2048 rows
    (the Pallas row tiles), pad8 below — shared by ShardedSystem.build
    and the probe-independent tier diagnosis (tier_kernel_name), so the
    plan math both consult always sees the size the kernel will run."""
    return (-(-maxnown // 256) * 256 if maxnown >= 2048
            else _pad8(maxnown))


def per_part_offsets(ps: PartitionedSystem) -> list[np.ndarray]:
    """Each part's sorted unique diagonal offsets — the ONE O(nnz)
    structure sweep behind stencil recognition, the DIA union/
    efficiency gates and the per-part band diagnosis, computed once per
    system and passed around (each of those re-swept the parts at 9M
    rows).  Structure-only: works on rowptr/colidx directly (to_coo
    would copy the value arrays too — pure waste at 100M-DOF scale)."""
    out = []
    for p in ps.parts:
        A = p.A_local
        if not A.nnz:
            out.append(np.empty(0, dtype=np.int64))
            continue
        # local row expansion, NOT the _rowids cache: caching it on
        # every part of every candidate system (ps AND its RCM relabel)
        # held 2x O(nnz) scratch through the whole build at 9M rows
        rowids = np.repeat(np.arange(A.nrows, dtype=np.int64), A.rowlens)
        out.append(np.unique(A.colidx.astype(np.int64) - rowids))
    return out


def local_dia_offsets(ps: PartitionedSystem,
                      per_part: list | None = None) -> tuple:
    """Union of nonzero-diagonal offsets over every part's local block
    (pass a precomputed :func:`per_part_offsets` to skip the sweep)."""
    if per_part is None:
        per_part = per_part_offsets(ps)
    offs: set = set()
    for po in per_part:
        offs.update(po.tolist())
    return tuple(sorted(int(o) for o in offs))


def _sgell_nown(maxnown: int) -> int:
    """The sgell local fmt wants TILE-aligned shard lengths (the pack's
    n_pad IS the padded owned-vector length, so the kernel output is the
    shard vector with no re-slicing)."""
    from acg_tpu.ops.sgell import TILE

    return max(-(-maxnown // TILE) * TILE, TILE)


def _try_local_sgell(ps: PartitionedSystem, vec_dtype,
                     force_interpret: bool = False,
                     min_fill: float | None = None):
    """Per-part sgell packs at the uniform padded shard length, or None
    when the tier does not apply (dtype, probe, or any part's fill below
    threshold).  ``force_interpret`` skips the probe — CPU tests.
    ``min_fill`` overrides the break-even gate (forced tiers pass 0.0)."""
    from acg_tpu.ops.sgell import (MIN_FILL, pack_csr, sgell_available,
                                   sgell_supported)

    if vec_dtype is None or not sgell_supported(vec_dtype):
        return None
    if not force_interpret and not sgell_available():
        return None
    fill = MIN_FILL if min_fill is None else min_fill
    nown = _sgell_nown(max((p.nown for p in ps.parts), default=1))
    packs = []
    for p in ps.parts:
        pk = pack_csr(p.A_local, vec_dtype, nrows=nown,
                      min_fill=fill if p.A_local.nnz else 0.0)
        if pk["vals"] is None:
            return None
        packs.append(pk)
    return packs


def recognize_parts(ps: PartitionedSystem, vec_dtype=None,
                    per_part: list | None = None):
    """(StencilSpec, "") when EVERY part's local block is the SAME
    verified constant-coefficient stencil (the distributed matrix-free
    tier's engagement condition: axis-aligned box partitions of a
    natural-order grid produce exactly this — each A_local is the
    Dirichlet-truncated stencil on its own sub-grid, and equal boxes
    share one grid shape so the SPMD program stays uniform), else
    (None, reason).  ``per_part`` is an optional precomputed
    :func:`per_part_offsets` (skips the arm-bound offset sweep)."""
    from acg_tpu.ops.stencil import recognize_stencil

    vdt = np.dtype(vec_dtype) if vec_dtype is not None else None
    spec0 = None
    for i, p in enumerate(ps.parts):
        spec, why = recognize_stencil(
            p.A_local, dtype=vdt,
            offsets=per_part[i] if per_part is not None else None)
        if spec is None:
            return None, f"part {i}: {why}"
        if spec0 is None:
            spec0 = spec
        elif spec != spec0:
            return None, (f"part {i} recognizes a different stencil "
                          f"(grid {spec.grid} vs {spec0.grid}) — the "
                          "SPMD shard program needs ONE uniform spec")
    if spec0 is None:
        return None, "no parts"
    return spec0, ""


def _stencil_report(spec, why: str) -> dict:
    from acg_tpu.ops.stencil import stencil_reject_report

    return spec.as_report() if spec is not None \
        else stencil_reject_report(why)


def _stencil_probe() -> bool:
    from acg_tpu.ops.stencil import stencil_available

    return stencil_available()


def _prefer_stencil(ps: PartitionedSystem, spec, vec_dtype,
                    mat_dtype) -> bool:
    """Auto takes the matrix-free tier per
    :func:`acg_tpu.ops.stencil.prefer_stencil` at the shard length."""
    from acg_tpu.ops.pallas_kernels import LANES
    from acg_tpu.ops.stencil import prefer_stencil

    if vec_dtype is None:
        return True
    nown = max((p.nown for p in ps.parts), default=1)
    return prefer_stencil(-(-nown // LANES) * LANES, spec.offsets,
                          spec.coeffs, vec_dtype, mat_dtype)


def resolve_local_fmt(ps: PartitionedSystem, fmt: str = "auto",
                      try_rcm: bool = True, vec_dtype=None,
                      sgell_interpret: bool = False,
                      stencil_interpret: bool = False,
                      tier_report: dict | None = None,
                      mat_dtype="auto"):
    """THE fmt="auto" decision, shared by every entry point: returns
    ``(ps, fmt, extra)`` with fmt resolved to "dia"/"sgell"/"ell";
    ``extra`` is the resolved DIA offsets, the per-part sgell packs, or
    None.

    DIA when the stacked local bands are dense enough
    (:func:`local_dia_efficiency` >= 0.25); for scattered orderings a
    per-part RCM pass (``try_rcm``) tries to recover a band — the
    distributed extension of the single-chip RCM route — possibly
    returning the relabeled system; when band recovery fails, the
    segmented-gather ELL tier is tried on the RCM-relabeled parts
    (bandwidth reduction is what makes the pack dense — the single-chip
    lesson, acg_tpu/solvers/cg.py) before the ELL gather floor.  One
    O(nnz) sweep per candidate; the resolved extras are returned so
    builders never re-sweep.

    ``tier_report``, when a dict, receives the probe-INDEPENDENT
    diagnosis as a byproduct (:func:`fill_tier_report`): the numbers
    behind every gate plus the tier the same system would take with the
    kernel probes green — i.e. on TPU — even when this host's probe is
    unavailable and the resolution lands on the xla-gather floor
    (VERDICT r5 "Next round" #2)."""
    if fmt == "stencil":
        # forced matrix-free tier: recognize or ERROR (never a silent
        # fallback); the Pallas kernel inside stays probe-gated — the
        # jnp grid-shift formulation runs everywhere
        spec, why = recognize_parts(ps, vec_dtype)
        if spec is None:
            from acg_tpu.errors import AcgError, Status

            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "format 'stencil' forced but the local "
                           "blocks are not one uniform recognized "
                           f"constant-coefficient stencil: {why}")
        if tier_report is not None:
            tier_report["stencil"] = _stencil_report(spec, why)
            fill_tier_report(tier_report, ps, "stencil", vec_dtype)
        return ps, "stencil", spec
    if fmt == "dia":
        return ps, fmt, local_dia_offsets(ps)
    if fmt != "auto":
        return ps, fmt, None
    # ONE per-part structure sweep feeds the stencil arm bound, the DIA
    # union/efficiency gates and the tier report's per-part diagnosis
    # (each of these re-swept the parts before — a triple O(nnz) cost
    # the 9M-row build wall paid for nothing)
    ppo = per_part_offsets(ps)
    # the matrix-free stencil tier outranks every stored tier when it
    # verifies (zero operator stream); recognition is skipped entirely
    # when nothing could consume the verdict (no probe, no interpret
    # force, no report asked) — the common CPU tier-1 path pays nothing
    if stencil_interpret or tier_report is not None or _stencil_probe():
        spec, why = recognize_parts(ps, vec_dtype, per_part=ppo)
        if tier_report is not None:
            tier_report["stencil"] = _stencil_report(spec, why)
        if spec is not None and (stencil_interpret or (
                _stencil_probe()
                and _prefer_stencil(ps, spec, vec_dtype, mat_dtype))):
            if tier_report is not None:
                fill_tier_report(tier_report, ps, "stencil", vec_dtype,
                                 per_part=ppo)
            return ps, "stencil", spec
    offs = local_dia_offsets(ps, per_part=ppo)
    eff = local_dia_efficiency(ps, offs)
    if tier_report is not None:
        tier_report.update(dia_efficiency=eff, dia_offsets=len(offs))
    if eff >= 0.25:
        if tier_report is not None:
            fill_tier_report(tier_report, ps, "dia", vec_dtype,
                             per_part=ppo)
        return ps, "dia", offs
    best_ps, best_ppo = ps, ppo
    rcm = False
    if try_rcm:
        from acg_tpu.partition.graph import rcm_localize

        ps_rcm = rcm_localize(ps)
        ppo_rcm = per_part_offsets(ps_rcm)
        offs_rcm = local_dia_offsets(ps_rcm, per_part=ppo_rcm)
        eff_rcm = local_dia_efficiency(ps_rcm, offs_rcm)
        if tier_report is not None:
            tier_report.update(rcm_dia_efficiency=eff_rcm,
                               rcm_dia_offsets=len(offs_rcm))
        if eff_rcm >= 0.25:
            if tier_report is not None:
                fill_tier_report(tier_report, ps_rcm, "rcm+dia",
                                 vec_dtype, per_part=ppo_rcm)
            return ps_rcm, "dia", offs_rcm
        best_ps, best_ppo = ps_rcm, ppo_rcm  # better sgell locality too
        rcm = True
    packs = _try_local_sgell(best_ps, vec_dtype,
                             force_interpret=sgell_interpret)
    if packs is not None:
        if tier_report is not None:
            tier_report["sgell_fill"] = [float(pk["fill"]) for pk in packs]
            fill_tier_report(tier_report, best_ps,
                             ("rcm+" if rcm else "") + "sgell", vec_dtype,
                             per_part=best_ppo)
        return best_ps, "sgell", packs
    if tier_report is not None:
        fill_tier_report(tier_report, best_ps, None, vec_dtype, rcm=rcm,
                         per_part=best_ppo)
    return ps, "ell", None


def fill_tier_report(report: dict, ps: PartitionedSystem,
                     resolved: str | None, vec_dtype, rcm: bool = False,
                     per_part: list | None = None):
    """Complete a fast-tier diagnosis dict (see
    :func:`resolve_local_fmt`): per-part RCM band-recovery efficiency,
    the WOULD-BE sgell fill (pack metadata only — the slot arrays are
    never materialized, pack_sgell short-circuits below min_fill, and a
    metadata-only fill comes from the linear-sweep slot counter, not
    the full layout), and the ``tpu_fmt`` the same system takes when
    the kernel probes are green.  ``resolved`` non-None means the host
    resolution already settled the tier (probe-independent gates) — the
    TPU answer is the same; None means the host landed on the ELL floor
    and the TPU outcome must be derived from metadata.  ``per_part`` is
    an optional precomputed :func:`per_part_offsets`."""
    from acg_tpu.ops.sgell import (MIN_FILL, sgell_fill_metadata,
                                   sgell_supported)

    if per_part is None:
        per_part = per_part_offsets(ps)
    # per-part band efficiency at each part's OWN offsets (how well a
    # per-part DIA would do if shards weren't stacked over the union)
    report["part_dia_efficiency"] = [
        float(p.A_local.nnz / (len(po) * max(p.A_local.nrows, 1)))
        if p.A_local.nnz else 0.0
        for p, po in zip(ps.parts, per_part)]
    # a verified stencil outranks every stored tier on TPU (the probe
    # is green there), whatever THIS host's probes let auto resolve
    stencil_tpu = bool(report.get("stencil", {}).get("recognized"))
    if resolved is not None:
        report["tpu_fmt"] = "stencil" if stencil_tpu else resolved
        return
    vdt = np.dtype(vec_dtype if vec_dtype is not None else np.float64)
    if "sgell_fill" not in report:
        # metadata-only would-be packs at the uniform padded shard
        # length: the CSR-direct slot counter — no pack expansions
        nown = _sgell_nown(max((p.nown for p in ps.parts), default=1))
        report["sgell_fill"] = [
            float(sgell_fill_metadata(p.A_local, nrows=nown)["fill"])
            if p.A_local.nnz else 1.0
            for p in ps.parts]
    fills = report["sgell_fill"]
    sgell_ok = (sgell_supported(vdt)
                and all(f >= MIN_FILL for f in fills))
    report["tpu_fmt"] = ("stencil" if stencil_tpu
                         else (("rcm+" if rcm else "")
                               + ("sgell" if sgell_ok else "ell")))


def tier_kernel_name(report: dict, ps: PartitionedSystem,
                     vec_dtype) -> str:
    """The kernel tier ``tpu_fmt`` implies ON TPU, derived from the
    Pallas VMEM-plan MATH alone (the plan functions carry no probe —
    only ``pallas_spmv_available`` does, and the whole point here is to
    answer without the chip).  DIA assumes the bf16 lossless-narrowing
    storage tier for wide vector dtypes — the measured default for
    stencil coefficients (PERF.md)."""
    fmt = report.get("tpu_fmt", "ell")
    base = fmt.split("+")[-1]
    if base == "stencil":
        return "pallas-stencil"
    if base == "sgell":
        return "pallas-sgell"
    if base != "dia":
        return "xla-gather"
    import jax.numpy as jnp

    # the plan functions are pure VMEM math; hbm_kernel_plan also checks
    # the probe (exactly what must NOT gate this answer), so the two HBM
    # plans are consulted directly in its documented priority order
    from acg_tpu.ops.pallas_kernels import (pallas_2d_plan,
                                            pallas_hbm2d_plan,
                                            pallas_hbm2d_ring_plan)

    vdt = np.dtype(vec_dtype if vec_dtype is not None else np.float64)
    bdt = np.dtype(jnp.bfloat16) if vdt.itemsize > 2 else vdt
    maxnown = max((p.nown for p in ps.parts), default=1)
    nown = _dia_padded_nown(maxnown)
    offsets = local_dia_offsets(ps)
    if pallas_2d_plan(nown, offsets, vdt, bdt) is not None:
        return "pallas-resident"
    if pallas_hbm2d_ring_plan(nown, offsets, vdt, bdt) is not None:
        return "pallas-hbm-ring"
    if pallas_hbm2d_plan(nown, offsets, vdt, bdt) is not None:
        return "pallas-hbm"
    return "xla-shift"


def local_dia_efficiency(ps: PartitionedSystem,
                         offsets: tuple | None = None) -> float:
    """Fraction of the stacked (P, D_union, NOWN) band storage that is real
    nonzeros — the distributed analog of ops.dia.dia_efficiency, deciding
    DIA vs ELL for the sharded LOCAL operator (same 0.25 break-even).
    Pass precomputed ``offsets`` to avoid an O(nnz) re-sweep."""
    D = len(offsets if offsets is not None else local_dia_offsets(ps))
    nown_max = max((p.nown for p in ps.parts), default=0)
    if D == 0 or nown_max == 0:
        return 0.0
    lnnz = sum(p.A_local.nnz for p in ps.parts)
    return lnnz / (D * nown_max * ps.nparts)


@dataclasses.dataclass
class ShardedSystem:
    """Stacked, padded, device-ready distributed operator + halo schedule."""

    mesh: jax.sharding.Mesh
    ps: PartitionedSystem
    nown_max: int                   # padded owned-vector length per shard
    nghost_max: int                 # padded ghost-vector length per shard
    lvals: jax.Array | None         # (P, NOWN, Ll) local ELL values
    lcols: jax.Array | None         # (P, NOWN, Ll)
    ivals: jax.Array                # (P, NOWN, Li) interface ELL values
    icols: jax.Array                # (P, NOWN, Li) cols into ghost vector
    halo: HaloTables
    send_idx: jax.Array             # (P, R, S)
    recv_idx: jax.Array             # (P, R, S)
    partner: jax.Array              # (P, R) partner part per round, -1 none
    pack_idx: jax.Array             # (P, B)
    ghost_src_part: jax.Array       # (P, G)
    ghost_src_pos: jax.Array        # (P, G)
    method: HaloMethod
    nnz: int
    nrows: int
    vec_dtype: str = "float64"      # compute/vector dtype; lvals/ivals may
    #                                 be stored narrower (mat_dtype policy,
    #                                 see acg_tpu/ops/dia.py)
    # DIA local operator (the gather-free fast path; chosen when the local
    # blocks are banded enough — structured slabs, or per-part RCM orders):
    lbands: jax.Array | None = None    # (P, D, NOWN) bands (or int8 masks)
    lscales: jax.Array | None = None   # (P, D) two-value tier scales
    loffsets: tuple = ()               # static union band offsets
    # segmented-gather ELL local operator (the unstructured fast path —
    # acg_tpu/ops/sgell.py — per shard, slots padded to the max):
    sgv: jax.Array | None = None       # (P, S*8, 128) slot values
    sgi: jax.Array | None = None       # (P, S*8, 128) lane indices
    sgs: jax.Array | None = None       # (P, S, 8) segment ids
    sgt: jax.Array | None = None       # (P, S) tile of slot
    sgf: jax.Array | None = None       # (P, S) first-slot-of-tile flags
    sg_S: int = 0                      # static padded slot count
    sg_ntiles: int = 0                 # static tiles per shard
    sg_interpret: bool = False         # CPU-test interpret-mode kernel
    # matrix-free stencil local operator (acg_tpu/ops/stencil.py): NO
    # device arrays at all — each shard's local block is one verified
    # constant-coefficient stencil on st_grid; the action is
    # regenerated in-kernel, so the local operator streams ZERO bytes:
    st_grid: tuple = ()                # static per-shard sub-grid shape
    st_offsets: tuple = ()             # static flat diagonal offsets
    st_digits: tuple = ()              # static per-arm axis digits
    st_coeffs: tuple = ()              # static per-arm coefficients
    st_interpret: bool = False         # CPU-test interpret-mode kernel
    # the interface correction as slices over contiguous border-row runs
    # (stack_iface_runs) — None keeps the ELL gather over ivals/icols:
    iruns: tuple | None = None         # (rows, cols, lens, vals) arrays
    iruns_pad: tuple = (0, 0)          # static ghost-vector zero margins

    @property
    def nparts(self) -> int:
        return self.ps.nparts

    @classmethod
    def build(cls, ps: PartitionedSystem, mesh: jax.sharding.Mesh | None = None,
              dtype=None, method: HaloMethod = HaloMethod.PPERMUTE,
              mat_dtype="auto", fmt: str = "auto",
              loffsets: tuple | None = None, spacks: list | None = None,
              sgell_interpret: bool = False, stspec=None,
              stencil_interpret: bool = False) -> "ShardedSystem":
        """Assemble device arrays from a host partition (the analog of
        solver init's device upload, reference acg/cgcuda.c:138-328).

        ``fmt`` picks the LOCAL operator form: "dia" stacks every part's
        local block as bands over the union of diagonal offsets — the
        gather-free SpMV streams at HBM bandwidth inside each shard, the
        distributed extension of the single-chip DIA fast path (reference
        analog: the fast merge-SpMV inside the overlapped hot loop,
        acg/cgcuda.c:847-883); "ell" keeps the padded-ELL gather form;
        "auto" picks DIA when the stacked bands are dense enough
        (:func:`local_dia_efficiency` >= 0.25).  The interface (ghost)
        operator is kept as ELL (``ivals``/``icols``); when every part's
        border rows form a few contiguous runs whose ghost columns are
        unit-stride windows (:func:`stack_iface_runs` — z-slabs of a
        natural-order grid), the shard program applies it as slices over
        those runs instead (``iruns``, :attr:`iface_fmt`).  Callers
        that already swept the parts (build_sharded) pass the resolved
        ``fmt`` plus ``loffsets`` so no O(nnz) sweep repeats here."""
        vdt = np.dtype(dtype if dtype is not None else np.float64)
        if (fmt == "auto" or (fmt == "dia" and loffsets is None)
                or (fmt == "stencil" and stspec is None)):
            # direct callers resolve here (no RCM relabel — the system
            # identity must not change under them); build_sharded resolves
            # WITH the RCM fallback before calling
            _, fmt, extra = resolve_local_fmt(
                ps, fmt, try_rcm=False, vec_dtype=vdt,
                sgell_interpret=sgell_interpret,
                stencil_interpret=stencil_interpret, mat_dtype=mat_dtype)
            if fmt == "dia":
                loffsets = extra
            elif fmt == "sgell":
                spacks = extra
            elif fmt == "stencil":
                stspec = extra
        if fmt == "sgell":
            from acg_tpu.errors import AcgError, Status
            from acg_tpu.ops.sgell import sgell_require_available

            # spacks is non-None exactly when fmt="auto" RESOLVED to
            # sgell (the gates already passed); a None here means the
            # caller FORCED the tier, and a forced tier must error, not
            # silently run something else (what a benchmark measures must
            # be what it asked for — ref cuda/acg-cuda.c:329-376)
            if spacks is None:
                sgell_require_available(vdt, interpret=sgell_interpret)
                spacks = _try_local_sgell(ps, vdt,
                                          force_interpret=sgell_interpret,
                                          min_fill=0.0)
                if spacks is None:
                    raise AcgError(Status.ERR_NOT_SUPPORTED,
                                   "format 'sgell' forced but the local "
                                   "blocks did not pack (degenerate "
                                   "geometry)")
        P = ps.nparts
        if mesh is None:
            mesh = make_mesh(P)
        maxnown = max(p.nown for p in ps.parts)
        # DIA shards want lane-aligned lengths so the Pallas kernel's row
        # tiles apply; 256-alignment costs <=12.5% padding above 2048 rows;
        # sgell shards ARE the pack's n_pad (TILE-aligned)
        if fmt == "sgell":
            NOWN = _sgell_nown(maxnown)
        elif fmt == "dia":
            NOWN = _dia_padded_nown(maxnown)
        elif fmt == "stencil":
            # lane-aligned shard lengths above the Pallas bound (the
            # stencil kernels consume lane-aligned vectors like DIA's),
            # pad8 below — the jnp grid-shift form takes any padding
            NOWN = _dia_padded_nown(maxnown)
        else:
            NOWN = _pad8(maxnown)
        G = _pad8(max(max((p.nghost for p in ps.parts), default=1), 1))
        Li = max(max((int(p.A_iface.rowlens.max()) if p.A_iface.nnz else 1)
                     for p in ps.parts), 1)

        def stack_ell(getter, width):
            # allocate at the vector dtype directly (a float64 stack cast
            # down later doubled peak memory and copy traffic at 9M rows)
            vals = np.zeros((P, NOWN, width), dtype=vdt)
            cols = np.zeros((P, NOWN, width), dtype=np.int32)
            for i, p in enumerate(ps.parts):
                E = EllMatrix.from_csr(getter(p), row_align=NOWN,
                                       min_width=width)
                vals[i] = E.vals[:NOWN]
                cols[i] = E.colidx[:NOWN]
            return vals, cols

        iv, ic = stack_ell(lambda p: p.A_iface, Li)
        runs = stack_iface_runs(ps, NOWN, G, Li, vdt)
        tables = build_halo_tables(ps, nghost_max=G)

        from acg_tpu.ops.dia import (DiaMatrix, lossless_cast,
                                     resolve_mat_dtype, two_value_scales)
        shard = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(PARTS_AXIS))

        def put(a):
            # multi-host-safe upload: each process materializes only its
            # addressable shards (replaces the reference's root-based MPI
            # scatter of submatrices, acg/graph.c:1731-1809)
            a = np.ascontiguousarray(a)
            return make_global_array(a.shape, shard, lambda idx: a[idx])

        lv = lc = lbands = lscales = None
        sgv = sgi = sgs = sgt = sgf = None
        sg_S = sg_ntiles = 0
        st_grid = st_offsets = st_digits = st_coeffs = ()
        if fmt == "stencil":
            # matrix-free: NOTHING to stack or upload — the whole local
            # operator is the static spec
            st_grid, st_offsets = stspec.grid, stspec.offsets
            st_digits, st_coeffs = stspec.digits, stspec.coeffs
            loffsets = ()
        elif fmt == "sgell":
            from acg_tpu.ops.sgell import (TILE, pad_pack,
                                           sgell_idx_narrow)

            S_pad = max(p["S"] for p in spacks)
            spacks = [pad_pack(p, S_pad) for p in spacks]
            sg_S, sg_ntiles = S_pad, spacks[0]["ntiles"]
            assert sg_ntiles * TILE == NOWN
            vstack = np.stack([p["vals"] for p in spacks])
            mdt = np.dtype(resolve_mat_dtype(vstack, mat_dtype, vdt))
            sgv = put(vstack if mdt == vdt else vstack.astype(mdt))
            sgi = put(sgell_idx_narrow(np.stack([p["idx"] for p in spacks]),
                                       interpret=sgell_interpret))
            sgs = put(np.stack([p["seg"] for p in spacks]))
            sgt = put(np.stack([p["tile"] for p in spacks]))
            sgf = put(np.stack([p["first"] for p in spacks]))
            loffsets = ()
        elif fmt == "dia":
            D = max(len(loffsets), 1)
            stack = np.zeros((P, D, NOWN), dtype=vdt)
            for i, p in enumerate(ps.parts):
                if not p.A_local.nnz:
                    continue
                dm = DiaMatrix.from_csr(p.A_local, row_align=NOWN)
                pos = np.searchsorted(np.asarray(loffsets), dm.offsets)
                stack[i, pos, :] = dm.bands[:, :NOWN]
            # storage tiers, mirroring DeviceDia.from_dia: lossless bf16
            # first (measured faster than the int8 tier end-to-end on v5e,
            # pre-bring-up record), then exact two-value int8 compression
            # (per-shard scales), else the vector dtype.  The bf16 scan
            # runs once; the stack is already at vdt (built above).
            ok_two = False
            if mat_dtype == "auto":
                bf16_ok = (vdt.itemsize > 2
                           and lossless_cast(stack, jnp.bfloat16))
                mdt = np.dtype(jnp.bfloat16) if bf16_ok else vdt
                if not bf16_ok:
                    scales = np.zeros((P, D), dtype=vdt)
                    ok_two = True
                    for i in range(P):
                        sc = two_value_scales(stack[i])
                        if sc is None:
                            ok_two = False
                            break
                        scales[i] = sc
            else:
                mdt = np.dtype(resolve_mat_dtype(stack, mat_dtype, vdt))
            if ok_two:
                lbands = put((stack != 0).astype(np.int8))
                lscales = put(scales)
            else:
                lbands = put(stack if mdt == vdt else stack.astype(mdt))
            del stack               # host copy freed once on device
        else:
            Ll = max(max((int(p.A_local.rowlens.max()) if p.A_local.nnz
                          else 1) for p in ps.parts), 1)
            lv, lc = stack_ell(lambda p: p.A_local, Ll)
            mdt = np.dtype(resolve_mat_dtype(lv, mat_dtype, vdt))
            if mdt != vdt and np.dtype(resolve_mat_dtype(iv, mat_dtype,
                                                         vdt)) == vdt:
                mdt = vdt       # both operators must narrow losslessly
            loffsets = ()

        def narrow(a):  # narrow on host before upload (no transient copy)
            a = np.asarray(a, dtype=vdt)
            return a if mdt == vdt else a.astype(mdt)

        if fmt in ("dia", "sgell", "stencil"):
            # interface values narrow independently (exactness per stream)
            mdt = np.dtype(resolve_mat_dtype(iv, mat_dtype, vdt))

        # stage the uploads and free each host stack as its device copy
        # lands — holding every numpy stack until the return doubled
        # the ELL-tier build footprint at 9M rows
        lvals_dev = lcols_dev = None
        if lv is not None:
            lvals_dev = put(narrow(lv))
            del lv
            lcols_dev = put(lc)
            del lc
        ivals_dev = put(narrow(iv))
        del iv
        icols_dev = put(ic)
        del ic
        iruns, iruns_pad = None, (0, 0)
        if runs is not None:
            rows, cols, lens, vals, iruns_pad = runs
            iruns = (put(rows), put(cols), put(lens), put(narrow(vals)))

        return cls(
            mesh=mesh, ps=ps, nown_max=NOWN, nghost_max=G,
            lvals=lvals_dev, lcols=lcols_dev,
            ivals=ivals_dev, icols=icols_dev,
            halo=tables,
            send_idx=put(tables.send_idx), recv_idx=put(tables.recv_idx),
            partner=put(tables.partner), pack_idx=put(tables.pack_idx),
            ghost_src_part=put(tables.ghost_src_part),
            ghost_src_pos=put(tables.ghost_src_pos),
            method=method, nnz=sum(p.A_local.nnz + p.A_iface.nnz
                                   for p in ps.parts),
            nrows=ps.nrows, vec_dtype=vdt.name,
            lbands=lbands, lscales=lscales, loffsets=loffsets,
            sgv=sgv, sgi=sgi, sgs=sgs, sgt=sgt, sgf=sgf,
            sg_S=sg_S, sg_ntiles=sg_ntiles,
            sg_interpret=sgell_interpret,
            st_grid=st_grid, st_offsets=st_offsets,
            st_digits=st_digits, st_coeffs=st_coeffs,
            st_interpret=stencil_interpret,
            iruns=iruns, iruns_pad=iruns_pad)

    # -- vector movement (ref acgvector scatter/gather, acg/vector.c:938+) --

    def to_sharded(self, x_global: np.ndarray) -> jax.Array:
        """Global host vector -> (P, NOWN) sharded device array
        (multi-host safe: each process fills only its shards).  A batched
        (B, n) input scatters every system, returning (P, B, NOWN) — the
        parts axis stays leading/sharded, the system axis rides along."""
        vdt = np.dtype(self.vec_dtype)
        x_global = np.asarray(x_global)
        if x_global.ndim == 2:
            B = x_global.shape[0]
            out = np.zeros((self.nparts, B, self.nown_max), dtype=vdt)
            for bi in range(B):
                for i, xl in enumerate(
                        self.ps.scatter_vector(x_global[bi])):
                    out[i, bi, : len(xl)] = xl
        else:
            out = np.zeros((self.nparts, self.nown_max), dtype=vdt)
            for i, xl in enumerate(self.ps.scatter_vector(x_global)):
                out[i, : len(xl)] = xl
        shard = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(PARTS_AXIS))
        return make_global_array(out.shape, shard, lambda idx: out[idx])

    def from_sharded(self, x: jax.Array) -> np.ndarray:
        """(P, [B,] NOWN) sharded array -> global host vector(s) (on
        every process, the analog of the reference's collective solution
        gather, cuda/acg-cuda.c:2388-2425)."""
        xh = gather_to_host(x)
        if xh.ndim == 3:
            return np.stack([
                self.ps.gather_vector([xh[i, bi]
                                       for i in range(self.nparts)])
                for bi in range(xh.shape[1])])
        return self.ps.gather_vector([xh[i] for i in range(self.nparts)])

    def zeros_sharded(self, nrhs: int | None = None) -> jax.Array:
        """All-zero sharded vector; ``nrhs`` adds a (B,) system axis."""
        shard = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(PARTS_AXIS))
        vdt = np.dtype(self.vec_dtype)
        mid = () if nrhs is None else (nrhs,)
        return make_global_array(
            (self.nparts,) + mid + (self.nown_max,), shard,
            lambda idx: np.zeros((len(range(*idx[0].indices(self.nparts))),)
                                 + mid + (self.nown_max,), dtype=vdt))

    # -- per-shard closures used inside shard_map --

    @property
    def local_fmt(self) -> str:
        if self.st_grid:
            return "stencil"
        if self.lbands is not None:
            return "dia"
        return "sgell" if self.sgv is not None else "ell"

    def local_op_arrays(self) -> tuple:
        """The traced array operands of the local SpMV, as one pytree.
        The matrix-free stencil tier has NONE — the empty tuple is the
        point: nothing enters the shard program for the local
        operator, so nothing can stream."""
        if self.st_grid:
            return ()
        if self.lbands is not None:
            return ((self.lbands, self.lscales) if self.lscales is not None
                    else (self.lbands,))
        if self.sgv is not None:
            return (self.sgv, self.sgi, self.sgs, self.sgt, self.sgf)
        return (self.lvals, self.lcols)

    def local_matvec_fn(self):
        """Per-shard local SpMV closure: mv(x_own, ops) with ``ops`` the
        shard's slices of :meth:`local_op_arrays` — band form streams
        gather-free (acg_tpu/ops/dia.py), ELL form gathers, stencil form
        synthesizes the action with no operand at all."""
        if self.st_grid:
            from acg_tpu.ops.stencil import stencil_matvec_any

            grid, offs = self.st_grid, self.st_offsets
            digs, cfs = self.st_digits, self.st_coeffs
            interp = self.st_interpret

            def mv(x, ops):
                # ops is the empty tuple — the matrix-free contract
                return stencil_matvec_any(x, grid, offs, digs, cfs,
                                          interpret=interp)
        elif self.lbands is not None:
            from acg_tpu.ops.dia import dia_matvec_best

            offsets, scaled = self.loffsets, self.lscales is not None

            def mv(x, ops):
                return dia_matvec_best(ops[0], offsets, x,
                                       scales=ops[1] if scaled else None)
        elif self.sgv is not None:
            from acg_tpu.ops.sgell import sgell_matvec_any

            S, ntiles, interp = self.sg_S, self.sg_ntiles, self.sg_interpret

            def mv(x, ops):
                v, idx, seg, tile, first = ops
                # 1-D or batched (B, n): one dispatch owner (sgell.py)
                return sgell_matvec_any(v, idx, seg, tile, first, x,
                                        S=S, ntiles=ntiles,
                                        interpret=interp)
        else:
            from acg_tpu.ops.spmv import ell_matvec

            def mv(x, ops):
                return ell_matvec(ops[0], ops[1], x)
        return mv

    @property
    def iface_fmt(self) -> str:
        """Form of the interface correction the shard program runs:
        "runs" (slices over contiguous border-row runs) or "ell"."""
        return "ell" if self.iruns is None else "runs"

    @property
    def iface_note(self) -> str:
        """The solve-result note naming :attr:`iface_fmt`, e.g.
        "iface runs(2)" with the static run count per shard."""
        if self.iruns is None:
            return "iface ell"
        return f"iface runs({self.iruns[0].shape[-1]})"

    def iface_op_arrays(self) -> tuple:
        """The traced array operands of the interface correction, as one
        pytree (the shard program's interface slot)."""
        return (self.ivals, self.icols) if self.iruns is None else self.iruns

    def iface_fns(self):
        """Per-shard interface correction closures ``(add, vec)`` over
        the shard's slices of :meth:`iface_op_arrays` and its ghost
        vector ``gh``: ``add(y, ops, gh, front=None)`` returns ``y`` plus
        the correction (written at ``front`` onwards when given — the
        padded vectors of the fused path); ``vec(ops, gh)`` returns the
        correction itself.  Vectors may carry a leading (B,) axis.

        The ELL form is the gather ``ell_matvec``.  The runs form reads
        each run's ghost window with one ``dynamic_slice`` per diagonal
        lane and adds the masked products into ``y`` with one
        ``dynamic_update_slice`` per run: no gather and no scatter
        (contract C5), and the same products and sums per border row."""
        from acg_tpu.ops.spmv import ell_matvec

        nown = self.nown_max
        if self.iruns is None:
            def vec(ops, gh):
                return ell_matvec(ops[0], ops[1], gh)

            def add(y, ops, gh, front=None):
                c = vec(ops, gh)
                if front is None:
                    return y + c
                return y.at[front: front + nown].add(c)
            return add, vec

        pad = self.iruns_pad

        def add(y, ops, gh, front=None):
            rows, cols, lens, vals = ops
            R, L, K = vals.shape
            if pad != (0, 0):
                gh = jnp.pad(gh, [(0, 0)] * (gh.ndim - 1) + [pad])
            pos = jax.lax.iota(jnp.int32, L)
            for r in range(R):
                acc = None
                for k in range(K):
                    g = jax.lax.dynamic_slice_in_dim(gh, cols[r, k], L,
                                                     axis=-1)
                    t = vals[r, :, k].astype(gh.dtype) * g
                    acc = t if acc is None else acc + t
                ws = jnp.minimum(rows[r], nown - L)
                off = rows[r] - ws
                live = (pos >= off) & (pos < off + lens[r])
                start = ws if front is None else ws + front
                cur = jax.lax.dynamic_slice_in_dim(y, start, L, axis=-1)
                y = jax.lax.dynamic_update_slice_in_dim(
                    y, cur + jnp.where(live, acc, 0), start, axis=-1)
            return y

        def vec(ops, gh):
            return add(jnp.zeros(gh.shape[:-1] + (nown,), gh.dtype), ops, gh)
        return add, vec

    def shard_halo_fn(self, wire: str = "f32"):
        """Returns halo(x_own, send_idx, recv_idx, partner, pack_idx, gsp,
        gpp) -> ghosts, for one shard (tables are that shard's slices).
        ``wire`` selects the on-wire message encoding
        (SolverOptions.halo_wire; acg_tpu/parallel/halo.py wire_encode):
        "f32" traces the exact pre-existing exchange; the compressed
        formats halve the payload without changing the collective
        count.  The RDMA path is a raw-buffer put and does not encode
        (rejected upstream by the distributed solvers)."""
        method, perms, G = self.method, self.halo.perms, self.nghost_max

        def halo_fn(x_own, send_idx, recv_idx, partner, pack_idx, gsp, gpp):
            if method == HaloMethod.PPERMUTE:
                return halo_ppermute(x_own, send_idx, recv_idx, perms, G,
                                     PARTS_AXIS, wire=wire)
            if method == HaloMethod.RDMA:
                from acg_tpu.parallel.rdma_halo import halo_rdma
                return halo_rdma(x_own, send_idx, recv_idx, partner, G,
                                 PARTS_AXIS)
            return halo_allgather(x_own, pack_idx, gsp, gpp, PARTS_AXIS,
                                  wire=wire)

        return halo_fn
