"""Collective/compute overlap pinned in the OPTIMIZED HLO (VERDICT r4
item 4).

The jaxpr-level data-independence test (test_cg_dist.py::
test_halo_and_local_spmv_are_data_independent) is necessary but not
sufficient: XLA's fusion pass can merge the local SpMV INTO the
ghost-correction add, producing a compiled program in which the local
compute transitively depends on the collective-permute — the exact
serialization the reference's split-phase schedule exists to avoid
(ref acg/cgcuda.c:847-883 begin/local/end/interface).

Round-5 findings (CPU mesh, optimized HLO):

- On the *XLA-formulation* local SpMV, XLA:CPU expands
  ``optimization_barrier`` early and then fuses the band compute with the
  ghost add — the compiled CPU program does serialize halo->SpMV.
  Harmless on CPU (its collectives are synchronous anyway); the barrier
  stays in solve_shard for the TPU pipeline, which honors barriers
  through fusion.  Only the halo-start half is asserted here.
- On the *fused Pallas* path — the production TPU path — the local
  kernel is an opaque unit (tpu_custom_call on hardware; a nested loop
  in interpret mode), which fusion cannot merge, so BOTH directions are
  asserted strictly: this test fails if the compiled hot loop ever makes
  the local kernel depend on the halo collective or vice versa.
"""

import numpy as np
import pytest

# ONE HLO grammar for all compiled-program tests: the dependence-cone
# analysis here and the CommAudit collective counting
# (tests/test_hlo_audit.py) share the parser in acg_tpu/obs/hlo.py, so
# "what overlaps" and "what is counted" are read from the same parse.
from acg_tpu.obs.hlo import parse_hlo as _parse_hlo

TAG = "acg.spmv.local"


def _tags(comps, comp, name, seen=None):
    """All op_name strings carried by an instruction, including every
    instruction inside its called computations (a fused or nested-loop op
    executes as one unit — a tag inside it is a tag on it)."""
    seen = seen if seen is not None else set()
    _, _, op_name, called = comps[comp][name][:4]
    out = {op_name} if op_name else set()
    for c in called:
        if c in comps and c not in seen:
            seen.add(c)
            for iname in comps[c]:
                if not iname.startswith("__"):
                    out |= _tags(comps, c, iname, seen)
    return out


def _defines_tag(comps, comp, name):
    """True when the instruction ITSELF is the tagged computation: its own
    op_name carries the tag, or it is a fusion/call whose called
    computation's ROOT op carries the tag.  (Merely CONTAINING a cloned
    cheap tagged op — e.g. a downstream fusion that duplicated a bitcast
    of the kernel output — does not count: consumers of the SpMV result
    legitimately depend on the halo too.)"""
    _, _, op_name, called = comps[comp][name][:4]
    if TAG in op_name:
        return True
    for c in called:
        root = comps.get(c, {}).get("__root__")
        if root and TAG in comps[c][root][2]:
            return True
    return False


def _cone(comps, comp, name):
    """Transitive operand cone of an instruction within its computation."""
    insts = comps[comp]
    out, stack = set(), [name]
    while stack:
        cur = stack.pop()
        if cur in out or cur not in insts:
            continue
        out.add(cur)
        stack.extend(insts[cur][1])
    return out


def _body_with_collectives(comps):
    """The (innermost) computations containing collective-permute ops."""
    return [c for c, insts in comps.items()
            if any(v[0] == "collective-permute" for v in insts.values())]


def _assert_halo_starts_independent(comps, body):
    insts = comps[body]
    permutes = [n for n, v in insts.items()
                if v[0] == "collective-permute"]
    assert permutes
    for p in permutes:
        cone = _cone(comps, body, p) - {p}
        tagged = [n for n in cone
                  if any(TAG in t for t in _tags(comps, body, n))]
        assert not tagged, (
            f"collective {p} depends on local SpMV ops {tagged[:3]} — "
            "halo serialized after SpMV")


def _assert_spmv_runs_during_halo(comps, body):
    insts = comps[body]
    spmv = [n for n in insts if not n.startswith("__")
            and _defines_tag(comps, body, n)]
    assert spmv, f"no '{TAG}'-defining compute in the while body " \
                 "(named_scope lost through compilation?)"
    for s in spmv:
        cone = _cone(comps, body, s) - {s}
        bad = [n for n in cone if insts[n][0] == "collective-permute"]
        assert not bad, (
            f"local SpMV op {s} depends on collectives {bad} — "
            "the compiled program serialized halo->SpMV")


def _lower_dist(ss, maxits=5):
    # the solver's own introspection hook (the object --explain audits)
    from acg_tpu.config import SolverOptions
    from acg_tpu.solvers.cg_dist import lowered_step

    return lowered_step(ss, options=SolverOptions(maxits=maxits,
                                                  residual_rtol=0.0,
                                                  residual_atol=0.0))


def test_halo_start_independent_xla_path():
    """XLA-formulation local SpMV: the collectives' operand cones must be
    SpMV-free (the halo can always start first).  The other direction is
    a known XLA:CPU fusion artifact — see module docstring."""
    from acg_tpu.solvers.cg_dist import build_sharded
    from acg_tpu.sparse import poisson3d_7pt

    A = poisson3d_7pt(8, dtype=np.float32)
    ss = build_sharded(A, nparts=8, dtype=np.float32)
    assert ss.local_fmt == "dia"
    comps = _parse_hlo(_lower_dist(ss).compile().as_text())
    bodies = _body_with_collectives(comps)
    assert bodies
    for body in bodies:
        _assert_halo_starts_independent(comps, body)


def test_overlap_preserved_fused_path(monkeypatch):
    """Production (fused Pallas) path: the compiled hot loop must keep
    the local kernel and the halo collective mutually independent — the
    structural precondition for the TPU latency-hiding scheduler to
    overlap them (ref split-phase schedule, acg/cgcuda.c:847-883)."""
    import importlib

    from acg_tpu.ops import pallas_kernels as pk
    from acg_tpu.solvers.cg_dist import build_sharded
    from acg_tpu.sparse import poisson3d_7pt

    cgd = importlib.import_module("acg_tpu.solvers.cg_dist")

    orig = pk.dia_matvec_pallas_2d_padded

    def interp(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setattr(pk, "dia_matvec_pallas_2d_padded", interp)
    monkeypatch.setitem(pk._SPMV_PROBE, "fused2d", True)
    # shards must be >= 2048 rows for the resident plan: 32^3/8 = 4096
    A = poisson3d_7pt(32, dtype=np.float32)
    ss = build_sharded(A, nparts=8, dtype=np.float32)
    assert cgd._dist_fused_plan(ss) is not None
    comps = _parse_hlo(_lower_dist(ss).compile().as_text())
    bodies = _body_with_collectives(comps)
    assert bodies
    for body in bodies:
        _assert_halo_starts_independent(comps, body)
        _assert_spmv_runs_during_halo(comps, body)
