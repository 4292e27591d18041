"""Matrix-free DeviceStencil operator tier (ISSUE 12).

Covers the acceptance matrix: recognition (and its rejections, with
reasons), matvec parity against the stored DIA tier (f64/f32/bf16,
batched), the interpret-mode Pallas kernels, probe-gated engagement
(fmt="auto" keeps the stored ladder unless the probe is green),
end-to-end cg / cg-pipelined bit-consistency with the dia tier at f64
(single-chip and 4-part CPU mesh), the zero operator stream +
vector-only roofline ceiling, the C13 matrix-free contract clause, and
the serve-session tier signature.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from acg_tpu.config import SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.ops.dia import DeviceDia, DiaMatrix
from acg_tpu.ops.stencil import (DeviceStencil, recognize_stencil,
                                 stencil_matvec, try_device_stencil,
                                 _probe_stencil_group, _probe_stpipe_group)
from acg_tpu.solvers.cg import build_device_operator, cg, cg_pipelined
from acg_tpu.sparse import poisson2d_5pt, poisson3d_7pt
from acg_tpu.sparse.csr import manufactured_rhs
from acg_tpu.sparse.poisson import (grid_partition_vector, poisson3d_27pt,
                                    poisson3d_7pt_dia,
                                    poisson3d_7pt_varcoef, random_spd)

OPTS = SolverOptions(maxits=800, residual_rtol=1e-10)


# -- recognition ------------------------------------------------------------


def test_recognize_poisson_family():
    spec, why = recognize_stencil(poisson3d_7pt(6))
    assert spec is not None, why
    assert spec.grid == (6, 6, 6)
    assert spec.offsets == (-36, -6, -1, 0, 1, 6, 36)
    assert sorted(spec.coeffs) == [-1.0] * 6 + [6.0]

    spec2, _ = recognize_stencil(poisson2d_5pt(9))
    assert spec2 is not None and spec2.grid == (9, 9)

    spec27, _ = recognize_stencil(poisson3d_27pt(5))
    assert spec27 is not None and spec27.grid == (5, 5, 5)
    assert len(spec27.offsets) == 27


def test_recognize_dia_form_matches_csr_form():
    s1, _ = recognize_stencil(poisson3d_7pt_dia(6))
    s2, _ = recognize_stencil(poisson3d_7pt(6))
    assert s1 == s2
    assert s1.spec_hash() == s2.spec_hash()


def test_recognize_rejections_carry_reasons():
    spec, why = recognize_stencil(poisson3d_7pt_varcoef(5))
    assert spec is None and "not uniform" in why
    spec, why = recognize_stencil(random_spd(256))
    assert spec is None and why

    # one perturbed interior entry breaks the uniformity/pattern proof
    A = poisson3d_7pt(5)
    vals = A.vals.copy()
    off_diag = np.flatnonzero(vals < 0)
    vals[off_diag[len(off_diag) // 2]] = -1.5
    import dataclasses

    Abad = dataclasses.replace(A, vals=vals)
    spec, why = recognize_stencil(Abad)
    assert spec is None and why


def test_recognize_non_square_rejected():
    from acg_tpu.sparse.csr import coo_to_csr

    A = coo_to_csr(np.array([0, 1]), np.array([0, 1]),
                   np.array([1.0, 1.0]), 2, 3)
    spec, why = recognize_stencil(A)
    assert spec is None and "square" in why


# -- matvec parity ----------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float64, np.float32, jnp.bfloat16])
def test_matvec_parity_vs_dia(dtype):
    """The jnp grid-shift action is BIT-identical to the stored DIA
    tier's shift action: same per-element products, same summation
    order, at every vector dtype."""
    A = poisson3d_7pt(6)
    dev_d = build_device_operator(A, dtype=dtype, fmt="dia")
    dev_s = build_device_operator(A, dtype=dtype, fmt="stencil")
    assert isinstance(dev_s, DeviceStencil)
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.pad(rng.standard_normal(A.nrows),
                           (0, dev_d.nrows_padded - A.nrows))).astype(
        jnp.dtype(dtype) if dtype is jnp.bfloat16 else dtype)
    yd = np.asarray(dev_d.matvec(x), dtype=np.float64)
    ys = np.asarray(dev_s.matvec(x), dtype=np.float64)
    assert np.array_equal(yd, ys)


def test_matvec_parity_batched():
    A = poisson2d_5pt(11)
    dev_d = build_device_operator(A, dtype=np.float64, fmt="dia")
    dev_s = build_device_operator(A, dtype=np.float64, fmt="stencil")
    rng = np.random.default_rng(1)
    xb = jnp.asarray(np.pad(rng.standard_normal((4, A.nrows)),
                            ((0, 0), (0, dev_d.nrows_padded - A.nrows))))
    assert np.array_equal(np.asarray(dev_d.matvec(xb)),
                          np.asarray(dev_s.matvec(xb)))


def test_padded_region_stays_zero():
    A = poisson2d_5pt(5)          # 25 rows -> padded to 32
    dev = build_device_operator(A, dtype=np.float64, fmt="stencil")
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        dev.nrows_padded))
    y = np.asarray(dev.matvec(x))
    assert np.all(y[A.nrows:] == 0.0)


# -- Pallas kernels (interpret mode) ---------------------------------------


def test_stencil_kernel_interpret():
    assert _probe_stencil_group(interpret=True)


def test_stencil_pipe_kernel_interpret():
    assert _probe_stpipe_group(interpret=True)


def test_interpret_matvec_routing():
    """A lane-aligned interpret-forced DeviceStencil routes matvec
    through the Pallas kernel and matches the jnp form."""
    A = poisson3d_7pt(16)          # 4096 rows: lane-aligned
    dev_i = DeviceStencil.from_matrix(A, dtype=np.float32,
                                      interpret=True)
    dev_j = DeviceStencil.from_matrix(A, dtype=np.float32)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(dev_i.nrows_padded)
                    .astype(np.float32))
    yi = np.asarray(dev_i.matvec(x))
    yj = np.asarray(dev_j.matvec(x))
    scale = np.abs(yj).max() or 1.0
    assert np.abs(yi - yj).max() < 1e-5 * scale


# -- probe-gated engagement -------------------------------------------------


def test_auto_stays_stored_without_probe():
    """On the CPU test backend the stencil probe is red: fmt="auto"
    must keep the stored ladder exactly as before."""
    dev = build_device_operator(poisson3d_7pt(6), dtype=np.float64)
    assert isinstance(dev, DeviceDia)


def test_auto_prefers_dia_kernel_past_stencil_vmem(monkeypatch):
    """Past the stencil kernel's VMEM bound auto takes the stored DIA
    tier when ITS Pallas kernel plans (256³ f32 on the chip: the HBM-ring
    kernel, not the XLA grid-shift form); with the bound back, the
    matrix-free tier wins again."""
    from acg_tpu.ops import pallas_kernels as pk
    from acg_tpu.ops import stencil as st

    monkeypatch.setitem(pk._SPMV_PROBE, "stencil2d", True)
    monkeypatch.setitem(pk._SPMV_PROBE, "fused2d", True)
    A = poisson3d_7pt(16, dtype=np.float32)
    assert isinstance(build_device_operator(A, dtype=np.float32),
                      DeviceStencil)
    monkeypatch.setattr(st, "_VMEM_BUDGET", 1024)
    dev = build_device_operator(A, dtype=np.float32)
    assert isinstance(dev, DeviceDia)


def test_auto_engages_with_probe(monkeypatch):
    from acg_tpu.ops import pallas_kernels as pk

    monkeypatch.setitem(pk._SPMV_PROBE, "stencil2d", True)
    dev = build_device_operator(poisson3d_7pt(6), dtype=np.float64)
    assert isinstance(dev, DeviceStencil)
    # a NON-stencil system under the same green probe keeps its tier
    dev2 = build_device_operator(poisson3d_7pt_varcoef(5),
                                 dtype=np.float64)
    assert not isinstance(dev2, DeviceStencil)


def test_forced_stencil_errors_on_non_stencil():
    with pytest.raises(AcgError) as e:
        build_device_operator(poisson3d_7pt_varcoef(5),
                              dtype=np.float64, fmt="stencil")
    assert e.value.status == Status.ERR_NOT_SUPPORTED


# -- end-to-end single chip -------------------------------------------------


def test_cg_consistent_with_dia_f64():
    A = poisson3d_7pt(10)
    _, b = manufactured_rhs(A, seed=0)
    r_d = cg(A, b, options=OPTS, fmt="dia")
    r_s = cg(A, b, options=OPTS, fmt="stencil")
    assert r_s.converged
    assert r_s.niterations == r_d.niterations
    # two programs (in-register coefficients vs streamed bands): XLA may
    # order the 7-term sum differently, so they agree to rounding, not
    # bit for bit
    np.testing.assert_allclose(r_s.x, r_d.x, rtol=1e-12,
                               atol=1e-14 * np.abs(r_d.x).max())
    assert r_s.operator_format == "stencil"
    assert r_s.kernel == "xla-gridshift"
    # certified true residual
    rres = np.linalg.norm(b - A.matvec(r_s.x)) / np.linalg.norm(b)
    assert rres < 1e-9


def test_cg_pipelined_consistent_with_dia_f64():
    A = poisson3d_7pt(10)
    _, b = manufactured_rhs(A, seed=1)
    r_d = cg_pipelined(A, b, options=OPTS, fmt="dia")
    r_s = cg_pipelined(A, b, options=OPTS, fmt="stencil")
    assert r_s.converged
    assert r_s.niterations == r_d.niterations
    # agreement to rounding, not bits: see the classic test above
    np.testing.assert_allclose(r_s.x, r_d.x, rtol=1e-10,
                               atol=1e-12 * np.abs(r_d.x).max())
    assert "stpipe2d disengaged" in r_s.kernel_note


def test_cg_batched_stencil():
    A = poisson2d_5pt(12)
    _, b = manufactured_rhs(A, seed=2)
    B = np.stack([b, 2.0 * b, -b])
    r = cg(A, B, options=OPTS, fmt="stencil")
    r_seq = cg(A, b, options=OPTS, fmt="stencil")
    assert r.nrhs == 3
    assert np.all(r.converged_per_system)
    # batched vs sequential equivalence at the repo's pinned tolerance
    # (tests/test_batched.py discipline: the reductions batch over the
    # last axis, not bit-for-bit vs 1-D vdot)
    np.testing.assert_allclose(r.x[0], r_seq.x, rtol=1e-12)


def test_cg_pipelined_interpret_megakernel():
    """End-to-end pipelined solve through the matrix-free single-kernel
    iteration (interpret mode) — engages, reports pallas-stpipe2d, and
    agrees with the jnp-path solve."""
    A = poisson3d_7pt(16)
    _, b = manufactured_rhs(A, seed=3)
    b32 = b.astype(np.float32)
    opts = SolverOptions(maxits=80, residual_rtol=1e-5)
    dev_i = DeviceStencil.from_matrix(A, dtype=np.float32,
                                      interpret=True)
    r_i = cg_pipelined(dev_i, b32, options=opts, dtype=np.float32)
    r_j = cg_pipelined(A, b32, options=opts, dtype=np.float32,
                       fmt="stencil")
    assert r_i.converged and r_j.converged
    assert r_i.kernel == "pallas-stpipe2d"
    assert r_i.kernel_note == ""
    scale = np.abs(r_j.x).max()
    assert np.abs(r_i.x - r_j.x).max() < 1e-4 * scale


def test_cg_classic_interpret_kernel():
    A = poisson3d_7pt(16)
    _, b = manufactured_rhs(A, seed=4)
    b32 = b.astype(np.float32)
    opts = SolverOptions(maxits=80, residual_rtol=1e-5)
    dev_i = DeviceStencil.from_matrix(A, dtype=np.float32,
                                      interpret=True)
    r = cg(dev_i, b32, options=opts, dtype=np.float32)
    assert r.converged
    assert r.kernel == "pallas-stencil"


# -- roofline: the vector-only ceiling -------------------------------------


def test_operator_stream_bytes_zero():
    dev = build_device_operator(poisson3d_7pt(8), dtype=np.float32,
                                fmt="stencil")
    assert dev.operator_stream_bytes() == 0
    assert dev.mat_itemsize == 0


def test_roofline_vector_only_ceiling():
    from acg_tpu.obs.roofline import roofline_for_operator

    A = poisson3d_7pt_dia(32, dtype=np.float32)
    dev_s = build_device_operator(A, dtype=np.float32, fmt="stencil")
    dev_d = build_device_operator(A, dtype=np.float32, fmt="dia")
    m_s = roofline_for_operator(dev_s, solver="cg-pipelined",
                                device_kind="TPU v5e")
    m_d = roofline_for_operator(dev_d, solver="cg-pipelined",
                                device_kind="TPU v5e")
    assert m_s.operator_format == "stencil"
    assert m_s.operator_bytes == 0
    assert m_s.vector_bytes == m_d.vector_bytes    # same stream model
    # the ceiling multiplies by exactly the old (bands+vectors):vectors
    # ratio — the deleted-band-stream claim as arithmetic
    assert m_s.predicted_iters_per_sec > m_d.predicted_iters_per_sec
    ratio = m_d.bytes_per_iter / m_s.bytes_per_iter
    assert ratio == pytest.approx(
        1.0 + m_d.operator_bytes / m_d.vector_bytes)


def test_roofline_sharded_interface_only():
    from acg_tpu.obs.roofline import roofline_for_sharded
    from acg_tpu.solvers.cg_dist import build_sharded

    A = poisson2d_5pt(16)
    part = grid_partition_vector((16, 16), (4, 1))
    ss = build_sharded(A, part=part, nparts=4, fmt="stencil")
    m = roofline_for_sharded(ss, device_kind="TPU v5e")
    assert m.operator_format == "stencil"
    # the local operator streams nothing; only the tiny interface
    # operator (a stored operator by design) remains — here row slabs,
    # so the border-row runs the shard program reads
    assert ss.iface_fmt == "runs"
    assert m.operator_bytes == sum(int(a.nbytes)
                                   for a in ss.iface_op_arrays())


# -- distributed ------------------------------------------------------------


def test_dist_stencil_bit_consistent_with_dia():
    from acg_tpu.solvers.cg_dist import (build_sharded, cg_dist,
                                         cg_pipelined_dist)

    A = poisson2d_5pt(16)
    _, b = manufactured_rhs(A, seed=5)
    part = grid_partition_vector((16, 16), (4, 1))
    ss_s = build_sharded(A, part=part, nparts=4, fmt="stencil")
    ss_d = build_sharded(A, part=part, nparts=4, fmt="dia")
    assert ss_s.local_fmt == "stencil"
    assert ss_s.local_op_arrays() == ()
    r_s = cg_dist(ss_s, b, options=OPTS)
    r_d = cg_dist(ss_d, b, options=OPTS)
    assert r_s.converged
    assert r_s.niterations == r_d.niterations
    assert np.array_equal(r_s.x, r_d.x)
    assert r_s.operator_format == "stencil"
    rp_s = cg_pipelined_dist(ss_s, b, options=OPTS)
    rp_d = cg_pipelined_dist(ss_d, b, options=OPTS)
    assert rp_s.converged
    assert np.array_equal(rp_s.x, rp_d.x)


def test_dist_stencil_batched():
    from acg_tpu.solvers.cg_dist import build_sharded, cg_dist

    A = poisson2d_5pt(12)
    _, b = manufactured_rhs(A, seed=6)
    part = grid_partition_vector((12, 12), (4, 1))
    ss = build_sharded(A, part=part, nparts=4, fmt="stencil")
    r = cg_dist(ss, np.stack([b, 0.5 * b]), options=OPTS)
    assert r.nrhs == 2 and np.all(r.converged_per_system)


def test_dist_tier_report_records_verdict():
    from acg_tpu.solvers.cg_dist import build_sharded

    A = poisson2d_5pt(16)
    part = grid_partition_vector((16, 16), (4, 1))
    # recognized: auto resolution stays stored on CPU (probe red), but
    # the report records the verdict and the TPU tier (probe green
    # there) is the matrix-free one
    tier = {}
    ss = build_sharded(A, part=part, nparts=4, fmt="auto",
                       tier_report=tier)
    assert ss.local_fmt == "dia"
    assert tier["stencil"]["recognized"] is True
    assert tier["stencil"]["structure_hash"]
    assert tier["tpu_fmt"] == "stencil"
    from acg_tpu.parallel.sharded import tier_kernel_name

    assert tier_kernel_name(tier, ss.ps, np.float64) == "pallas-stencil"
    # NOT recognized (scattered partition): the report says why
    tier2 = {}
    build_sharded(A, nparts=4, partition_method="multilevel",
                  fmt="auto", tier_report=tier2)
    assert tier2["stencil"]["recognized"] is False
    assert tier2["stencil"]["reason"]
    assert tier2["tpu_fmt"] != "stencil"


def test_dist_forced_stencil_errors_on_scattered_partition():
    from acg_tpu.solvers.cg_dist import build_sharded

    A = poisson2d_5pt(16)
    with pytest.raises(AcgError) as e:
        build_sharded(A, nparts=4, partition_method="multilevel",
                      fmt="stencil")
    assert e.value.status == Status.ERR_NOT_SUPPORTED


def test_dist_stencil_interpret_engages_auto():
    from acg_tpu.solvers.cg_dist import build_sharded

    A = poisson2d_5pt(16)
    part = grid_partition_vector((16, 16), (4, 1))
    ss = build_sharded(A, part=part, nparts=4, fmt="auto",
                       stencil_interpret=True)
    assert ss.local_fmt == "stencil"
    assert ss.st_interpret


def test_dist_uneven_slabs_rejected():
    """Unequal sub-grids cannot share one SPMD spec — the forced tier
    errors with the parts-disagree reason."""
    from acg_tpu.solvers.cg_dist import build_sharded

    A = poisson2d_5pt(10)
    part = grid_partition_vector((10, 10), (4, 1))    # 3/3/2/2 slabs
    with pytest.raises(AcgError):
        build_sharded(A, part=part, nparts=4, fmt="stencil")


# -- the C13 matrix-free contract clause ------------------------------------


def test_verify_matrix_free_single_chip():
    from acg_tpu.analysis.contracts import verify_matrix_free
    from acg_tpu.obs.hlo import while_body_param_leaves
    from acg_tpu.solvers.cg import compile_step

    A = poisson2d_5pt(12)
    opts = SolverOptions(maxits=5, residual_rtol=1e-9)
    dev_s = build_device_operator(A, dtype=np.float32, fmt="stencil")
    dev_d = build_device_operator(A, dtype=np.float32, fmt="dia")
    b = np.ones(A.nrows)
    txt_s = compile_step(dev_s, b, options=opts).as_text()
    txt_d = compile_step(dev_d, b, options=opts).as_text()
    band_dims = (tuple(dev_d.bands.shape),)
    assert verify_matrix_free(txt_s, txt_d,
                              dev_d.operator_stream_bytes(),
                              band_dims=band_dims) == []
    # the stored program's while body carries the band stack (possibly
    # re-laid-out by the compiler — per-diagonal slices on XLA:CPU), the
    # matrix-free body does not: the byte delta is at least the stream
    pb_d = sum(b_ for _, _, b_ in while_body_param_leaves(txt_d))
    pb_s = sum(b_ for _, _, b_ in while_body_param_leaves(txt_s))
    assert pb_d - pb_s >= dev_d.operator_stream_bytes()


def test_verify_matrix_free_catches_stored_program():
    """Seeded-mutation style: handing the checker a stored-tier program
    as the 'matrix-free' one fires C13 on both the band-dims clause and
    the byte-delta clause."""
    from acg_tpu.analysis.contracts import verify_matrix_free
    from acg_tpu.solvers.cg import compile_step

    A = poisson2d_5pt(12)
    opts = SolverOptions(maxits=5, residual_rtol=1e-9)
    dev_d = build_device_operator(A, dtype=np.float32, fmt="dia")
    txt_d = compile_step(dev_d, np.ones(A.nrows), options=opts).as_text()
    viols = verify_matrix_free(txt_d, txt_d,
                               dev_d.operator_stream_bytes(),
                               band_dims=(tuple(dev_d.bands.shape),))
    assert viols and all(v.rule == "C13" for v in viols)


def test_registry_fast_includes_stencil_case():
    from acg_tpu.analysis.registry import registry_cases

    fast = registry_cases(fast=True)
    st = [c for c in fast if c.fmt == "stencil" and c.precond == "none"]
    assert len(st) == 1 and st[0].nparts == 1
    full = registry_cases(fast=False)
    st_full = [c for c in full
               if c.fmt == "stencil" and c.precond == "none"]
    assert len(st_full) == 16
    assert {c.nparts for c in st_full} == {1, 4}
    assert {c.solver for c in st_full} == {"cg", "cg-pipelined"}


# -- serve: the tier is part of the executable signature --------------------


def test_session_signature_distinguishes_tier():
    from acg_tpu.serve.session import Session

    A = poisson3d_7pt(8)
    opts = SolverOptions(maxits=300, residual_rtol=1e-9)
    s_st = Session(A, options=opts, fmt="stencil", prep_cache=None,
                   share_prepared=False)
    s_di = Session(A, options=opts, fmt="dia", prep_cache=None,
                   share_prepared=False)
    sig_st = s_st._signature("cg", 1, opts)
    sig_di = s_di._signature("cg", 1, opts)
    assert sig_st != sig_di
    assert "stencil" in sig_st and "dia" in sig_di
    _, b = manufactured_rhs(A, seed=7)
    r1 = s_st.solve(b)
    r2 = s_st.solve(2.0 * b)
    assert r1.converged and r2.converged
    assert r1.operator_format == "stencil"
    assert s_st.counters["executable"] == {
        "hits": 1, "misses": 1,
        "compile_seconds": s_st.counters["executable"]["compile_seconds"]}
    assert np.array_equal(r2.x, 2.0 * r1.x) or np.allclose(
        r2.x, 2.0 * r1.x, rtol=1e-12)


def test_session_dist_stencil():
    from acg_tpu.serve.session import Session

    A = poisson3d_7pt(8)
    part = grid_partition_vector((8, 8, 8), (4, 1, 1))
    opts = SolverOptions(maxits=300, residual_rtol=1e-9)
    s = Session(A, options=opts, nparts=4, part=part, fmt="stencil",
                prep_cache=None, share_prepared=False)
    _, b = manufactured_rhs(A, seed=8)
    r = s.solve(b, solver="cg-pipelined")
    assert r.converged and r.operator_format == "stencil"
