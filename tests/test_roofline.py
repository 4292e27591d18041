"""Analytic roofline model (acg_tpu/obs/roofline.py): traffic math,
chip-table resolution, batched scaling, and the sharded variant."""

import numpy as np
import pytest

from acg_tpu.obs.roofline import (CHIP_HBM_GBPS,
                                  RooflineModel, hbm_gbps_for,
                                  roofline_for_operator,
                                  roofline_for_sharded)
from acg_tpu.errors import AcgError, Status
from acg_tpu.solvers.base import _cg_blas1_bytes
from acg_tpu.sparse import poisson2d_5pt, poisson3d_7pt


def _dia_dev(n=16, dtype=np.float64):
    from acg_tpu.ops.dia import DeviceDia, DiaMatrix

    A = poisson2d_5pt(n, dtype=dtype)
    return A, DeviceDia.from_dia(DiaMatrix.from_csr(A), dtype=dtype,
                                 mat_dtype="auto")


def test_hbm_gbps_resolution():
    assert hbm_gbps_for("TPU v5e") == CHIP_HBM_GBPS["TPU v5e"]
    assert hbm_gbps_for("TPU v5p") == CHIP_HBM_GBPS["TPU v5p"]
    # longest-substring match: "TPU v5 lite" must NOT hit "TPU v5"
    assert hbm_gbps_for("TPU v5 lite") == CHIP_HBM_GBPS["TPU v5 lite"]
    # an explicit override always wins
    assert hbm_gbps_for("TPU v5e", override=100.0) == 100.0
    assert hbm_gbps_for("cpu", override=100.0) == 100.0


@pytest.mark.parametrize("kind", ["cpu", None, "TPU v9x"])
def test_hbm_gbps_unknown_kind_raises(kind):
    # no default peak: a kind without a published figure is an error
    with pytest.raises(AcgError) as e:
        hbm_gbps_for(kind)
    assert e.value.status == Status.ERR_NOT_SUPPORTED


def test_roofline_off_chip_is_not_measured():
    _, dev = _dia_dev()
    m = roofline_for_operator(dev, solver="cg")     # CPU: no device kind
    assert m.hbm_gbps is None and m.device_kind is None
    assert m.as_dict()["predicted_iters_per_sec"] is None
    assert "predicted ceiling: not measured" in m.report()
    assert m.frac(100.0) != m.frac(100.0)           # NaN


def test_dia_operator_bytes_at_storage_width():
    _, dev = _dia_dev()
    # Poisson bands narrow losslessly to bf16 under mat_dtype="auto":
    # the operator stream is priced at the ACTUAL 2 B/value width
    assert dev.bands.dtype == np.dtype("bfloat16").newbyteorder("=") \
        or dev.bands.dtype.itemsize == 2
    assert dev.operator_stream_bytes() == dev.bands.size * 2


def test_roofline_model_math_classic_dia():
    _, dev = _dia_dev()
    m = roofline_for_operator(dev, solver="cg", hbm_gbps=819.0)
    n = dev.nrows_padded
    vb = np.dtype(dev.vec_dtype).itemsize
    expect_vec = 2 * n * vb + _cg_blas1_bytes(n, vb, False)
    assert m.operator_bytes == dev.operator_stream_bytes()
    assert m.vector_bytes == expect_vec
    assert m.bytes_per_iter == m.operator_bytes + m.vector_bytes
    assert m.predicted_iters_per_sec == pytest.approx(
        819.0e9 / m.bytes_per_iter)
    assert m.operator_format == "dia"


def test_roofline_pipelined_uses_pipelined_blas1_model():
    _, dev = _dia_dev()
    mc = roofline_for_operator(dev, solver="cg", hbm_gbps=819.0)
    mp = roofline_for_operator(dev, solver="cg-pipelined",
                               hbm_gbps=819.0)
    n, vb = dev.nrows_padded, np.dtype(dev.vec_dtype).itemsize
    assert mp.vector_bytes - mc.vector_bytes == \
        _cg_blas1_bytes(n, vb, True) - _cg_blas1_bytes(n, vb, False)


def test_roofline_batched_scales_vectors_not_operator():
    _, dev = _dia_dev()
    m1 = roofline_for_operator(dev, nrhs=1, hbm_gbps=819.0)
    m8 = roofline_for_operator(dev, nrhs=8, hbm_gbps=819.0)
    assert m8.operator_bytes == m1.operator_bytes
    assert m8.vector_bytes == 8 * m1.vector_bytes
    # the batching win: 8× the work for < 8× the bytes
    assert m8.bytes_per_iter < 8 * m1.bytes_per_iter


def test_roofline_frac():
    _, dev = _dia_dev()
    m = roofline_for_operator(dev, hbm_gbps=819.0)
    assert m.frac(m.predicted_iters_per_sec) == pytest.approx(1.0)
    assert m.frac(m.predicted_iters_per_sec / 2) == pytest.approx(0.5)
    assert np.isnan(m.frac(float("nan")))


def test_roofline_ell_charges_index_stream():
    from acg_tpu.ops.spmv import DeviceEll
    from acg_tpu.sparse import random_spd
    from acg_tpu.sparse.ell import EllMatrix

    A = random_spd(256, degree=4, dtype=np.float64)
    dev = DeviceEll.from_ell(EllMatrix.from_csr(A), dtype=np.float64,
                             mat_dtype=None)
    expect = (dev.vals.size * dev.vals.dtype.itemsize
              + dev.colidx.size * dev.colidx.dtype.itemsize)
    assert dev.operator_stream_bytes() == expect
    m = roofline_for_operator(dev, hbm_gbps=819.0)
    assert m.operator_format == "ell"
    assert m.operator_bytes == expect
    # gather family: 3 SpMV vector streams vs DIA's 2
    n, vb = dev.nrows_padded, np.dtype(dev.vec_dtype).itemsize
    assert m.vector_bytes == 3 * n * vb + _cg_blas1_bytes(n, vb, False)


def test_roofline_sharded():
    from acg_tpu.solvers.cg_dist import build_sharded

    A = poisson2d_5pt(12, dtype=np.float64)
    ss = build_sharded(A, nparts=4)
    m = roofline_for_sharded(ss, hbm_gbps=819.0)
    assert m.nparts == 4
    assert m.operator_bytes > 0
    # the mesh streams in parallel: the ceiling scales by nparts
    assert m.predicted_iters_per_sec == pytest.approx(
        4 * 819.0e9 / m.bytes_per_iter)
    assert m.as_dict()["nparts"] == 4


def test_roofline_report_and_dict():
    import json

    _, dev = _dia_dev()
    m = roofline_for_operator(dev, nrhs=4, hbm_gbps=819.0,
                              device_kind="TPU v5e")
    rep = m.report()
    assert "predicted ceiling" in rep
    assert "nrhs=4" in rep
    assert "819" in rep
    d = json.loads(json.dumps(m.as_dict()))
    assert d["bytes_per_iter"] == m.bytes_per_iter
    assert d["predicted_iters_per_sec"] == pytest.approx(
        m.predicted_iters_per_sec)
    assert d["device_kind"] == "TPU v5e"


def test_base_byte_models_nrhs_scaling():
    """The shared byte models (solvers/base.py) scale only the vector
    half with nrhs — operator stream read once for all systems."""
    from acg_tpu.solvers.base import (cg_bytes_per_iter,
                                      cg_bytes_per_iter_dia)

    one = cg_bytes_per_iter(1000, 100, val_bytes=4)
    four = cg_bytes_per_iter(1000, 100, val_bytes=4, nrhs=4)
    operator = 1000 * (4 + 4)
    assert four - operator == 4 * (one - operator)

    one = cg_bytes_per_iter_dia(7, 100, val_bytes=4, mat_bytes=2)
    four = cg_bytes_per_iter_dia(7, 100, val_bytes=4, mat_bytes=2,
                                 nrhs=4)
    operator = 7 * 100 * 2
    assert four - operator == 4 * (one - operator)
