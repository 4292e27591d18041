"""HPCG's multigrid preconditioner (acg_tpu/ops/mg.py) against the plain
float64 reference (acg_tpu/solvers/mg_host.py), on the CPU: the V-cycle,
the symmetry of M, the preconditioned CG loop, the compiled program,
the normal entry points, and every combination that must refuse."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acg_tpu.config import SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.obs.hlo import while_body_profile
from acg_tpu.ops.dia import DiaMatrix
from acg_tpu.ops.mg import mg_plan, vcycle
from acg_tpu.ops.stencil import recognize_stencil
from acg_tpu.solvers.cg import cg, lowered_step
from acg_tpu.solvers.mg_host import (pcg_host, stencil_apply, symgs_host,
                                     symgs_host_lexicographic, vcycle_host)
from acg_tpu.sparse import (poisson2d_5pt, poisson3d_7pt,
                            poisson3d_7pt_varcoef, poisson3d_27pt,
                            poisson3d_27pt_dia)

from test_trace_layers import _layers_of_while_bodies

GEN = {7: poisson3d_7pt, 27: poisson3d_27pt}
MG = SolverOptions(maxits=500, residual_rtol=1e-5, precond="mg")


@functools.lru_cache(maxsize=None)
def _plan(points: int, grid: tuple, levels: int = 4):
    spec, why = recognize_stencil(GEN[points](*grid))
    assert spec is not None, why
    return mg_plan(spec, levels)


@functools.lru_cache(maxsize=None)
def _device_vcycle(points: int, grid: tuple, dtype: str):
    plan = _plan(points, grid)
    return jax.jit(lambda r: vcycle(plan, r.astype(dtype)))


def _rhs(grid, seed=0):
    return np.random.default_rng(seed).standard_normal(grid)


@pytest.mark.parametrize("points, grid", [(7, (16, 16, 16)),
                                          (27, (16, 24, 32))])
@pytest.mark.parametrize("dtype, tol", [("float64", 1e-12),
                                        ("float32", 1e-5)])
def test_vcycle_matches_the_host_reference(points, grid, dtype, tol):
    """z = M r of the device V-cycle against vcycle_host in float64:
    equal to rounding in float64, within float32's accumulated rounding
    through 4 levels in float32."""
    plan = _plan(points, grid)
    r = _rhs(grid)
    want = vcycle_host(r, plan.arms, plan.levels)
    got = np.asarray(_device_vcycle(points, grid, dtype)(jnp.asarray(r)))
    assert got.dtype == np.dtype(dtype)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= tol


@pytest.mark.parametrize("points, grid", [(7, (16, 16, 16)),
                                          (27, (16, 24, 32))])
def test_the_preconditioner_is_symmetric(points, grid):
    """r'(M s) = s'(M r) to 1e-10 relative in float64: the colour sweep
    runs forward then backward, so M is symmetric and CG may use it."""
    f = _device_vcycle(points, grid, "float64")
    r, s = _rhs(grid, 1), _rhs(grid, 2)
    rms = float(np.sum(r * np.asarray(f(jnp.asarray(s)))))
    smr = float(np.sum(s * np.asarray(f(jnp.asarray(r)))))
    assert abs(rms - smr) <= 1e-10 * abs(rms)
    assert float(np.sum(r * np.asarray(f(jnp.asarray(r))))) > 0


@pytest.mark.parametrize("points, grid, dtype, slack", [
    (7, (16, 24, 32), np.float64, 0), (7, (16, 24, 32), np.float32, 1),
    (27, (16, 16, 16), np.float64, 0), (27, (16, 16, 16), np.float32, 1)])
def test_mg_pcg_takes_the_host_iterations(points, grid, dtype, slack):
    """cg with precond='mg' takes pcg_host's iterations over vcycle_host
    (equal in float64, within one in float32), reports the
    preconditioner in its path, and its x meets the tolerance by the
    true residual."""
    A = GEN[points](*grid, dtype=dtype)
    b = _rhs(grid).reshape(-1)
    rtol = 1e-8 if dtype == np.float64 else 1e-5
    o = SolverOptions(maxits=500, residual_rtol=rtol, precond="mg")
    res = cg(A, b, options=o, dtype=dtype)
    plan = _plan(points, grid)
    _x, k = pcg_host(
        lambda v: stencil_apply(v.reshape(grid), plan.arms).reshape(-1),
        lambda v: vcycle_host(v.reshape(grid), plan.arms,
                              plan.levels).reshape(-1), b, rtol, 500)
    assert res.converged and abs(res.niterations - k) <= slack
    assert res.precond == "mg4 symgs8c" and res.path[-1] == "mg4 symgs8c"
    true = np.linalg.norm(b - A.matvec(np.asarray(res.x, np.float64)))
    assert true <= 2 * rtol * np.linalg.norm(b)
    # the preconditioner earns its place: plain CG takes far more
    plain = cg(A, b, options=SolverOptions(maxits=500, residual_rtol=rtol),
               dtype=dtype)
    assert plain.precond == "" and plain.niterations > 2 * res.niterations


def test_lexicographic_and_colour_orders_take_similar_counts():
    """HPCG's own lexicographic SymGS and the 8-colour sweep at 32³
    (27-point, rtol 1e-5): the orders differ, the counts stay within
    half of each other (recorded in PERF.md)."""
    grid = (32, 32, 32)
    plan = _plan(27, grid)
    b = _rhs(grid).reshape(-1)

    def counts(symgs):
        return pcg_host(
            lambda v: stencil_apply(v.reshape(grid), plan.arms).reshape(-1),
            lambda v: vcycle_host(v.reshape(grid), plan.arms, plan.levels,
                                  symgs).reshape(-1), b, 1e-5, 500)[1]

    colour = counts(symgs_host)
    lexi = counts(symgs_host_lexicographic)
    assert abs(lexi - colour) <= 0.5 * colour


def _mg_text(fmt: str) -> str:
    A = poisson3d_7pt(16, dtype=np.float32)
    return lowered_step(A, np.ones(A.nrows, np.float32), options=MG,
                        dtype=np.float32, fmt=fmt).compile().as_text()


@pytest.fixture(scope="module")
def mg_texts():
    return {fmt: _mg_text(fmt) for fmt in ("auto", "stencil")}


@pytest.mark.parametrize("fmt", ["auto", "stencil"])
def test_mg_loop_has_no_gather_or_scatter(mg_texts, fmt):
    """The MG-PCG while body (7-point 16³, on the stored-band and the
    matrix-free tier) holds no gather and no scatter: injection is a
    strided slice, prolongation an add into a colour part, each colour
    update an in-place dynamic-update-slice."""
    prof = while_body_profile(mg_texts[fmt])
    assert prof.gathers == 0 and prof.scatters == 0


@pytest.mark.parametrize("fmt, ops", [("auto", 168), ("stencil", 202)])
def test_unpreconditioned_loop_is_unchanged(fmt, ops):
    """With precond='none' the loop has no acg.precond op and the op
    count the loop had before the preconditioner existed (7-point 16³,
    float32 vectors, 64-bit mode on as in every test)."""
    A = poisson3d_7pt(16, dtype=np.float32)
    txt = lowered_step(A, np.ones(A.nrows, np.float32),
                       options=SolverOptions(maxits=5, residual_rtol=1e-6),
                       dtype=np.float32, fmt=fmt).compile().as_text()
    assert "acg.precond" not in txt
    assert sum(while_body_profile(txt).op_counts.values()) == ops


@pytest.mark.parametrize("fmt", ["auto", "stencil"])
def test_every_mg_loop_op_carries_a_layer_scope(mg_texts, fmt):
    layers, unscoped = _layers_of_while_bodies(mg_texts[fmt])
    assert not unscoped, f"ops with no acg.* scope: {unscoped}"
    assert {"acg.spmv", "acg.blas1", "acg.check", "acg.precond",
            "acg.mg.smooth", "acg.mg.residual", "acg.mg.restrict",
            "acg.mg.prolong"} <= layers


def _refusals():
    cg_mod = importlib.import_module("acg_tpu.solvers.cg")
    dist_mod = importlib.import_module("acg_tpu.solvers.cg_dist")

    A = poisson3d_7pt(16)
    b = np.ones(A.nrows)
    deep = SolverOptions(maxits=50, precond="mg", pipeline_depth=2)
    sstep = SolverOptions(maxits=50, precond="mg", sstep=4)
    return {
        "pipelined": lambda: cg_mod.cg_pipelined(A, b, options=MG),
        "pipelined aot": lambda: cg_mod.aot_step(
            A, b, options=MG, solver="cg-pipelined"),
        "sstep": lambda: cg_mod.cg_sstep(A, b, options=sstep),
        "deep": lambda: cg_mod.cg_pipelined_deep(A, b, options=deep),
        "dist": lambda: dist_mod.cg_dist(A, b, options=MG, nparts=2),
        "dist lowered": lambda: dist_mod.lowered_step(
            A, b, options=MG, nparts=2),
        "dist aot": lambda: dist_mod.aot_step(A, b, options=MG, nparts=2),
        "batch": lambda: cg(A, np.ones((2, A.nrows)), options=MG),
        "segments": lambda: cg(A, b, options=SolverOptions(
            maxits=50, precond="mg", segment_iters=10)),
        "2-D stencil": lambda: cg(poisson2d_5pt(16), np.ones(256),
                                  options=MG),
        "variable coefficients": lambda: cg(
            poisson3d_7pt_varcoef(16), b, options=MG),
        "ELL tier": lambda: cg(A, b, options=MG, fmt="ell"),
        "grid does not halve": lambda: cg(poisson3d_7pt(12), np.ones(1728),
                                          options=MG),
        "coarsest too small": lambda: cg(poisson3d_7pt(8), np.ones(512),
                                         options=MG),
    }


@pytest.mark.parametrize("case", sorted(_refusals()))
def test_unsupported_combinations_refuse(case):
    """Never a silent unpreconditioned solve: ERR_NOT_SUPPORTED."""
    with pytest.raises(AcgError) as e:
        _refusals()[case]()
    assert e.value.status == Status.ERR_NOT_SUPPORTED


def test_session_solves_through_the_aot_step_as_cg_does():
    from acg_tpu.serve.session import Session

    A = poisson3d_7pt(16)
    b = _rhs((16, 16, 16)).reshape(-1)
    o = SolverOptions(maxits=200, residual_rtol=1e-8, precond="mg",
                      mg_levels=2)
    sess = Session(A, options=o, prep_cache=None)
    got = sess.solve(b)
    want = cg(A, b, options=o)
    assert sess.counters["executable"]["misses"] == 1
    assert got.precond == "mg2 symgs8c"
    assert got.niterations == want.niterations
    np.testing.assert_allclose(got.x, want.x, rtol=0, atol=1e-12)


def test_cli_precond_mg_solves_a_27_point_system(tmp_path, capsys):
    from acg_tpu.cli import main as cli_main
    from acg_tpu.io import write_mtx
    from acg_tpu.io.mtxfile import MtxFile

    A = poisson3d_27pt(8)
    r, c, v = A.to_coo()
    keep = r >= c
    path = tmp_path / "A.mtx"
    write_mtx(path, MtxFile(symmetry="symmetric", nrows=A.nrows,
                            ncols=A.ncols, nnz=int(keep.sum()),
                            rowidx=r[keep], colidx=c[keep], vals=v[keep]))
    args = [str(path), "--manufactured-solution", "--precond", "mg",
            "--mg-levels", "2", "--max-iterations", "200",
            "--residual-rtol", "1e-10", "-q"]
    assert cli_main(args) == 0
    out = capsys.readouterr().out
    assert float(out.split("manufactured solution error: ")[1].split()[0]) \
        < 1e-8
    # over a mesh the preconditioner is refused, with a nonzero exit
    assert cli_main(args + ["--nparts", "2"]) != 0


def test_27_point_dia_generator_equals_the_csr_form():
    for grid in ((12, 12, 12), (3, 4, 5)):
        want = DiaMatrix.from_csr(poisson3d_27pt(*grid))
        got = poisson3d_27pt_dia(*grid)
        assert got.offsets == want.offsets and got.nnz == want.nnz
        np.testing.assert_array_equal(got.bands, want.bands)
