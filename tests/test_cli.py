"""CLI + tools end-to-end tests (SURVEY §7.6: L6 driver parity)."""

import os

import numpy as np
import pytest

from acg_tpu.cli import main as cli_main
from acg_tpu.io import read_mtx, write_mtx
from acg_tpu.io.mtxfile import MtxFile
from acg_tpu.sparse import poisson2d_5pt
from acg_tpu.tools.mtx2bin import main as mtx2bin_main
from acg_tpu.tools.mtxpartition import main as mtxpartition_main


@pytest.fixture
def matrix_file(tmp_path):
    A = poisson2d_5pt(8)
    r, c, v = A.to_coo()
    keep = r >= c
    m = MtxFile(symmetry="symmetric", nrows=A.nrows, ncols=A.ncols,
                nnz=int(keep.sum()), rowidx=r[keep], colidx=c[keep],
                vals=v[keep])
    p = tmp_path / "A.mtx"
    write_mtx(p, m)
    return str(p)


def test_cli_manufactured(matrix_file, capsys):
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "-q"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "manufactured solution error:" in out
    assert "total iterations:" in out
    err = float(out.split("manufactured solution error: ")[1].split()[0])
    assert err < 1e-8


def test_cli_pipelined(matrix_file, capsys):
    rc = cli_main([matrix_file, "--solver", "acg-pipelined",
                   "--manufactured-solution", "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "-q"])
    assert rc == 0
    assert "manufactured solution error:" in capsys.readouterr().out


def test_cli_host_solver(matrix_file, capsys):
    rc = cli_main([matrix_file, "--solver", "host",
                   "--manufactured-solution", "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "-q"])
    assert rc == 0


def test_cli_distributed(matrix_file, capsys):
    rc = cli_main([matrix_file, "--nparts", "4", "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "-q"])
    assert rc == 0
    out = capsys.readouterr().out
    err = float(out.split("manufactured solution error: ")[1].split()[0])
    assert err < 1e-8


def test_cli_solution_output(matrix_file, tmp_path, capsys):
    sol = tmp_path / "x.mtx"
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--output-solution", str(sol)])
    assert rc == 0
    x = read_mtx(sol)
    assert x.nrows == 64


def test_cli_not_converged_exit_code(matrix_file, capsys):
    rc = cli_main([matrix_file, "--max-iterations", "2",
                   "--residual-rtol", "1e-12", "-q"])
    assert rc == 1
    assert "did not converge" in capsys.readouterr().err


def test_cli_comm_matrix(matrix_file, capsys):
    rc = cli_main([matrix_file, "--nparts", "4", "--output-comm-matrix",
                   "--manufactured-solution", "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "-q"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "%%MatrixMarket matrix coordinate integer general" in out


def test_cli_epsilon_shift(matrix_file, capsys):
    rc = cli_main([matrix_file, "--epsilon", "1.0",
                   "--manufactured-solution", "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "-q"])
    assert rc == 0  # shifted SPD matrix still converges (different A)


def test_mtxpartition_roundtrip(matrix_file, tmp_path, capsys):
    part_file = tmp_path / "part.mtx"
    rc = mtxpartition_main([matrix_file, "--parts", "4",
                            "-o", str(part_file), "-v"])
    assert rc == 0
    part = read_mtx(part_file)
    assert part.nrows == 64
    assert set(np.unique(part.vals.astype(int))) == {0, 1, 2, 3}
    # consume it in the driver (ref --partition flow)
    rc = cli_main([matrix_file, "--nparts", "4",
                   "--partition", str(part_file),
                   "--manufactured-solution", "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "-q"])
    assert rc == 0


def test_mtx2bin_roundtrip(matrix_file, tmp_path, capsys):
    bin_file = tmp_path / "A.bin"
    rc = mtx2bin_main([matrix_file, str(bin_file), "-v"])
    assert rc == 0
    m_text = read_mtx(matrix_file)
    m_bin = read_mtx(bin_file)
    np.testing.assert_array_equal(m_bin.rowidx, m_text.rowidx)
    np.testing.assert_allclose(m_bin.vals, m_text.vals)
    # solve from the binary file
    rc = cli_main([str(bin_file), "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "-q"])
    assert rc == 0


def test_cli_checkpoint_resume(matrix_file, tmp_path, capsys):
    # run with tiny maxits -> not converged, checkpoint written;
    # resume finishes the solve from the partial solution
    ckpt = tmp_path / "state.npz"
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "5", "--residual-rtol", "1e-10",
                   "--write-checkpoint", str(ckpt), "-q"])
    assert rc == 1 and ckpt.exists()
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--resume", str(ckpt), "-q"])
    assert rc == 0
    out = capsys.readouterr().out
    err = float(out.split("manufactured solution error: ")[1].split()[0])
    assert err < 1e-8


def test_checkpoint_roundtrip(tmp_path):
    from acg_tpu.utils.checkpoint import load_checkpoint, save_checkpoint
    p = str(tmp_path / "c.npz")
    x = np.linspace(0, 1, 10)
    save_checkpoint(p, x, niterations=42, rnrm2=1e-5, meta={"n": 10})
    x2, nit, rn, meta = load_checkpoint(p)
    np.testing.assert_array_equal(x2, x)
    assert nit == 42 and rn == pytest.approx(1e-5)
    assert int(meta["n"]) == 10


def test_fpexcept_reported(matrix_file, capsys):
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "-q"])
    assert rc == 0
    assert "floating-point exceptions: none" in capsys.readouterr().out


def test_cli_enables_x64_for_float64(matrix_file):
    """Regression: the CLI must enable jax_enable_x64 for --dtype float64 —
    without it arrays silently truncate to f32 and pipelined CG hits a
    spurious roundoff breakdown ("matrix is not positive definite") before
    reaching tight tolerances.  Run in a subprocess so the conftest's
    global x64 enable can't mask the bug."""
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo_root, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, "-m", "acg_tpu.cli", matrix_file,
         "--manufactured-solution", "--solver", "acg-pipelined",
         "--nparts", "4", "--dtype", "float64",
         "--residual-rtol", "1e-11", "--max-iterations", "2000", "-q"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "not positive definite" not in out.stdout + out.stderr


def test_cli_reference_compat_flags(matrix_file, tmp_path, capsys):
    """Reference command lines (-z, --comm TYPE) run unchanged: -z is a
    no-op (gzip is sniffed from magic bytes), and every --comm backend
    collapses onto the XLA mesh (ref cuda/acg-cuda.c usage text)."""
    import gzip
    import shutil

    gz = tmp_path / "A.mtx.gz"
    with open(matrix_file, "rb") as fin, gzip.open(gz, "wb") as fout:
        shutil.copyfileobj(fin, fout)
    rc = cli_main(["-z", str(gz), "--comm", "nccl", "--nparts", "2",
                   "--manufactured-solution", "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "-q"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "manufactured solution error:" in out


def test_cli_comm_nvshmem_maps_to_rdma_halo():
    """--comm nvshmem (device-initiated comm in the reference) resolves to
    the rdma halo tier; an explicit --halo wins over --comm."""
    from acg_tpu.cli import make_parser, resolve_halo

    def resolved(argv):
        args = make_parser().parse_args(argv + ["A.mtx"])
        return resolve_halo(args.comm, args.halo)

    assert resolved(["--comm", "nvshmem"]) == "rdma"
    assert resolved(["--comm", "rocshmem"]) == "rdma"
    assert resolved(["--comm", "mpi"]) == "ppermute"
    assert resolved([]) == "ppermute"
    assert resolved(["--comm", "nvshmem", "--halo", "allgather"]) == "allgather"


def test_cli_io_errors_are_clean(tmp_path, capsys):
    """Missing files, corrupt checkpoints, and size mismatches exit 1 with
    one clean error line — no tracebacks (fuzz-derived regressions)."""
    assert cli_main(["/nonexistent-matrix.mtx", "-q"]) == 1
    assert "error:" in capsys.readouterr().err
    assert mtx2bin_main(["/nonexistent.mtx", str(tmp_path / "o.bin")]) == 1
    assert "error:" in capsys.readouterr().err
    assert mtxpartition_main(["/nonexistent.mtx", "--parts", "2"]) == 1
    assert "error:" in capsys.readouterr().err


def test_cli_resume_rejects_corrupt_and_wrong_size(matrix_file, tmp_path,
                                                   capsys):
    bad = tmp_path / "bad.npz"
    bad.write_text("not a zipfile")
    assert cli_main([matrix_file, "--resume", str(bad), "-q"]) == 1
    assert "error:" in capsys.readouterr().err
    from acg_tpu.utils.checkpoint import save_checkpoint
    wrong = tmp_path / "wrong.npz"
    save_checkpoint(str(wrong), np.ones(5), niterations=3, rnrm2=0.1)
    assert cli_main([matrix_file, "--resume", str(wrong), "-q"]) == 1
    err = capsys.readouterr().err
    # the hardened loader rejects the mismatch AT the checkpoint (shape
    # validated against the problem — utils/checkpoint.py), before the
    # generic initial-guess check ever sees it
    assert "wrong matrix" in err and "error:" in err


def test_cli_mat_precision_int8(matrix_file, capsys):
    """--mat-precision int8 forces the exact mask tier through the CLI
    (poisson2d bands are two-valued), and solves correctly."""
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--mat-precision", "int8", "--dtype", "float32",
                   "--residual-rtol", "1e-5", "--max-iterations", "500"])
    assert rc == 0


def test_cli_reference_negation_flags(matrix_file):
    """The reference's --no-* negations and the cuSPARSE algorithm
    selector are accepted (drop-in compatibility,
    ref cuda/acg-cuda.c:714,753,774).  The selector is validated against
    the reference's accepted set (default/csr-1/csr-2, case-insensitive;
    ref returns EINVAL otherwise)."""
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--no-manufactured-solution",
                   "--output-comm-matrix", "--no-output-comm-matrix",
                   "--cusparse-spmv-alg", "CSR-2",
                   "--max-iterations", "200", "--residual-rtol", "1e-5"])
    assert rc == 0


def test_cli_cusparse_alg_rejects_unknown(matrix_file):
    """An unknown cuSPARSE algorithm selector is a usage error, as in the
    reference (cuda/acg-cuda.c:726 returns EINVAL) — typo'd drop-in
    scripts must not silently proceed."""
    import pytest

    with pytest.raises(SystemExit) as exc:
        cli_main([matrix_file, "--cusparse-spmv-alg", "csrmvalg2"])
    assert exc.value.code == 2


def test_cli_checkpoint_resume_distributed(matrix_file, tmp_path, capsys):
    """Checkpoint/resume across DISTRIBUTED solves: the checkpoint holds
    the global solution, so a partial 4-part solve resumes on a
    different part count (the reference's restart story needs matching
    ranks; global-vector checkpoints are rank-free)."""
    ckpt = tmp_path / "dist.npz"
    rc = cli_main([matrix_file, "--manufactured-solution", "--nparts", "4",
                   "--max-iterations", "5", "--residual-rtol", "1e-10",
                   "--write-checkpoint", str(ckpt), "-q"])
    assert rc == 1 and ckpt.exists()
    rc = cli_main([matrix_file, "--manufactured-solution", "--nparts", "2",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--resume", str(ckpt), "-q"])
    assert rc == 0
    out = capsys.readouterr().out
    err = float(out.split("manufactured solution error: ")[1].split()[0])
    assert err < 1e-8


# ---------------------------------------------------------------------------
# --explain: the solver introspection layer (ISSUE 3)


def test_cli_explain_prints_audit_and_roofline(matrix_file, tmp_path,
                                               capsys):
    """Acceptance: --explain on a small problem prints the CommAudit +
    roofline report BEFORE solving, and the same data round-trips
    through --output-stats-json at schema acg-tpu-stats/13."""
    from acg_tpu.obs.export import SCHEMA, load_stats_document

    sj = tmp_path / "stats.json"
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--explain", "--output-stats-json", str(sj), "-q"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "CommAudit" in out
    assert "per-iteration collectives" in out
    assert "roofline model" in out
    # no chip here: the ceiling is not measured, never a default number
    assert "predicted ceiling: not measured" in out
    # round-trip: load_stats_document validates on read
    doc = load_stats_document(str(sj))
    assert doc["schema"] == SCHEMA == "acg-tpu-stats/13"
    intro = doc["introspection"]
    audit = intro["comm_audit"]
    roof = intro["roofline"]
    assert audit is not None and roof is not None
    # single chip: no collectives anywhere in the compiled step
    assert audit["per_iteration"]["ppermute"]["count"] == 0
    assert audit["total"]["allreduce"]["count"] == 0
    assert roof["bytes_per_iter"] > 0
    assert roof["predicted_iters_per_sec"] is None
    assert roof["measured_iters_per_sec"] is None \
        or roof["measured_iters_per_sec"] > 0
    assert "roofline_frac" in roof


def test_cli_explain_distributed_counts_collectives(matrix_file,
                                                    tmp_path, capsys):
    from acg_tpu.obs.export import load_stats_document

    sj = tmp_path / "stats.json"
    rc = cli_main([matrix_file, "--nparts", "4", "--solver",
                   "acg-pipelined", "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--explain", "--output-stats-json", str(sj), "-q"])
    assert rc == 0
    doc = load_stats_document(str(sj))
    audit = doc["introspection"]["comm_audit"]
    # the pipelined-CG claim as exported data: ONE psum per iteration
    assert audit["per_iteration"]["allreduce"]["count"] == 1
    assert audit["per_iteration"]["ppermute"]["count"] > 0
    roof = doc["introspection"]["roofline"]
    assert roof["nparts"] == 4


def test_cli_explain_hbm_gbps_override(matrix_file, capsys):
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--explain", "--hbm-gbps", "123", "-q"])
    assert rc == 0
    assert "123 GB/s" in capsys.readouterr().out


def test_cli_explain_host_solver_warns(matrix_file, capsys):
    rc = cli_main([matrix_file, "--solver", "host",
                   "--manufactured-solution", "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "--explain", "-q"])
    assert rc == 0
    captured = capsys.readouterr()
    assert "warning: --explain" in captured.err
    assert "CommAudit" not in captured.out


def test_cli_stats_json_without_explain_has_null_introspection(
        matrix_file, tmp_path):
    from acg_tpu.obs.export import load_stats_document

    sj = tmp_path / "stats.json"
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--output-stats-json", str(sj), "-q"])
    assert rc == 0
    doc = load_stats_document(str(sj))
    assert doc["introspection"] == {"comm_audit": None, "roofline": None,
                                    "halo_wire": None}


def test_cli_profile_records_actual_warmup_count(matrix_file, tmp_path):
    """Stats-document honesty: --profile forces warmup solves OFF; the
    exported options block must record the warmup count actually used
    (0), not the requested --warmup."""
    import json

    sj = tmp_path / "stats.json"
    prof = tmp_path / "trace"
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--warmup", "3", "--profile", str(prof),
                   "--output-stats-json", str(sj), "-q"])
    assert rc == 0
    doc = json.loads(sj.read_text())
    assert doc["options"]["warmup"] == 0
    # without --profile the requested count is used AND recorded
    rc = cli_main([matrix_file, "--manufactured-solution",
                   "--max-iterations", "500", "--residual-rtol", "1e-10",
                   "--warmup", "2", "--output-stats-json", str(sj), "-q"])
    assert rc == 0
    doc = json.loads(sj.read_text())
    assert doc["options"]["warmup"] == 2
