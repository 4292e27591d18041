"""``acg-tpu`` command-line driver.

The TPU counterpart of the reference drivers (reference cuda/acg-cuda.c /
hip/acg-hip.c): same positional arguments (A [b] [x0], Matrix Market files),
same flag vocabulary (usage text at cuda/acg-cuda.c:312-377, defaults at
:489-530), same pipeline:

  read A -> (optionally) partition -> build device operator(s) ->
  b from file / ones / manufactured solution -> solve -> stats ->
  (optionally) write solution.

Differences by design: the ``--comm`` backends collapse onto the XLA
collective compiler over the device mesh — ``--comm`` is still accepted,
mapping mpi/nccl/rccl onto the compiled ``--halo ppermute`` schedule and
nvshmem/rocshmem (device-initiated comm) onto ``--halo rdma``, the Pallas
remote-DMA tier (see :func:`resolve_halo`); ``--nparts`` selects how many
mesh devices to shard over (the reference gets this from ``mpirun -np``);
``--format`` picks the device operator layout (dia/ell), a TPU concern
with no CUDA analog.

Run: ``python -m acg_tpu.cli A.mtx --solver acg-pipelined -v``
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from acg_tpu import __version__
from acg_tpu.config import HaloMethod, SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.io import read_mtx, write_mtx
from acg_tpu.io.mtxfile import MtxFile, vector_to_mtx
from acg_tpu.sparse.csr import csr_from_mtx, manufactured_rhs
from acg_tpu.utils.stats import (format_solver_stats,
                                 reduce_stats_across_processes)


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="acg-tpu",
        description="Solve a linear system Ax=b using the conjugate "
                    "gradient (CG) method on TPU.")
    p.add_argument("A", help="path to Matrix Market file for the matrix A")
    p.add_argument("b", nargs="?", default=None,
                   help="optional Matrix Market file for right-hand side b")
    p.add_argument("x0", nargs="?", default=None,
                   help="optional Matrix Market file for initial guess x0")
    # input options; -z is accepted so reference command lines run
    # unchanged (ref cuda/acg-cuda.c usage "-z, --gzip ... filter files
    # through gzip"), but it is a no-op: gzip input is auto-detected from
    # the 2-byte magic header regardless of file extension
    p.add_argument("-z", "--gzip", "--gunzip", "--ungzip",
                   action="store_true", dest="gzip",
                   help="accepted for reference compatibility; gzip input "
                        "is auto-detected, so this is a no-op")
    p.add_argument("--binary", action="store_true",
                   help="read Matrix Market files in binary format")
    # partitioning options
    p.add_argument("--partition", metavar="FILE", default=None,
                   help="read partition vector from Matrix Market file")
    p.add_argument("--binary-partition", action="store_true",
                   help="read partition vector in binary format")
    p.add_argument("--partition-method", default="auto",
                   choices=["auto", "chunk", "rb", "bfs", "kway",
                            "multilevel"],
                   help="graph partitioner when no --partition file [auto]; "
                        "rb/kway mirror METIS recursive/k-way "
                        "(ref acg/metis.h:39); multilevel = the HEM "
                        "V-cycle (best general-graph cuts, see PERF.md); "
                        "chunk = contiguous row slabs (band-preserving, "
                        "exact for structured orderings); auto picks chunk "
                        "for banded matrices")
    p.add_argument("--seed", type=int, default=0, help="random seed [0]")
    p.add_argument("--nparts", type=int, default=1,
                   help="number of row shards / mesh devices [1]")
    p.add_argument("--nrhs", type=int, default=1, metavar="K",
                   help="solve K right-hand sides against the one "
                        "operator in a single batched device loop "
                        "(multi-RHS: the operator stream is read once "
                        "per iteration for ALL K systems; per-system "
                        "stats ride the acg-tpu-stats/13 export).  The "
                        "right-hand side is replicated K times — the "
                        "request-batching throughput mode.  K=1 is "
                        "exactly the ordinary solver [1]")
    # solver options
    p.add_argument("--solver", default="acg",
                   choices=["acg", "acg-pipelined", "acg-sstep",
                            "cg-sstep", "acg-device",
                            "acg-device-pipelined", "acg-pipelined-deep",
                            "cg-pipelined-deep", "host", "petsc",
                            "petsc-pipelined"],
                   help="solver variant [acg]; acg-device* are aliases of "
                        "acg* (the whole loop already runs on device); "
                        "acg-sstep / cg-sstep run the communication-"
                        "reduced s-step family (one Gram reduction per "
                        "--sstep iterations, arXiv:2501.03743); "
                        "acg-pipelined-deep / cg-pipelined-deep run the "
                        "depth-l pipelined loop (--pipeline-depth "
                        "reductions in flight, true-residual-certified "
                        "exits); petsc* run the SciPy differential "
                        "baseline (ref acg/cgpetsc.h)")
    p.add_argument("--sstep", type=int, default=4, metavar="S",
                   help="s-step block size for --solver acg-sstep: the "
                        "loop builds an S-dimensional Newton-shifted "
                        "Krylov basis per outer step and pays ONE Gram "
                        "psum + ONE (deep) halo exchange per S "
                        "iterations; 2 <= S <= 16 — basis conditioning "
                        "caps the useful range (s <= 6 f64, s <= 4 f32; "
                        "an indefinite Gram falls back to classic CG "
                        "automatically, see SolveResult.kernel_note) "
                        "[4]")
    p.add_argument("--pipeline-depth", type=int, default=2, metavar="L",
                   help="depth for --solver acg-pipelined-deep: the loop "
                        "keeps L dot-block reductions in flight behind "
                        "shifted-Newton-basis recurrences and certifies "
                        "every exit against the true residual; 2 <= L "
                        "<= 8 (L=1 dispatches the ordinary pipelined "
                        "solver, bit-identically) [2]")
    p.add_argument("--precond", default="none", choices=["none", "mg"],
                   help="preconditioner of single-chip classic CG: mg = "
                        "HPCG's multigrid V-cycle (8-colour symmetric "
                        "Gauss-Seidel, injection; needs a 3-D "
                        "constant-coefficient stencil whose grid halves "
                        "--mg-levels - 1 times) [none]")
    p.add_argument("--mg-levels", type=int, default=4, metavar="L",
                   help="grids in the --precond mg V-cycle [4]")
    p.add_argument("--max-iterations", type=int, default=100, metavar="N",
                   help="maximum number of iterations [100]")
    p.add_argument("--diff-atol", type=float, default=0.0, metavar="TOL")
    p.add_argument("--diff-rtol", type=float, default=0.0, metavar="TOL")
    p.add_argument("--residual-atol", type=float, default=0.0, metavar="TOL")
    p.add_argument("--residual-rtol", type=float, default=1e-9,
                   metavar="TOL")
    p.add_argument("--epsilon", type=float, default=0.0, metavar="TOL",
                   help="add TOL to the diagonal of A [0]")
    p.add_argument("--warmup", type=int, default=1, metavar="N",
                   help="perform N warmup solves before the timed solve, so "
                        "tsolve excludes compile time [1]  (the reference "
                        "warms up each op CLASS 10x before timing, "
                        "cuda/acg-cuda.c:511; one whole-solve warmup here "
                        "warms every op and the compile cache at once)")
    p.add_argument("--check-every", type=int, default=1, metavar="K",
                   help="test convergence every K iterations inside the "
                        "device loop (amortizes the stopping test) [1]")
    p.add_argument("--residual-replacement", type=int, default=None,
                   metavar="R",
                   help="pipelined CG: recompute r/w/s/z from their "
                        "definitions every R iterations, correcting "
                        "recurrence drift at tight tolerances (0 = off) "
                        "[50 for float32, 0 for float64]")
    # resilience options (acg_tpu/robust/)
    p.add_argument("--resilient", action="store_true",
                   help="run the solve under the self-healing supervisor "
                        "(acg_tpu/robust/supervisor.py): segmented "
                        "solves with atomic checkpoints, on-device "
                        "non-finite detection, host certification of "
                        "the true residual, and a bounded escalation "
                        "ladder (restart -> forced residual replacement "
                        "-> xla kernel tier -> allgather halo -> host "
                        "oracle); the RecoveryReport is exported in the "
                        "acg-tpu-stats/13 'resilience' block")
    p.add_argument("--max-restarts", type=int, default=4, metavar="N",
                   help="bound on the supervisor's recovery attempts "
                        "(ladder steps) before giving up [4]")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="K",
                   help="supervised segment length in iterations: the "
                        "supervisor checkpoints to --write-checkpoint "
                        "after every K iterations, bounding the work a "
                        "preemption can lose (0 = one segment) "
                        "[0; requires --resilient]")
    p.add_argument("--inject-fault", action="append", default=[],
                   metavar="KIND@ITER", dest="inject_fault",
                   help="deterministic fault injection (repeatable): "
                        "KIND is spmv|halo|reduction|carry with an "
                        "optional -nan|-inf|-scale suffix (device "
                        "faults, traced into the loop as data), or "
                        "segment-kill|checkpoint-corrupt (host faults; "
                        "require --resilient, ITER = segment ordinal). "
                        "Without --resilient a device fault exercises "
                        "DETECTION: the solve ends status "
                        "ERR_FAULT_DETECTED, exit code 1")
    # serving options (acg_tpu/serve/: persistent Session + coalescing
    # admission queue — the solver-as-a-service layer, ROADMAP item 3)
    p.add_argument("--serve", metavar="FILE", default=None,
                   help="serve mode: prepare the operator ONCE (Session: "
                        "read/partition/operator-build/compile paid once, "
                        "executables cached by static signature) and "
                        "process solve requests from FILE ('-' = stdin), "
                        "one command per line: 'solve [B.mtx]' solves one "
                        "right-hand side (default: the CLI's b); "
                        "'batch K [B.mtx]' submits K concurrent requests "
                        "through the coalescing queue (ONE batched "
                        "device solve); 'stats' prints the session "
                        "counters; 'health' the serving health snapshot "
                        "(rolling failure rate, p50/p99 queue wait and "
                        "dispatch wall, per-signature breaker states); "
                        "'metrics [prom]' the runtime-metrics registry "
                        "snapshot (JSON, or Prometheus text with "
                        "'prom'; enable with --metrics); 'flightrec' "
                        "the flight recorder's last-N request "
                        "timelines.  One JSON line per completed "
                        "request on stdout; exit 1 if any request "
                        "failed")
    p.add_argument("--serve-max-batch", type=int, default=8, metavar="B",
                   help="coalescing queue: max requests per batched "
                        "dispatch [8]")
    p.add_argument("--serve-max-wait-ms", type=float, default=0.0,
                   metavar="MS",
                   help="coalescing queue: max time the oldest pending "
                        "request waits for batch-mates before dispatch "
                        "[0 = dispatch whatever is queued]")
    p.add_argument("--serve-buckets", default=None, metavar="B1,B2,..",
                   help="admitted padded batch sizes (bounds executable-"
                        "cache cardinality) [powers of two up to "
                        "--serve-max-batch]")
    p.add_argument("--replicas", type=int, default=1, metavar="R",
                   help="serve mode: run R replicas (each its own "
                        "Session + service) behind one admission front "
                        "(acg_tpu/serve/fleet.py) with health-weighted "
                        "seeded routing and failover — a replica dying "
                        "mid-flight has its tickets re-dispatched on a "
                        "survivor with failover_from provenance in the "
                        "audit documents [1 = a bare service]")
    p.add_argument("--elastic", action="store_true",
                   help="serve mode, with --replicas >= 2: the fleet "
                        "HEALS (acg_tpu/serve/fleet.py elastic=True) — "
                        "a dead replica is replaced by a fresh one "
                        "warmed from the prepared-operator cache, "
                        "admitted only after a probe-gated canary "
                        "solve certified bit-for-bit against the "
                        "fleet reference; repeated probe failures "
                        "park a replica QUARANTINED under seeded "
                        "exponential backoff")
    p.add_argument("--min-replicas", type=int, default=None, metavar="R",
                   help="with --elastic: start the metrics-driven "
                        "autoscaler (acg_tpu/serve/autoscale.py) with "
                        "this width floor [off; floor 1 when only the "
                        "other autoscaler flags are given]")
    p.add_argument("--max-replicas", type=int, default=None, metavar="R",
                   help="with --elastic: the autoscaler's width "
                        "ceiling [--replicas when another autoscaler "
                        "flag starts it]")
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   metavar="MS",
                   help="with --elastic: the autoscaler's end-to-end "
                        "p99 SLO target — a windowed breach grows the "
                        "fleet by one (cooldown + hysteresis "
                        "prevent thrash); every resize lands an "
                        "autoscale-decision finding [off]")
    # admission robustness (acg_tpu/serve/admission.py): deadlines,
    # bounded retry, circuit breaker, load shedding — all default OFF
    # (the dispatched program is then bit-identical to plain serving);
    # certified under injected faults by scripts/chaos_serve.py
    p.add_argument("--deadline-ms", type=float, default=0.0, metavar="MS",
                   help="per-request deadline: a request still queued at "
                        "the deadline is SHED with a classified "
                        "ERR_TIMEOUT response (complete audit document "
                        "included); one waiting on another dispatch "
                        "classifies at the deadline with the late "
                        "result re-pollable [0 = no deadline]")
    p.add_argument("--queue-deadline-ms", type=float, default=0.0,
                   metavar="MS",
                   help="the in-queue slice of --deadline-ms: bounds "
                        "time waiting for dispatch, leaving the "
                        "remainder as solve budget [0 = the whole "
                        "deadline]")
    p.add_argument("--max-retries", type=int, default=0, metavar="N",
                   help="bounded retry for TRANSIENT request failures "
                        "(ERR_NONFINITE / ERR_FAULT_DETECTED — the PR 4 "
                        "classification): re-run the request alone up "
                        "to N times with seeded jittered backoff before "
                        "any --resilient escalation; deterministic "
                        "failures (breakdown, invalid value) fail fast "
                        "[0 = no retries]")
    p.add_argument("--breaker-threshold", type=int, default=0,
                   metavar="K",
                   help="circuit breaker: K consecutive failures on one "
                        "(solver, bucket, dtype) signature trip it OPEN "
                        "— further requests fast-fail ERR_OVERLOADED or "
                        "degrade (pipelined/s-step -> classic CG) until "
                        "a half-open probe succeeds after the cooldown "
                        "[0 = no breaker]")
    p.add_argument("--breaker-cooldown-ms", type=float, default=1000.0,
                   metavar="MS",
                   help="how long an OPEN breaker waits before "
                        "half-opening for one probe request [1000]")
    p.add_argument("--serve-max-depth", type=int, default=0, metavar="D",
                   help="load shedding: reject new requests with "
                        "ERR_OVERLOADED once the queue backlog reaches "
                        "D pending requests, instead of letting queue "
                        "wait grow unboundedly [0 = unbounded]")
    p.add_argument("--no-degrade", action="store_false", dest="degrade",
                   help="disable the degradation ladder: breaker-open "
                        "pipelined/s-step traffic fast-fails instead of "
                        "being served by classic CG")
    p.add_argument("--obs-port", type=int, default=None, metavar="PORT",
                   help="serve mode: bind the read-only HTTP "
                        "observability plane (acg_tpu/serve/obsplane.py: "
                        "GET /metrics Prometheus text, /metrics.json, "
                        "/health, /findings, /flightrec, /trace.json, "
                        "/history?window=S) on 127.0.0.1:PORT and start "
                        "the metrics time-series sampler; 0 = an "
                        "ephemeral port (the bound URL is logged at -v) "
                        "[default: no plane, no sampler — the "
                        "zero-overhead clause]")
    p.add_argument("--obs-interval-s", type=float, default=0.5,
                   metavar="S",
                   help="observability plane: the MetricsHistory "
                        "sampler interval (registry + fleet observe() "
                        "scraped into the bounded ring backing "
                        "/history) [0.5]")
    p.add_argument("--prep-cache", metavar="DIR", default=None,
                   help="disk-backed preprocessing cache: partition "
                        "vectors + partitioned systems keyed by graph "
                        "content hash (acg_tpu/partition/cache.py), so "
                        "repeated runs on the same matrix pay zero "
                        "partitioning [default: in-process memory cache "
                        "only]")
    p.add_argument("--no-prep-cache", action="store_true",
                   help="disable preprocessing reuse entirely (the "
                        "escape hatch: every run re-partitions)")
    # device options
    p.add_argument("--comm", default=None,
                   choices=["none", "mpi", "nccl", "nvshmem",
                            "rccl", "rocshmem"],
                   help="reference compatibility (ref cuda/acg-cuda.c "
                        "'--comm TYPE'): every backend collapses onto the "
                        "XLA collective compiler over the device mesh; "
                        "nvshmem/rocshmem (device-initiated comm) select "
                        "'--halo rdma', the Pallas remote-DMA tier, unless "
                        "--halo is given explicitly")
    p.add_argument("--halo", default=None,
                   choices=["ppermute", "allgather", "rdma"],
                   help="halo exchange schedule over the mesh [ppermute]")
    p.add_argument("--halo-wire", default="f32",
                   choices=["f32", "bf16", "int16-delta"],
                   help="on-wire halo message encoding [f32 = exact, the "
                        "pre-existing exchange]; bf16 / int16-delta "
                        "halve the ppermute payload without changing "
                        "the collective count (accumulation stays "
                        "full-precision — only the wire is narrow; see "
                        "PERF.md 'Deep pipeline + wire compression "
                        "methodology' for the tolerance floors); "
                        "incompatible with --halo rdma")
    p.add_argument("--format", default="auto",
                   choices=["auto", "dia", "ell", "sgell", "stencil"],
                   help="device operator layout [auto]; a forced layout "
                        "errors if its kernel is unavailable rather than "
                        "silently falling back (sgell: segmented-gather "
                        "ELL, requires the Mosaic kernel probe to pass; "
                        "stencil: the matrix-free tier — errors unless "
                        "the matrix is a verified constant-coefficient "
                        "grid stencil, acg_tpu/ops/stencil.py)")
    p.add_argument("--cusparse-spmv-alg", default=None, metavar="ALG",
                   type=str.lower,
                   choices=["default", "csr-1", "csr-2"],
                   help="reference compatibility (ref cuda/acg-cuda.c:714 "
                        "cuSPARSE algorithm selector, validated against "
                        "the same accepted set, case-insensitive): "
                        "accepted and mapped onto this framework's layout "
                        "choice — use --format to control the SpMV "
                        "formulation here")
    p.add_argument("--dtype", default="float64",
                   choices=["float32", "float64"],
                   help="value precision [float64; use float32 on real TPU]")
    p.add_argument("--idx-size", type=int, default=32, choices=[32, 64],
                   help="column-index width, the acgidx_t analog "
                        "(ref acg/config.h IDXSIZE) [32]")
    p.add_argument("--mat-precision", default="auto",
                   choices=["auto", "same", "bfloat16", "float32", "int8"],
                   help="operator STORAGE precision (compute stays at "
                        "--dtype): auto = narrow to bfloat16 only when "
                        "exact (integer stencil coefficients); same = "
                        "store at --dtype; int8 = force the exact "
                        "two-value mask tier (DIA bands only; errors if "
                        "the operator is not two-valued); bfloat16/"
                        "float32 = opt into mixed-precision CG [auto]")
    # verification
    p.add_argument("--manufactured-solution", action="store_true",
                   help="use a manufactured solution and right-hand side")
    p.add_argument("--no-manufactured-solution", action="store_false",
                   dest="manufactured_solution",
                   help="disable the manufactured solution (ref "
                        "cuda/acg-cuda.c:753)")
    # output options
    p.add_argument("--numfmt", default="%.17g", metavar="FMT",
                   help="printf-style format for numeric output")
    p.add_argument("--output-comm-matrix", action="store_true",
                   help="print communication matrix to standard output")
    p.add_argument("--no-output-comm-matrix", action="store_false",
                   dest="output_comm_matrix",
                   help="disable the communication-matrix output (ref "
                        "cuda/acg-cuda.c:774)")
    p.add_argument("--output-halo", action="store_true",
                   help="print the halo exchange pattern (ref acghalo_fwrite)")
    p.add_argument("--per-op-stats", action="store_true",
                   help="time each op class in isolation and fill the "
                        "per-op breakdown table (ref ACG_ENABLE_PROFILING)")
    p.add_argument("--monitor-every", type=int, default=0, metavar="K",
                   help="stream one 'iteration k: rnrm2 ...' line to "
                        "stderr every K iterations from inside the fused "
                        "device loop (throttled jax.debug.callback; the "
                        "reference's verbose per-iteration residuals). "
                        "-vv enables it with K=1 [0 = off]")
    p.add_argument("--explain", action="store_true",
                   help="before solving, compile the solver step and "
                        "print its introspection report: a CommAudit of "
                        "the optimized HLO (collectives per iteration "
                        "with byte sizes, fusion count, backend "
                        "cost/memory analysis) plus the analytic "
                        "roofline model (per-iteration HBM traffic and "
                        "the predicted iteration-rate ceiling); both are "
                        "embedded in --output-stats-json (schema "
                        "acg-tpu-stats/13, 'introspection' block)")
    p.add_argument("--hbm-gbps", type=float, default=None, metavar="GBPS",
                   help="HBM bandwidth for the roofline model, in GB/s "
                        "[default: from the per-chip table in "
                        "acg_tpu/obs/roofline.py, keyed by the detected "
                        "device kind]")
    p.add_argument("--output-stats-json", metavar="FILE", default=None,
                   help="write the complete stats block (per-op counters, "
                        "norms, convergence history, phase spans, "
                        "capability matrix) as one machine-readable JSON "
                        "document (schema acg-tpu-stats/13; lint with "
                        "scripts/check_stats_schema.py)")
    p.add_argument("--metrics", action="store_true",
                   help="enable the process runtime-metrics registry "
                        "(acg_tpu/obs/metrics.py): counters/gauges/"
                        "histograms across the serve stack, the "
                        "partition cache and the solvers, snapshotted "
                        "into the stats export's 'metrics' block and "
                        "the --serve REPL's 'metrics' command.  "
                        "Host-side only — the compiled program is "
                        "bit-identical with or without it [off]")
    p.add_argument("--trace-json", metavar="FILE", default=None,
                   help="write the run's host phase spans (and, in "
                        "--serve mode, the per-request flight-recorder "
                        "timelines) as a Chrome trace-event JSON file — "
                        "open in Perfetto / chrome://tracing "
                        "(acg_tpu/obs/events.py)")
    p.add_argument("--output-solution", metavar="FILE", default=None,
                   help="write solution vector to Matrix Market FILE")
    p.add_argument("--write-checkpoint", metavar="FILE", default=None,
                   help="save solver state (solution + iteration count) to "
                        "a binary .npz checkpoint, even on non-convergence")
    p.add_argument("--resume", metavar="FILE", default=None,
                   help="resume from a checkpoint written by "
                        "--write-checkpoint (overrides x0)")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="capture a jax.profiler trace of the solve into DIR")
    p.add_argument("-v", "--verbose", action="count", default=0)
    p.add_argument("-q", "--quiet", action="store_true",
                   help="suppress solution output")
    p.add_argument("--version", action=_VersionAction, nargs=0,
                   help="print version and capability matrix, then exit")
    return p


class _VersionAction(argparse.Action):
    """Version + capability matrix (the analog of the reference's
    --version capability report, cuda/acg-cuda.c:382-440, which lists
    MPI/NCCL/NVSHMEM/cuSPARSE availability and device info).  The matrix
    itself comes from obs.export.capability_info — the same dict the
    --output-stats-json document embeds, so the printed report and the
    exported one cannot drift."""

    def __call__(self, parser, namespace, values, option_string=None):
        from acg_tpu.obs.export import capability_info

        info = capability_info()
        print(f"acg-tpu {info['version']}")
        if info.get("jax") is not None:
            print(f"  jax: {info['jax']}  jaxlib: {info['jaxlib']}")
            print(f"  platform: {', '.join(info['platforms'])} "
                  f"({info['ndevices']} device(s))")
            print(f"  device kind: {', '.join(info['device_kinds'])}")
            print(f"  processes: {info['processes']}")
            print(f"  x64 enabled: {info['x64']}")
        else:
            print("  jax backend unavailable: "
                  f"{info.get('backend_error', 'unknown')}")
        print(f"  native host library: "
              f"{'yes' if info['native_host_library'] else 'no (python fallback)'}")
        if info.get("scipy"):
            print(f"  scipy baseline (--solver petsc): {info['scipy']}")
        else:
            print("  scipy baseline (--solver petsc): unavailable")
        parser.exit()


def resolve_halo(comm: str | None, halo: str | None) -> str:
    """Map the reference's --comm spelling onto a halo tier: an explicit
    --halo always wins; otherwise nvshmem/rocshmem (device-initiated comm)
    mean the Pallas remote-DMA tier and everything else the compiled
    ppermute schedule."""
    if halo is not None:
        return halo
    return "rdma" if comm in ("nvshmem", "rocshmem") else "ppermute"


def _log(args, msg):
    if args.verbose:
        print(msg, file=sys.stderr, flush=True)


def _first_system(x):
    """ONE representative solution of a --nrhs batch: the CLI replicates
    a single b across the batch, so the systems are identical and every
    1-D consumer (checkpoint, manufactured-error report, solution
    output) takes system 0 through THIS helper — one owner of the
    convention."""
    x = np.asarray(x)
    return x[0] if x.ndim == 2 else x


def _cli_prep_cache(args):
    """The CLI's prep-cache spec (acg_tpu/partition/cache.py):
    --no-prep-cache = off, --prep-cache DIR = disk-backed, default =
    the in-process memory cache."""
    if args.no_prep_cache:
        return None
    return args.prep_cache if args.prep_cache else "auto"


def _serve_main(args, tracer, A, b, options, fault_specs) -> int:
    """--serve: the solver-as-a-service REPL (acg_tpu/serve/).  One
    Session holds the prepared operator; commands submit right-hand
    sides through the coalescing admission queue; one JSON line per
    completed request goes to stdout."""
    import json

    from acg_tpu.serve import AdmissionPolicy, Session, SolverService

    if args.solver == "host" or args.solver.startswith("petsc"):
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       f"--serve drives the device solvers (--solver "
                       f"{args.solver} prepares no resident operator)")
    if args.nrhs > 1:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       "--serve batches requests through its own queue; "
                       "--nrhs does not apply (use 'batch K')")
    if fault_specs:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "--inject-fault targets one supervised solve; "
                       "serve-mode recovery is --resilient (per-request "
                       "solve_resilient escalation)")
    mat_dtype = {"auto": "auto", "same": None}.get(
        args.mat_precision, args.mat_precision)
    part = None
    if args.partition:
        # the pinned partition vector is honored exactly as in the
        # one-shot path (silently re-partitioning would change halo
        # structure and tiers under the user)
        pm = read_mtx(args.partition,
                      binary=args.binary_partition or None)
        part = pm.vals.astype(np.int32)
    try:
        buckets = (tuple(int(v) for v in args.serve_buckets.split(","))
                   if args.serve_buckets else ())
    except ValueError:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       f"--serve-buckets {args.serve_buckets!r}: "
                       "expected a comma-separated list of ints "
                       "(e.g. 1,4,8)")
    if args.replicas < 1:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       "--replicas must be >= 1")
    if args.elastic and args.replicas < 2:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       "--elastic heals a replica FLEET; it needs "
                       "--replicas >= 2")
    autoscale_on = any(v is not None for v in (
        args.min_replicas, args.max_replicas, args.slo_p99_ms))
    if autoscale_on and not args.elastic:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       "--min-replicas/--max-replicas/--slo-p99-ms "
                       "drive the autoscaler of an elastic fleet; "
                       "they need --elastic")
    admission = AdmissionPolicy(
        deadline_ms=args.deadline_ms,
        queue_deadline_ms=args.queue_deadline_ms,
        max_retries=args.max_retries, seed=args.seed,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_ms=args.breaker_cooldown_ms,
        max_queue_depth=args.serve_max_depth,
        degrade=args.degrade)
    # ONE Session-build parameter set for both branches (the fleet and
    # the bare service must never silently diverge on a build knob)
    session_kw = dict(
        nparts=args.nparts, part=part, dtype=np.dtype(args.dtype),
        fmt=args.format, mat_dtype=mat_dtype,
        halo=HaloMethod(args.halo),
        partition_method=args.partition_method, seed=args.seed,
        options=options, tracer=tracer,
        prep_cache=_cli_prep_cache(args), nrhs=args.serve_max_batch)
    if args.replicas > 1:
        # the replica fleet (acg_tpu/serve/fleet.py): R sessions behind
        # one admission front — the REPL commands below read a Fleet
        # exactly like a single service (shared duck type)
        from acg_tpu.serve import Fleet

        svc = Fleet(
            A, replicas=args.replicas, solver=args.solver,
            options=options, max_batch=args.serve_max_batch,
            max_wait_ms=args.serve_max_wait_ms, buckets=buckets,
            resilient=args.resilient, max_restarts=args.max_restarts,
            admission=admission, seed=args.seed,
            elastic=args.elastic, session_kw=session_kw)
    else:
        svc = SolverService(
            Session(A, **session_kw), solver=args.solver,
            options=options, max_batch=args.serve_max_batch,
            max_wait_ms=args.serve_max_wait_ms, buckets=buckets,
            resilient=args.resilient, max_restarts=args.max_restarts,
            admission=admission)

    def _read_rhs(path: str):
        vec = read_mtx(path, binary=args.binary or None).vals.astype(
            np.dtype(args.dtype))
        if vec.shape[0] != A.nrows:
            raise AcgError(Status.ERR_INVALID_VALUE,
                           f"right-hand side {path!r} has {vec.shape[0]} "
                           f"entries, matrix has {A.nrows} rows")
        return vec

    def _emit(resp):
        print(json.dumps(resp.summary()), flush=True)
        return resp

    def _emit_rejected(e: Exception, lineno: int) -> int:
        """A request REFUSED before admission (non-finite RHS, an
        unreadable/missing/truncated RHS file, size mismatch) is a
        classified per-request outcome, not a session-fatal error: one
        JSON line, session continues — the 'one line per request; exit
        1 if any failed' contract holds for invalid requests too (a
        poisoned request must not take down the service, that is the
        admission layer's whole point)."""
        if isinstance(e, AcgError):
            if e.status not in (Status.ERR_INVALID_VALUE,
                                Status.ERR_INVALID_FORMAT,
                                Status.ERR_EOF):
                raise e     # operational errors stay session-fatal
            status = e.status.name
        else:               # OSError: the RHS file itself (open/read)
            status = Status.ERR_INVALID_VALUE.name
        print(json.dumps({"request": None, "ok": False,
                          "status": status, "line": lineno,
                          "error": str(e)}), flush=True)
        return 1

    obsplane = None
    obs_history = None
    if args.obs_port is not None:
        # the wire-scrapeable observability plane (ISSUE 18): a
        # read-only HTTP admin server + the metrics time-series
        # sampler over the live service; absent the flag neither
        # exists (the zero-overhead clause)
        from acg_tpu.obs.history import MetricsHistory
        from acg_tpu.serve.obsplane import ObsPlane

        obs_history = MetricsHistory(
            interval_s=args.obs_interval_s, fleet=svc)
        obs_history.start()
        obsplane = ObsPlane(svc, port=args.obs_port,
                            history=obs_history, tracer=tracer).start()
        _log(args, f"observability plane listening on {obsplane.url}")

    autoscaler = None
    scaler_history = None
    if autoscale_on:
        # the metrics-driven autoscaler (acg_tpu/serve/autoscale.py):
        # a host-side control loop reading the MetricsHistory window —
        # reuses the --obs-port sampler when one exists, otherwise runs
        # a dedicated in-process sampler just for its signals
        from acg_tpu.serve.autoscale import Autoscaler

        asc_min = (args.min_replicas if args.min_replicas is not None
                   else 1)
        asc_max = (args.max_replicas if args.max_replicas is not None
                   else max(args.replicas, asc_min))
        if not asc_min <= args.replicas <= asc_max:
            raise AcgError(Status.ERR_INVALID_VALUE,
                           f"autoscaler bounds [{asc_min}, {asc_max}] "
                           f"must contain --replicas {args.replicas}")
        if obs_history is None:
            from acg_tpu.obs.history import MetricsHistory
            scaler_history = MetricsHistory(fleet=svc)
            scaler_history.start()
        # NOTE: an explicit None check — MetricsHistory has __len__,
        # so a just-started (empty) sampler is FALSY
        autoscaler = Autoscaler(
            svc, history=(obs_history if obs_history is not None
                          else scaler_history),
            min_replicas=asc_min, max_replicas=asc_max,
            slo_p99_ms=args.slo_p99_ms)
        autoscaler.start()
        _log(args, f"autoscaler running: width [{asc_min}, {asc_max}]"
                   + (f", p99 SLO {args.slo_p99_ms} ms"
                      if args.slo_p99_ms is not None else ""))

    nfailed = 0
    last_audit = None
    fh = sys.stdin if args.serve == "-" else open(args.serve)
    try:
        for lineno, raw in enumerate(fh, 1):
            line = raw.split("#", 1)[0].strip()
            if not line:
                continue
            tok = line.split()
            cmd = tok[0].lower()
            if cmd in ("quit", "exit"):
                break
            if cmd == "stats":
                print(json.dumps(svc.stats(), default=str), flush=True)
            elif cmd == "health":
                print(json.dumps(svc.health(), default=str), flush=True)
            elif cmd == "metrics":
                # the runtime-metrics registry (enable with --metrics):
                # 'metrics' = one JSON snapshot line, 'metrics prom' =
                # the Prometheus text exposition
                from acg_tpu.obs.metrics import registry
                if len(tok) > 1 and tok[1].lower() == "prom":
                    sys.stdout.write(registry().prometheus_text())
                    sys.stdout.flush()
                else:
                    print(json.dumps(registry().snapshot()), flush=True)
            elif cmd == "flightrec":
                # the flight recorder: the last N request timelines
                # (trace IDs match the audit documents' session/
                # admission trace_id)
                print(json.dumps(svc.flightrec.dump()), flush=True)
            elif cmd == "flush":
                svc.flush()
            elif cmd == "solve":
                try:
                    rhs = _read_rhs(tok[1]) if len(tok) > 1 else b
                    resp = _emit(svc.solve(rhs))
                    last_audit = resp.audit or last_audit
                    nfailed += 0 if resp.ok else 1
                except (OSError, AcgError) as e:
                    nfailed += _emit_rejected(e, lineno)
            elif cmd == "batch":
                if len(tok) < 2 or not tok[1].isdigit():
                    raise AcgError(Status.ERR_INVALID_VALUE,
                                   f"--serve line {lineno}: batch needs "
                                   "a request count ('batch K [B.mtx]')")
                try:
                    rhs = _read_rhs(tok[2]) if len(tok) > 2 else b
                    reqs = [svc.submit(rhs)
                            for _ in range(int(tok[1]))]
                except (OSError, AcgError) as e:
                    nfailed += _emit_rejected(e, lineno)
                    continue
                for req in reqs:
                    resp = _emit(req.response())
                    last_audit = resp.audit or last_audit
                    nfailed += 0 if resp.ok else 1
            else:
                raise AcgError(Status.ERR_INVALID_VALUE,
                               f"--serve line {lineno}: unknown command "
                               f"{cmd!r} (solve|batch|stats|health|"
                               "metrics|flightrec|flush|quit)")
    finally:
        if fh is not sys.stdin:
            fh.close()
        if autoscaler is not None:
            autoscaler.stop()
        if scaler_history is not None:
            scaler_history.stop()
        if obsplane is not None:
            obsplane.stop()
        if obs_history is not None:
            obs_history.stop()
    svc.flush()
    if args.trace_json:
        # host phase spans + every recorded request timeline, one
        # timebase — the whole serving run opens in Perfetto
        from acg_tpu.obs.events import write_chrome_trace
        write_chrome_trace(args.trace_json, tracer=tracer,
                           recorder=svc.flightrec)
        _log(args, f"chrome trace written to {args.trace_json!r}")
    st = svc.stats()
    nsubmitted = (st["routing"]["assignments"] if "routing" in st
                  else st["queue"]["submitted"])
    _log(args, f"serve: {nsubmitted} request(s), "
               f"{nfailed} failed")
    if args.output_stats_json and last_audit is not None:
        from acg_tpu.obs.export import write_stats_json
        # the audit record of the LAST completed request — a complete
        # schema-/6 document whose session block carries the cumulative
        # cache/queue counters at that point
        write_stats_json(args.output_stats_json, last_audit)
        _log(args, f"stats document written to "
                   f"{args.output_stats_json!r}")
    return 1 if nfailed else 0


def main(argv=None) -> int:
    from acg_tpu.errors import run_main
    return run_main(lambda: _main(argv))


def _main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    from acg_tpu.utils.backend import enable_compile_cache
    enable_compile_cache()
    # phase-span tracer: the pipeline's host timeline (read / partition /
    # operator-build / warmup / solve), logged at -v and exported into
    # --output-stats-json; spans also emit jax.profiler.TraceAnnotation
    # so they line up with --profile traces (acg_tpu/obs/trace.py)
    from acg_tpu.obs.trace import SpanTracer
    tracer = SpanTracer(log=(lambda m: _log(args, m)))

    # --metrics: turn the process registry ON before any instrumented
    # path runs (host-side only; default off, the zero-overhead clause)
    if args.metrics:
        from acg_tpu.obs.metrics import enable_metrics
        enable_metrics()

    args.halo = resolve_halo(args.comm, args.halo)
    # -vv turns on the live residual stream (reference verbose mode);
    # an explicit --monitor-every K sets the throttle
    if args.verbose >= 2 and args.monitor_every == 0:
        args.monitor_every = 1
    if args.cusparse_spmv_alg is not None:
        print(f"note: --cusparse-spmv-alg {args.cusparse_spmv_alg} is a "
              "cuSPARSE selector with no TPU analog; the SpMV formulation "
              f"here is chosen by --format (currently '{args.format}')",
              file=sys.stderr)

    # multi-host bootstrap FIRST, before any backend use — the MPI_Init
    # contract of the reference driver (cuda/acg-cuda.c:891); silent no-op
    # for a plain single-process run, cluster-autodetect on TPU pods
    from acg_tpu.parallel.multihost import init_multihost
    init_multihost()

    # validate --numfmt up front (ref fmtspec_parse, acg/fmtspec.c, called
    # during option parsing cuda/acg-cuda.c:363-366)
    from acg_tpu.utils.fmtspec import parse_fmtspec
    try:
        args.numfmt = str(parse_fmtspec(args.numfmt))
    except AcgError as e:
        print(f"error: --numfmt: {e}", file=sys.stderr)
        return 2

    # honor 64-bit value requests on device (see config.ensure_x64_for)
    from acg_tpu.config import ensure_x64_for
    ensure_x64_for(np.dtype(args.dtype))
    import jax
    if args.dtype == "float64" and jax.devices()[0].platform == "tpu":
        from acg_tpu.utils.backend import f64_report
        f64 = f64_report()
        if not f64["f64_ok"]:
            print("WARNING: --dtype float64 on this TPU does not keep "
                  f"float64 precision ({f64}); results carry less than "
                  "double precision.  Use --dtype float32 on the chip.",
                  file=sys.stderr)

    # 1. read A (ref cuda/acg-cuda.c:1296-1331)
    _log(args, f"reading matrix {args.A!r}")
    from acg_tpu.config import index_dtype
    with tracer.span("read"):
        m = read_mtx(args.A, binary=args.binary or None)
        A = csr_from_mtx(m, val_dtype=np.dtype(args.dtype),
                         idx_dtype=index_dtype(args.idx_size))
        if args.epsilon:
            A = A.shift_diagonal(args.epsilon)
    _log(args, f"matrix: {A.nrows} rows, {A.nnz} nonzeros")

    # 2. right-hand side: file / manufactured / ones
    #    (ref cuda/acg-cuda.c:1813-2049)
    xstar = None
    if args.manufactured_solution:
        xstar, b = manufactured_rhs(A, seed=args.seed)
        _log(args, "using manufactured solution")
    elif args.b:
        b = read_mtx(args.b, binary=args.binary or None).vals.astype(A.vals.dtype)
        if b.shape[0] != A.nrows:
            raise AcgError(Status.ERR_INVALID_VALUE,
                           f"right-hand side has {b.shape[0]} "
                           f"entries, matrix has {A.nrows} rows")
    else:
        b = np.ones(A.nrows, dtype=A.vals.dtype)
    x0 = None
    if args.x0:
        x0 = read_mtx(args.x0, binary=args.binary or None).vals.astype(A.vals.dtype)
    resumed_iters = 0
    if args.resume:
        from acg_tpu.utils.checkpoint import load_checkpoint
        # validate the checkpoint against THIS problem (shape + dtype
        # kind) — a checkpoint from another matrix or a truncated file
        # is a clean ERR_INVALID_FORMAT here, not a trace error later
        x0, resumed_iters, _, _ = load_checkpoint(
            args.resume, expect_shape=(A.nrows,),
            expect_dtype=np.dtype(args.dtype))
        x0 = x0.astype(A.vals.dtype)
        _log(args, f"resuming from {args.resume!r} "
                   f"({resumed_iters} prior iterations)")
    if x0 is not None and x0.shape[-1] != A.nrows:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       f"initial guess has {x0.shape[-1]} entries, "
                       f"matrix has {A.nrows} rows")
    if args.nrhs < 1:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       f"--nrhs must be >= 1, got {args.nrhs}")
    if args.nrhs > 1:
        if args.solver == "host" or args.solver.startswith("petsc"):
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           f"--nrhs > 1 requires a device solver "
                           f"(--solver {args.solver} solves one system "
                           "at a time)")
        # replicate into the (B, n) multi-RHS batch; K=1 stays on the
        # 1-D path (bit-for-bit today's solve).  x0 stays 1-D — the
        # solvers broadcast a shared guess across the batch themselves
        # (base.conform_x0_batch)
        b = np.tile(np.asarray(b)[None, :], (args.nrhs, 1))

    # resilience flags: parse --inject-fault specs up front (a bad spec
    # is a usage error, not a mid-solve surprise) and classify them
    from acg_tpu.robust.faults import FaultSpec
    fault_specs = [FaultSpec.parse(s) for s in args.inject_fault]
    if any(f.kind == "replica-kill" for f in fault_specs):
        # the one-shot pipeline has no consumer for replica death (the
        # supervisor fires only segment-kill/checkpoint-corrupt) —
        # accepting it here would report a drill that never ran
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "replica-kill is a fleet fault: drive it through "
                       "the serve layer (scripts/chaos_serve.py --fleet,"
                       " or Fleet.inject_fault)")
    device_faults = [f for f in fault_specs if f.is_device]
    host_faults = [f for f in fault_specs if not f.is_device]
    if host_faults and not args.resilient:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       f"host-level faults ({host_faults[0]}) simulate "
                       "preemption/corruption of the SUPERVISED solve "
                       "and require --resilient")
    if len(device_faults) > 1 and not args.resilient:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       "a plain solve injects at most one device fault; "
                       "use --resilient for multi-fault scenarios")
    if args.resilient and args.nrhs > 1:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "--resilient supervises one right-hand side "
                       "(run per-system supervision for --nrhs > 1)")
    if args.checkpoint_every and not args.resilient:
        print("warning: --checkpoint-every segments the SUPERVISED "
              "solve and requires --resilient; ignored", file=sys.stderr)

    # with --profile, warmup solves are skipped (see the nwarmup note
    # below); the options block — printed AND exported — must record the
    # warmup count actually used, not the requested one (a stats document
    # claiming warmup=1 for a profiled cold solve misattributes compile
    # time to the solve it describes).  Injection and supervised solves
    # skip warmup too (a warmup solve would hit the same deterministic
    # fault first; the supervisor's first segment warms the caches).
    nwarmup = 0 if (args.profile or fault_specs
                    or args.resilient) else args.warmup
    sstep_mode = "sstep" in args.solver
    deep_mode = "deep" in args.solver
    if sstep_mode and not 2 <= args.sstep <= 16:
        # map to the clean one-line CLI error every other invalid flag
        # produces (SolverOptions' own ValueError would traceback)
        raise AcgError(Status.ERR_INVALID_VALUE,
                       f"--sstep {args.sstep}: the s-step block size "
                       "must be in [2, 16] (basis conditioning is the "
                       "practical ceiling; see PERF.md)")
    if deep_mode and not 1 <= args.pipeline_depth <= 8:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       f"--pipeline-depth {args.pipeline_depth}: the "
                       "pipeline depth must be in [1, 8] (basis "
                       "conditioning caps the useful range; depth 1 "
                       "IS the ordinary pipelined solver)")
    if args.mg_levels < 1:
        raise AcgError(Status.ERR_INVALID_VALUE,
                       f"--mg-levels {args.mg_levels}: at least 1 grid")
    if args.halo_wire != "f32" and args.halo == "rdma":
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "--halo-wire compresses the collective message "
                       "encodings; the RDMA tier is a raw-buffer put "
                       "with no encode/decode hook (use --halo "
                       "ppermute or allgather)")
    options = SolverOptions(
        maxits=args.max_iterations, diffatol=args.diff_atol,
        diffrtol=args.diff_rtol, residual_atol=args.residual_atol,
        residual_rtol=args.residual_rtol, warmup=nwarmup,
        check_every=args.check_every,
        replace_every=args.residual_replacement,
        monitor_every=args.monitor_every,
        sstep=args.sstep if sstep_mode else 0,
        pipeline_depth=args.pipeline_depth if deep_mode else 1,
        halo_wire=args.halo_wire,
        precond=args.precond, mg_levels=args.mg_levels,
        # detection rides along whenever injection or supervision is on
        guard_nonfinite=bool(args.resilient or fault_specs))

    # serve mode (acg_tpu/serve/): hand the prepared inputs to the
    # session REPL — the rest of this driver is the one-shot pipeline
    if args.serve is not None:
        return _serve_main(args, tracer, A, b, options, fault_specs)

    # 3. partition (ref cuda/acg-cuda.c:1485-1800) + solve (:2209-2261)
    solver = args.solver
    pipelined = "pipelined" in solver
    mat_dtype = {"auto": "auto", "same": None}.get(
        args.mat_precision, args.mat_precision)

    # with --profile, warmup solves are skipped: a warmup failure (e.g.
    # non-convergence) would otherwise raise before the trace context even
    # opens, producing an empty profile of exactly the solve the user is
    # trying to inspect; the trace then simply includes compile time
    # (nwarmup was resolved above, BEFORE SolverOptions, so the exported
    # options block reports the count actually used)
    # warmup solves run with the live monitor muted HOST-SIDE (otherwise
    # every warmup repeats the whole residual stream) — muting via the
    # options would change the static jit key and make the timed solve
    # recompile, defeating --warmup (obs.monitor.muted docstring)
    import contextlib as _ctl

    def _warm_mute():
        if not options.monitor_every:
            return _ctl.nullcontext()
        from acg_tpu.obs.monitor import muted
        return muted()

    import contextlib

    @contextlib.contextmanager
    def _maybe_profile():
        if args.profile:
            import jax
            with jax.profiler.trace(args.profile):
                yield
        else:
            yield

    def _checkpoint(res):
        if args.write_checkpoint and res is not None:
            from acg_tpu.utils.checkpoint import save_checkpoint
            x_ck = _first_system(res.x)
            if not np.all(np.isfinite(np.asarray(x_ck))):
                # a fault/NaN-poisoned partial solution is not a valid
                # resume point (load_checkpoint would reject it anyway)
                print("warning: not checkpointing a non-finite partial "
                      "solution (nothing to resume from)",
                      file=sys.stderr)
                return
            # checkpoint ONE representative solution (_first_system)
            # so the file stays 1-D and --resume works with or without
            # --nrhs
            save_checkpoint(args.write_checkpoint, x_ck,
                            niterations=res.niterations + resumed_iters,
                            rnrm2=res.rnrm2)
            _log(args, f"checkpoint written to {args.write_checkpoint!r}")

    dev = ss = None
    # --explain payload: filled by _run_explain, embedded by _export_stats
    # ("model" holds the live RooflineModel so the post-solve measured
    # rate can be priced against it; "contract" the static-contract
    # verdict block for the schema-/7 export)
    intro = {"comm_audit": None, "roofline": None, "model": None,
             "contract": None, "halo_wire": None}
    # --resilient payload: the RecoveryReport dict, set by the resilient
    # path (success or failure) and exported in the schema-/4
    # 'resilience' block (null for plain solves)
    resil = {"report": None}

    def _run_explain(dev=None, ss=None):
        """Compile the solver step, audit its HLO, and print the
        introspection report (CommAudit + roofline) BEFORE the solve —
        the instrument panel of the observability layer.  Every stage
        degrades with a warning rather than blocking the solve."""
        if not args.explain:
            return
        from acg_tpu.obs.hlo import audit_compiled, format_comm_audit
        from acg_tpu.obs.roofline import (roofline_for_operator,
                                          roofline_for_sharded)
        with tracer.span("explain"):
            # one definition for both the audit and the roofline — the
            # two must describe the SAME program kind
            skind = ("cg-sstep" if sstep_mode
                     else "cg-pipelined-deep" if deep_mode
                     else "cg-pipelined" if pipelined else "cg")
            audit = None
            hlo_txt = None
            try:
                if ss is not None:
                    from acg_tpu.solvers.cg_dist import \
                        compile_step as dist_compile_step
                    compiled = dist_compile_step(ss, b, options=options,
                                                 solver=skind)
                else:
                    from acg_tpu.solvers.cg import compile_step
                    compiled = compile_step(dev, b, x0=x0, options=options,
                                            solver=skind)
                hlo_txt = compiled.as_text()
                audit = audit_compiled(compiled)
            except Exception as e:
                print(f"warning: --explain: compiled-HLO audit "
                      f"unavailable: {e}", file=sys.stderr)
            # the static-contract verdict (acg_tpu/analysis/): the same
            # compiled program the CommAudit prices, checked against the
            # configuration's DECLARED per-iteration model
            verdict_line = None
            if hlo_txt is not None:
                try:
                    from acg_tpu.analysis.contracts import (
                        contract_block, format_verdict, verify_hlo_text)
                    from acg_tpu.analysis.registry import contract_for
                    contract = contract_for(skind, options, dev=dev,
                                            ss=ss, nrhs=args.nrhs)
                    cviols = verify_hlo_text(hlo_txt, contract)
                    verdict_line = format_verdict(contract, cviols)
                    intro["contract"] = contract_block(contract, cviols)
                except Exception as e:
                    print(f"warning: --explain: contract verdict "
                          f"unavailable: {e}", file=sys.stderr)
            model = None
            try:
                if ss is not None:
                    model = roofline_for_sharded(
                        ss, solver=skind, nrhs=args.nrhs,
                        hbm_gbps=args.hbm_gbps, sstep=options.sstep,
                        halo_wire=options.halo_wire)
                else:
                    model = roofline_for_operator(
                        dev, solver=skind, nrhs=args.nrhs,
                        hbm_gbps=args.hbm_gbps, sstep=options.sstep)
            except Exception as e:
                print(f"warning: --explain: roofline model unavailable: "
                      f"{e}", file=sys.stderr)
        if audit is not None:
            # s-step bodies advance s solver iterations: the printed
            # report and the exported per-solver-iteration counts both
            # carry the 1/s accounting
            ipb = max(options.sstep, 1)
            print(format_comm_audit(
                audit, title=f"{solver}, nparts={args.nparts}, "
                             f"nrhs={args.nrhs}",
                iters_per_body=ipb))
            intro["comm_audit"] = audit.as_dict(iters_per_body=ipb)
        if verdict_line is not None:
            print(verdict_line)
        if model is not None:
            print(model.report())
            intro["roofline"] = model.as_dict()
            intro["model"] = model
        # the /11 wire-accounting block: what dtype the halo messages
        # actually cross the mesh at, and what fraction of the
        # identity-wire payload that saves (null ratio single-chip —
        # there is no halo to compress)
        from acg_tpu.parallel.halo import wire_itemsize
        vdt = np.dtype(args.dtype)
        wdt = {"bf16": "bfloat16", "int16-delta": "int16"}.get(
            options.halo_wire, vdt.name)
        wi = wire_itemsize(options.halo_wire, vdt)
        intro["halo_wire"] = {
            "wire": options.halo_wire, "dtype": wdt,
            "itemsize": int(wi),
            "bytes_saved_ratio": (1.0 - wi / vdt.itemsize
                                  if ss is not None else None)}

    def _per_op(res):
        """Fill the per-op table; runs for failed solves too — per-op
        timing does not depend on convergence."""
        if not args.per_op_stats or res is None:
            return
        if ss is not None:
            from acg_tpu.utils.profile import profile_dist_ops
            profile_dist_ops(ss, res.stats, res.niterations,
                             pipelined=pipelined,
                             replace_every=options.replace_every)
        if dev is not None:
            from acg_tpu.utils.profile import profile_ops
            profile_ops(dev, res.stats, res.niterations,
                        pipelined=pipelined,
                        replace_every=options.replace_every)

    if args.residual_replacement and not pipelined:
        print("warning: --residual-replacement applies to pipelined "
              "solvers only (--solver acg-pipelined"
              + ("; the s-step loop replaces its residual every block "
                 "by construction" if sstep_mode else "")
              + "); ignored", file=sys.stderr)
    if (args.output_halo or args.output_comm_matrix) and args.nparts <= 1:
        print("warning: --output-halo/--output-comm-matrix describe the "
              "inter-shard pattern and require --nparts > 1; ignored",
              file=sys.stderr)
    if args.per_op_stats and (solver == "host" or solver.startswith("petsc")):
        # _per_op times the DEVICE op classes (dev/ss); the host and scipy
        # solvers build neither, so the table would silently stay empty
        print("warning: --per-op-stats times the device op classes and "
              f"applies to the acg* solvers only (--solver {solver} "
              "builds no device operator); ignored", file=sys.stderr)
    if args.explain and (solver == "host" or solver.startswith("petsc")):
        print("warning: --explain audits the compiled device program and "
              f"applies to the acg* solvers only (--solver {solver} "
              "compiles none); ignored", file=sys.stderr)
    if args.resilient and (solver == "host" or solver.startswith("petsc")):
        if fault_specs:
            # the plain host/petsc path has no consumer for ANY fault
            # kind — silently dropping specs that were validated above
            # would report a run that tested nothing
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           f"--inject-fault requires a device solver "
                           f"under --resilient (--solver {solver} has "
                           "no injection sites)")
        print("warning: --resilient supervises the acg* device solvers "
              f"(--solver {solver} IS the host-oracle ladder rung); "
              "running the plain solve", file=sys.stderr)
        args.resilient = False
    if device_faults and not args.resilient \
            and (solver == "host" or solver.startswith("petsc")):
        print("warning: --inject-fault corrupts the compiled device "
              f"loop and applies to the acg* solvers only (--solver "
              f"{solver}); ignored", file=sys.stderr)
        device_faults = []
    if args.resilient and sstep_mode:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "--resilient supervises the classic/pipelined "
                       "solvers; the s-step loop certifies its own "
                       "exits and falls back to classic CG on an "
                       "indefinite Gram (run --solver acg under "
                       "--resilient instead)")
    if args.resilient and deep_mode:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "--resilient supervises the classic/pipelined "
                       "solvers; the deep-pipelined loop certifies "
                       "every exit against the true residual and "
                       "falls back to classic CG on persistent "
                       "drift/breakdown already (run --solver acg "
                       "under --resilient instead)")
    if args.per_op_stats and sstep_mode:
        print("warning: --per-op-stats has no per-op model for the "
              "s-step block structure yet; ignored", file=sys.stderr)
        args.per_op_stats = False
    if args.check_every != 1 and sstep_mode:
        print("warning: --check-every has no effect on the s-step loop "
              "(convergence is decided at every s-iteration block "
              "boundary, the Gram reduction's natural cadence); ignored",
              file=sys.stderr)
    if args.explain and args.resilient:
        print("warning: --explain audits ONE compiled program; a "
              "resilient solve may run several (per ladder rung) — "
              "skipped under --resilient", file=sys.stderr)
    elif args.explain and device_faults:
        # compile_step would audit the fault-FREE program (and the
        # pipelined fused plan differs: injection gates off the pipe2d
        # mega-kernel), contradicting the audit's what-runs-is-what-is-
        # audited contract — skip rather than mislead
        print("warning: --explain audits the fault-free program and "
              "--inject-fault runs the injection-shaped one; skipped",
              file=sys.stderr)
        args.explain = False

    def _export_stats(res, reduced):
        """--output-stats-json: one machine-readable document carrying
        the full stats block (runs for failed solves too, like the
        printed block — a non-converged trajectory is exactly what the
        telemetry is for).  ``reduced`` is the cross-process-reduced
        SolveStats, computed ONCE by the caller and shared with the
        printed block (the reduction is a collective in multi-process
        runs — issue it once, and export exactly what is printed)."""
        if not args.output_stats_json or res is None:
            return
        from acg_tpu.obs.export import (build_stats_document,
                                        sanitize_tree, write_stats_json)
        roofline = intro["roofline"]
        if roofline is not None and res.stats is not None:
            # price the measured rate against the predicted ceiling —
            # the "% of roofline" number the introspection layer exists
            # to report (see PERF.md "Roofline methodology").  Both sides
            # are LOOP iterations/sec: one loop iteration advances all
            # nrhs systems and the model's bytes_per_iter already carries
            # the ×B vector streams
            measured = res.stats.iterations_per_sec()
            roofline = dict(roofline,
                            measured_iters_per_sec=measured,
                            roofline_frac=intro["model"].frac(measured))
        from acg_tpu.obs.metrics import snapshot_or_none
        doc = build_stats_document(
            solver=solver, options=options, res=res, stats=reduced,
            nunknowns=A.nrows, nparts=args.nparts,
            phases=tracer.as_dicts(),
            introspection=sanitize_tree(
                {"comm_audit": intro["comm_audit"],
                 "roofline": roofline,
                 "halo_wire": intro["halo_wire"]}),
            resilience=resil["report"],
            contract=intro["contract"],
            metrics=snapshot_or_none())
        write_stats_json(args.output_stats_json, doc)
        _log(args, f"stats document written to {args.output_stats_json!r}")

    def _write_trace():
        """--trace-json: the host phase timeline in Chrome trace-event
        format (runs for failed solves too — a post-mortem wants the
        timeline most)."""
        if not args.trace_json:
            return
        from acg_tpu.obs.events import write_chrome_trace
        write_chrome_trace(args.trace_json, tracer=tracer)
        _log(args, f"chrome trace written to {args.trace_json!r}")

    try:
        if solver == "host":
            from acg_tpu.solvers.cg_host import cg_host
            with tracer.span("solve"):
                res = cg_host(A, b, x0=x0, options=options)
        elif solver.startswith("petsc"):
            from acg_tpu.solvers.baseline import cg_scipy
            with tracer.span("solve"):
                # --output-stats-json consumes the trajectory, so opt
                # into per-iteration true-residual recording (an extra
                # SpMV per iteration inside the baseline's timed window)
                res = cg_scipy(A, b, x0=x0, options=options,
                               record_history=(True if args.output_stats_json
                                               else None))
        elif args.resilient:
            # the self-healing path: segmented supervision + escalation
            # ladder (acg_tpu/robust/supervisor.py); the supervisor
            # builds its own operators per ladder rung and records each
            # segment as a span on THIS tracer, so the recovery
            # timeline lands in the exported phases block
            from acg_tpu.robust.supervisor import solve_resilient
            if args.partition:
                print("warning: --resilient partitions internally; "
                      "--partition file ignored (use --partition-method)",
                      file=sys.stderr)
            with tracer.span("solve"), _maybe_profile():
                res, rep = solve_resilient(
                    A, b, x0=x0, options=options,
                    solver="cg-pipelined" if pipelined else "cg",
                    nparts=args.nparts, dtype=np.dtype(args.dtype),
                    fmt=args.format, mat_dtype=mat_dtype,
                    halo=HaloMethod(args.halo),
                    partition_method=args.partition_method,
                    seed=args.seed, max_restarts=args.max_restarts,
                    checkpoint_path=args.write_checkpoint,
                    checkpoint_every=args.checkpoint_every,
                    faults=fault_specs, tracer=tracer)
            resil["report"] = rep.as_dict()
            if args.verbose:
                for s in rep.steps:
                    _log(args, f"[resilience] {s.action}: {s.detail}")
        elif args.nparts > 1:
            from acg_tpu.solvers.cg_dist import (build_sharded, cg_dist,
                                                 cg_pipelined_dist)
            from acg_tpu.partition.cache import (cached_partition_graph,
                                                 graph_hashes,
                                                 resolve_prep_cache)
            # ONE resolved cache instance and ONE O(nnz) content hash
            # (the split structure/values triple) shared by the
            # partition lookup and the partitioned-system lookup inside
            # build_sharded
            prep = resolve_prep_cache(_cli_prep_cache(args))
            ghash = graph_hashes(A) if prep is not None else None
            part = None
            if args.partition:
                pm = read_mtx(args.partition,
                              binary=args.binary_partition or None)
                part = pm.vals.astype(np.int32)
            else:
                with tracer.span("partition"):
                    part = cached_partition_graph(
                        A, args.nparts, method=args.partition_method,
                        seed=args.seed, cache=prep, ghash=ghash)
            with tracer.span("operator-build"):
                ss = build_sharded(
                    A, nparts=args.nparts, part=part,
                    dtype=np.dtype(args.dtype),
                    method=HaloMethod(args.halo),
                    partition_method=args.partition_method, seed=args.seed,
                    mat_dtype=mat_dtype, fmt=args.format,
                    prep_cache=prep, ghash=ghash)
            if args.output_halo:
                from acg_tpu.parallel.halo import halo_describe
                print(halo_describe(ss.ps, ss.halo))
            if args.output_comm_matrix:
                from acg_tpu.partition.graph import comm_matrix
                M = comm_matrix(ss.ps)
                cm = MtxFile(nrows=M.shape[0], ncols=M.shape[1],
                             nnz=int((M > 0).sum()), field="integer")
                r, c = np.nonzero(M)
                cm.rowidx, cm.colidx, cm.vals = r, c, M[r, c]
                sys.stdout.write(
                    f"%%MatrixMarket matrix coordinate integer general\n"
                    f"{M.shape[0]} {M.shape[1]} {len(r)}\n")
                for i, j, vv in zip(r + 1, c + 1, M[r, c]):
                    sys.stdout.write(f"{i} {j} {vv}\n")
            _run_explain(ss=ss)
            if sstep_mode:
                from acg_tpu.solvers.cg_dist import cg_sstep_dist
                fn = cg_sstep_dist
            elif deep_mode:
                from acg_tpu.solvers.cg_dist import cg_pipelined_deep_dist
                fn = cg_pipelined_deep_dist
            else:
                fn = cg_pipelined_dist if pipelined else cg_dist
            if nwarmup:
                with tracer.span("compile/warmup"), _warm_mute():
                    for _ in range(nwarmup):
                        fn(ss, b, x0=x0, options=options,
                           fmt=args.format)
            with tracer.span("solve"), _maybe_profile():
                # fmt rides along purely for the path report: the
                # prebuilt system pins the layout, and a forced format
                # must show up as such in the stats block
                # (SolveResult.kernel_note)
                res = fn(ss, b, x0=x0, options=options, fmt=args.format,
                         fault=device_faults[0] if device_faults
                         else None)
        else:
            from acg_tpu.solvers.cg import (build_device_operator, cg,
                                            cg_pipelined)
            with tracer.span("operator-build"):
                dev = build_device_operator(A, dtype=np.dtype(args.dtype),
                                            fmt=args.format,
                                            mat_dtype=mat_dtype)
            _run_explain(dev=dev)
            if sstep_mode:
                from acg_tpu.solvers.cg import cg_sstep
                fn = cg_sstep
            elif deep_mode:
                from acg_tpu.solvers.cg import cg_pipelined_deep
                fn = cg_pipelined_deep
            else:
                fn = cg_pipelined if pipelined else cg
            if nwarmup:
                with tracer.span("compile/warmup"), _warm_mute():
                    for _ in range(nwarmup):
                        fn(dev, b, x0=x0, options=options,
                           fmt=args.format)
            with tracer.span("solve"), _maybe_profile():
                # fmt: path-report only (operator already built); see the
                # distributed branch above
                res = fn(dev, b, x0=x0, options=options, fmt=args.format,
                         fault=device_faults[0] if device_faults
                         else None)
    except AcgError as e:
        res = getattr(e, "result", None)
        rep = getattr(e, "recovery", None)
        if rep is not None:
            # a failed resilient solve still exports its full
            # RecoveryReport — the post-mortem is the point
            resil["report"] = rep.as_dict()
        print(f"error: {e}", file=sys.stderr)
        if res is None:
            return 1
        # fall through to print stats for the failed solve, like the
        # reference prints stats before reporting non-convergence; a
        # checkpoint of the partial solution enables --resume
        _checkpoint(res)
        _per_op(res)
        reduced = reduce_stats_across_processes(res.stats)
        _export_stats(res, reduced)
        _write_trace()
        print(format_solver_stats(reduced, res, options,
                                  nunknowns=A.nrows, nprocs=args.nparts))
        return 1
    if device_faults and not args.resilient and res is not None:
        # the solve succeeded despite an injection request: say exactly
        # why, or a vacuous trial reads as "the solver survived a
        # fault" (the supervisor's fault-unfired steps and the fuzzer's
        # vacuous counter guard the same hole)
        f = device_faults[0]
        if res.niterations <= f.iteration:
            print(f"warning: injected fault {f} never fired (solve "
                  f"ended after {res.niterations} iteration(s), before "
                  "the fault window)", file=sys.stderr)
        elif f.mode == "scale":
            print(f"warning: injected fault {f} fired, but scale-mode "
                  "corruption is finite and invisible to the "
                  "non-finiteness guard — use --resilient to certify "
                  "the true residual", file=sys.stderr)
    _checkpoint(res)
    _per_op(res)
    reduced = reduce_stats_across_processes(res.stats)
    _export_stats(res, reduced)
    _write_trace()

    # 4. stats block (ref acgsolver_fwrite, acg/cg.c:665-828)
    print(format_solver_stats(reduced, res, options, nunknowns=A.nrows,
                              nprocs=args.nparts))

    # 5. manufactured-solution error report (ref cuda/acg-cuda.c:2376-2385)
    if xstar is not None:
        # report ONE representative error (a norm over all K identical
        # rows would inflate by sqrt(K) and stop being comparable with
        # the K=1 number)
        x_err = _first_system(res.x)
        x0_err = None if x0 is None else _first_system(x0)
        err = float(np.linalg.norm(x_err - xstar))
        err0 = float(np.linalg.norm(xstar if x0_err is None
                                    else xstar - x0_err))
        print(f"manufactured solution error: {args.numfmt % err} "
              f"(initial: {args.numfmt % err0})")

    # 6. solution output (ref cuda/acg-cuda.c:2388-2425)
    x_out = np.asarray(res.x)
    if x_out.ndim == 2:
        # Matrix Market vectors are 1-D: write ONE representative
        # solution (_first_system)
        if args.output_solution or not args.quiet:
            print(f"note: --nrhs {res.nrhs}: writing the first system's "
                  "solution", file=sys.stderr)
        x_out = _first_system(x_out)
    if args.output_solution:
        write_mtx(args.output_solution, vector_to_mtx(x_out),
                  numfmt=args.numfmt)
    elif not args.quiet:
        for v in x_out:
            sys.stdout.write((args.numfmt % v) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
