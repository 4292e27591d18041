"""Closed-loop serving benchmark over a Session (ROADMAP item 3).

Measures what the serve layer exists to amortize: a request generator
drives N right-hand sides through a :class:`~acg_tpu.serve.SolverService`
(coalescing queue + executable cache) with seeded arrival jitter, across
a sweep of B-buckets, and reports

- **requests/s** (closed loop: the N requests' total wall),
- **cold wall** — the first request, which pays operator build + compile
  (exactly the per-invocation cost the one-shot CLI pays every time),
- **amortized warm wall** per request at the steady state,

so the headline claim ("a warm session serves a request for the price
of one batched dispatch, not one pipeline run") is a measured number on
the gated artifact trajectory.

One JSON line per configuration through the shared
:func:`~acg_tpu.obs.export.bench_record` schema (linted by
``scripts/check_stats_schema.py`` inside BENCH_* wrappers).

``--replicas N`` (ISSUE 15) runs the same closed loop through a
:class:`~acg_tpu.serve.fleet.Fleet` of N replicas and adds the fleet
columns — aggregate req/s, per-replica share and routing skew
(max−min share) — so ROADMAP item 1(c)'s "linear request throughput
scaling" claim is a measured row on the gated trajectory, not prose.

``--elastic`` (ISSUE 19, needs ``--replicas >= 2``) measures the COST
of self-healing instead: the closed loop runs through an elastic fleet,
one replica is killed halfway through, and the record reports the
replacement's **time-to-READY** (spawn through probe-gated admission)
— measured twice, once WARM (``share_prepared=True``: the resurrection
hits the process prepared-operator cache and pays zero re-prep) and
once COLD (``share_prepared=False``: full operator rebuild) — plus the
**throughput dip**: closed-loop req/s before vs after the kill, the
measured serving price of losing and regrowing a replica.

``--sequence`` (ISSUE 20) measures **iteration amortization** instead
of wall amortization: a seeded correlated request stream (random-walk
RHS, ``b_{t+1} = b_t + sigma*||b_t||*w_t``) is served twice over the
same right-hand sides — once WARM (``Session(recycle=True)`` +
``SolverService(warm_start=True)``: each solve may start from the
nearest recent solution, certified by a true-residual check) and once
COLD — both to the same FIXED absolute accuracy
(``residual_atol = tol*||b_0||``; a relative-to-``r0`` stop would
merely tighten the warm target instead of shortening it).  The run
reports per-request iteration counts, their decay, and the aggregate
iterations + req/s speedup, and writes the gated
``acg-tpu-seqbench/1`` artifact (``--output``), schema-validated
before the write.  Every solution in BOTH streams is true-residual
certified; a stream with any uncertified answer reports
``all_certified: false`` and the bench exits non-zero.

Usage:
  python scripts/bench_serve.py [--grid N] [--n-requests N]
                                [--buckets 1,4,8] [--jitter-ms 2]
                                [--replicas N]
  python scripts/bench_serve.py --replicas 2 --elastic  # healing cost
  python scripts/bench_serve.py --sequence --nparts 4 --cpu-mesh 4 \
                                --output SEQBENCH_r01.json
  python scripts/bench_serve.py --dry-run     # CPU-sized smoke pass

``--dry-run`` shrinks everything (tiny grid, few requests, no sleeps)
so the full wiring — session build, queue coalescing, demux, record
schema — executes in seconds on the CPU backend; the tier-1 smoke test
runs exactly this (and ``--sequence --dry-run`` is check_all's
seq-bench leg, printing its own summary without touching the default
mode's two-record output).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def run_point(A, *, solver: str, options, n_requests: int,
              max_batch: int, jitter_s: float, rng, dry_run: bool,
              replicas: int = 1):
    """One closed-loop sweep point (``replicas > 1``: the same closed
    loop through a Fleet — cold wall then covers every replica's
    compile, and the fleet columns ride the record).  Returns the
    metrics dict."""
    from acg_tpu.serve import Fleet, Session, SolverService

    t0 = time.perf_counter()
    if replicas > 1:
        svc = Fleet(A, replicas=replicas, solver=solver,
                    options=options, max_batch=max_batch,
                    seed=int(rng.integers(2 ** 31)),
                    session_kw=dict(prep_cache=None,
                                    share_prepared=False))
    else:
        session = Session(A, options=options, prep_cache=None,
                          share_prepared=False)
        svc = SolverService(session, solver=solver, options=options,
                            max_batch=max_batch)
    n = A.nrows
    dtype = (svc.replicas[0].session.dtype if replicas > 1
             else session.dtype)
    bs = rng.standard_normal((n_requests, n)).astype(dtype)
    # cold request: pays compile (the one-shot CLI's per-invocation
    # toll).  A fleet's cold phase warms EVERY replica — the closed
    # loop then never routes onto a cold executable
    cold0 = time.perf_counter()
    if replicas > 1:
        svc.warmup(bs[0])
    else:
        resp = svc.solve(bs[0], request_id="cold")
        assert resp.ok, f"cold request failed: {resp.status}"
    cold_wall = time.perf_counter() - cold0
    build_wall = cold0 - t0

    # closed loop with arrival jitter: submit in bursts whose size the
    # jitter draws, await each burst (the coalescing window)
    t0 = time.perf_counter()
    i, occup, nresp = 1, 0.0, 0
    while i < n_requests:
        burst = int(rng.integers(1, max_batch + 1))
        reqs = [svc.submit(bs[j])
                for j in range(i, min(i + burst, n_requests))]
        if jitter_s > 0:
            time.sleep(float(rng.uniform(0, jitter_s)))
        for req in reqs:
            r = req.response()
            assert r.ok, f"request failed: {r.status}"
            occup += r.occupancy
            nresp += 1
        i += len(reqs)
    warm_wall = time.perf_counter() - t0
    m = {
        "requests_per_sec": nresp / warm_wall if warm_wall > 0 else None,
        "cold_wall_s": cold_wall,
        "build_wall_s": build_wall,
        "amortized_wall_s": warm_wall / max(nresp, 1),
        "mean_occupancy": occup / max(nresp, 1),
    }
    if replicas > 1:
        # the fleet columns (ISSUE 15): aggregate throughput above,
        # routing profile + per-replica load here — the "linear request
        # throughput scaling" claim as a measured row
        fst = svc.stats()
        reps = fst["replicas"].values()
        health = svc.health()
        m.update({
            "batches": sum(r["service"]["queue"]["batches"]
                           for r in reps),
            "executable_misses": sum(
                r["service"]["session"]["cache"]["executable"]["misses"]
                for r in reps),
            "health_status": health["status"],
            "failure_rate": None,
            "p50_queue_wait_ms": None, "p99_queue_wait_ms": None,
            "p50_dispatch_wall_ms": None, "p99_dispatch_wall_ms": None,
            "replicas": replicas,
            "per_replica_share": fst["routing"]["shares"],
            "routing_skew": fst["routing"]["skew"],
            "failovers": fst["routing"]["failovers"],
        })
        return m
    st = svc.stats()
    # the serving-health rolling window (ISSUE 10): queue-wait /
    # dispatch-wall percentiles and the failure rate ride the record,
    # so the gated trajectory tracks tail latency, not just throughput
    health = svc.health()

    def _p(block, key):
        v = health["window"][block][key]
        return None if v is None else round(v, 3)

    m.update({
        "batches": st["queue"]["batches"],
        "executable_misses":
            st["session"]["cache"]["executable"]["misses"],
        "health_status": health["status"],
        "failure_rate": health["window"]["failure_rate"],
        # the router-facing health fields (ISSUE 15 satellite): the
        # record pins that a drained-to-idle service reports ready
        # with nothing in flight
        "ready": health["ready"],
        "inflight": health["inflight"],
        "p50_queue_wait_ms": _p("queue_wait", "p50_ms"),
        "p99_queue_wait_ms": _p("queue_wait", "p99_ms"),
        "p50_dispatch_wall_ms": _p("dispatch_wall", "p50_ms"),
        "p99_dispatch_wall_ms": _p("dispatch_wall", "p99_ms"),
    })
    return m


def run_elastic_point(A, *, solver: str, options, n_requests: int,
                      max_batch: int, jitter_s: float, rng,
                      replicas: int, share_prepared: bool):
    """The self-healing cost point (ISSUE 19): the closed loop through
    an elastic fleet with one replica killed halfway.  The reconciler
    heals the width mid-loop; the record carries the replacement's
    time-to-READY (``share_prepared`` decides warm vs cold) and the
    before/after-kill throughput."""
    from acg_tpu.serve import Fleet
    from acg_tpu.serve.session import clear_prepared_cache

    # each point measures its own cache story: warm hits must come
    # from THIS fleet's construction, not a previous sweep point's
    clear_prepared_cache()
    fleet = Fleet(A, replicas=replicas, solver=solver,
                  options=options, max_batch=max_batch,
                  seed=int(rng.integers(2 ** 31)),
                  elastic=True, heal_interval_s=0.02,
                  session_kw=dict(prep_cache=None,
                                  share_prepared=share_prepared))
    try:
        n = A.nrows
        dtype = fleet.replicas[0].session.dtype
        bs = rng.standard_normal((n_requests, n)).astype(dtype)
        fleet.warmup(bs[0])
        kill_at_i = max(n_requests // 2, 1)
        kill_t = None
        done_t: list[float] = []
        t0 = time.perf_counter()
        i = 0
        while i < n_requests:
            burst = int(rng.integers(1, max_batch + 1))
            reqs = [fleet.submit(bs[j])
                    for j in range(i, min(i + burst, n_requests))]
            if kill_t is None and i + len(reqs) > kill_at_i:
                victim = next(r.replica_id for r in fleet.replicas
                              if r.state == "READY")
                fleet.kill(victim)
                kill_t = time.perf_counter() - t0
            if jitter_s > 0:
                time.sleep(float(rng.uniform(0, jitter_s)))
            for req in reqs:
                r = req.response()
                assert r.ok, f"request failed: {r.status}"
                done_t.append(time.perf_counter() - t0)
            i += len(reqs)
        wall = time.perf_counter() - t0
        # the reconciler heals asynchronously — wait for its record
        deadline = time.perf_counter() + 60
        while not fleet.resurrection_log \
                and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert fleet.resurrection_log, \
            "the killed replica was never resurrected"
        entry = fleet.resurrection_log[-1]
        pre = sum(t < kill_t for t in done_t)
        post = len(done_t) - pre
        rps_pre = pre / kill_t if kill_t > 0 else None
        rps_post = (post / (wall - kill_t)
                    if wall > kill_t and post else None)
        return {
            "time_to_ready_s": round(float(entry["wall_s"]), 6),
            "warm_resurrection": bool(entry["warm"]),
            "resurrections": int(fleet.resurrections),
            "kill_at_s": round(float(kill_t), 4),
            "rps_pre_kill": (None if rps_pre is None
                             else round(rps_pre, 3)),
            "rps_post_kill": (None if rps_post is None
                              else round(rps_post, 3)),
            "throughput_dip": (None if not rps_pre or not rps_post
                               else round(rps_post / rps_pre, 3)),
            "replicas": replicas,
        }
    finally:
        fleet.shutdown()


def _sequence_stream(n, requests, sigma, rng, dtype):
    """Seeded correlated RHS stream: a random walk whose step is
    ``sigma`` of the current norm — consecutive requests are near
    neighbors, the warm-start registry's favorable (and realistic:
    time-stepping, parameter continuation) regime."""
    bs = np.empty((requests, n), dtype)
    b = rng.standard_normal(n).astype(dtype)
    bs[0] = b
    for t in range(1, requests):
        step = rng.standard_normal(n)
        step /= np.linalg.norm(step)     # ||b_{t+1} - b_t|| == sigma*||b_t||
        b = (b + np.asarray(sigma * float(np.linalg.norm(b)), dtype)
             * step.astype(dtype))
        bs[t] = b
    return bs


def _run_sequence_stream(A, bs, *, solver, options, nparts: int,
                         warm: bool, tol_abs: float):
    """Serve the stream serially through one service (warm or cold);
    every solution is true-residual certified HERE, independently of
    the service's own donor certification.  Returns the per-stream
    block of the seqbench artifact."""
    from acg_tpu.serve import Session, SolverService

    sess = Session(A, nparts=nparts, options=options, prep_cache=None,
                   share_prepared=False, recycle=warm)
    svc = SolverService(sess, solver=solver, options=options,
                        max_batch=1, warm_start=warm)
    iters, served_warm, rejected = [], 0, 0
    all_certified = True
    try:
        # untimed compile warm-up on an ANTI-correlated probe (sketch
        # distance ~2 from every stream RHS, so its solution can never
        # be proposed as a donor): both streams' walls then measure
        # serving, not XLA
        r = svc.submit(np.ascontiguousarray(-bs[0])).response()
        assert r.ok, f"warm-up request failed: {r.status}"
        t0 = time.perf_counter()
        for b in bs:
            r = svc.submit(b).response()
            assert r.ok, f"sequence request failed: {r.status}"
            iters.append(int(r.audit["result"]["niterations"]))
            ws = r.audit.get("warmstart") or {}
            if ws.get("rejected"):
                rejected += 1
            elif ws.get("source") == "recycled":
                served_warm += 1
            x = np.asarray(r.result.x, np.float64)
            resid = float(np.linalg.norm(
                np.asarray(b, np.float64)
                - np.asarray(A.matvec(x), np.float64)))
            ok = bool(np.isfinite(resid) and resid <= 10.0 * tol_abs)
            all_certified = all_certified and ok
    finally:
        svc.close()
    wall = time.perf_counter() - t0
    block = {
        "iterations": iters,
        "total_iterations": int(sum(iters)),
        "wall_s": round(wall, 4),
        "req_per_s": (round(len(iters) / wall, 3) if wall > 0
                      else None),
        "all_certified": all_certified,
    }
    if warm:
        block["served_warm"] = served_warm
        block["rejected"] = rejected
    return block


def run_sequence(args) -> int:
    """The --sequence entry point: warm vs cold over one stream, the
    gated ``acg-tpu-seqbench/1`` artifact."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.obs.export import (SEQBENCH_SCHEMA,
                                    validate_seqbench_document)
    from acg_tpu.sparse import poisson3d_7pt

    if args.dry_run:
        grid, requests, maxits, tol = 8, 5, 400, 1e-5
    else:
        grid, requests, maxits, tol = (args.grid, args.n_requests,
                                       2000, args.tol)
    dtype = np.dtype(args.dtype).type
    A = poisson3d_7pt(grid, dtype=dtype)
    rng = np.random.default_rng(args.seed)
    bs = _sequence_stream(A.nrows, requests, args.sigma, rng, dtype)
    # fixed-ACCURACY serving: the stop is absolute, anchored to the
    # stream's opening norm, so warm and cold answer the same question
    # and a good donor saves decades instead of tightening the target
    tol_abs = tol * float(np.linalg.norm(np.asarray(bs[0], np.float64)))
    options = SolverOptions(maxits=maxits, residual_rtol=0.0,
                            residual_atol=tol_abs)

    blocks = {}
    for name, warm in (("cold", False), ("warm", True)):
        blocks[name] = _run_sequence_stream(
            A, bs, solver=args.solver, options=options,
            nparts=args.nparts, warm=warm, tol_abs=tol_abs)
    cold_t = blocks["cold"]["total_iterations"]
    warm_t = blocks["warm"]["total_iterations"]
    cold_rps, warm_rps = (blocks["cold"]["req_per_s"],
                          blocks["warm"]["req_per_s"])
    doc = {
        "schema": SEQBENCH_SCHEMA,
        "seed": int(args.seed),
        "config": {"solver": args.solver, "nparts": int(args.nparts),
                   "nrows": int(A.nrows), "requests": int(requests),
                   "sigma": float(args.sigma)},
        "warm": blocks["warm"],
        "cold": blocks["cold"],
        "speedup": {
            "aggregate_iterations": (round(cold_t / warm_t, 4)
                                     if warm_t else 0.0),
            "aggregate_req_per_s": (
                None if not cold_rps or not warm_rps
                else round(warm_rps / cold_rps, 4)),
        },
    }
    problems = validate_seqbench_document(doc)
    if problems:     # the writer must conform to its own schema
        for msg in problems:
            print(f"bench_serve: malformed seqbench document: {msg}",
                  file=sys.stderr)
        return 2
    print(json.dumps(doc), flush=True)
    if args.output:
        with open(args.output, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"seqbench artifact written to {args.output!r}",
              file=sys.stderr)
    if not (blocks["warm"]["all_certified"]
            and blocks["cold"]["all_certified"]):
        print("bench_serve: uncertified solution in the stream",
              file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Closed-loop serving throughput over a Session.")
    ap.add_argument("--grid", type=int, default=96,
                    help="3-D Poisson grid edge [96]")
    ap.add_argument("--n-requests", type=int, default=64,
                    help="requests per sweep point [64]")
    ap.add_argument("--buckets", default="1,4,8",
                    help="comma-separated max-batch sweep [1,4,8]")
    ap.add_argument("--jitter-ms", type=float, default=2.0,
                    help="max arrival jitter between bursts [2 ms]")
    ap.add_argument("--solver", default="cg",
                    choices=["cg", "cg-pipelined"])
    ap.add_argument("--replicas", type=int, default=1,
                    help="closed loop through a Fleet of N replicas "
                         "(adds per-replica share + routing skew) [1]")
    ap.add_argument("--elastic", action="store_true",
                    help="measure the self-healing cost instead: kill "
                         "a replica mid-loop and report time-to-READY "
                         "(warm vs cold resurrection) + the throughput "
                         "dip (needs --replicas >= 2)")
    ap.add_argument("--sequence", action="store_true",
                    help="iteration-amortization bench: serve a seeded "
                         "random-walk RHS stream warm (recycle + "
                         "warm_start) vs cold to the same absolute "
                         "accuracy; writes the acg-tpu-seqbench/1 "
                         "artifact")
    ap.add_argument("--sigma", type=float, default=1e-4,
                    help="--sequence random-walk step, as a fraction "
                         "of the current RHS norm [1e-4]")
    ap.add_argument("--nparts", type=int, default=1,
                    help="--sequence mesh partitions [1]")
    ap.add_argument("--tol", type=float, default=1e-6,
                    help="--sequence accuracy: residual_atol = "
                         "tol*||b_0|| for BOTH streams [1e-6]")
    ap.add_argument("--cpu-mesh", type=int, default=0, metavar="N",
                    help="force an N-device virtual CPU mesh before "
                         "backend init (0 = ambient backend) [0]")
    ap.add_argument("--output", metavar="FILE",
                    help="--sequence: write the SEQBENCH artifact here")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU-sized smoke pass: tiny grid, 8 requests, "
                         "no sleeps — exercises the full wiring without "
                         "a device")
    args = ap.parse_args(argv)
    if args.elastic and args.replicas < 2:
        print("bench_serve: --elastic needs --replicas >= 2 (healing "
              "is a fleet behavior)", file=sys.stderr)
        return 2

    if args.cpu_mesh:
        from acg_tpu.utils.backend import force_cpu_mesh

        force_cpu_mesh(args.cpu_mesh)
    if args.sequence:
        if not args.dry_run and not args.cpu_mesh:
            from acg_tpu.utils.backend import require_tpu

            require_tpu()
        return run_sequence(args)

    from acg_tpu.config import SolverOptions
    from acg_tpu.obs.export import bench_record
    from acg_tpu.sparse import poisson3d_7pt

    if args.dry_run:
        grid, n_req, jitter, maxits = 8, 8, 0.0, 40
    else:
        from acg_tpu.utils.backend import require_tpu

        require_tpu()
        grid, n_req = args.grid, args.n_requests
        jitter, maxits = args.jitter_ms / 1e3, 400

    dtype = np.dtype(args.dtype).type
    A = poisson3d_7pt(grid, dtype=dtype)
    options = SolverOptions(maxits=maxits, residual_rtol=1e-5)
    rng = np.random.default_rng(args.seed)

    if args.elastic:
        # the healing-cost sweep: per bucket, a warm point (shared
        # prepared-operator cache) and a cold one (full re-prep) — the
        # time-to-READY delta is the cache's measured value
        for max_batch in (int(s) for s in args.buckets.split(",")):
            for mode in ("warm", "cold"):
                m = run_elastic_point(
                    A, solver=args.solver, options=options,
                    n_requests=n_req, max_batch=max_batch,
                    jitter_s=jitter, rng=rng, replicas=args.replicas,
                    share_prepared=(mode == "warm"))
                ttr = m.pop("time_to_ready_s")
                print(json.dumps(bench_record(
                    metric=f"serve_elastic_time_to_ready_{mode}"
                           f"_poisson7pt_{grid}cubed"
                           f"_{np.dtype(dtype).name}_mb{max_batch}"
                           f"_r{args.replicas}",
                    value=round(ttr * 1e3, 3),
                    unit="ms",
                    solver=args.solver,
                    max_batch=max_batch,
                    n_requests=n_req,
                    dry_run=bool(args.dry_run),
                    **m,
                )), flush=True)
        return 0

    for max_batch in (int(s) for s in args.buckets.split(",")):
        m = run_point(A, solver=args.solver, options=options,
                      n_requests=n_req, max_batch=max_batch,
                      jitter_s=jitter, rng=rng, dry_run=args.dry_run,
                      replicas=args.replicas)
        rps = m.pop("requests_per_sec")
        for k in ("cold_wall_s", "build_wall_s"):
            m[k] = round(m[k], 4)
        m["amortized_wall_s"] = round(m["amortized_wall_s"], 5)
        m["mean_occupancy"] = round(m["mean_occupancy"], 3)
        suffix = (f"_r{args.replicas}" if args.replicas > 1 else "")
        print(json.dumps(bench_record(
            metric=f"serve_req_per_sec_poisson7pt_{grid}cubed"
                   f"_{np.dtype(dtype).name}_mb{max_batch}{suffix}",
            value=None if rps is None else round(rps, 3),
            unit="req/s",
            solver=args.solver,
            max_batch=max_batch,
            n_requests=n_req,
            dry_run=bool(args.dry_run),
            **m,
        )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
