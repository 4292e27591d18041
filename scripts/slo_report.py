#!/usr/bin/env python
"""Sustained-load SLO harness over a live serve Session (ROADMAP item 1).

``bench_serve.py`` measures CLOSED-loop throughput (submit a burst, wait,
repeat — the arrival rate adapts to the service rate, so queues never
grow); certifying "millions of users" serving needs the opposite: a
seeded **open-loop** arrival process that submits on ITS schedule
regardless of how the service is doing, which is the only way queue
growth, shedding and tail latency ever show their real faces.  This
harness drives exactly that:

- **seeded arrivals** — a Poisson process (exponential inter-arrival
  gaps drawn from ``--seed``) at ``--rate`` req/s, with a **burst
  phase** in the middle at ``--burst-rate`` (the flash-crowd model:
  steady → spike → steady), the whole schedule precomputed so a run
  reproduces exactly from its seed;
- **a live service** — requests flow through the full production path
  (:class:`~acg_tpu.serve.SolverService`: admission → coalescing queue
  → cached-executable dispatch → demux), with the admission knobs
  (``--deadline-ms``, ``--max-depth``) available so shed/timeout
  behavior under overload is measured, not assumed;
- **the SLO report** — a schema-validated ``acg-tpu-slo/3`` artifact
  (acg_tpu/obs/export.py ``validate_slo_document``): p50/p99/p999 of
  end-to-end, queue-wait and dispatch latency, throughput, the
  success/shed/timeout/degraded rates, per-status outcome counts and
  the final runtime-metrics snapshot (the registry is enabled for the
  run's duration — the harness is the metrics layer's first consumer);
- **the sentinel summary** (ISSUE 16) — ``--findings`` attaches the
  fleet observatory's serving sentinels
  (:mod:`acg_tpu.obs.sentinel`) for the run — a background poller
  evaluates queue-depth growth / shed spikes per replica — and embeds
  the resulting ``SentinelHub.summary()`` (+ finding records) as the
  /3 ``findings`` block; without the flag the block is null (older /1
  and /2 artifacts keep linting);
- **the replica-kill blip** (ISSUE 15) — ``--replicas R`` drives the
  same open-loop schedule through a :class:`~acg_tpu.serve.fleet.Fleet`
  of R replicas, and ``--kill-at T`` kills one replica T seconds into
  the measured window.  In-flight tickets fail over to survivors (zero
  lost tickets still asserted) and the /2 artifact's ``fleet`` block
  records the per-replica shares, the failed-over count and the
  **p99 failover blip**: end-to-end p99 before the kill, in the blip
  window right after it, and after the window — the measured cost of a
  replica death under sustained load;
- **the elastic recovery blip** (ISSUE 19) — ``--elastic`` serves
  through a SELF-HEALING fleet (``Fleet(elastic=True)``:
  probe-gated admission, warm resurrection from the shared
  prepared-operator cache) and emits an ``acg-tpu-slo/4`` artifact
  whose ``fleet.elastic`` sub-block records the recovery story of a
  ``--kill-at`` death under sustained load: the ``resurrections``
  count, ``time_to_ready_s`` (the replacement's spawn-to-READY wall,
  probe included), ``warm`` (did the replacement hit the prepared
  cache) and ``recovery_p99_ms`` — the ``{pre, during, post}`` e2e
  p99 around the kill, where unlike the fixed-width ``SLO_r02.json``
  blip the fleet is back at FULL width for the post window.

``--dry-run`` is the CPU-sized wiring smoke (tiny grid, ~2 s of load)
run by ``scripts/check_all.py`` and tier-1; ``--cpu-mesh`` forces the
virtual CPU mesh for full runs so multi-part and multi-replica serving
topologies are checkable without a chip (the committed
``SLO_r01.json`` / ``SLO_r02.json`` ship CPU-mesh numbers; on-chip
runs are queued in PERF.md "Open measurements").

Usage::

  python scripts/slo_report.py [--seed N] [--grid N] [--nparts P]
      [--rate RPS --duration-s D --burst-rate RPS --burst-duration-s D]
      [--deadline-ms MS] [--max-depth D] [--out SLO_rXX.json]
  python scripts/slo_report.py --replicas 2 --kill-at 6 --cpu-mesh \
      --out SLO_r02.json                          # the failover blip
  python scripts/slo_report.py --replicas 2 --kill-at 6 --elastic \
      --cpu-mesh --out SLO_r03.json               # the recovery blip
  python scripts/slo_report.py --dry-run          # tier-1 smoke
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

sys.path.insert(0, ".")

import numpy as np


def arrival_schedule(rng, phases: list[dict]) -> list[tuple[float, str]]:
    """Precompute the (t, phase_kind) arrival list for the whole run:
    per phase, exponential gaps at that phase's rate until its duration
    is spent.  Seeded ⇒ the exact schedule reproduces from --seed."""
    out = []
    t0 = 0.0
    for ph in phases:
        rate, dur = float(ph["rate_rps"]), float(ph["duration_s"])
        t = t0
        while True:
            t += float(rng.exponential(1.0 / rate)) if rate > 0 else dur
            if t >= t0 + dur:
                break
            out.append((t, ph["kind"]))
        t0 += dur
    return out


def percentiles_ms(vals) -> dict:
    if not vals:
        return {k: None for k in ("p50_ms", "p99_ms", "p999_ms",
                                  "mean_ms", "max_ms")}
    a = np.asarray(vals, np.float64) * 1e3
    return {"p50_ms": round(float(np.percentile(a, 50)), 3),
            "p99_ms": round(float(np.percentile(a, 99)), 3),
            "p999_ms": round(float(np.percentile(a, 99.9)), 3),
            "mean_ms": round(float(a.mean()), 3),
            "max_ms": round(float(a.max()), 3)}


def run_load(svc, nrows: int, schedule, rng, deadline_bound_s: float,
             dtype, kill_at: float | None = None,
             kill_fn=None) -> dict:
    """Drive the precomputed open-loop schedule; returns the raw
    samples.  One waiter thread per request collects its classified
    response — requests are NEVER awaited before the next arrival (open
    loop), and a submission that falls behind schedule submits
    immediately rather than skipping (the backlog is the point).

    ``kill_at``/``kill_fn``: the replica-kill event — ``kill_fn`` fires
    ``kill_at`` seconds after the measured window opens (a timer
    thread, so the kill lands whatever the arrival process is doing)."""
    # seeded right-hand sides, distinct per request
    bs = rng.standard_normal((len(schedule), nrows)).astype(dtype)
    samples: list[dict] = []
    lock = threading.Lock()
    waiters = []

    def wait_one(req, t_submit, t_s):
        resp = req.response(timeout=deadline_bound_s)
        if resp.status == "ERR_TIMEOUT" and not resp.shed:
            # provisional caller timeout: resume once — the drill bound
            # is generous, a second expiry is the real classification
            resp = req.response(timeout=deadline_bound_s)
        with lock:
            samples.append({
                "status": resp.status, "ok": bool(resp.ok),
                "shed": bool(resp.shed),
                "degraded": bool(resp.degraded),
                "t_s": t_s,
                "e2e_s": time.perf_counter() - t_submit,
                "queue_wait_s": float(resp.queue_wait),
                "dispatch_s": float(resp.wall),
                "replica": getattr(resp, "replica_id", None),
                "failed_over": bool(getattr(resp, "failover_from",
                                            None)),
                "trace_id": (resp.audit or {}).get(
                    "session", {}).get("trace_id")})

    t_start = time.perf_counter()
    killer = None
    if kill_at is not None and kill_fn is not None:
        killer = threading.Timer(kill_at, kill_fn)
        killer.daemon = True
        killer.start()
    for i, (t_arr, _kind) in enumerate(schedule):
        delay = t_arr - (time.perf_counter() - t_start)
        if delay > 0:
            time.sleep(delay)
        t_submit = time.perf_counter()
        req = svc.submit(bs[i])
        w = threading.Thread(target=wait_one,
                             args=(req, t_submit, t_submit - t_start))
        w.start()
        waiters.append(w)
    svc.flush()
    for w in waiters:
        w.join(timeout=300)
    if killer is not None:
        killer.cancel()
    wall = time.perf_counter() - t_start
    return {"samples": samples, "wall_s": wall,
            "submitted": len(schedule)}


def fleet_block(samples, *, replicas: int, killed: str | None,
                kill_at: float | None,
                blip_window_s: float = 2.0,
                elastic: dict | None = None) -> dict:
    """The slo-/2 ``fleet`` block: per-replica classified-response
    shares plus, when a replica was killed, the failed-over count and
    the p99 failover blip — end-to-end p99 of the samples submitted
    before the kill, inside the blip window after it, and after the
    window.  ``elastic`` (an ``--elastic`` run's resurrection metadata)
    adds the slo-/4 ``elastic`` sub-block, its ``recovery_p99_ms``
    sharing the blip windows."""
    per: dict[str, int] = {}
    for s in samples:
        if s.get("replica"):
            per[s["replica"]] = per.get(s["replica"], 0) + 1
    out = {"replicas": int(replicas), "per_replica": per,
           "kill": None, "failover": None}
    if elastic is not None:
        out["elastic"] = {**elastic, "recovery_p99_ms": None}
    if killed is None or kill_at is None:
        return out

    def _p99(win):
        vals = [s["e2e_s"] for s in win]
        return (None if not vals
                else round(float(np.percentile(
                    np.asarray(vals, np.float64) * 1e3, 99)), 3))

    pre = [s for s in samples if s["t_s"] < kill_at]
    during = [s for s in samples
              if kill_at <= s["t_s"] < kill_at + blip_window_s]
    post = [s for s in samples if s["t_s"] >= kill_at + blip_window_s]
    out["kill"] = {"replica": killed, "at_s": float(kill_at)}
    out["failover"] = {
        "failed_over": sum(s["failed_over"] for s in samples),
        "blip_window_s": float(blip_window_s),
        "blip_p99_ms": {"pre": _p99(pre), "during": _p99(during),
                        "post": _p99(post)},
    }
    if elastic is not None:
        out["elastic"]["recovery_p99_ms"] = {
            "pre": _p99(pre), "during": _p99(during),
            "post": _p99(post)}
    return out


def build_report(*, seed: int, config: dict, phases: list[dict],
                 load: dict, metrics_snapshot, fleet=None,
                 findings=None,
                 schema: str = "acg-tpu-slo/3") -> dict:
    samples = load["samples"]
    n = max(len(samples), 1)
    outcomes: dict[str, int] = {}
    for s in samples:
        outcomes[s["status"]] = outcomes.get(s["status"], 0) + 1
    # queue-wait / dispatch distributions take only requests whose
    # dispatch actually COMPLETED: shed requests never ran, and a
    # terminal mid-solve timeout reports wall 0.0 (demux never reached
    # it) with queue_wait pinned at the deadline — both would distort
    # the percentiles exactly under overload (the PR 10 window
    # discipline; end-to-end keeps every classified sample)
    ran = [s for s in samples if not s["shed"] and s["dispatch_s"] > 0]
    doc = {
        "schema": schema,
        "seed": int(seed),
        "config": config,
        "load": {
            "phases": phases,
            "submitted": int(load["submitted"]),
            "completed": len(samples),
            "wall_s": round(load["wall_s"], 3),
        },
        "latency_ms": {
            "end_to_end": percentiles_ms([s["e2e_s"] for s in samples]),
            # queue-wait / dispatch only for requests that actually ran
            # (a shed request has no meaningful wait/wall — the PR 10
            # window discipline)
            "queue_wait": percentiles_ms([s["queue_wait_s"]
                                          for s in ran]),
            "dispatch": percentiles_ms([s["dispatch_s"] for s in ran]),
        },
        "throughput_rps": (round(len(samples) / load["wall_s"], 3)
                           if load["wall_s"] > 0 else None),
        "rates": {
            "success": round(sum(s["ok"] for s in samples) / n, 4),
            "shed": round(sum(s["shed"] for s in samples) / n, 4),
            "timeout": round(sum(s["status"] == "ERR_TIMEOUT"
                                 for s in samples) / n, 4),
            "degraded": round(sum(s["degraded"] for s in samples) / n,
                              4),
        },
        "outcomes": outcomes,
        "metrics": metrics_snapshot,
        "fleet": fleet,
        # /3: the sentinel summary of a --findings run (null otherwise)
        "findings": findings,
    }
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Open-loop sustained-load SLO report over a live "
                    "serve Session (seeded Poisson + burst arrivals).")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grid", type=int, default=48,
                    help="2-D Poisson grid edge [48]")
    ap.add_argument("--nparts", type=int, default=4,
                    help="mesh devices to shard the operator over [4]")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a Fleet of R replicas (each on "
                         "its own --nparts operator) instead of one "
                         "service [1]")
    ap.add_argument("--kill-at", type=float, default=None, metavar="T",
                    help="kill one replica T seconds into the measured "
                         "window (needs --replicas >= 2): the failover "
                         "blip measurement")
    ap.add_argument("--solver", default="cg",
                    choices=["cg", "cg-pipelined"])
    ap.add_argument("--dtype", default="float64")
    ap.add_argument("--rate", type=float, default=10.0,
                    help="steady-phase Poisson arrival rate, req/s [10]")
    ap.add_argument("--duration-s", type=float, default=4.0,
                    help="each steady phase's length [4]")
    ap.add_argument("--burst-rate", type=float, default=40.0,
                    help="burst-phase arrival rate, req/s [40]")
    ap.add_argument("--burst-duration-s", type=float, default=2.0,
                    help="burst phase length [2]")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="coalescing window [5]")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline (0 = none)")
    ap.add_argument("--max-depth", type=int, default=0,
                    help="load-shedding queue bound (0 = unbounded)")
    ap.add_argument("--maxits", type=int, default=400)
    ap.add_argument("--elastic", action="store_true",
                    help="serve through a SELF-HEALING fleet "
                         "(Fleet(elastic=True): probe-gated admission, "
                         "warm resurrection) and emit an acg-tpu-slo/4 "
                         "artifact with the fleet.elastic recovery "
                         "block (needs --replicas >= 2)")
    ap.add_argument("--findings", action="store_true",
                    help="attach the serving sentinels for the run "
                         "(acg_tpu/obs/sentinel.py) and embed the "
                         "finding summary as the slo/3 findings block")
    ap.add_argument("--out", metavar="FILE", default=None,
                    help="write the acg-tpu-slo/3 artifact here "
                         "(validated before writing)")
    ap.add_argument("--cpu-mesh", action="store_true",
                    help="force the 8-device virtual CPU mesh (full "
                         "runs without a chip; --dry-run implies it)")
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU-sized wiring smoke: tiny grid, ~2 s of "
                         "load — what check_all.py and tier-1 run")
    args = ap.parse_args(argv)

    if args.dry_run or args.cpu_mesh:
        from acg_tpu.utils.backend import force_cpu_mesh

        force_cpu_mesh(8)
    else:
        from acg_tpu.utils.backend import require_tpu

        require_tpu()
    if args.dry_run:
        args.grid, args.nparts, args.maxits = 10, 1, 200
        args.rate, args.duration_s = 12.0, 0.8
        args.burst_rate, args.burst_duration_s = 40.0, 0.4
        args.max_wait_ms = 2.0

    if args.kill_at is not None and args.replicas < 2:
        print("slo_report: --kill-at needs --replicas >= 2 (a killed "
              "singleton has no survivor to fail over to)",
              file=sys.stderr)
        return 2
    if args.elastic and args.replicas < 2:
        print("slo_report: --elastic needs --replicas >= 2 (healing "
              "is a fleet behavior)", file=sys.stderr)
        return 2

    from acg_tpu.config import SolverOptions
    from acg_tpu.obs import metrics as obs_metrics
    from acg_tpu.obs.export import validate_slo_document
    from acg_tpu.serve import (AdmissionPolicy, Fleet, Session,
                               SolverService)
    from acg_tpu.sparse import poisson2d_5pt

    rng = np.random.default_rng(args.seed)
    phases = [
        {"kind": "poisson", "rate_rps": args.rate,
         "duration_s": args.duration_s},
        {"kind": "burst", "rate_rps": args.burst_rate,
         "duration_s": args.burst_duration_s},
        {"kind": "poisson", "rate_rps": args.rate,
         "duration_s": args.duration_s},
    ]
    schedule = arrival_schedule(rng, phases)
    if not schedule:
        print("slo_report: empty arrival schedule (raise --rate or "
              "--duration-s)", file=sys.stderr)
        return 2

    dtype = np.dtype(args.dtype)
    A = poisson2d_5pt(args.grid, dtype=dtype.type)
    options = SolverOptions(maxits=args.maxits, residual_rtol=1e-6)
    # the harness is the metrics layer's consumer: registry ON for the
    # run, final snapshot into the artifact, prior state restored
    was_enabled = obs_metrics.metrics_enabled()
    obs_metrics.enable_metrics()
    # the kill victim is chosen AT the kill: the replica with the most
    # in-flight work — the worst case the drill exists to measure (a
    # dead idle replica has nothing to fail over)
    victim_box: dict = {}
    try:
        pol = AdmissionPolicy(deadline_ms=args.deadline_ms,
                              max_queue_depth=args.max_depth,
                              seed=args.seed)
        if args.replicas > 1:
            # --elastic: the self-healing fleet — probe-gated
            # admission on, reconciler healing a --kill-at death
            # mid-run, replicas sharing the process prepared-operator
            # cache so the resurrection is WARM (zero re-prep: the
            # time_to_ready_s the artifact records is the warm wall)
            svc = Fleet(
                A, replicas=args.replicas, solver=args.solver,
                options=options, max_batch=args.max_batch,
                max_wait_ms=args.max_wait_ms, admission=pol,
                seed=args.seed, elastic=args.elastic,
                flightrec_capacity=max(len(schedule), 16),
                session_kw=dict(nparts=args.nparts, dtype=dtype,
                                prep_cache=None,
                                share_prepared=args.elastic))
            # warm EVERY replica outside the measured window — the
            # routed path must never pay a compile on whichever
            # replica the seed picks first
            try:
                svc.warmup(np.ones(A.nrows, dtype=dtype))
            except Exception as e:
                print(f"slo_report: fleet warmup failed ({e})",
                      file=sys.stderr)
                return 2
        else:
            session = Session(A, nparts=args.nparts, dtype=dtype,
                              options=options, prep_cache=None,
                              share_prepared=False)
            svc = SolverService(
                session, solver=args.solver, options=options,
                max_batch=args.max_batch,
                max_wait_ms=args.max_wait_ms, admission=pol,
                flightrec_capacity=max(len(schedule), 16))
            # one warm request outside the measured window: the cold
            # compile is bench_serve's metric, not an SLO tail sample
            warm = svc.solve(np.ones(A.nrows, dtype=dtype))
            if not warm.ok:
                print(f"slo_report: warmup solve failed "
                      f"({warm.status})", file=sys.stderr)
                return 2
        # baseline AFTER the warm request: the snapshot in the artifact
        # covers exactly the measured window (request counts match
        # load.submitted; the cold compile stays out of the histograms,
        # matching the "cold compile excluded" clause)
        obs_metrics.reset_metrics()
        bound = max((args.deadline_ms / 1e3) * 4, 60.0)

        # --findings: the serving sentinels watch the run.  A fleet
        # already owns a hub (replica deaths land there); a single
        # service gets a fresh one.  The poller samples health() a few
        # times a second — queue-depth growth and shed spikes are
        # window phenomena a single post-run snapshot cannot see.
        hub = poll_stop = poller = None
        if args.findings:
            from acg_tpu.obs.sentinel import SentinelHub, ServingSentinel

            hub = (svc.sentinels if args.replicas > 1
                   else SentinelHub())
            watcher = ServingSentinel(
                hub, depth_limit=(args.max_depth or 8),
                shed_spike=0.5)
            poll_stop = threading.Event()

            def _poll():
                while not poll_stop.wait(0.2):
                    try:
                        if args.replicas > 1:
                            for r in svc.replicas:
                                if r.state == "READY":
                                    watcher.evaluate(
                                        r.replica_id,
                                        r.service.health())
                        else:
                            watcher.evaluate("r0", svc.health())
                    except Exception:
                        pass

            poller = threading.Thread(target=_poll, daemon=True)
            poller.start()

        def kill_busiest():
            live = [r for r in svc.replicas if r.state == "READY"]
            victim = max(
                live,
                key=lambda r: r.service.queue.inflight).replica_id
            victim_box["id"] = victim
            svc.kill(victim)

        load = run_load(
            svc, A.nrows, schedule, rng, bound, dtype,
            kill_at=args.kill_at,
            kill_fn=(kill_busiest if args.kill_at is not None
                     else None))
        snapshot = obs_metrics.registry().snapshot()
        if poll_stop is not None:
            poll_stop.set()
            poller.join(timeout=2.0)
    finally:
        if not was_enabled:
            obs_metrics.disable_metrics()
    if args.kill_at is not None and "id" not in victim_box:
        # the operator asked for a failover measurement and no kill
        # fired (timer past the load window, or the kill thread died):
        # a clean-looking artifact with kill:null would be a lie
        print(f"slo_report: --kill-at {args.kill_at} never fired "
              "(load window ended first?) — no failover was measured",
              file=sys.stderr)
        return 1
    config = {
        "solver": args.solver, "nparts": int(args.nparts),
        "replicas": int(args.replicas),
        "grid": int(args.grid), "nrows": int(A.nrows),
        "dtype": dtype.name, "max_batch": int(args.max_batch),
        "max_wait_ms": float(args.max_wait_ms),
        "deadline_ms": float(args.deadline_ms),
        "max_depth": int(args.max_depth),
        "backend": "cpu-mesh" if (args.dry_run or args.cpu_mesh)
                   else "device",
        "dry_run": bool(args.dry_run),
        "elastic": bool(args.elastic),
    }
    elastic_meta = None
    if args.elastic:
        last = (svc.resurrection_log[-1] if svc.resurrection_log
                else None)
        elastic_meta = {
            "resurrections": int(svc.resurrections),
            "time_to_ready_s": (round(float(last["wall_s"]), 6)
                                if last else None),
            "warm": (bool(last["warm"]) if last else None),
        }
    fleet = (None if args.replicas <= 1
             else fleet_block(load["samples"], replicas=args.replicas,
                              killed=victim_box.get("id"),
                              kill_at=args.kill_at,
                              elastic=elastic_meta))
    findings = (None if hub is None
                else {**hub.summary(), "items": hub.as_dicts()})
    doc = build_report(seed=args.seed, config=config, phases=phases,
                       load=load, metrics_snapshot=snapshot,
                       fleet=fleet, findings=findings,
                       schema=("acg-tpu-slo/4" if args.elastic
                               else "acg-tpu-slo/3"))
    problems = validate_slo_document(doc)
    if problems:
        print("slo_report: non-conforming artifact:", file=sys.stderr)
        for msg in problems:
            print(f"  {msg}", file=sys.stderr)
        return 1
    if load["submitted"] != len(load["samples"]):
        print(f"slo_report: LOST TICKETS: {load['submitted']} "
              f"submitted, {len(load['samples'])} classified",
              file=sys.stderr)
        return 1
    print(json.dumps(doc))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"slo_report: artifact written to {args.out!r}",
              file=sys.stderr)
    e2e = doc["latency_ms"]["end_to_end"]
    print(f"slo_report: {load['submitted']} requests, "
          f"{doc['throughput_rps']} req/s, e2e p50/p99/p999 = "
          f"{e2e['p50_ms']}/{e2e['p99_ms']}/{e2e['p999_ms']} ms, "
          f"success rate {doc['rates']['success']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
