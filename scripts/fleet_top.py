#!/usr/bin/env python
"""Fleet observatory ops console (ISSUE 16): live replica table +
the lintable ``acg-tpu-obs/1`` artifact.

The sensor half of the ROADMAP item-2 autoscaler: build a replica
:class:`~acg_tpu.serve.fleet.Fleet`, drive a seeded open-loop-ish
request stream at it, and SCRAPE it the way an external agent would —
only through :meth:`Fleet.observe` (registry snapshot + health +
active findings per replica, no private attribute access).  Each
scrape lands in a :class:`~acg_tpu.obs.aggregate.FleetAggregator`
ring; the console renders the replica table (state / inflight / queue
depth / window p50/p99 / shed / active findings) per scrape interval,
and the final ring becomes the windowed-rollup artifact.

Sentinels watched the same run (:mod:`acg_tpu.obs.sentinel`):

- the :class:`ServingSentinel` evaluates every scrape's health block
  (queue-depth growth, shed spikes);
- a :class:`ConvergenceSentinel` consumes each classified response's
  ``SolveResult`` (iteration-count EWMA per operator hash + residual
  history scan);
- the **deliberate stagnation probe**: one fault-spec'd solve (a
  scale-mode SpMV fault mid-solve) on a run-to-maxits canary session —
  its residual history plateaus at machine precision, tripping the
  ``residual-stagnation`` finding by construction (the acceptance
  drill: the artifact must carry at least one injected finding);
- the :class:`ModelDriftSentinel` reconciles the probe's measured
  iterations/s against the static roofline ceiling and the live
  executable's re-audited collective count against the pinned
  CommAudit (on a CPU mesh the rate reconciliation trips the
  below-floor ``model-drift`` finding — a CPU is honestly not the
  modeled TPU; see PERF.md "drift sentinel denominators").

``--once`` renders one table and writes the validated artifact (the
``scripts/check_all.py`` leg and the committed ``OBS_r01.json`` /
``OBS_r02.json``); without it the console loops ``--scrapes`` times
at ``--interval-s``.  ``--dry-run`` is the CPU-sized smoke.

The in-process run also feeds a
:class:`~acg_tpu.obs.history.MetricsHistory` sampler (one sample per
scrape round), so the emitted artifact is the ``acg-tpu-obs/2``
superset: the raw sampled series + windowed rate/gauge/quantile
queries ride in the ``history`` block (ISSUE 18).  Against an ELASTIC
fleet (``--elastic``, or a wire scrape of one) the console shows the
elastic line — target width, resurrections, QUARANTINED count, the
last autoscaler decision with its reason — and the artifact upgrades
to ``acg-tpu-obs/3`` (the fleet block carries the elastic keys).

``--url http://HOST:PORT`` is the WIRE mode (ISSUE 18): the console
runs against a live observability plane
(:class:`~acg_tpu.serve.obsplane.ObsPlane`, CLI ``--obs-port``)
instead of building an in-process Fleet — scrapes hit
``GET /metrics.json``, findings come from ``/findings``, the history
block from ``/history``, and the ``--once`` artifact is built from
the same aggregation path (identical modulo timestamps/meta to the
in-process document for the same fleet state).  Read-only: wire mode
drives no traffic and runs no stagnation probe (it cannot inject a
fault through a read-only plane), so the probe-finding assertion
applies to in-process runs only.

Usage::

  python scripts/fleet_top.py --once --dry-run --out /tmp/OBS.json
  python scripts/fleet_top.py --once --cpu-mesh --out OBS_r02.json
  python scripts/fleet_top.py --cpu-mesh --scrapes 6 --interval-s 1
  python scripts/fleet_top.py --url http://127.0.0.1:9100 --once \\
      --out /tmp/OBS_wire.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _fmt(v, nd: int = 1) -> str:
    return "-" if v is None else f"{v:.{nd}f}"


def replica_table(obs: dict) -> str:
    """Render one Fleet.observe() block as the ops console table."""
    head = (f"{'replica':<9}{'state':<10}{'inflight':>9}{'depth':>7}"
            f"{'p50_ms':>9}{'p99_ms':>9}{'shed':>6}{'fail%':>7}"
            f"{'findings':>9}")
    lines = [head, "-" * len(head)]
    for rid in sorted(obs["replicas"]):
        r = obs["replicas"][rid]
        h = r.get("health") or {}
        w = h.get("window") or {}
        dw = w.get("dispatch_wall") or {}
        fr = w.get("failure_rate")
        lines.append(
            f"{rid:<9}{r.get('state', '?'):<10}"
            f"{r.get('inflight', 0):>9}{h.get('depth', 0) or 0:>7}"
            f"{_fmt(dw.get('p50_ms')):>9}{_fmt(dw.get('p99_ms')):>9}"
            f"{h.get('shed', 0) or 0:>6}"
            f"{_fmt(None if fr is None else fr * 100):>7}"
            f"{len(r.get('findings') or []):>9}")
    fs = obs.get("findings_summary") or {}
    lines.append(f"fleet: {obs.get('status', '?')}  "
                 f"ready={obs.get('replicas_ready')}  "
                 f"failovers={obs.get('failovers')}  "
                 f"findings={fs.get('total', 0)} "
                 f"(worst={fs.get('worst')})")
    if "resurrections" in obs:
        # the elastic line (ISSUE 19): QUARANTINED members show in the
        # state column; here the healing/width story + last decision
        a = obs.get("autoscaler")
        decision = ("-" if not a else
                    f"{a.get('decision')} {a.get('previous')}->"
                    f"{a.get('target')} ({a.get('reason')})")
        lines.append(f"elastic: target={obs.get('target_replicas')}  "
                     f"resurrections={obs.get('resurrections')}  "
                     f"quarantined={obs.get('quarantined')}  "
                     f"autoscaler={decision}")
    for rid in sorted(obs["replicas"]):
        for f in (obs["replicas"][rid].get("findings") or []):
            lines.append(f"  ! {rid} [{f['severity']}] {f['kind']}: "
                         f"{f['summary']}")
    return "\n".join(lines)


def _stagnation_probe(A, hub, solver: str, dtype) -> dict:
    """The deliberate finding: a fault-spec'd run-to-maxits solve on a
    canary session.  All stopping criteria zeroed => the loop runs all
    maxits iterations; past convergence the residual plateaus at
    machine precision, so the trailing-window improvement is ~0 and
    the stagnation sentinel MUST trip.  The scale-mode SpMV fault at
    iteration 10 adds the injected mid-solve jolt the drill names;
    the probe's own sentinel runs with the divergence tripwire
    disabled (``divergence_factor=inf``) so the transient jolt — which
    CG recovers from — cannot fire first and mask the plateau, which
    is the detector under test here."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.obs.roofline import roofline_for_operator
    from acg_tpu.obs.sentinel import (ConvergenceSentinel,
                                      ModelDriftSentinel)
    from acg_tpu.partition.cache import graph_hash
    from acg_tpu.robust.faults import FaultSpec
    from acg_tpu.serve.session import Session

    conv = ConvergenceSentinel(hub, divergence_factor=float("inf"))

    opts = SolverOptions(maxits=160, residual_rtol=0.0,
                         residual_atol=0.0, diffatol=0.0, diffrtol=0.0)
    sess = Session(A, dtype=dtype, options=opts, prep_cache=None,
                   share_prepared=False)
    try:
        rng = np.random.default_rng(7)
        b = rng.standard_normal(A.nrows).astype(dtype)
        res = sess.solve(b, solver=solver, options=opts,
                         fault=FaultSpec(kind="spmv", iteration=10,
                                         mode="scale"))
        ophash = graph_hash(A)
        found = conv.observe_result(res, operator_hash=ophash)
        # predicted-vs-measured reconciliation off the same probe: the
        # roofline ceiling is the rate denominator; the warm re-audited
        # executable supplies the measured collective count (a drift
        # there would mean the cached program itself changed)
        model = roofline_for_operator(sess.operator, solver=solver)
        pinned = sess.audit(solver=solver, options=opts)
        measured = (res.niterations / res.stats.tsolve
                    if res.stats.tsolve > 0 else 0.0)
        drift = ModelDriftSentinel(hub).reconcile(
            measured_iters_per_sec=measured,
            predicted_iters_per_sec=model.predicted_iters_per_sec,
            collectives_measured=sess.audit(
                solver=solver, options=opts).allreduce.count,
            collectives_predicted=pinned.allreduce.count,
            operator_hash=ophash)
        return {"niterations": int(res.niterations),
                "iters_per_sec": float(measured),
                "predicted_iters_per_sec":
                    model.as_dict()["predicted_iters_per_sec"],
                "findings": [f.kind for f in found + drift]}
    finally:
        sess.close()


def _http_json(url: str, timeout: float = 15.0):
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _as_fleet_obs(obs: dict) -> dict:
    """Normalize a scrape unit to the Fleet.observe() shape: a bare
    SolverService's ``observe()`` (one replica, no fleet block)
    becomes a one-replica fleet view so the table renderer and the
    artifact's ``fleet`` block work unchanged."""
    if "replicas" in obs:
        return obs
    rid = str(obs.get("replica_id"))
    h = obs.get("health") or {}
    return {
        "status": h.get("status", "?"),
        "replicas_ready": 1 if h.get("ready") else 0,
        "failovers": 0,
        "replicas": {rid: {"replica_id": rid,
                           "metrics": obs.get("metrics"),
                           "health": h,
                           "state": ("READY" if h.get("ready")
                                     else "DEAD"),
                           "routed": int(h.get("requests") or 0),
                           "failovers_in": 0,
                           "inflight": int(h.get("inflight") or 0),
                           "findings": []}},
        "findings_summary": {"total": 0, "worst": None, "by_kind": {},
                             "by_severity": {}, "by_replica": {}},
    }


def _main_url(args) -> int:
    """Wire mode: the ops console against a live observability plane
    (read-only — scrape, render, emit; no traffic, no probe)."""
    import urllib.error

    from acg_tpu.obs.aggregate import (FleetAggregator,
                                       build_obs_document,
                                       write_obs_document)
    from acg_tpu.obs.export import validate_obs_document

    base = args.url.rstrip("/")
    nscrapes = max(args.scrapes, 2)
    agg = FleetAggregator(capacity=nscrapes)
    obs = None
    for i in range(nscrapes):
        obs = _as_fleet_obs(_http_json(base + "/metrics.json"))
        agg.ingest({rid: r.get("metrics")
                    for rid, r in obs["replicas"].items()})
        if not args.once and i < nscrapes - 1:
            print(replica_table(obs))
            print()
        if i < nscrapes - 1 and args.interval_s > 0:
            time.sleep(args.interval_s)
    print(replica_table(obs))

    fnd = _http_json(base + "/findings")
    try:
        history = _http_json(base + "/history")
    except urllib.error.HTTPError as e:
        if e.code != 404:       # 404 = no sampler attached: a /1 doc
            raise
        history = None
    doc = build_obs_document(
        agg, fleet=obs, findings=fnd.get("findings") or [],
        history=history,
        meta={"seed": int(args.seed), "mode": "url", "url": base,
              "scrapes": nscrapes})
    problems = validate_obs_document(doc)
    if problems:
        print("fleet_top: non-conforming artifact:", file=sys.stderr)
        for msg in problems:
            print(f"  {msg}", file=sys.stderr)
        return 1
    if args.out:
        write_obs_document(doc, args.out)
        print(f"fleet_top: artifact written to {args.out!r}",
              file=sys.stderr)
    else:
        print(json.dumps(doc["findings_summary"]))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Fleet observatory: scrape a live replica fleet "
                    "(in-process, or over the HTTP observability "
                    "plane with --url), render the replica table, "
                    "emit the acg-tpu-obs/1../3 artifact.")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grid", type=int, default=24,
                    help="2-D Poisson grid edge [24]")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--solver", default="cg",
                    choices=["cg", "cg-pipelined"])
    ap.add_argument("--dtype", default="float64")
    ap.add_argument("--maxits", type=int, default=400)
    ap.add_argument("--scrapes", type=int, default=4,
                    help="scrape rounds (ring samples) [4]")
    ap.add_argument("--interval-s", type=float, default=0.5,
                    help="pause between scrape rounds [0.5]")
    ap.add_argument("--requests-per-scrape", type=int, default=4)
    ap.add_argument("--once", action="store_true",
                    help="one final table + the artifact, no live loop "
                         "pacing (CI mode)")
    ap.add_argument("--out", metavar="FILE", default=None,
                    help="write the validated acg-tpu-obs/2 artifact")
    ap.add_argument("--cpu-mesh", action="store_true",
                    help="force the 8-device virtual CPU mesh")
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU-sized smoke (tiny grid, 2 scrapes) — the "
                         "check_all.py leg")
    ap.add_argument("--elastic", action="store_true",
                    help="build the in-process fleet elastic "
                         "(ISSUE 19: probe-gated admission, reconciler "
                         "on) — the table grows the elastic line "
                         "(target width, resurrections, QUARANTINED "
                         "count, last autoscaler decision) and the "
                         "artifact the acg-tpu-obs/3 fleet block; "
                         "wire mode shows the same line whenever the "
                         "scraped fleet is elastic")
    ap.add_argument("--url", metavar="URL", default=None,
                    help="scrape a live observability plane "
                         "(http://HOST:PORT) instead of building an "
                         "in-process fleet; read-only wire mode")
    args = ap.parse_args(argv)

    if args.url:
        return _main_url(args)

    if args.dry_run or args.cpu_mesh:
        from acg_tpu.utils.backend import force_cpu_mesh

        force_cpu_mesh(8)
    else:
        from acg_tpu.utils.backend import require_tpu

        require_tpu()
    if args.dry_run:
        args.grid, args.maxits = 10, 200
        args.scrapes, args.requests_per_scrape = 2, 3
        args.interval_s = 0.0

    from acg_tpu.config import SolverOptions
    from acg_tpu.obs import metrics as obs_metrics
    from acg_tpu.obs.aggregate import (FleetAggregator,
                                       build_obs_document,
                                       write_obs_document)
    from acg_tpu.obs.export import validate_obs_document
    from acg_tpu.obs.history import MetricsHistory
    from acg_tpu.obs.sentinel import (ConvergenceSentinel,
                                      ServingSentinel)
    from acg_tpu.serve.fleet import Fleet
    from acg_tpu.sparse import poisson2d_5pt

    dtype = np.dtype(args.dtype)
    A = poisson2d_5pt(args.grid, dtype=dtype.type)
    options = SolverOptions(maxits=args.maxits, residual_rtol=1e-6)
    rng = np.random.default_rng(args.seed)

    was_enabled = obs_metrics.metrics_enabled()
    obs_metrics.enable_metrics()
    fleet = None
    try:
        fleet = Fleet(A, replicas=args.replicas, solver=args.solver,
                      options=options, max_batch=2, buckets=(1, 2),
                      seed=args.seed, elastic=args.elastic,
                      session_kw=dict(dtype=dtype, prep_cache=None,
                                      share_prepared=args.elastic))
        fleet.warmup(np.ones(A.nrows, dtype=dtype))

        hub = fleet.sentinels
        conv = ConvergenceSentinel(hub)
        watcher = ServingSentinel(hub, depth_limit=8)
        agg = FleetAggregator(capacity=max(args.scrapes, 2))
        # the /2 history block: manually sampled (no background
        # thread) — one sample per scrape round, same cadence
        history = MetricsHistory(capacity=max(args.scrapes + 2, 2),
                                 interval_s=max(args.interval_s, 0.001),
                                 fleet=fleet)

        def scrape() -> dict:
            obs = fleet.observe()
            agg.ingest({rid: r.get("metrics")
                        for rid, r in obs["replicas"].items()})
            history.sample()
            for rid, r in obs["replicas"].items():
                if r.get("health") is not None:
                    watcher.evaluate(rid, r["health"])
            return obs

        obs = scrape()             # the window's left edge, pre-load
        for _ in range(args.scrapes - 1):
            reqs = [fleet.submit(
                rng.standard_normal(A.nrows).astype(dtype))
                for _ in range(args.requests_per_scrape)]
            fleet.flush()
            for req in reqs:
                resp = req.response(timeout=120)
                if resp.ok and resp.result is not None:
                    conv.observe_result(
                        resp.result, operator_hash=f"g{args.grid}",
                        replica_id=resp.replica_id)
            if args.interval_s > 0:
                time.sleep(args.interval_s)
            obs = scrape()
            if not args.once:
                print(replica_table(obs))
                print()

        # the deliberately-injected finding (acceptance drill)
        probe = _stagnation_probe(A, hub, args.solver, dtype)
        obs = scrape()             # findings now visible per replica

        print(replica_table(obs))
        doc = build_obs_document(
            agg, fleet=obs, findings=hub, history=history,
            meta={"seed": int(args.seed), "grid": int(args.grid),
                  "replicas": int(args.replicas),
                  "solver": args.solver, "dtype": dtype.name,
                  "backend": ("cpu-mesh"
                              if (args.dry_run or args.cpu_mesh)
                              else "device"),
                  "dry_run": bool(args.dry_run),
                  "probe": probe})
        problems = validate_obs_document(doc)
        if problems:
            print("fleet_top: non-conforming artifact:",
                  file=sys.stderr)
            for msg in problems:
                print(f"  {msg}", file=sys.stderr)
            return 1
        kinds = {f["kind"] for f in doc["findings"]}
        if "residual-stagnation" not in kinds:
            print("fleet_top: the stagnation probe raised no "
                  f"residual-stagnation finding (got {sorted(kinds)})",
                  file=sys.stderr)
            return 1
        if args.out:
            write_obs_document(doc, args.out)
            print(f"fleet_top: artifact written to {args.out!r}",
                  file=sys.stderr)
        else:
            print(json.dumps(doc["findings_summary"]))
        return 0
    finally:
        if fleet is not None:
            fleet.shutdown()
        if not was_enabled:
            obs_metrics.disable_metrics()


if __name__ == "__main__":
    sys.exit(main())
