#!/usr/bin/env python
"""Seeded chaos drill for the serve stack (the ISSUE 10 proof layer).

PR 4 certified the node-level recovery ladder by injecting deterministic
faults and asserting the recovery trail (tests/test_resilience.py); this
drill applies the same discipline to the REQUEST level: it drives
concurrent traffic through a live :class:`~acg_tpu.serve.SolverService`
while injecting

- **device faults** (PR 4 :class:`~acg_tpu.robust.faults.FaultSpec`,
  through ``SolverService.inject_fault``) — transient storms the
  bounded-retry ladder must clear, persistent storms that must trip the
  per-signature circuit breaker on schedule;
- **deadline storms** — bursts beyond the (artificially slowed) service
  rate with deadlines shorter than the backlog, so requests expire both
  in-queue (shed) and mid-solve (classified at the deadline);
- **poisoned right-hand sides** — NaN/Inf RHS that must be rejected at
  admission so they can never ride a coalesced batch into a neighbor's
  shared device program;
- **overload bursts** — submissions beyond the bounded queue depth that
  must shed with ``ERR_OVERLOADED`` instead of backlogging.

Certification, asserted per configuration of the ``{cg, cg-pipelined}``
× ``{single-chip, 4-part mesh}`` matrix:

1. EVERY submitted request terminates with a CLASSIFIED terminal
   response — zero hangs, zero lost tickets (the queue drains to
   depth 0, every ticket completes exactly once);
2. responses arrive within the request deadline plus one dispatch wall
   (a compiled device program is not preemptible: a request whose OWN
   dispatch overruns completes late with its real outcome; a request
   waiting on OTHERS' work classifies at its deadline);
3. every response's audit document validates at ``acg-tpu-stats/13``
   (trace-ID cross-link included);
4. circuit-breaker transitions match the seeded fault schedule, entry
   for entry (CLOSED→OPEN after exactly ``threshold`` failures,
   OPEN→HALF_OPEN at cooldown, HALF_OPEN→CLOSED on the clean probe).

``--fleet`` runs the REPLICA-KILL drill instead (ISSUE 15,
acg_tpu/serve/fleet.py): concurrent bursts through a :class:`Fleet` of
R replicas while one replica is killed MID-BURST by a ``replica-kill``
:class:`~acg_tpu.robust.faults.FaultSpec` through
``Session.solve(fault=)``.  Certified per configuration:

1. 100% classified terminal responses, zero lost tickets — the dead
   replica's in-flight tickets fail over to survivors and SUCCEED;
2. every re-dispatched response (and its schema-/10 audit ``fleet``
   block) carries ``failover_from`` provenance naming the dead replica,
   and its trace ID survives the hop (the same trace appears in both
   replicas' flight recorders);
3. the killed replica parks at DEAD and receives no post-kill traffic —
   the survivors absorb the whole load;
4. the kill lands a critical ``replica-death`` sentinel finding
   (ISSUE 16, acg_tpu/obs/sentinel.py) on ``fleet.sentinels`` with the
   victim's ``replica_id`` as provenance;
5. a surviving replica then DRAINS gracefully: zero new tickets while
   finishing in-flight work, exiting with an empty, closed queue;
6. the read-only observability plane (ISSUE 18,
   acg_tpu/serve/obsplane.py) rides the drill and stays LIVE through
   the kill window: a background poller hammers its ``/health``
   through the burst and every poll answers HTTP 200, and the
   ``replica-death`` finding is visible over the wire at ``/findings``
   before the drill exits;
7. the WARM-START failover sub-drill (ISSUE 20) on a fresh
   2-replica fleet with ``warm_start=True`` + shared preparation: a
   correlated random-walk stream with one replica killed mid-sequence
   — every solution true-residual certified (a stale donor may cost
   iterations, never a wrong answer), every audit linting at
   acg-tpu-stats/13 with an enabled ``warmstart`` block, and the
   successor serving warm from the SHARED recycle state after the
   kill.

``--fleet --elastic`` runs the SELF-HEALING drill (ISSUE 19,
``Fleet(elastic=True)`` + acg_tpu/serve/autoscale.py).  Certified per
configuration:

1. every replica enters the routing table through the probe gate — a
   seeded canary solve certified bit-for-bit against the fleet
   reference — and REPEATED mid-burst kills each heal back to target
   width through a WARM resurrection (prepared-operator cache hit)
   with zero lost tickets, 100% classified responses, /12 audits
   carrying the elastic fleet block, and a ``replica-resurrection``
   finding per kill;
2. the autoscaler grows the fleet on a burst-driven SLO breach and
   shrinks it back on sustained idle, with EVERY resize recorded as an
   ``autoscale-decision`` finding (reason included) asserted over the
   wire at ``/findings``, and ``/health`` polls answering 200 through
   every kill window;
3. a replica killed DURING its resurrection probe parks DEAD and the
   next reconciliation pass replaces the replacement;
4. a poisoned replica (NaN-injected probe) fails admission K times,
   parks QUARANTINED with ZERO routed traffic, and re-admits cleanly
   after its seeded exponential backoff.

One JSON summary line per configuration; exit 0 iff every configuration
certifies.  Seeded end to end: right-hand sides, fault schedules and
backoff jitter all derive from ``--seed``, so a failure reproduces
exactly.

Usage::

  python scripts/chaos_serve.py [--seed N] [--grid N] [--configs ...]
  python scripts/chaos_serve.py --fleet [--replicas R]   # kill drill
  python scripts/chaos_serve.py --fleet --elastic   # healing drill
  python scripts/chaos_serve.py --dry-run        # CPU smoke (tier-1)
  python scripts/chaos_serve.py --dry-run --fleet  # check_all leg 7
  python scripts/chaos_serve.py --dry-run --fleet --elastic  # leg 10

``--dry-run`` shrinks the problem and runs a reduced config list (the
full matrix stays the default for certification runs); the tier-1 smoke
and ``scripts/check_all.py`` run exactly this, mirroring the
``bench_serve.py --dry-run`` pattern.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

sys.path.insert(0, ".")

import numpy as np

# every response must end in one of these classifications — anything
# else (or a hang) fails the drill
_CLASSIFIED = ("SUCCESS", "ERR_NOT_CONVERGED", "ERR_TIMEOUT",
               "ERR_OVERLOADED", "ERR_FAULT_DETECTED", "ERR_NONFINITE",
               "ERR_NOT_CONVERGED_INDEFINITE_MATRIX")

_EXPECTED_BREAKER_TRAIL = (("CLOSED", "OPEN"), ("OPEN", "HALF_OPEN"),
                           ("HALF_OPEN", "CLOSED"))


class DrillFailure(AssertionError):
    pass


def _require(cond, msg: str):
    if not cond:
        raise DrillFailure(msg)


class _Collector:
    """Every response of one configuration, with the wall it took to
    arrive — the zero-hangs / all-classified / audits-valid evidence."""

    def __init__(self):
        self.responses = []     # (scenario, response, wall_s, bound_s)
        # every SolverService the battery created, so a DrillFailure
        # can dump their flight recorders (the black box is for
        # crashes — the last-N request timelines ride the failure
        # report)
        self.services = []
        self._lock = threading.Lock()

    def add(self, scenario: str, resp, wall_s: float,
            bound_s: float | None):
        with self._lock:
            self.responses.append((scenario, resp, wall_s, bound_s))

    def certify(self):
        from acg_tpu.obs.export import validate_stats_document

        counts = {"requests": len(self.responses), "success": 0,
                  "timeouts": 0, "shed": 0, "overloaded": 0,
                  "degraded": 0, "retried": 0, "faulted": 0}
        for scenario, resp, wall, bound in self.responses:
            _require(resp is not None,
                     f"{scenario}: a request produced NO response")
            _require(resp.status in _CLASSIFIED,
                     f"{scenario}: unclassified status {resp.status!r}")
            _require(resp.audit is not None,
                     f"{scenario}: response without an audit document")
            problems = validate_stats_document(resp.audit)
            _require(problems == [],
                     f"{scenario}: audit fails /10 lint: {problems}")
            _require(resp.audit["schema"] == "acg-tpu-stats/13",
                     f"{scenario}: audit at {resp.audit['schema']}")
            _require(resp.audit["session"]["trace_id"],
                     f"{scenario}: audit without a trace_id (the "
                     "flight-recorder cross-link)")
            _require(resp.audit["admission"] is not None,
                     f"{scenario}: audit without an admission block")
            if bound is not None:
                _require(wall <= bound,
                         f"{scenario}: response took {wall:.3f}s, "
                         f"deadline bound {bound:.3f}s (a hang)")
            counts["success"] += bool(resp.ok)
            counts["timeouts"] += resp.status == "ERR_TIMEOUT"
            counts["overloaded"] += resp.status == "ERR_OVERLOADED"
            counts["shed"] += bool(resp.shed)
            counts["degraded"] += bool(resp.degraded)
            counts["retried"] += resp.retries > 0
            counts["faulted"] += resp.status == "ERR_FAULT_DETECTED"
        return counts


def _service(session, solver, options, collector, **kw):
    from acg_tpu.serve import SolverService

    svc = SolverService(session, solver=solver, options=options, **kw)
    collector.services.append(svc)
    return svc


def _burst(svc, bs, scenario, collector, bound_s=None, ids=None):
    """Submit a burst concurrently (one thread per request), await every
    response, record (response, wall) pairs.  Returns the responses in
    submission order."""
    out = [None] * len(bs)
    errs = []

    def worker(i):
        try:
            req = svc.submit(bs[i], request_id=(None if ids is None
                                                else ids[i]))
            t0 = time.perf_counter()
            resp = req.response()
            out[i] = (req, resp, time.perf_counter() - t0)
        except Exception as e:          # pragma: no cover - diagnostics
            errs.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(bs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    _require(not errs, f"{scenario}: worker errors {errs}")
    _require(all(v is not None for v in out),
             f"{scenario}: lost ticket (a worker never returned)")
    for req, resp, wall in out:
        collector.add(scenario, resp, wall, bound_s)
    return [v[1] for v in out]


def _slowed(svc, service_s: float):
    """Wrap the queue's dispatch with a fixed service time — the chaos
    harness's slow-backend model (deadline storms need a service rate
    the drill controls, not whatever the host happens to do)."""
    inner = svc.queue._dispatch

    def slow(bb):
        time.sleep(service_s)
        return inner(bb)

    svc.queue._dispatch = slow
    return svc


# ---------------------------------------------------------------------------
# scenarios (each returns a dict of per-scenario evidence)


def scenario_clean(session, solver, options, rng, collector, n):
    svc = _service(session, solver, options, collector,
                   max_batch=max(2, n // 2))
    bs = [rng.standard_normal(session.nrows) for _ in range(n)]
    resps = _burst(svc, bs, "clean", collector)
    _require(all(r.ok for r in resps),
             f"clean: {sum(not r.ok for r in resps)} of {n} failed")
    svc.flush()
    _require(svc.queue.depth == 0, "clean: queue did not drain")
    return {"n": n}


def scenario_poisoned(session, solver, options, rng, collector, n):
    """NaN/Inf RHS rejected at the door; concurrent clean neighbors
    converge."""
    from acg_tpu.errors import AcgError, Status

    svc = _service(session, solver, options, collector, max_batch=n)
    bs = [rng.standard_normal(session.nrows) for _ in range(n)]
    rejected = 0
    for poison in (np.nan, np.inf):
        bad = np.ones(session.nrows)
        bad[int(rng.integers(session.nrows))] = poison
        try:
            svc.submit(bad)
            raise DrillFailure("poisoned: non-finite RHS was ADMITTED")
        except AcgError as e:
            _require(e.status == Status.ERR_INVALID_VALUE,
                     f"poisoned: rejection status {e.status.name}")
            rejected += 1
    resps = _burst(svc, bs, "poisoned-neighbors", collector)
    _require(all(r.ok for r in resps),
             "poisoned: a clean neighbor failed to converge")
    return {"rejected": rejected, "neighbors_ok": len(resps)}


def scenario_fault_retry(session, solver, options, rng, collector, n):
    """Transient device faults clear under bounded seeded retry."""
    from acg_tpu.robust.faults import FaultSpec
    from acg_tpu.serve import AdmissionPolicy

    pol = AdmissionPolicy(max_retries=2, backoff_ms=2.0,
                          seed=int(rng.integers(2 ** 31)))
    svc = _service(session, solver, options, collector, max_batch=1,
                   admission=pol)
    retried = 0
    for _ in range(n):
        svc.inject_fault(FaultSpec(
            kind=str(rng.choice(["spmv", "reduction"])),
            iteration=int(rng.integers(1, 6)), mode="nan"))
        b = rng.standard_normal(session.nrows)
        t0 = time.perf_counter()
        resp = svc.solve(b)
        collector.add("fault-retry", resp,
                      time.perf_counter() - t0, None)
        _require(resp.ok, f"fault-retry: not recovered ({resp.status})")
        _require(resp.retries >= 1,
                 "fault-retry: recovered without a recorded retry")
        retried += resp.retries
    return {"n": n, "retries": retried}


def scenario_breaker(session, solver, options, rng, collector,
                     cooldown_ms):
    """Persistent faults trip the breaker on the seeded schedule; the
    cooldown probe closes it; the transition trail matches exactly."""
    from acg_tpu.robust.faults import FaultSpec
    from acg_tpu.serve import AdmissionPolicy

    threshold = 2
    pol = AdmissionPolicy(breaker_threshold=threshold,
                          breaker_cooldown_ms=cooldown_ms,
                          degrade=False)
    svc = _service(session, solver, options, collector, max_batch=1,
                   admission=pol)
    statuses = []
    for i in range(threshold):
        svc.inject_fault(FaultSpec(kind="spmv",
                                   iteration=int(rng.integers(1, 6)),
                                   mode="nan"))
        t0 = time.perf_counter()
        resp = svc.solve(rng.standard_normal(session.nrows))
        collector.add("breaker-trip", resp,
                      time.perf_counter() - t0, None)
        statuses.append(resp.status)
    _require(statuses == ["ERR_FAULT_DETECTED"] * threshold,
             f"breaker: fault storm statuses {statuses}")
    # breaker now OPEN: fast-fail without touching the device
    t0 = time.perf_counter()
    resp = svc.solve(rng.standard_normal(session.nrows))
    wall = time.perf_counter() - t0
    collector.add("breaker-open", resp, wall, None)
    _require(resp.status == "ERR_OVERLOADED" and resp.shed,
             f"breaker: open state served {resp.status}")
    _require(wall < cooldown_ms / 1e3,
             "breaker: fast-fail was not fast")
    time.sleep(cooldown_ms / 1e3 * 1.2)
    # half-open probe (clean) closes it
    t0 = time.perf_counter()
    resp = svc.solve(rng.standard_normal(session.nrows))
    collector.add("breaker-probe", resp,
                  time.perf_counter() - t0, None)
    _require(resp.ok, f"breaker: clean probe failed ({resp.status})")
    trail = tuple((t["from"], t["to"])
                  for t in svc.health()["breaker_transitions"])
    _require(trail == _EXPECTED_BREAKER_TRAIL,
             f"breaker: transition trail {trail} != seeded schedule "
             f"{_EXPECTED_BREAKER_TRAIL}")
    return {"trail": [list(t) for t in trail],
            "trips": svc.stats()["admission"]["breaker_trips"]}


def scenario_degrade(session, solver, options, rng, collector):
    """Pipelined/s-step traffic degrades onto classic CG while its
    breaker is open (provenance recorded)."""
    from acg_tpu.robust.faults import FaultSpec
    from acg_tpu.serve import AdmissionPolicy

    if solver == "cg":
        return {"skipped": "classic CG has no degradation target"}
    pol = AdmissionPolicy(breaker_threshold=1,
                          breaker_cooldown_ms=60_000.0, degrade=True)
    svc = _service(session, solver, options, collector, max_batch=1,
                   admission=pol)
    svc.inject_fault(FaultSpec(kind="spmv",
                               iteration=int(rng.integers(1, 6)),
                               mode="nan"))
    t0 = time.perf_counter()
    resp = svc.solve(rng.standard_normal(session.nrows))
    collector.add("degrade-trip", resp, time.perf_counter() - t0, None)
    _require(resp.status == "ERR_FAULT_DETECTED",
             f"degrade: trip status {resp.status}")
    t0 = time.perf_counter()
    resp = svc.solve(rng.standard_normal(session.nrows))
    collector.add("degrade-served", resp,
                  time.perf_counter() - t0, None)
    _require(resp.ok and resp.degraded
             and resp.degraded_from == solver,
             f"degrade: expected classic-CG service, got "
             f"status={resp.status} degraded={resp.degraded} "
             f"from={resp.degraded_from}")
    adm = resp.audit["admission"]
    _require(adm["degraded"] and adm["degraded_from"] == solver,
             "degrade: provenance missing from the audit document")
    return {"degraded_from": resp.degraded_from}


def scenario_deadline_storm(session, solver, options, rng, collector,
                            n, service_ms, deadline_ms):
    """A burst beyond the (slowed) service rate with deadlines shorter
    than the backlog: the head of the line succeeds, the tail expires —
    in-queue (shed) or mid-solve — and EVERYONE classifies within
    deadline + one dispatch wall."""
    from acg_tpu.serve import AdmissionPolicy

    pol = AdmissionPolicy(deadline_ms=deadline_ms)
    svc = _slowed(_service(session, solver, options, collector,
                           max_batch=2, buckets=(1, 2),
                           admission=pol),
                  service_ms / 1e3)
    bs = [rng.standard_normal(session.nrows) for _ in range(n)]
    bound = (deadline_ms + service_ms) / 1e3 + 1.0   # + slack
    resps = _burst(svc, bs, "deadline-storm", collector, bound_s=bound)
    svc.flush()
    nok = sum(r.ok for r in resps)
    nto = sum(r.status == "ERR_TIMEOUT" for r in resps)
    _require(nok + nto == n,
             f"deadline-storm: {n - nok - nto} responses were neither "
             "SUCCESS nor ERR_TIMEOUT")
    _require(nto >= 1, "deadline-storm: the storm never bit "
                       "(no request timed out — lower the deadline)")
    _require(svc.queue.depth == 0, "deadline-storm: queue not drained")
    return {"n": n, "success": nok, "timeouts": nto}


def scenario_load_shed(session, solver, options, rng, collector, n):
    """Submissions beyond the bounded queue depth shed at admission."""
    from acg_tpu.serve import AdmissionPolicy

    depth = 2
    pol = AdmissionPolicy(max_queue_depth=depth)
    svc = _slowed(_service(session, solver, options, collector,
                           max_batch=2, buckets=(1, 2),
                           admission=pol),
                  0.05)
    bs = [rng.standard_normal(session.nrows) for _ in range(n)]
    resps = _burst(svc, bs, "load-shed", collector)
    svc.flush()
    nshed = sum(r.status == "ERR_OVERLOADED" for r in resps)
    nok = sum(r.ok for r in resps)
    _require(nok + nshed == n,
             f"load-shed: {n - nok - nshed} responses were neither "
             "SUCCESS nor ERR_OVERLOADED")
    _require(nshed >= 1, "load-shed: the burst never exceeded the "
                         "depth bound (raise n)")
    _require(svc.queue.depth == 0, "load-shed: queue not drained")
    return {"n": n, "success": nok, "overloaded": nshed}


# ---------------------------------------------------------------------------
# the replica-kill drill (ISSUE 15, acg_tpu/serve/fleet.py)


def _wire_json(url: str, timeout: float = 10.0):
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


class _HealthPoller:
    """Hammers the plane's ``/health`` from a background thread through
    the kill window, recording every HTTP status + decoded body status
    (or the error).  The liveness evidence (ISSUE 18): the probe is
    NEVER unanswered while a replica dies mid-burst."""

    def __init__(self, url: str, interval_s: float = 0.025):
        self.url = url
        self.interval_s = interval_s
        self.codes: list[int] = []
        self.statuses: list[str | None] = []
        self.errors: list[str] = []
        self._stop_evt = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="chaos-health-poll",
                                        daemon=True)

    def start(self) -> "_HealthPoller":
        self._thread.start()
        return self

    def _run(self):
        import urllib.request

        while not self._stop_evt.is_set():
            try:
                with urllib.request.urlopen(self.url,
                                            timeout=5) as resp:
                    body = json.loads(resp.read().decode())
                self.codes.append(int(resp.status))
                self.statuses.append(body.get("status"))
            except Exception as e:      # any failed poll is evidence
                self.errors.append(repr(e))
            self._stop_evt.wait(self.interval_s)

    def stop(self) -> dict:
        self._stop_evt.set()
        self._thread.join(timeout=10)
        return {"n": len(self.codes), "codes": self.codes,
                "statuses": self.statuses, "errors": self.errors}


def run_fleet_drill(A, solver: str, replicas: int, *, seed: int,
                    maxits: int, n: int) -> dict:
    """Kill 1 of R replicas mid-burst; certify zero lost tickets, 100%
    classified terminal responses, failover provenance + trace-ID
    continuity, survivors absorbing the load, and a graceful drain.
    Raises :class:`DrillFailure` on any violated invariant."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.obs.export import validate_stats_document
    from acg_tpu.robust.faults import FaultSpec
    from acg_tpu.serve import Fleet

    rng = np.random.default_rng(seed)
    deep = "deep" in solver
    options = SolverOptions(maxits=maxits, residual_rtol=1e-6,
                            guard_nonfinite=True,
                            pipeline_depth=2 if deep else 1)
    fleet = Fleet(A, replicas=replicas, solver=solver, options=options,
                  max_batch=2, buckets=(1, 2), seed=seed,
                  session_kw=dict(prep_cache=None,
                                  share_prepared=False))
    fleet.warmup(np.ones(A.nrows))

    # the observability plane rides the whole drill (ISSUE 18): the
    # read-only HTTP admin over the live fleet must keep answering
    # /health and /findings THROUGH the kill window
    from acg_tpu.serve.obsplane import ObsPlane
    plane = ObsPlane(fleet).start()
    poller = _HealthPoller(plane.url + "/health").start()
    try:
        # phase 1: clean burst — every replica takes traffic
        bs = [rng.standard_normal(A.nrows) for _ in range(n)]
        reqs = [fleet.submit(b) for b in bs]
        fleet.flush()
        clean = [r.response() for r in reqs]
        _require(all(r.ok for r in clean),
                 f"fleet-clean: {sum(not r.ok for r in clean)} of {n} "
                 "failed before any fault was injected")

        # phase 2: the kill — a replica-kill FaultSpec dies
        # MID-dispatch on whichever routed request reaches the victim
        # first; every ticket riding that dispatch (and everything
        # queued behind it) must fail over to survivors and classify
        victim = fleet.assignments[-1]
        fleet.inject_fault(victim, FaultSpec(kind="replica-kill",
                                             iteration=0))
        burst = [rng.standard_normal(A.nrows) for _ in range(2 * n)]
        out = [None] * len(burst)
        errs = []

        def worker(i):
            try:
                out[i] = fleet.submit(
                    burst[i], request_id=f"kill-{i}").response()
            except Exception as e:  # pragma: no cover - diagnostics
                errs.append((i, e))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(burst))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        _require(not errs, f"fleet-kill: worker errors {errs}")
        _require(all(v is not None for v in out),
                 "fleet-kill: lost ticket (a worker never returned)")
        _require(fleet.replica(victim).state == "DEAD",
                 f"fleet-kill: victim {victim} never died "
                 f"(state {fleet.replica(victim).state}; no routed "
                 "request reached it — change --seed)")
        failed_over = [r for r in out if r.failover_from]
        _require(len(failed_over) >= 1,
                 "fleet-kill: the kill bit no in-flight ticket "
                 "(nothing failed over)")
        for resp in out + clean:
            _require(resp.status in _CLASSIFIED,
                     f"fleet-kill: unclassified status {resp.status!r}")
            _require(resp.audit is not None,
                     "fleet-kill: response without an audit document")
            problems = validate_stats_document(resp.audit)
            _require(problems == [],
                     f"fleet-kill: audit fails /10 lint: {problems}")
            fl = resp.audit["fleet"]
            _require(fl is not None
                     and fl["replica_id"] == resp.replica_id,
                     "fleet-kill: audit fleet block missing or wrong "
                     "replica_id")
        _require(all(r.ok for r in out),
                 f"fleet-kill: {sum(not r.ok for r in out)} of "
                 f"{len(out)} requests did not survive the kill "
                 "(failover should have rescued every one)")
        if deep:
            # ISSUE 17: the deep-pipelined exit is TRUE-residual
            # certified (the uncompressed cert_matvec,
            # solvers/cg_dist.py) — a mid-flight replica kill must
            # re-deliver a CERTIFIED solve on the survivor, not merely
            # a classified one, and it must come from the deep program
            # (depth >= 2 in the audited options)
            for resp in out + clean:
                o = resp.audit["options"]
                _require(int(o.get("pipeline_depth", 1)) >= 2,
                         "fleet-kill: a deep-drill response was not "
                         "served by the deep-pipelined program")
                rr = resp.audit["result"]["relative_residual"]
                _require(rr is not None and rr <= 1.01e-6,
                         "fleet-kill: deep solve exit not "
                         f"drift-certified (relative residual {rr!r} "
                         "above rtol)")
        for resp in failed_over:
            _require(victim in resp.failover_from,
                     f"fleet-kill: failover_from {resp.failover_from} "
                     f"does not name the dead replica {victim}")
            fl = resp.audit["fleet"]
            _require(fl["failover_from"] == list(resp.failover_from)
                     and fl["hops"] == len(resp.failover_from),
                     "fleet-kill: audit fleet provenance disagrees "
                     "with the response")
            _require(resp.replica_id != victim,
                     "fleet-kill: a post-kill response claims the "
                     "dead replica served it")
        # trace-ID continuity: the failed-over request's ONE trace
        # appears in at least two replicas' flight recorders (submit on
        # the victim, failover + response on the survivor)
        dump = fleet.flightrec.dump()
        tid = failed_over[0].audit["session"]["trace_id"]
        spans = [d for d in dump if d["trace_id"] == tid]
        _require(len(spans) >= 2,
                 f"fleet-kill: trace {tid} did not survive the hop "
                 f"({len(spans)} timeline(s) in the merged recorders)")
        _require(any(ev["event"] == "failover"
                     for d in spans for ev in d["events"]),
                 f"fleet-kill: no failover event on trace {tid}")
        # the finding plane (ISSUE 16): the kill must land exactly one
        # replica-death sentinel finding attributed to the victim
        deaths = fleet.sentinels.findings(kind="replica-death")
        _require(any(f.replica_id == victim for f in deaths),
                 "fleet-kill: no replica-death finding names the "
                 f"victim {victim} (got "
                 f"{[(f.kind, f.replica_id) for f in deaths]})")
        _require(all(f.severity == "critical" for f in deaths),
                 "fleet-kill: replica-death finding not critical")
        # ISSUE 18: the plane stayed live through the kill window —
        # every /health poll answered HTTP 200 with a parseable body
        polls = poller.stop()
        _require(not polls["errors"],
                 "fleet-kill: /health went unanswered during the kill "
                 f"window: {polls['errors'][:3]}")
        _require(polls["n"] >= 1,
                 "fleet-kill: the health poller completed no poll")
        _require(all(c == 200 for c in polls["codes"]),
                 "fleet-kill: non-200 /health during the kill window "
                 f"({sorted(set(polls['codes']))})")
        # ... and the replica-death finding is visible OVER THE WIRE
        # before the drill exits
        wired = _wire_json(plane.url + "/findings")
        _require(any(f.get("kind") == "replica-death"
                     and f.get("replica_id") == victim
                     for f in wired.get("findings", [])),
                 "fleet-kill: /findings over the wire does not carry "
                 f"the replica-death finding for {victim}")

        # phase 3: graceful drain of a survivor — zero new tickets
        # while in-flight work finishes, the queue exits empty+closed
        survivor = next(r.replica_id for r in fleet.replicas
                        if r.state == "READY")
        routed_before = fleet.replica(survivor).routed
        _require(fleet.drain(survivor),
                 f"fleet-drain: {survivor} did not drain clean")
        svc = fleet.replica(survivor).service
        _require(svc.queue.depth == 0 and svc.queue.inflight == 0
                 and svc.queue.closed,
                 "fleet-drain: drained replica's queue is not "
                 "empty+closed")
        _require(fleet.replica(survivor).routed == routed_before,
                 "fleet-drain: a DRAINING replica received new "
                 "tickets")
        _require(fleet.replica(survivor).state == "DEAD",
                 "fleet-drain: drained replica did not park at DEAD")
        if all(r.state == "DEAD" for r in fleet.replicas):
            # the whole fleet is gone: admission must refuse CLEANLY
            from acg_tpu.errors import AcgError, Status
            try:
                fleet.submit(np.ones(A.nrows))
                _require(False, "fleet-drain: an all-DEAD fleet "
                                "admitted a request")
            except AcgError as e:
                _require(e.status == Status.ERR_OVERLOADED,
                         f"fleet-drain: all-DEAD refusal was "
                         f"{e.status.name}, not ERR_OVERLOADED")
        # the warm-start failover sub-drill (ISSUE 20) rides the fleet
        # drill on a FRESH fleet: the killed-and-drained one above has
        # no survivors left to serve warm
        ws_report = run_warmstart_drill(A, solver, seed=seed,
                                        maxits=maxits)
        return {"config": f"fleet/{solver}/r{replicas}", "seed": seed,
                "ok": True, "requests": len(out) + len(clean),
                "victim": victim, "failed_over": len(failed_over),
                "obsplane": {"url": plane.url,
                             "health_polls": int(polls["n"])},
                "warmstart": ws_report,
                "routing": fleet.stats()["routing"]}
    finally:
        poller.stop()
        plane.stop()


def run_warmstart_drill(A, solver: str, *, seed: int,
                        maxits: int) -> dict:
    """The warm-start failover sub-drill (ISSUE 20): a 2-replica fleet
    with ``warm_start=True`` and SHARED preparation (fleet replicas
    then share one :class:`~acg_tpu.serve.session.RecycleState`) serves
    a correlated random-walk stream; one replica is killed
    mid-sequence.  Certifies that

    - every solution in the stream — before and after the kill — passes
      the TRUE-residual check against the host matrix (a stale donor
      can cost iterations, never a wrong answer);
    - every audit lints at acg-tpu-stats/13 and carries an enabled
      ``warmstart`` block;
    - the successor serves WARM from the shared recycle state at least
      once after the kill (or the drill fails — "cleanly cold forever"
      would mean the shared-state handoff is broken for a correlated
      stream this tight).
    """
    from acg_tpu.config import SolverOptions
    from acg_tpu.obs.export import validate_stats_document
    from acg_tpu.serve import Fleet
    from acg_tpu.serve.session import clear_prepared_cache

    # this drill measures ITS OWN shared-state story, not a previous
    # config's leftover donors
    clear_prepared_cache()
    rng = np.random.default_rng(seed ^ 0x5EED)
    deep = "deep" in solver
    options = SolverOptions(maxits=maxits, residual_rtol=1e-6,
                            guard_nonfinite=True,
                            pipeline_depth=2 if deep else 1)
    fleet = Fleet(A, replicas=2, solver=solver, options=options,
                  max_batch=2, buckets=(1, 2), seed=seed,
                  warm_start=True,
                  session_kw=dict(prep_cache=None, share_prepared=True,
                                  recycle=True))
    try:
        fleet.warmup(np.ones(A.nrows))
        nreq = 6
        b = rng.standard_normal(A.nrows)
        victim = None
        warm_served = post_kill_warm = 0
        for t in range(nreq):
            resp = fleet.submit(np.ascontiguousarray(b),
                                request_id=f"warm-{t}").response()
            _require(resp.ok and resp.status in _CLASSIFIED,
                     f"warm-drill: request {t} not served clean "
                     f"(status {resp.status!r})")
            problems = validate_stats_document(resp.audit)
            _require(problems == [],
                     f"warm-drill: audit fails /13 lint: {problems}")
            ws = resp.audit.get("warmstart")
            _require(isinstance(ws, dict) and ws.get("enabled") is True,
                     "warm-drill: audit without an enabled warmstart "
                     "block")
            x = np.asarray(resp.result.x, np.float64)
            b64 = np.asarray(b, np.float64)
            resid = float(np.linalg.norm(
                b64 - np.asarray(A.matvec(x), np.float64)))
            _require(np.isfinite(resid)
                     and resid <= 1e-5 * float(np.linalg.norm(b64)),
                     f"warm-drill: request {t} exited with a WRONG "
                     f"answer (true residual {resid:.3e}) — a donor "
                     "survived certification it should have failed")
            if ws.get("source") == "recycled" and not ws.get("rejected"):
                warm_served += 1
                if victim is not None:
                    post_kill_warm += 1
            if t == nreq // 2 - 1:
                victim = next(r.replica_id for r in fleet.replicas
                              if r.state == "READY")
                fleet.kill(victim)
            b = b + 1e-3 * float(np.linalg.norm(b)) \
                * rng.standard_normal(A.nrows)
        _require(warm_served >= 1,
                 "warm-drill: no request in a sigma=1e-3 correlated "
                 "stream was served warm")
        _require(post_kill_warm >= 1,
                 "warm-drill: the successor never served warm from the "
                 "shared recycle state after the kill")
        return {"requests": nreq, "victim": victim,
                "warm_served": warm_served,
                "post_kill_warm": post_kill_warm}
    finally:
        fleet.shutdown()


# ---------------------------------------------------------------------------
# the elastic drill (ISSUE 19, acg_tpu/serve/fleet.py elastic=True +
# acg_tpu/serve/autoscale.py)


def _await_width(fleet, want: int, timeout_s: float = 60.0) -> bool:
    """Poll until the fleet has ``want`` READY replicas (the reconciler
    heals asynchronously)."""
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        if sum(r.state == "READY" for r in fleet.replicas) >= want:
            return True
        time.sleep(0.01)
    return False


def run_elastic_drill(A, solver: str, replicas: int, *, seed: int,
                      maxits: int, n: int) -> dict:
    """The self-healing certification (ISSUE 19):

    1. probe-gated construction — every replica enters the routing
       table through the canary gate (satellite 1: no READY without a
       passed probe);
    2. REPEATED kills mid-burst — after each kill the fleet heals back
       to target width through a warm (prepared-cache) resurrection,
       with zero lost tickets, 100% classified responses and a
       ``replica-resurrection`` finding, all visible over the wire;
    3. the autoscaler — a burst breaches a tiny SLO target and the
       fleet grows (decision applied through ``scale_to``), sustained
       idle shrinks it back (draining the scale-up spawn); EVERY
       resize lands an ``autoscale-decision`` finding with its reason,
       asserted over the wire at ``/findings``;
    4. a kill DURING resurrection — the half-admitted replacement dies
       mid-probe and the next reconciliation pass replaces IT (run on
       a second ``auto_heal=False`` fleet so the reconciler daemon
       cannot race the drill's manual lifecycle steps);
    5. a poisoned replica — fails its admission probe K times, parks
       QUARANTINED with ZERO routed traffic, and recovers through the
       backoff re-probe.

    Raises :class:`DrillFailure` on any violated invariant."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.obs import metrics as obs_metrics
    from acg_tpu.obs.export import validate_stats_document
    from acg_tpu.obs.history import MetricsHistory
    from acg_tpu.robust.faults import FaultSpec
    from acg_tpu.serve import Autoscaler, Fleet
    from acg_tpu.serve.obsplane import ObsPlane
    from acg_tpu.serve.session import clear_prepared_cache

    rng = np.random.default_rng(seed)
    options = SolverOptions(maxits=maxits, residual_rtol=1e-6,
                            guard_nonfinite=True)
    was_enabled = obs_metrics.metrics_enabled()
    obs_metrics.enable_metrics()
    clear_prepared_cache()      # measure the warm path honestly
    fleet = fleet2 = hist = scaler = plane = poller = None
    kills = 0
    try:
        # the warm path: share_prepared=True puts every replica's
        # prepared operator in the process-level cache — a resurrection
        # must hit it (zero re-prep)
        fleet = Fleet(A, replicas=replicas, solver=solver,
                      options=options, max_batch=2, buckets=(1, 2),
                      seed=seed, elastic=True, heal_interval_s=0.02,
                      session_kw=dict(prep_cache=None,
                                      share_prepared=True))
        hist = MetricsHistory(capacity=64, fleet=fleet)
        plane = ObsPlane(fleet, history=hist).start()
        poller = _HealthPoller(plane.url + "/health").start()

        # phase 1: probe-gated construction (satellite 1)
        for r in fleet.replicas:
            _require(r.state == "READY",
                     f"elastic-admit: {r.replica_id} is {r.state} "
                     "after construction")
            _require(r.probes >= 1,
                     f"elastic-admit: {r.replica_id} entered the "
                     "routing table without a probe")
        clean = _elastic_burst(fleet, rng, A.nrows, n,
                               "elastic-clean")
        _require(all(r.ok for r in clean),
                 "elastic-clean: a pre-kill request failed")

        # phase 2: repeated kills — heal back to width each time
        for round_i in range(2):
            victim = fleet.assignments[-1]
            _require(fleet.replica(victim).state == "READY",
                     f"elastic-kill[{round_i}]: victim {victim} not "
                     "READY (routing drift — change --seed)")
            fleet.inject_fault(victim, FaultSpec(kind="replica-kill",
                                                 iteration=0))
            kills += 1
            out = _elastic_burst(fleet, rng, A.nrows, 2 * n,
                                 f"elastic-kill[{round_i}]")
            _require(all(r.ok for r in out),
                     f"elastic-kill[{round_i}]: "
                     f"{sum(not r.ok for r in out)} of {len(out)} "
                     "requests did not survive the kill")
            _require(fleet.replica(victim).state == "DEAD",
                     f"elastic-kill[{round_i}]: victim {victim} never "
                     "died (no routed request reached it)")
            _require(_await_width(fleet, replicas),
                     f"elastic-kill[{round_i}]: fleet never healed "
                     f"back to width {replicas} (resurrections: "
                     f"{fleet.resurrection_log})")
            for resp in out:
                _require(resp.status in _CLASSIFIED,
                         f"elastic-kill[{round_i}]: unclassified "
                         f"status {resp.status!r}")
                problems = validate_stats_document(resp.audit)
                _require(problems == [],
                         f"elastic-kill[{round_i}]: audit fails /12 "
                         f"lint: {problems}")
            _require(fleet.resurrections >= round_i + 1,
                     f"elastic-kill[{round_i}]: no resurrection "
                     "recorded")
        _require(all(e["warm"] for e in fleet.resurrection_log),
                 "elastic-heal: a resurrection missed the prepared-"
                 f"operator cache (log: {fleet.resurrection_log})")
        _require(all(e["admitted"] for e in fleet.resurrection_log),
                 "elastic-heal: a resurrected replica was never "
                 "admitted")
        res_findings = fleet.sentinels.findings(
            kind="replica-resurrection")
        _require(len(res_findings) >= kills,
                 f"elastic-heal: {kills} kills but only "
                 f"{len(res_findings)} resurrection findings")
        # the healed fleet serves: audits carry the elastic snapshot
        resp = fleet.solve(rng.standard_normal(A.nrows))
        _require(resp.ok, "elastic-heal: post-heal request failed")
        fl = resp.audit["fleet"]
        _require(fl["resurrections"] == fleet.resurrections,
                 "elastic-heal: audit fleet block does not carry the "
                 f"resurrection count (got {fl})")

        # phase 3: the autoscaler — burst-driven scale-up observed
        # over the wire, idle-driven scale-down, every resize audited
        scaler = Autoscaler(fleet, history=hist,
                            min_replicas=1,
                            max_replicas=replicas + 1,
                            slo_p99_ms=1e-3,    # any real solve breaches
                            cooldown_s=0.0, window_s=600.0)
        resizes = 0
        hist.sample()
        _elastic_burst(fleet, rng, A.nrows, 2 * n, "elastic-scale")
        hist.sample()
        d = scaler.step()
        _require(d.action == "up" and d.applied,
                 f"elastic-scale: burst did not scale up "
                 f"(decision: {d.as_dict()})")
        resizes += 1
        _require(fleet.target_replicas == replicas + 1,
                 f"elastic-scale: target is {fleet.target_replicas}, "
                 f"expected {replicas + 1}")
        _require(_await_width(fleet, replicas + 1),
                 "elastic-scale: the scale-up never materialized")
        wired = _wire_json(plane.url + "/health")
        _require(wired.get("target_replicas") == replicas + 1
                 and wired.get("elastic") is True,
                 "elastic-scale: /health over the wire does not show "
                 "the scale-up")
        # sustained idle: a short window holding only traffic-free
        # samples ⇒ zero rates, no p99 ⇒ calm ⇒ scale-down (drains
        # the newest READY replica — the scale-up spawn unwinds)
        scaler.slo_p99_ms = None
        hist.sample()
        time.sleep(0.05)
        hist.sample()
        scaler.window_s = 0.04
        d = scaler.step()
        _require(d.action == "down" and d.applied,
                 f"elastic-scale: sustained idle did not scale down "
                 f"(decision: {d.as_dict()})")
        resizes += 1
        _require(fleet.target_replicas == replicas,
                 "elastic-scale: scale-down did not restore the "
                 f"target (at {fleet.target_replicas})")
        # EVERY resize carries a Finding with a reason — over the wire
        wired = _wire_json(plane.url + "/findings")
        audited = [f for f in wired.get("findings", [])
                   if f.get("kind") == "autoscale-decision"]
        _require(len(audited) == resizes,
                 f"elastic-scale: {resizes} resizes but "
                 f"{len(audited)} autoscale-decision findings over "
                 "the wire")
        _require(all((f.get("evidence") or {}).get("reason")
                     for f in audited),
                 "elastic-scale: an autoscale-decision finding has no "
                 "reason")
        _require(any(f.get("kind") == "replica-resurrection"
                     for f in wired.get("findings", [])),
                 "elastic-heal: resurrection findings not visible "
                 "over the wire")

        # the plane stayed live through every kill window
        polls = poller.stop()
        _require(not polls["errors"] and polls["n"] >= 1
                 and all(c == 200 for c in polls["codes"]),
                 "elastic: /health went unanswered during the drill "
                 f"({polls['errors'][:3]})")

        # phases 4-5 run manual lifecycle steps that the reconciler
        # daemon would race: a second elastic fleet, auto_heal=False
        fleet2 = Fleet(A, replicas=replicas, solver=solver,
                       options=options, max_batch=2, buckets=(1, 2),
                       seed=seed + 1, elastic=True, auto_heal=False,
                       max_probe_failures=2, quarantine_backoff_s=0.05,
                       session_kw=dict(prep_cache=None,
                                       share_prepared=True))

        # phase 4: kill DURING resurrection — the replacement dies
        # mid-probe; the next reconciliation pass replaces IT
        victim = next(r.replica_id for r in fleet2.replicas
                      if r.state == "READY")
        fleet2.kill(victim)
        half = fleet2.spawn(admit=False)    # a resurrection, half done
        fleet2.inject_fault(half.replica_id,
                            FaultSpec(kind="replica-kill", iteration=0))
        _require(not fleet2.admit(half.replica_id),
                 "elastic-midkill: a replica whose probe dispatch "
                 "died was admitted")
        _require(fleet2.replica(half.replica_id).state == "DEAD",
                 "elastic-midkill: the killed-during-probe replica "
                 f"is {fleet2.replica(half.replica_id).state}, not "
                 "DEAD")
        healed = fleet2.maintain()
        _require(len(healed["spawned"]) >= 1,
                 f"elastic-midkill: maintain() spawned nothing "
                 f"({healed})")
        _require(sum(r.state == "READY" for r in fleet2.replicas)
                 == replicas,
                 "elastic-midkill: the fleet never recovered from a "
                 "kill during resurrection")

        # phase 5: the poisoned replica — probe fails K times, parks
        # QUARANTINED, receives ZERO traffic, recovers after backoff
        poisoned = fleet2.spawn(admit=False)
        for _ in range(fleet2.max_probe_failures):
            fleet2.inject_fault(poisoned.replica_id,
                                FaultSpec(kind="spmv", iteration=0,
                                          mode="nan"))
        _require(not fleet2.admit(poisoned.replica_id),
                 "elastic-poison: a probe-failing replica was "
                 "admitted")
        _require(poisoned.state == "QUARANTINED",
                 f"elastic-poison: poisoned replica is "
                 f"{poisoned.state}, not QUARANTINED")
        qf = fleet2.sentinels.findings(kind="replica-quarantine")
        _require(any(f.replica_id == poisoned.replica_id for f in qf),
                 "elastic-poison: no replica-quarantine finding names "
                 f"{poisoned.replica_id}")
        traffic = _elastic_burst(fleet2, rng, A.nrows, n,
                                 "elastic-poison")
        _require(all(r.ok for r in traffic),
                 "elastic-poison: traffic failed while a replica was "
                 "quarantined")
        _require(poisoned.routed == 0,
                 f"elastic-poison: QUARANTINED replica received "
                 f"{poisoned.routed} routed requests (must be 0)")
        time.sleep(0.15)                    # past the seeded backoff
        deadline = time.perf_counter() + 30
        while poisoned.state != "READY" \
                and time.perf_counter() < deadline:
            fleet2.maintain()
            time.sleep(0.01)
        _require(poisoned.state == "READY",
                 "elastic-poison: the quarantined replica never "
                 "re-admitted after its backoff")
        return {"config": f"elastic/{solver}/r{replicas}",
                "seed": seed, "ok": True, "kills": kills,
                "resurrections": int(fleet.resurrections),
                "resurrection_log": fleet.resurrection_log,
                "resizes": resizes,
                "quarantined_replica": poisoned.replica_id,
                "health_polls": int(polls["n"]),
                "obsplane": plane.url}
    finally:
        if poller is not None:
            poller.stop()
        if plane is not None:
            plane.stop()
        if scaler is not None:
            scaler.stop()
        if hist is not None:
            hist.stop()
        for fl in (fleet, fleet2):
            if fl is not None:
                fl.shutdown()
        if not was_enabled:
            obs_metrics.disable_metrics()


def _elastic_burst(fleet, rng, nrows: int, n: int, scenario: str):
    """Concurrent burst through the fleet; zero lost tickets
    asserted."""
    bs = [rng.standard_normal(nrows) for _ in range(n)]
    out = [None] * n
    errs = []

    def worker(i):
        try:
            out[i] = fleet.submit(bs[i]).response()
        except Exception as e:      # pragma: no cover - diagnostics
            errs.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    _require(not errs, f"{scenario}: worker errors {errs}")
    _require(all(v is not None for v in out),
             f"{scenario}: lost ticket (a worker never returned)")
    return out


# ---------------------------------------------------------------------------


def run_config(A, solver: str, nparts: int, *, seed: int, maxits: int,
               n: int, cooldown_ms: float, service_ms: float,
               deadline_ms: float) -> dict:
    """The full seeded scenario battery for one (solver, nparts)
    configuration; returns the certification summary (raises
    DrillFailure on any violated invariant)."""
    from acg_tpu.config import SolverOptions
    from acg_tpu.serve import Session

    rng = np.random.default_rng(seed)
    options = SolverOptions(maxits=maxits, residual_rtol=1e-6,
                            guard_nonfinite=True)
    session = Session(A, nparts=nparts, options=options,
                      prep_cache=None, share_prepared=False)
    collector = _Collector()
    try:
        evidence = {
            "clean": scenario_clean(session, solver, options, rng,
                                    collector, n),
            "poisoned": scenario_poisoned(session, solver, options, rng,
                                          collector, max(2, n // 2)),
            "fault_retry": scenario_fault_retry(session, solver, options,
                                                rng, collector, 2),
            "breaker": scenario_breaker(session, solver, options, rng,
                                        collector, cooldown_ms),
            "degrade": scenario_degrade(session, solver, options, rng,
                                        collector),
            "deadline_storm": scenario_deadline_storm(
                session, solver, options, rng, collector, n,
                service_ms, deadline_ms),
            "load_shed": scenario_load_shed(session, solver, options,
                                            rng, collector, n),
        }
        counts = collector.certify()
    except DrillFailure as e:
        # attach the flight recorders of the most recent services: the
        # last-N request timelines (trace IDs matching the failing
        # audits) ARE the post-mortem — main() prints them with the
        # failure report
        e.flightrec = [svc.flightrec.dump()
                       for svc in collector.services[-3:]]
        raise
    return {"config": f"{solver}/nparts{nparts}", "seed": seed,
            "ok": True, **counts, "scenarios": evidence}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Seeded chaos drill over the serve stack.")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grid", type=int, default=48,
                    help="2-D Poisson grid edge [48]")
    ap.add_argument("--n-requests", type=int, default=8,
                    help="requests per traffic scenario [8]")
    ap.add_argument("--configs", default=None,
                    help="comma-separated SOLVER:NPARTS list "
                         "[cg:1,cg:4,cg-pipelined:1,cg-pipelined:4; "
                         "dry-run default cg:1,cg-pipelined:4].  With "
                         "--fleet: SOLVER:REPLICAS "
                         "[cg:2,cg:3,cg-pipelined:2,"
                         "cg-pipelined-deep:2; dry-run "
                         "cg:2,cg-pipelined-deep:2]")
    ap.add_argument("--fleet", action="store_true",
                    help="run the replica-kill drill over a Fleet "
                         "(ISSUE 15) instead of the scenario battery")
    ap.add_argument("--elastic", action="store_true",
                    help="with --fleet: run the self-healing drill "
                         "(ISSUE 19) — repeated kills healed by warm "
                         "resurrection, kill-during-resurrection, "
                         "poisoned-probe quarantine, autoscaler "
                         "resizes audited over the wire")
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU-sized smoke: tiny grid, reduced config "
                         "list — the tier-1 / check_all wiring pass")
    args = ap.parse_args(argv)
    if args.elastic and not args.fleet:
        ap.error("--elastic requires --fleet")

    if args.dry_run:
        from acg_tpu.utils.backend import force_cpu_mesh

        force_cpu_mesh(8)
        grid, maxits, n = 10, 200, 4
        cooldown_ms, service_ms, deadline_ms = 150.0, 120.0, 150.0
        configs = args.configs or (
            "cg:2" if args.elastic
            else "cg:2,cg-pipelined-deep:2" if args.fleet
            else "cg:1,cg-pipelined:4")
    else:
        from acg_tpu.utils.backend import require_tpu

        require_tpu()
        grid, maxits, n = args.grid, 600, args.n_requests
        cooldown_ms, service_ms, deadline_ms = 500.0, 250.0, 400.0
        configs = args.configs or (
            "cg:2,cg-pipelined:2" if args.elastic
            else "cg:2,cg:3,cg-pipelined:2,cg-pipelined-deep:2"
            if args.fleet
            else "cg:1,cg:4,cg-pipelined:1,cg-pipelined:4")

    from acg_tpu.sparse import poisson2d_5pt

    A = poisson2d_5pt(grid)
    rc = 0
    for spec in configs.split(","):
        solver, _, arity = spec.strip().partition(":")
        try:
            if args.fleet and args.elastic:
                report = run_elastic_drill(
                    A, solver, int(arity or 2), seed=args.seed,
                    maxits=maxits, n=n)
            elif args.fleet:
                report = run_fleet_drill(
                    A, solver, int(arity or 2), seed=args.seed,
                    maxits=maxits, n=n)
            else:
                report = run_config(
                    A, solver, int(arity or 1), seed=args.seed,
                    maxits=maxits, n=n, cooldown_ms=cooldown_ms,
                    service_ms=service_ms, deadline_ms=deadline_ms)
        except DrillFailure as e:
            report = {"config": spec.strip(), "seed": args.seed,
                      "ok": False, "failure": str(e),
                      # the flight-recorder dump: per recent service,
                      # the last-N request event timelines at failure
                      "flight_recorder": getattr(e, "flightrec", None)}
            rc = 1
        print(json.dumps(report), flush=True)
    certified = ("chaos_serve: CERTIFIED — fleet healed every kill "
                 "through warm probe-gated resurrection, poisoned "
                 "replica quarantined with zero traffic, every "
                 "autoscaler resize audited over the wire"
                 if args.fleet and args.elastic else
                 "chaos_serve: CERTIFIED — zero lost tickets under the "
                 "replica kill, failover provenance in every "
                 "re-dispatched audit, drained replica exited empty, "
                 "warm-start successor served certified from the "
                 "shared recycle state"
                 if args.fleet else
                 "chaos_serve: CERTIFIED — every request classified, "
                 "every audit at acg-tpu-stats/13, breaker trail on "
                 "schedule")
    print(certified if rc == 0 else
          "chaos_serve: FAILED (see the per-config reports above)",
          file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
