"""Per-request supervision over a Session + CoalescingQueue.

:class:`SolverService` is the request-facing face of the serve layer:
``submit(b)`` admits a right-hand side into the coalescing queue and
returns a :class:`Request`; ``request.response()`` yields a
:class:`ServeResponse` carrying

- the demuxed per-request :class:`~acg_tpu.solvers.base.SolveResult`
  (or the failure classification),
- the **audit record**: the schema-versioned stats-export document
  (``acg-tpu-stats/13``, acg_tpu/obs/export.py) with the per-request
  ``session`` block (cache hit/miss counters, queue wait, batch
  occupancy, request id) and the ``admission`` block (deadline budget,
  retries used, breaker state, shed/degraded flags) — every response is
  a complete, lintable telemetry document, failed, shed and timed-out
  requests included (that is when the telemetry matters, the PR 4
  contract);
- queue/batch metadata (wait, bucket, occupancy, whether the dispatch
  hit the executable cache).

The **admission-robustness layer** (acg_tpu/serve/admission.py) wraps
every request in the production safety net: per-request deadlines
(in-queue expiry sheds with ``ERR_TIMEOUT``; ``response()`` is a
classified terminal response at the deadline, never an exception or a
hang, with late results re-pollable via :meth:`Request.repoll`),
bounded seeded-backoff retries for TRANSIENT failures (the PR 4
classification), a per-``(solver, bucket, dtype)`` circuit breaker
with an audited OPEN/HALF_OPEN/CLOSED lifecycle, bounded-depth load
shedding (``ERR_OVERLOADED``), and graceful degradation of
pipelined/s-step traffic onto classic CG while its breaker is open.
All of it defaults OFF — a default :class:`AdmissionPolicy` leaves the
dispatched program and per-request results bit-identical to the plain
serve layer.

``resilient=True`` gives failed requests ``solve_resilient()``
semantics: the request is re-run ALONE under the self-healing
supervisor (acg_tpu/robust/supervisor.py) against the session's host
matrix — segmented attempts, host certification of the true residual,
the bounded escalation ladder — and the response carries the
RecoveryReport in its audit document's ``resilience`` block.  The
admission retry ladder runs FIRST (cheap identical re-runs for
transient corruption); ``solve_resilient()`` is the escalation for
what retries cannot clear.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time

import numpy as np

from acg_tpu.config import SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.obs import metrics as _metrics
from acg_tpu.obs.events import FlightRecorder, new_trace_id
from acg_tpu.obs.trace import span
from acg_tpu.serve.admission import (AdmissionPolicy, AdmissionRecord,
                                     BreakerBoard, RollingWindow,
                                     HALF_OPEN, OPEN)
from acg_tpu.serve.queue import CoalescingQueue, QueuePolicy, Ticket
from acg_tpu.serve.session import Session, _normalize_solver
from acg_tpu.solvers.base import SolveResult, SolveStats

# runtime telemetry (acg_tpu/obs/metrics.py; no-ops until
# enable_metrics()): request outcomes and end-to-end latency, recorded
# host-side at response classification — the counters behind the SLO
# harness's final snapshot
_M_REQUESTS = _metrics.counter(
    "acg_serve_requests_total",
    "Classified request responses by outcome status", ("status",))
_M_E2E = _metrics.histogram(
    "acg_serve_request_seconds",
    "End-to-end request latency, submit to classified response")
_M_SHED = _metrics.counter(
    "acg_serve_shed_total", "Requests shed (admission or queue)")
_M_RETRIES = _metrics.counter(
    "acg_serve_retries_total", "Admission-layer retry attempts")
_M_DEGRADED = _metrics.counter(
    "acg_serve_degraded_total",
    "Requests served by the degradation ladder")
_M_TIMEOUTS = _metrics.counter(
    "acg_serve_timeouts_total", "Requests classified ERR_TIMEOUT")

# the per-request audit's metrics block, memoized: the snapshot is a
# PROCESS-global walk of every family (O(registry) dicts), identical
# across the requests of any instant — rebuilding it per classified
# response would tax the service exactly when it is busiest.  A short
# TTL keeps audits fresh without the per-request cost; the benign race
# (two threads rebuild, one wins) is harmless.
_SNAPSHOT_TTL_S = 0.25
_snapshot_cache = {"t": float("-inf"), "snap": None}


def _metrics_block() -> dict | None:
    """None when the registry is disabled (the default); else a
    recent-within-TTL ``MetricsRegistry.snapshot()``."""
    if not _metrics.metrics_enabled():
        return None
    now = time.monotonic()
    if _snapshot_cache["snap"] is None \
            or now - _snapshot_cache["t"] > _SNAPSHOT_TTL_S:
        _snapshot_cache["snap"] = _metrics.registry().snapshot()
        _snapshot_cache["t"] = now
    return _snapshot_cache["snap"]

# admission-terminal statuses: outcomes the ADMISSION layer produced
# (nothing ran, or the deadline passed) — retrying or escalating them
# through solve_resilient would re-run work the client has already
# classified/abandoned
_ADMISSION_TERMINAL = (Status.ERR_TIMEOUT, Status.ERR_OVERLOADED)


@dataclasses.dataclass
class ServeResponse:
    """One request's complete outcome."""

    request_id: str
    ok: bool
    status: str
    result: object | None          # per-request SolveResult (or None)
    error: str | None
    audit: dict | None             # acg-tpu-stats/13 document
    queue_wait: float
    batch_size: int                # real requests coalesced together
    bucket: int                    # padded batch size dispatched
    occupancy: float
    cache_hit: bool                # executable cache hit at dispatch
    wall: float                    # dispatch wall (shared by the batch)
    recovered: bool = False        # solve_resilient() rescued it
    shed: bool = False             # never dispatched (deadline/overload)
    degraded: bool = False         # served by the degradation ladder
    degraded_from: str | None = None   # the solver it degraded FROM
    retries: int = 0               # admission retries consumed
    # replica-fleet provenance (ISSUE 15): which replica served this
    # response, and — for a failed-over request — the ordered chain of
    # replicas whose deaths it survived (None outside a fleet)
    replica_id: str | None = None
    failover_from: list | None = None

    def summary(self) -> dict:
        """The one-line JSON the CLI serve REPL prints per request."""
        r = self.result
        d = {
            "request": self.request_id, "ok": self.ok,
            "status": self.status,
            "iterations": None if r is None else int(r.niterations),
            "relative_residual": (None if r is None
                                  else float(r.relative_residual)),
            "batched": self.batch_size, "bucket": self.bucket,
            "queue_wait_ms": round(self.queue_wait * 1e3, 3),
            "cache_hit": self.cache_hit,
            "wall_ms": round(self.wall * 1e3, 3),
            "recovered": self.recovered,
        }
        # admission outcomes ride the line only when they happened, so
        # default-policy REPL output stays byte-compatible
        if self.shed:
            d["shed"] = True
        if self.degraded:
            d["degraded"] = True
            d["degraded_from"] = self.degraded_from
        if self.retries:
            d["retries"] = self.retries
        if self.replica_id is not None:
            d["replica"] = self.replica_id
        if self.failover_from:
            d["failover_from"] = list(self.failover_from)
        return d


class Request:
    """Handle for a submitted request (wraps the queue ticket).

    ``response(timeout)`` NEVER raises on expiry: a caller timeout or a
    deadline expiry yields a classified ``ERR_TIMEOUT``
    :class:`ServeResponse`.  A deadline expiry is terminal (cached); a
    bare caller timeout is provisional — calling ``response()`` again
    resumes waiting.  Either way the underlying ticket stays live, so a
    late batch completion is recoverable through :meth:`repoll` with no
    double-dispatch (the queue completes each ticket exactly once)."""

    def __init__(self, service: "SolverService", ticket: Ticket | None,
                 record: AdmissionRecord | None = None,
                 request_id: str | None = None,
                 response: ServeResponse | None = None):
        self._service = service
        self._ticket = ticket
        self._record = record
        self._rid = (request_id if request_id is not None
                     else ticket.request_id if ticket is not None
                     else None)
        self._response = response
        self._final = response is not None
        self._lock = threading.Lock()

    @property
    def request_id(self) -> str:
        return self._rid

    def response(self, timeout: float | None = None) -> ServeResponse:
        with self._lock:
            if not self._final:
                resp, final = self._service._finish_request(
                    self._ticket, timeout, self._record)
                self._response, self._final = resp, final
            return self._response

    def repoll(self) -> ServeResponse:
        """Late-result path: if the batch completed AFTER a terminal
        ``ERR_TIMEOUT`` response was issued, upgrade to the real
        outcome (the ticket was completed exactly once by its dispatch;
        this merely reads it)."""
        with self._lock:
            late = (self._final and self._response is not None
                    and self._response.status == "ERR_TIMEOUT"
                    and self._ticket is not None
                    and not self._ticket.shed and self._ticket.done)
            if late:
                # the terminal timeout was already counted in the
                # service stats/health window; this late read must not
                # count the same request twice
                resp, final = self._service._finish_request(
                    self._ticket, 0.0, self._record, count=False)
                if final:
                    self._response = resp
                return self._response
        return self.response(timeout=0.0)


class SolverService:
    """The admission front of one :class:`Session` (one operator, one
    solver configuration — requests differing only in their right-hand
    side coalesce; a different solver/options needs its own service)."""

    def __init__(self, session: Session, *, solver: str = "cg",
                 options: SolverOptions | None = None,
                 max_batch: int = 8, max_wait_ms: float = 0.0,
                 buckets=(), resilient: bool = False,
                 max_restarts: int = 4,
                 admission: AdmissionPolicy | None = None,
                 flightrec_capacity: int = 256,
                 replica_id: str | None = None,
                 warm_start: bool = False):
        self.session = session
        # fleet membership (ISSUE 15, acg_tpu/serve/fleet.py): the
        # bounded replica label on this service's audit documents and
        # response summaries; None for a bare service (its audits then
        # carry fleet: null — the /10 back-compat shape)
        self.replica_id = replica_id
        # the flight recorder (acg_tpu/obs/events.py): the last N
        # request timelines, bounded memory, always on — per-request
        # trace IDs are minted here at submit and cross-linked into the
        # audit documents (session/admission trace_id, schema /9)
        self.flightrec = FlightRecorder(capacity=flightrec_capacity)
        self.solver = _normalize_solver(solver)
        self.options = (options if options is not None
                        else session.default_options)
        self.resilient = bool(resilient)
        self.max_restarts = int(max_restarts)
        # x0 warm-start serving (ISSUE 20): OFF by default (the
        # zero-overhead clause — disabled, the dispatch path never
        # touches the recycle state).  When on, a request without a
        # client x0 is offered the nearest recent solution as its
        # initial guess, certified after the solve by the TRUE residual
        # against the session's host matrix; a donor that fails
        # certification triggers one cold re-solve — a bad donor can
        # cost iterations, never correctness.
        self.warm_start = bool(warm_start)
        self._nwarm = 0
        self._nwarm_rejected = 0
        self.admission = (admission if admission is not None
                          else AdmissionPolicy())
        self.queue = CoalescingQueue(
            self._dispatch,
            QueuePolicy(max_batch=max_batch,
                        max_wait=max_wait_ms / 1e3,
                        buckets=tuple(buckets)))
        self._ids = itertools.count()
        self._nfailed = 0
        self._nrecovered = 0
        self._nshed = 0
        self._ndegraded = 0
        self._nretries = 0
        self._ntimeouts = 0
        self._board = (BreakerBoard(self.admission)
                       if self.admission.breaker_threshold > 0 else None)
        self._rng = np.random.default_rng(self.admission.seed)
        self._window = RollingWindow(self.admission.window)
        # the chaos-drill injection surface (scripts/chaos_serve.py):
        # each dispatch consumes at most one queued FaultSpec
        self._fault_plans: collections.deque = collections.deque()

    # -- chaos hook -----------------------------------------------------

    def inject_fault(self, spec) -> None:
        """Queue one deterministic :class:`~acg_tpu.robust.faults.
        FaultSpec` for a future dispatch (FIFO, one per dispatch) — the
        seeded chaos drill's injection surface.  Pair with
        ``options.guard_nonfinite=True`` so the device guard converts
        the corruption into a classified ``ERR_FAULT_DETECTED``."""
        self._fault_plans.append(spec)

    def _next_fault(self):
        try:
            return self._fault_plans.popleft()
        except IndexError:
            return None

    # -- dispatch (called by the queue, under its dispatch lock) --------

    def _route(self):
        """The dispatch-time breaker decision: ``(solver,
        degraded_from)`` — or ``(None, None)`` meaning fast-fail the
        batch with ERR_OVERLOADED (breaker open, no degradation
        available)."""
        if self._board is None:
            return self.solver, None
        admit, state, sig = self._board.admit(self.solver,
                                              self.session.dtype)
        if admit:
            return self.solver, None
        if self.admission.degrade and self.solver != "cg":
            ok2, _, _ = self._board.admit("cg", self.session.dtype)
            if ok2:
                return "cg", self.solver
        return None, None

    def _dispatch(self, bb, x0=None):
        nrhs = bb.shape[0] if bb.ndim == 2 else 1
        solver, degraded_from = self._route()
        meta = {"solver": solver, "degraded_from": degraded_from}
        if solver is None:
            e = AcgError(Status.ERR_OVERLOADED,
                         "circuit breaker open: request fast-failed at "
                         "dispatch (no degradation target)")
            e.dispatch_meta = meta
            raise e
        fault = self._next_fault()
        hit = (fault is None
               and self.session.has_executable(solver, nrhs,
                                               self.options,
                                               has_x0=x0 is not None))
        meta["cache_hit"] = hit
        ok = False
        # the session spans this dispatch closed: its requests' audit
        # documents carry them (SpanTracer.as_dicts)
        first = self.session.tracer.mark()
        try:
            res = self.session.solve(bb, solver=solver,
                                     options=self.options, x0=x0,
                                     fault=fault)
            ok = bool(res.converged)
        except AcgError as e:
            e.dispatch_meta = meta
            raise
        finally:
            meta["span_marks"] = (first, self.session.tracer.mark())
            if self._board is not None:
                self._board.record(solver, nrhs, self.session.dtype, ok)
        return res, meta

    # -- submission -----------------------------------------------------

    def submit(self, b, request_id: str | None = None, *,
               x0=None, trace_id: str | None = None,
               fleet_meta: dict | None = None) -> Request:
        """Admit one right-hand side.  ``x0`` is an optional client
        initial guess (it rides the coalesced batch as an operand and
        only ever changes iteration counts, never the certified
        answer); when absent and ``warm_start`` is on, the session's
        recycle state may donate one from a recent nearby solution.
        ``trace_id`` pins the request's trace ID instead of minting a
        fresh one — the fleet failover path re-submits a dead replica's
        ticket on a survivor under the SAME trace ID, so the flight
        recorders' timelines join across the hop.  ``fleet_meta`` is
        the failover provenance the audit's schema-/10 ``fleet`` block
        records (Fleet-internal)."""
        b = np.asarray(b)
        if b.ndim != 1:
            raise AcgError(Status.ERR_INVALID_VALUE,
                           "submit() admits ONE right-hand side per "
                           "request (the queue builds the batch)")
        if b.shape[0] != self.session.nrows:
            raise AcgError(Status.ERR_INVALID_VALUE,
                           f"right-hand side has {b.shape[0]} entries, "
                           f"operator has {self.session.nrows} rows")
        if not np.all(np.isfinite(b)):
            # reject the poison at the door: a NaN/Inf RHS would ride
            # the coalesced batch into the SHARED device program and
            # contaminate every batch-mate's reductions — the one
            # failure mode coalescing must never socialize
            raise AcgError(Status.ERR_INVALID_VALUE,
                           "right-hand side contains non-finite values "
                           "(rejected at admission: a NaN/Inf system "
                           "would poison its coalesced batch-mates)")
        x0_meta = None
        if x0 is not None:
            x0 = np.asarray(x0)
            if x0.shape != b.shape:
                raise AcgError(Status.ERR_INVALID_VALUE,
                               f"x0 shape {x0.shape} does not match the "
                               f"right-hand side {b.shape}")
            if not np.all(np.isfinite(x0)):
                raise AcgError(Status.ERR_INVALID_VALUE,
                               "x0 contains non-finite values (rejected "
                               "at admission: a NaN/Inf guess would "
                               "poison its coalesced batch-mates)")
            x0_meta = {"source": "client", "sketch_distance": None}
        elif self.warm_start:
            x0, x0_meta = self.session.recycle_state.propose(b)
            if x0 is None:
                x0_meta = None      # no donor: an ordinary cold request
        if request_id is None:
            request_id = f"req-{next(self._ids)}"
        self.session.counters["requests"] += 1
        pol = self.admission
        now = time.perf_counter()
        # per-request trace: one ID for the whole submit -> coalesce ->
        # dispatch -> demux -> response path, one flight-recorder
        # timeline (the timeline's first event is "submit"; a failover
        # re-submission reuses the ORIGINAL trace ID so the hop is one
        # trace across two recorders)
        trace = self.flightrec.begin(
            request_id, trace_id if trace_id is not None
            else new_trace_id())
        if fleet_meta is not None:
            trace.event("failover",
                        hop=int(fleet_meta.get("hops", 0)),
                        from_replica=(fleet_meta.get("failover_from")
                                      or [None])[-1],
                        to_replica=self.replica_id)
        if x0_meta is not None and x0_meta.get("source") == "recycled":
            trace.event("warmstart",
                        sketch_distance=x0_meta.get("sketch_distance"))
        rec = AdmissionRecord(
            policy=pol, admitted_at=now, trace_id=trace.trace_id,
            fleet_meta=fleet_meta,
            deadline_s=(None if pol.deadline_s is None
                        else now + pol.deadline_s),
            queue_deadline_s=(None if pol.queue_deadline_s is None
                              else now + pol.queue_deadline_s))
        # load shedding: a bounded backlog rejects NOW instead of
        # queueing work whose deadline will have died of old age
        if pol.max_queue_depth > 0 \
                and self.queue.depth >= pol.max_queue_depth:
            return self._preset(request_id, b, rec, Status.ERR_OVERLOADED,
                                f"queue depth {self.queue.depth} >= "
                                f"bound {pol.max_queue_depth} "
                                "(request shed at admission)",
                                trace=trace)
        if self._board is not None:
            admit, state, sig = self._board.peek(self.solver,
                                                 self.session.dtype)
            rec.breaker_state = state
            rec.breaker_signature = sig
            if not admit and not (pol.degrade and self.solver != "cg"):
                return self._preset(
                    request_id, b, rec, Status.ERR_OVERLOADED,
                    f"circuit breaker {state} for {sig} "
                    "(fast-fail; no degradation target)", trace=trace)
        try:
            ticket = self.queue.submit(
                b, request_id, queue_deadline=rec.queue_deadline_s,
                trace=trace, x0=x0, x0_meta=x0_meta)
        except AcgError as e:
            if e.status == Status.ERR_OVERLOADED:
                # closed queue (drain/shutdown): a classified terminal
                # response, like any other admission refusal
                return self._preset(request_id, b, rec,
                                    Status.ERR_OVERLOADED, str(e),
                                    trace=trace)
            raise
        return Request(self, ticket, rec)

    def _preset(self, request_id: str, b, rec: AdmissionRecord,
                status: Status, msg: str, trace=None) -> Request:
        """A request refused at admission: a complete, classified,
        audit-carrying terminal response without ever touching the
        queue."""
        rec.shed = True
        self._nshed += 1
        self._nfailed += 1
        self._window.record(False)      # failure; no latency sample
        #                                 (nothing ever ran)
        if trace is not None:
            trace.event("shed", status=status.name, where="admission")
            trace.event("response", status=status.name, ok=False)
        _M_REQUESTS.labels(status=status.name).inc()
        _M_SHED.inc()
        audit = self._stub_audit(b, request_id, status, rec,
                                 trace_id=rec.trace_id)
        resp = ServeResponse(
            request_id=request_id, ok=False, status=status.name,
            result=None, error=msg, audit=audit, queue_wait=0.0,
            batch_size=0, bucket=0, occupancy=0.0, cache_hit=False,
            wall=0.0, shed=True, retries=0,
            replica_id=self.replica_id,
            failover_from=(rec.fleet_meta or {}).get("failover_from"))
        return Request(self, None, rec, request_id=request_id,
                       response=resp)

    def solve(self, b, request_id: str | None = None,
              timeout: float | None = None) -> ServeResponse:
        """Synchronous convenience: submit + wait."""
        return self.submit(b, request_id).response(timeout)

    def flush(self) -> None:
        self.queue.flush()

    def close(self, drain: bool = True,
              shed_status: Status = Status.ERR_OVERLOADED) -> None:
        """Graceful shutdown (idempotent): the queue rejects new
        submits with classified ``ERR_OVERLOADED`` responses, the
        backlog is deterministically drained (``drain=True``) or shed
        with ``shed_status``, and every waiter wakes with a terminal
        outcome.  The session is NOT closed here — it may back other
        services (the fleet closes sessions when it retires a
        replica)."""
        self.queue.close(drain=drain, shed_status=shed_status)

    # -- response assembly ----------------------------------------------

    def _finish_request(self, ticket: Ticket, timeout,
                        record: AdmissionRecord | None,
                        count: bool = True
                        ) -> tuple[ServeResponse, bool]:
        """Wait, then respond (:meth:`_respond`, one
        ``acg.serve.respond`` span).  ``count=False`` is the repoll
        path: the request was already counted into the failure/shed/
        window stats when its terminal timeout was issued."""
        rec = (record if record is not None
               else AdmissionRecord(policy=self.admission))
        # the caller's timeout never waits past the request deadline
        eff = timeout
        rem = rec.remaining_s()
        if rem is not None:
            eff = rem if eff is None else min(eff, rem)
        res, err = None, None
        try:
            res = ticket.result(None if eff is None
                                else max(eff, 0.0))
        except TimeoutError:
            rem = rec.remaining_s()
            if rem is None or rem > 0:
                # bare caller timeout: provisional — response() again
                # resumes the wait, the ticket stays completable
                return self._timeout_response(ticket, rec,
                                              terminal=False), False
            # deadline expired: shed from the queue if still pending
            rec.expired = True
            if not ticket.done:
                self.queue.cancel(ticket, AcgError(
                    Status.ERR_TIMEOUT,
                    f"deadline ({self.admission.deadline_ms:.0f} ms) "
                    "expired before a result was produced"))
            if not ticket.done:
                # dispatched but unfinished: the device program cannot
                # be preempted — classify NOW (the client contract),
                # leave the late result re-pollable.  No latency
                # samples: the wait/wall of an abandoned in-flight
                # request is unknown at this point.
                if count:
                    self._ntimeouts += 1
                    self._nfailed += 1
                    self._window.record(False)
                    _M_REQUESTS.labels(status="ERR_TIMEOUT").inc()
                    _M_TIMEOUTS.inc()
                    _M_E2E.observe(time.perf_counter()
                                   - ticket.enqueue_t)
                if ticket.trace is not None:
                    ticket.trace.event("response", status="ERR_TIMEOUT",
                                       ok=False, terminal=True)
                return self._timeout_response(ticket, rec,
                                              terminal=True), True
            try:
                res = ticket.result(0.0)
            except AcgError as e:
                err = e
                res = getattr(e, "result", None)
        except AcgError as e:
            err = e
            res = getattr(e, "result", None)
        tr = ticket.trace
        with span("acg.serve.respond",
                  trace_id=tr.trace_id if tr is not None else ""):
            return self._respond(ticket, rec, res, err, count)

    def _respond(self, ticket: Ticket, rec: AdmissionRecord, res, err,
                 count: bool) -> tuple[ServeResponse, bool]:
        """Classify a finished request — retry or recover it, certify a
        warm start — and assemble its response and audit document."""
        resil_report = None
        recovered = False
        # the authoritative per-dispatch bit, recorded by _dispatch
        # BEFORE the solve (a cold signature compiles = a miss)
        exec_hit = bool(ticket.dispatch_meta.get("cache_hit", False))
        solver_used = ticket.dispatch_meta.get("solver", self.solver)
        rec.degraded_from = ticket.dispatch_meta.get("degraded_from")
        rec.degraded = rec.degraded_from is not None
        if ticket.shed or (err is not None and getattr(err, "status",
                           None) == Status.ERR_OVERLOADED):
            rec.shed = True
        if err is not None and getattr(err, "status", None) \
                == Status.ERR_TIMEOUT:
            rec.expired = True
        # bounded retry: transient failures re-run ALONE with seeded
        # backoff (the PR 4 classification decides; deterministic
        # failures fall straight through)
        if err is not None and self._can_retry(err):
            res, err = self._retry(ticket, res, err, rec,
                                   solver_used or self.solver)
        # resilient escalation is for LIVE requests only: an expired
        # request's client already holds its classified ERR_TIMEOUT
        # (running the ladder now would blow the deadline contract by
        # seconds of device work), and a repoll (count=False) "merely
        # reads" the late outcome — it must never re-run anything
        if err is not None and self.resilient and count \
                and not rec.expired \
                and getattr(err, "status", None) \
                not in _ADMISSION_TERMINAL:
            res, err, resil_report, recovered = self._recover(ticket,
                                                              res, err)
        ok = err is None and res is not None and bool(res.converged)
        # warm-start epilogue (ISSUE 20): certify a donor-served result
        # against the TRUE residual (a stale/adversarial donor triggers
        # one cold re-solve — never a wrong answer), then feed the
        # solution back into the donor pool.  Entirely skipped for a
        # plain service (the zero-overhead clause) and on repolls.
        warmstart = None
        ws = getattr(ticket, "x0_meta", None)
        if count and (self.warm_start or ws is not None):
            res, err, ok, warmstart = self._warmstart_finish(
                ticket, res, err, ok, ws)
        if count:
            if not ok:
                self._nfailed += 1
            if rec.shed:
                self._nshed += 1
            if rec.degraded:
                self._ndegraded += 1
            if err is not None and getattr(err, "status", None) \
                    == Status.ERR_TIMEOUT:
                self._ntimeouts += 1
            # latency samples only for requests that actually RAN: a
            # shed/fast-failed request (queue-deadline expiry, breaker
            # open at dispatch) has no meaningful wait/wall — zeros
            # and deadline-length waits would skew the percentiles
            # exactly when the service is under stress
            ran = bool(ticket.bucket) and not rec.shed
            self._window.record(
                ok,
                ticket.queue_wait if ran else None,
                ticket.dispatch_wall if ran else None)
        status = (getattr(getattr(res, "status", None), "name", None)
                  or (err.status.name if err is not None
                      and hasattr(err, "status") else "SUCCESS"))
        if ticket.trace is not None:
            ticket.trace.event("response", status=status, ok=ok)
        if count:
            # runtime telemetry: one classified response = one sample
            # (repolls excluded, like the window/counter stats above)
            _M_REQUESTS.labels(status=status).inc()
            _M_E2E.observe(time.perf_counter() - ticket.enqueue_t)
            if rec.shed:
                _M_SHED.inc()
            if rec.degraded:
                _M_DEGRADED.inc()
            if status == "ERR_TIMEOUT":
                _M_TIMEOUTS.inc()
        audit = self._audit_document(ticket, res, resil_report,
                                     exec_hit, rec, status,
                                     solver=solver_used or self.solver,
                                     warmstart=warmstart)
        return ServeResponse(
            request_id=ticket.request_id, ok=ok, status=status,
            result=res, error=None if err is None else str(err),
            audit=audit, queue_wait=ticket.queue_wait,
            batch_size=ticket.batch_size, bucket=ticket.bucket,
            occupancy=ticket.occupancy, cache_hit=exec_hit,
            wall=ticket.dispatch_wall, recovered=recovered,
            shed=rec.shed, degraded=rec.degraded,
            degraded_from=rec.degraded_from,
            retries=rec.retries_used,
            replica_id=self.replica_id,
            failover_from=(rec.fleet_meta or {}).get(
                "failover_from")), True

    def _timeout_response(self, ticket: Ticket, rec: AdmissionRecord,
                          terminal: bool) -> ServeResponse:
        """Classified ERR_TIMEOUT response.  Provisional responses get
        a full stub audit too — EVERY response is a complete, lintable
        telemetry document by contract; the cost (one |b| norm + a span
        snapshot) is the same order as any response's audit build, paid
        only on a poll that elapsed."""
        wait = time.perf_counter() - ticket.enqueue_t \
            if not ticket.done else ticket.queue_wait
        audit = self._stub_audit(ticket.b, ticket.request_id,
                                 Status.ERR_TIMEOUT, rec)
        kind = ("deadline expired" if terminal
                else "response(timeout) elapsed (provisional; call "
                     "response() again to resume waiting)")
        return ServeResponse(
            request_id=ticket.request_id, ok=False,
            status=Status.ERR_TIMEOUT.name, result=None,
            error=f"request timed out: {kind}", audit=audit,
            queue_wait=wait, batch_size=ticket.batch_size,
            bucket=ticket.bucket, occupancy=ticket.occupancy,
            cache_hit=False, wall=ticket.dispatch_wall,
            retries=rec.retries_used,
            replica_id=self.replica_id,
            failover_from=(rec.fleet_meta or {}).get("failover_from"))

    def _can_retry(self, err) -> bool:
        from acg_tpu.robust.supervisor import classify_failure

        return (self.admission.max_retries > 0
                and hasattr(err, "status")
                and classify_failure(err.status) == "transient")

    def _retry(self, ticket: Ticket, res, err, rec: AdmissionRecord,
               solver: str):
        """Bounded seeded-backoff retry of a TRANSIENT failure: the
        request re-runs ALONE (bucket-1 signature) against the warm
        session, up to ``max_retries`` times within its deadline."""
        from acg_tpu.robust.supervisor import classify_failure

        for attempt in range(1, self.admission.max_retries + 1):
            delay = self.admission.backoff_s(attempt, self._rng)
            rem = rec.remaining_s()
            if rem is not None and rem <= delay:
                rec.expired = rem <= 0
                break       # no deadline budget for another attempt
            if delay > 0:
                time.sleep(delay)
            rec.retries_used = attempt
            rec.backoffs_ms.append(delay * 1e3)
            self._nretries += 1
            _M_RETRIES.inc()
            if ticket.trace is not None:
                ticket.trace.event("retry", attempt=attempt,
                                   backoff_ms=round(delay * 1e3, 3))
            ok = False
            try:
                with self.session.tracer.span("retry"):
                    res2 = self.session.solve(
                        ticket.b, solver=solver, options=self.options,
                        x0=getattr(ticket, "x0", None))
                ok = bool(res2.converged)
                if ok:
                    res, err = res2, None
                else:
                    res, err = res2, AcgError(res2.status)
            except AcgError as e2:
                res = getattr(e2, "result", res)
                err = e2
            finally:
                if self._board is not None:
                    self._board.record(solver, 1, self.session.dtype,
                                       ok)
            if err is None \
                    or classify_failure(err.status) != "transient":
                break
        return res, err

    # -- warm start (ISSUE 20) ------------------------------------------

    def _certified(self, b, res, ok: bool) -> bool:
        """True-residual certification against the session's HOST
        matrix: ``‖b - A x‖ <= 10 * max(atol, rtol*‖b‖)`` (the slack
        absorbs recurrence-vs-true rounding; a poisoned donor misses by
        orders of magnitude, not a factor).  A non-converged or
        non-finite result never certifies."""
        if not ok or res is None:
            return False
        A = self.session.A
        if not hasattr(A, "matvec"):
            return True     # no host operator to certify against
        x = np.asarray(res.x, dtype=np.float64)
        if x.shape != (self.session.nrows,) \
                or not np.all(np.isfinite(x)):
            return False
        b = np.asarray(b, dtype=np.float64)
        o = self.options
        tol = max(o.residual_atol,
                  o.residual_rtol * float(np.linalg.norm(b)))
        if tol <= 0:
            return True     # no residual stop configured: nothing to pin
        r = b - np.asarray(A.matvec(x), dtype=np.float64)
        return float(np.linalg.norm(r)) <= 10.0 * tol

    def _warmstart_finish(self, ticket: Ticket, res, err, ok: bool,
                          ws: dict | None):
        """Certify / reject / observe, and build the audit document's
        ``warmstart`` block.  The rejection path re-solves ALONE with a
        cold x0 (worst case: the same iterations a cold request pays),
        so the response status reflects the PROBLEM, not the donor."""
        state = self.session.recycle_state if self.warm_start else None
        donor = ws is not None and ws.get("source") == "recycled"
        rejected = False
        if donor:
            self._nwarm += 1
        if donor and not self._certified(ticket.b, res, ok):
            rejected = True
            self._nwarm_rejected += 1
            if state is not None:
                state.reject()
            if ticket.trace is not None:
                ticket.trace.event(
                    "warmstart-rejected",
                    sketch_distance=ws.get("sketch_distance"))
            try:
                with self.session.tracer.span("warmstart-recheck"):
                    res2 = self.session.solve(ticket.b,
                                              solver=self.solver,
                                              options=self.options)
                ok = bool(res2.converged)
                res, err = res2, (None if ok
                                  else AcgError(res2.status))
            except AcgError as e2:
                res = getattr(e2, "result", res)
                err, ok = e2, False
        saved = None
        warm_served = donor and not rejected
        if ok and state is not None and res is not None:
            if warm_served:
                saved = state.iterations_saved(res.niterations)
            state.observe(ticket.b, res.x, res.niterations,
                          warm=warm_served)
        warmstart = {
            "enabled": bool(self.warm_start),
            "source": (ws or {}).get("source", "none"),
            "sketch_distance": (ws or {}).get("sketch_distance"),
            "iterations_saved": saved,
            "rejected": rejected,
        }
        return res, err, ok, warmstart

    def _recover(self, ticket: Ticket, res, err):
        """solve_resilient() semantics for a failed request: re-run it
        ALONE under the self-healing supervisor against the session's
        host matrix."""
        from acg_tpu.robust.supervisor import solve_resilient

        s = self.session
        if not hasattr(s.A, "matvec"):
            return res, err, None, False
        o = dataclasses.replace(self.options, guard_nonfinite=True)
        try:
            with s.tracer.span("recover"):
                res2, rep = solve_resilient(
                    s.A, ticket.b, options=o, solver=self.solver,
                    nparts=s.nparts, dtype=s.dtype, fmt=s.fmt,
                    mat_dtype=s.mat_dtype, halo=s.halo,
                    partition_method=s.partition_method, seed=s.seed,
                    max_restarts=self.max_restarts, tracer=s.tracer)
            self._nrecovered += 1
            return res2, None, rep.as_dict(), True
        except AcgError as e2:
            rep = getattr(e2, "recovery", None)
            res2 = getattr(e2, "result", None) or res
            return res2, e2, (rep.as_dict() if rep is not None
                              else None), False

    # -- audit documents ------------------------------------------------

    def _fleet_block(self, rec: AdmissionRecord) -> dict | None:
        """The schema-/12 ``fleet`` block: null for a bare service
        (back-compat), else this replica's identity plus the failover
        chain the Fleet threaded through ``submit(fleet_meta=)`` — and,
        since /12, the elastic-fleet snapshot (``resurrections``,
        ``quarantined``, ``autoscaler``): a plain fleet's defaults, the
        real :meth:`Fleet._fleet_state` numbers when an elastic fleet
        threaded ``fleet_meta["fleet_state"]``."""
        if self.replica_id is None and rec.fleet_meta is None:
            return None
        meta = rec.fleet_meta or {}
        ff = meta.get("failover_from")
        state = meta.get("fleet_state") or {}
        return {"replica_id": (self.replica_id if self.replica_id
                               is not None else "unfleeted"),
                "failover_from": list(ff) if ff else None,
                "hops": int(meta.get("hops", len(ff) if ff else 0)),
                "resurrections": int(state.get("resurrections", 0)),
                "quarantined": int(state.get("quarantined", 0)),
                "autoscaler": state.get("autoscaler")}

    def _admission_block(self, rec: AdmissionRecord) -> dict:
        trips = 0
        if self._board is not None:
            if rec.breaker_signature is not None:
                st = self._board.states().get(rec.breaker_signature)
                trips = st["trips"] if st else self._board.trips
            else:
                trips = self._board.trips
        return rec.as_dict(trips=trips)

    def _stub_result(self, b, status: Status) -> SolveResult:
        """A zero-work SolveResult for requests that never produced one
        (shed, overloaded, timed out): enough structure for a complete,
        schema-valid audit document — nothing ran, and the document says
        exactly that."""
        bnrm = float(np.linalg.norm(np.asarray(b, np.float64)))
        return SolveResult(
            x=np.zeros(0), converged=False, niterations=0, bnrm2=bnrm,
            r0nrm2=bnrm, rnrm2=bnrm, stats=SolveStats(),
            status=status, residual_history=None)

    def _stub_audit(self, b, request_id: str, status: Status,
                    rec: AdmissionRecord,
                    trace_id: str | None = None) -> dict:
        from acg_tpu.obs.export import build_stats_document

        stub = self._stub_result(b, status)
        t = _StubTicket(request_id, trace_id=(trace_id if trace_id
                                              is not None
                                              else rec.trace_id))
        return build_stats_document(
            solver=self.solver, options=self.options, res=stub,
            stats=stub.stats, nunknowns=self.session.nrows,
            nparts=self.session.nparts,
            phases=self.session.tracer.as_dicts(since=0, until=0),
            session=self.session_block(t, False),
            admission=self._admission_block(rec),
            metrics=_metrics_block(),
            fleet=self._fleet_block(rec))

    def _audit_document(self, ticket: Ticket, res, resil_report,
                        exec_hit: bool, rec: AdmissionRecord,
                        status: str,
                        solver: str | None = None,
                        warmstart: dict | None = None) -> dict | None:
        """The per-request audit record: one complete ``acg-tpu-stats/13``
        document (validated by the shared linter at write time in the
        CLI; built here for every response — success, failure, shed and
        timeout alike).  ``solver`` is the solver that actually RAN the
        dispatch (the degradation ladder may have routed a pipelined
        request onto classic CG — the document must say so, not report
        the nominal solver); ``warmstart`` is the /13 donor-provenance
        block (null for a plain request — back-compat shape)."""
        from acg_tpu.obs.export import build_stats_document

        if res is None or res.stats is None:
            res = self._stub_result(
                ticket.b, getattr(Status, status, Status.ERR_TIMEOUT))
        return build_stats_document(
            solver=solver if solver is not None else self.solver,
            options=self.options, res=res,
            stats=res.stats, nunknowns=self.session.nrows,
            nparts=self.session.nparts,
            phases=self.session.tracer.as_dicts(
                *ticket.dispatch_meta.get("span_marks", (0, 0))),
            resilience=resil_report,
            session=self.session_block(ticket, exec_hit),
            admission=self._admission_block(rec),
            metrics=_metrics_block(),
            fleet=self._fleet_block(rec),
            warmstart=warmstart)

    def session_block(self, ticket, exec_hit: bool) -> dict:
        """The schema-/6 ``session`` block for one request (+ the /9
        ``trace_id`` cross-link into the flight-recorder timeline and
        the Chrome trace export)."""
        c = self.session.counters
        tr = getattr(ticket, "trace", None)
        return {
            "request_id": str(ticket.request_id),
            "trace_id": (tr.trace_id if tr is not None
                         else getattr(ticket, "trace_id", None)),
            "cache": {
                "executable_hit": bool(exec_hit),
                "executable": {
                    "hits": int(c["executable"]["hits"]),
                    "misses": int(c["executable"]["misses"]),
                },
                "prepared": {
                    "hits": int(c["prepared"]["hits"]),
                    "misses": int(c["prepared"]["misses"]),
                },
            },
            "queue": {
                # instantaneous backlog the dispatch left behind — NOT
                # the cumulative max (queue.stats() reports that
                # separately as max_depth)
                "wait_seconds": float(ticket.queue_wait),
                "depth": int(ticket.depth_at_dispatch),
            },
            "batch": {
                "size": int(max(ticket.batch_size, 1)),
                "bucket": int(max(ticket.bucket, 1)),
                "occupancy": float(ticket.occupancy),
            },
        }

    # -- introspection --------------------------------------------------

    def stats(self) -> dict:
        """Merged session + queue + admission counters (the ``stats``
        REPL command and bench_serve's reporting read this)."""
        return {"session": self.session.stats(),
                "queue": self.queue.stats(),
                "requests_failed": self._nfailed,
                "requests_recovered": self._nrecovered,
                "admission": {
                    "shed": self._nshed,
                    "degraded": self._ndegraded,
                    "retries": self._nretries,
                    "timeouts": self._ntimeouts,
                    "breaker_trips": (0 if self._board is None
                                      else self._board.trips),
                },
                "warmstart": {
                    "enabled": self.warm_start,
                    "served": self._nwarm,
                    "rejected": self._nwarm_rejected,
                }}

    def routing_health(self) -> dict:
        """The fleet router's per-submit subset of :meth:`health` —
        ready bit, inflight, window failure rate, breaker-open flag —
        without the percentile sorts, transition-trail copy and nested
        dicts of the full snapshot (this runs once per eligible replica
        per submit; the full ``health()`` is the poller's path)."""
        states = {} if self._board is None else self._board.states()
        return {
            "ready": (not self.queue.closed
                      and not self.session.dead),
            "inflight": int(self.queue.inflight),
            "failure_rate": self._window.failure_rate() or 0.0,
            "breaker_open": any(v["state"] == OPEN
                                for v in states.values()),
        }

    def health(self) -> dict:
        """The serving health snapshot (the REPL ``health`` command and
        bench_serve's report): rolling-window failure rate and p50/p99
        queue-wait / dispatch-wall percentiles, per-signature breaker
        states, backlog depth, cumulative admission counters.  The
        top-level ``status`` collapses it to one word: ``overloaded``
        (some breaker OPEN), ``degraded`` (a breaker half-open, or
        failures in the window), else ``ok``."""
        w = self._window.summary()
        states = {} if self._board is None else self._board.states()
        any_open = any(v["state"] == OPEN for v in states.values())
        any_half = any(v["state"] == HALF_OPEN
                       for v in states.values())
        fr = w["failure_rate"] or 0.0
        status = ("overloaded" if any_open
                  else "degraded" if (any_half or fr > 0) else "ok")
        sld = self.queue.since_last_dispatch()
        return {
            "status": status,
            # the router-facing fields (ISSUE 15): can this service
            # take traffic at all, how much is already riding it, and
            # how stale its dispatcher is — the health-weighted fleet
            # router and the REPL `health` command read these
            "ready": (not self.queue.closed
                      and not self.session.dead),
            "inflight": int(self.queue.inflight),
            "since_last_dispatch_s": (None if sld is None
                                      else float(sld)),
            "depth": int(self.queue.depth),
            "window": w,
            "breakers": states,
            "breaker_transitions": (
                [] if self._board is None
                else list(self._board.transitions)),
            "requests": int(self.session.counters["requests"]),
            "failed": int(self._nfailed),
            "shed": int(self._nshed),
            "degraded": int(self._ndegraded),
            "retries": int(self._nretries),
            "timeouts": int(self._ntimeouts),
            "recovered": int(self._nrecovered),
        }

    def observe(self) -> dict:
        """One observatory scrape unit (ISSUE 16): the registry
        snapshot — FRESH, not the TTL-cached per-audit block, since a
        scraper computing window rates wants current counters — plus
        the full :meth:`health` snapshot and this service's replica
        identity.  ``metrics`` is None while the registry is disabled
        (the zero-overhead default).  ``scripts/fleet_top.py`` and
        :meth:`acg_tpu.serve.fleet.Fleet.observe` read exactly this;
        no scraper touches private attributes."""
        return {
            "replica_id": self.replica_id,
            "metrics": (_metrics.registry().snapshot()
                        if _metrics.metrics_enabled() else None),
            "health": self.health(),
        }


class _StubTicket:
    """Session-block shape for a request that never had a queue ticket
    (refused at admission)."""

    def __init__(self, request_id: str, trace_id: str | None = None):
        self.request_id = request_id
        self.trace_id = trace_id
        self.trace = None
        self.queue_wait = 0.0
        self.depth_at_dispatch = 0
        self.batch_size = 0
        self.bucket = 0
        self.occupancy = 0.0
