"""Persistent solver session: prepared operator + executable cache.

A :class:`Session` is the residency layer between the solvers and
traffic: the operator pipeline (read → partition → tier resolution →
device placement) runs ONCE, through the same phase seams the CLI
traces (``SpanTracer`` spans named exactly as in ``acg_tpu/cli.py``),
and every subsequent solve dispatches into an **AOT-compiled
executable** cached by static signature

    (solver kind, nparts/mesh, padded b shape incl. B, vector dtype,
     operator tier, sstep, static SolverOptions fields)

via the solvers' ``lowered_step``/``aot_step`` hooks — a cache hit
skips read, partition, operator build AND compile entirely (asserted by
tests/test_serve.py on the span list and the compile counter), paying
only the O(n) host pad/scatter of the new right-hand side.

Preparation itself is cached twice over:

- the **prepared-operator cache** (process-level, keyed by graph content
  hash + build parameters) hands a second Session on the same matrix
  the already-uploaded device operator — zero preprocessing, zero
  upload;
- below it, the partition/halo-table **prep cache**
  (``acg_tpu/partition/cache.py``, memory + optional disk) serves
  fresh builds of the same graph across processes.

Sessions are thread-compatible: :meth:`solve` serializes dispatch under
a lock (one device program at a time — the queue layer above provides
the concurrency model).
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from acg_tpu.config import HaloMethod, SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.obs import metrics as _metrics
from acg_tpu.obs.trace import SpanTracer, span

# runtime telemetry (acg_tpu/obs/metrics.py; no-ops until
# enable_metrics()): the executable / prepared-operator cache traffic
# and compile wall — all recorded host-side around the unchanged
# dispatch
_M_EXEC = _metrics.counter(
    "acg_serve_executable_cache_total",
    "AOT-executable cache lookups by outcome", ("outcome",))
_M_PREPARED = _metrics.counter(
    "acg_serve_prepared_operator_total",
    "Prepared-operator cache lookups by outcome", ("outcome",))
_M_COMPILE = _metrics.histogram(
    "acg_serve_compile_seconds",
    "Wall seconds per executable-cache-miss compile")
_M_SOLVES = _metrics.counter(
    "acg_serve_session_solves_total",
    "Session dispatches by path", ("path",))

# solver-name normalization: the CLI spellings all collapse onto the
# four device loop kinds (config.SolverKind aliases)
_KINDS = {
    "cg": "cg", "acg": "cg", "acg-device": "cg", "cg-device": "cg",
    "cg-pipelined": "cg-pipelined", "acg-pipelined": "cg-pipelined",
    "acg-device-pipelined": "cg-pipelined",
    "cg-device-pipelined": "cg-pipelined",
    "cg-sstep": "cg-sstep", "acg-sstep": "cg-sstep",
    "cg-pipelined-deep": "cg-pipelined-deep",
    "acg-pipelined-deep": "cg-pipelined-deep",
    "cg-recycled": "cg-recycled", "acg-recycled": "cg-recycled",
}

# the prepared-operator cache (the reuse half of ROADMAP item 4, at the
# device level): graph hash + build params -> (dev-or-ss, nrows, nnz).
# Process-level and unbounded by design — a serving process holds a
# handful of operators, each already resident in device memory anyway.
_PREPARED: dict = {}
_PREPARED_LOCK = threading.Lock()

# the iteration-amortization store (ROADMAP item 6): per prepared
# operator, the spectral/solution state recent solves left behind —
# warm-start donors, refined s-step shift schedules, the deflation
# basis.  Keyed exactly like _PREPARED (the structure⊕values hash
# split), so fleet replicas sharing a prepared operator share its
# recycle state too — a failover successor serves warm from the same
# donors its dead predecessor fed.
_RECYCLE: dict = {}


def _normalize_solver(solver: str) -> str:
    kind = _KINDS.get(solver)
    if kind is None:
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       f"Session serves the device solvers "
                       f"(cg, cg-pipelined, cg-pipelined-deep, "
                       f"cg-sstep, cg-recycled); got {solver!r}")
    return kind


class RecycleState:
    """Per-operator iteration-amortization state (process-level when the
    prepared-operator cache key exists, else per-Session).

    Three stores, all fed by completed solves and all OPTIONAL inputs to
    later ones — every consumer certifies, so stale or adversarial
    content can cost iterations but never correctness:

    - **warm-start donors**: the last few solutions with a seeded sparse
      sketch of their right-hand side; :meth:`propose` returns the
      nearest donor's ``x`` (by normalized sketch distance) as an x0
      candidate, guarded downstream by true-residual certification;
    - **refined s-step shifts**: the Leja-ordered Ritz-value schedule
      ``cg_sstep_while`` computes per solve, reused as ``shifts0`` so a
      later s-step solve skips Chebyshev/power seeding;
    - **deflation basis**: an orthonormal basis of recent solutions (+
      its small projected operator), consumed by the ``cg-recycled``
      solver's setup-time Galerkin projection.

    The sketch is SPARSE (d rows × m sampled ±1 entries), so sketching
    a 9M-row RHS touches ~1k entries, not the vector."""

    SKETCH_ROWS = 16
    SKETCH_COLS = 64
    MAX_DONORS = 8
    MAX_DEFLATION = 8
    # normalized sketches are unit vectors: unrelated RHS pairs sit near
    # sqrt(2); a correlated stream sits near 0.  Generous by design —
    # certification, not the threshold, guards correctness.
    ACCEPT_DISTANCE = 0.9

    def __init__(self, n: int, seed: int = 0):
        self.n = int(n)
        self.lock = threading.Lock()
        rng = np.random.default_rng((int(seed) << 16) ^ 0x5EED)
        m = min(self.n, self.SKETCH_COLS)
        self._idx = rng.integers(0, self.n,
                                 size=(self.SKETCH_ROWS, m))
        self._sgn = rng.choice([-1.0, 1.0],
                               size=(self.SKETCH_ROWS, m))
        self.donors = collections.deque(maxlen=self.MAX_DONORS)
        self.shifts: dict = {}          # sstep s -> refined schedule
        self._basis = None              # cached (W, WtAW)
        self._basis_version = -1
        self._version = 0               # bumps on every observe()
        self.cold_iters_ema: float | None = None
        self.counters = {"proposals": 0, "hits": 0, "observed": 0,
                         "rejected": 0, "shift_reuses": 0}

    def sketch(self, b) -> np.ndarray:
        """Normalized sparse sketch of one RHS (host, O(d*m))."""
        b = np.asarray(b, dtype=np.float64)
        v = (b[self._idx] * self._sgn).sum(axis=1)
        nrm = float(np.linalg.norm(v))
        return v / nrm if nrm > 0 else v

    def propose(self, b):
        """``(x0, meta)``: the nearest recent solution when its RHS
        sketch sits within :data:`ACCEPT_DISTANCE`, else ``(None,
        meta)``.  ``meta`` is the audit document's ``warmstart``
        material (donor source + sketch distance)."""
        sk = self.sketch(b)
        with self.lock:
            self.counters["proposals"] += 1
            best, best_d = None, float("inf")
            for d in self.donors:
                dist = float(np.linalg.norm(sk - d["sketch"]))
                if dist < best_d:
                    best, best_d = d, dist
            if best is None or best_d > self.ACCEPT_DISTANCE:
                return None, {"source": "none",
                              "sketch_distance": (None if best is None
                                                  else best_d)}
            self.counters["hits"] += 1
            return best["x"].copy(), {"source": "recycled",
                                      "sketch_distance": best_d}

    def observe(self, b, x, niterations: int, warm: bool = False) -> None:
        """Feed one successful solution back (single-RHS only — the
        demuxed per-request shape).  Cold solves also update the
        iteration EMA the ``iterations_saved`` audit field is measured
        against."""
        x = np.asarray(x, dtype=np.float64)
        if x.ndim != 1 or x.shape[0] != self.n \
                or not np.all(np.isfinite(x)):
            return
        sk = self.sketch(b)
        with self.lock:
            self.donors.append({"sketch": sk, "x": x.copy(),
                                "niterations": int(niterations)})
            self._version += 1
            self.counters["observed"] += 1
            if not warm:
                ema = self.cold_iters_ema
                self.cold_iters_ema = (float(niterations) if ema is None
                                       else 0.8 * ema
                                       + 0.2 * float(niterations))

    def iterations_saved(self, niterations: int):
        """Iterations below the cold EMA this warm solve ran (None
        before any cold sample exists)."""
        with self.lock:
            if self.cold_iters_ema is None:
                return None
            return int(round(self.cold_iters_ema - float(niterations)))

    def reject(self) -> None:
        with self.lock:
            self.counters["rejected"] += 1

    # -- s-step shift schedules -----------------------------------------

    def get_shifts(self, s: int):
        with self.lock:
            sh = self.shifts.get(int(s))
            if sh is not None:
                self.counters["shift_reuses"] += 1
                return np.array(sh, copy=True)
            return None

    def put_shifts(self, s: int, shifts) -> None:
        sh = np.asarray(shifts, dtype=np.float64)
        # batched solves refine per system; keep one schedule (system 0)
        if sh.ndim == 2:
            sh = sh[0]
        if sh.ndim != 1 or not np.all(np.isfinite(sh)) \
                or not np.all(sh > 0):
            return
        with self.lock:
            self.shifts[int(s)] = np.array(sh, copy=True)

    # -- deflation basis -------------------------------------------------

    def deflation_basis(self, matvec=None):
        """Orthonormal basis ``W`` over recent solutions plus its
        projected operator ``WtAW = W'AW`` (host; needs ``matvec`` on
        the first call after new donors).  ``(None, None)`` until at
        least two donors exist."""
        with self.lock:
            if self._basis is not None \
                    and self._basis_version == self._version:
                return self._basis
            xs = [d["x"] for d in self.donors]
            version = self._version
        if len(xs) < 2 or matvec is None:
            return None, None
        V = np.stack(xs[-self.MAX_DEFLATION:], axis=1)
        Q, R = np.linalg.qr(V)
        # drop directions QR found numerically dependent
        keep = np.abs(np.diag(R)) > 1e-12 * max(
            float(np.abs(np.diag(R)).max()), 1e-300)
        W = Q[:, keep]
        if W.shape[1] == 0:
            return None, None
        AW = np.stack([np.asarray(matvec(W[:, j]), dtype=np.float64)
                       for j in range(W.shape[1])], axis=1)
        WtAW = W.T @ AW
        with self.lock:
            self._basis = (W, WtAW)
            self._basis_version = version
        return W, WtAW

    def stats(self) -> dict:
        with self.lock:
            return {"donors": len(self.donors),
                    "shift_schedules": len(self.shifts),
                    "cold_iters_ema": self.cold_iters_ema,
                    **{k: int(v) for k, v in self.counters.items()}}


class Session:
    """A prepared, device-resident linear operator plus its executable
    cache — solve many right-hand sides against one matrix without
    re-paying preprocessing or compilation.

    ``A`` is a host matrix (CsrMatrix/EllMatrix/DiaMatrix) or a path is
    given via ``path=`` (Matrix Market, read in the "read" span).
    ``nparts > 1`` prepares the sharded distributed operator; 1 the
    single-chip operator.  ``prep_cache`` routes partitioning through
    :mod:`acg_tpu.partition.cache` (``"auto"`` = the process default,
    ``None`` = off); ``share_prepared=False`` opts out of the
    process-level prepared-operator cache (tests use this to measure
    cold builds).  ``nrhs`` is the batch width the single-chip operator
    tier is chosen for (a SolverService's ``max_batch``: the stencil and
    batched-DIA kernels plan differently per width —
    acg_tpu/ops/stencil.py ``prefer_stencil``)."""

    def __init__(self, A=None, *, path: str | None = None, nparts: int = 1,
                 part=None,
                 dtype=np.float64, fmt: str = "auto", mat_dtype="auto",
                 halo: HaloMethod = HaloMethod.PPERMUTE,
                 partition_method: str = "auto", seed: int = 0,
                 epsilon: float = 0.0, binary=None,
                 options: SolverOptions = SolverOptions(),
                 tracer: SpanTracer | None = None, log=None,
                 prep_cache="auto", share_prepared: bool = True,
                 recycle: bool = False, nrhs: int = 1):
        if (A is None) == (path is None):
            raise AcgError(Status.ERR_INVALID_VALUE,
                           "Session needs exactly one of A or path")
        self.tracer = tracer if tracer is not None else SpanTracer(log=log)
        self.nparts = int(nparts)
        # an explicit part vector (the CLI's --partition FILE) pins the
        # partitioning; it bypasses the partitioner AND the process
        # prepared-operator cache (whose key does not cover it)
        self.part = None if part is None else np.asarray(part,
                                                         dtype=np.int32)
        self.dtype = np.dtype(dtype)
        self.fmt = fmt
        self.mat_dtype = mat_dtype
        self.nrhs = int(nrhs)
        self.halo = HaloMethod(halo)
        self.partition_method = partition_method
        self.seed = int(seed)
        self.default_options = options
        from acg_tpu.partition.cache import resolve_prep_cache

        self.prep_cache = resolve_prep_cache(prep_cache)
        self._share_prepared = bool(share_prepared)
        # spectral recycling (ROADMAP item 6): OFF by default — the
        # zero-overhead clause; when on, s-step solves reuse refined
        # shift schedules and cg-recycled consumes the deflation basis
        # from this operator's RecycleState
        self.recycle = bool(recycle)
        self._recycle_state: RecycleState | None = None

        if path is not None:
            from acg_tpu.io import read_mtx
            from acg_tpu.sparse.csr import csr_from_mtx

            with self.tracer.span("read"), span("acg.prep.assemble"):
                m = read_mtx(path, binary=binary)
                A = csr_from_mtx(m, val_dtype=self.dtype)
        if epsilon:
            A = A.shift_diagonal(epsilon)
        self.A = A

        # counters surfaced by stats() and the acg-tpu-stats/13 session
        # block: executable-cache traffic, prepared-operator traffic,
        # dispatch volume
        self.counters = {
            "executable": {"hits": 0, "misses": 0, "compile_seconds": 0.0},
            "prepared": {"hits": 0, "misses": 0},
            "solves": 0, "uncached_solves": 0, "requests": 0,
        }
        self._exec: dict = {}
        self._lock = threading.RLock()
        # the fleet failure model (ISSUE 15): a dead session fails every
        # dispatch with a transient-classified ERR_FAULT_DETECTED —
        # exactly what a replica whose devices stopped answering looks
        # like from the host.  Set by a "replica-kill" FaultSpec through
        # solve(fault=) or directly by kill(); never cleared (a dead
        # replica is replaced, not resurrected).
        self.dead = False
        self._closed = False
        self._prepare()

    # -- preparation ----------------------------------------------------

    def _graph_hash(self):
        """The operator's content hashes (the split GraphHashes triple
        — full, structure, values), computed AT MOST ONCE per Session
        (an O(nnz) pass) and shared by the prepared-operator key, the
        partition cache's structure tier, and build_sharded."""
        if not hasattr(self, "_ghash"):
            from acg_tpu.partition.cache import graph_hashes

            try:
                self._ghash = graph_hashes(self.A)
            except Exception:
                self._ghash = None   # non-CSR operator: no content key
        return self._ghash

    def _prepare_key(self):
        if self.part is not None:
            return None     # a pinned part vector is outside the key
        ghash = self._graph_hash()
        if ghash is None:
            return None
        return (ghash.full, self.nparts, self.dtype.name, self.fmt,
                str(self.mat_dtype), self.halo.value,
                self.partition_method, self.seed, self.nrhs)

    def _prepare(self):
        """Partition + tier resolution + device placement, once — or a
        prepared-operator cache hit (same graph hash + build params)."""
        key = self._prepare_key() if self._share_prepared else None
        if key is not None:
            with _PREPARED_LOCK:
                hit = _PREPARED.get(key)
            if hit is not None:
                self._dev, self._ss = hit
                self.counters["prepared"]["hits"] += 1
                _M_PREPARED.labels(outcome="hit").inc()
                return
        self._dev = self._ss = None
        if self.nparts > 1:
            from acg_tpu.partition.cache import cached_partition_graph
            from acg_tpu.solvers.cg_dist import build_sharded

            ghash = (self._graph_hash()
                     if self.prep_cache is not None else None)
            part = self.part
            if part is None:
                with self.tracer.span("partition"), \
                        span("acg.prep.partition"):
                    part = cached_partition_graph(
                        self.A, self.nparts,
                        method=self.partition_method,
                        seed=self.seed, cache=self.prep_cache,
                        ghash=ghash)
            with self.tracer.span("operator-build"):
                self._ss = build_sharded(
                    self.A, nparts=self.nparts, part=part,
                    dtype=self.dtype, method=self.halo,
                    partition_method=self.partition_method,
                    seed=self.seed, mat_dtype=self.mat_dtype,
                    fmt=self.fmt, prep_cache=self.prep_cache,
                    ghash=ghash)
        else:
            from acg_tpu.solvers.cg import build_device_operator

            with self.tracer.span("operator-build"):
                self._dev = build_device_operator(
                    self.A, dtype=self.dtype, fmt=self.fmt,
                    mat_dtype=self.mat_dtype, nrhs=self.nrhs)
        self.counters["prepared"]["misses"] += 1
        _M_PREPARED.labels(outcome="miss").inc()
        if key is not None:
            with _PREPARED_LOCK:
                _PREPARED[key] = (self._dev, self._ss)

    @property
    def operator(self):
        """The prepared operator: a ShardedSystem (nparts > 1) or a
        single-chip device operator."""
        return self._ss if self._ss is not None else self._dev

    @property
    def recycle_state(self) -> RecycleState:
        """This operator's :class:`RecycleState` — shared process-wide
        through the prepared-operator key when this Session shares
        preparation (fleet replicas and failover successors then read
        the same donors/shifts), private otherwise.  Created lazily:
        a session that never warm-starts or recycles never touches it."""
        if self._recycle_state is None:
            key = self._prepare_key() if self._share_prepared else None
            if key is not None:
                with _PREPARED_LOCK:
                    st = _RECYCLE.get(key)
                    if st is None:
                        st = RecycleState(self.nrows, seed=self.seed)
                        _RECYCLE[key] = st
            else:
                st = RecycleState(self.nrows, seed=self.seed)
            self._recycle_state = st
        return self._recycle_state

    @property
    def nrows(self) -> int:
        return (self._ss.nrows if self._ss is not None
                else self.A.nrows if hasattr(self.A, "nrows")
                else self._dev.nrows)

    # -- the executable cache -------------------------------------------

    def _tier(self) -> str:
        """The prepared operator's tier name ("stencil"/"dia"/"sgell"/
        "ell"), part of every executable signature: the matrix-free
        stencil program and a stored-band program are DIFFERENT
        executables even when every other static field matches — a
        cached executable must never cross tiers (the tier decides the
        while-body operand set, not just the kernel)."""
        if self._ss is not None:
            return self._ss.local_fmt
        from acg_tpu.obs.roofline import _format_name

        return _format_name(self._dev)

    def _signature(self, kind: str, nrhs: int, o: SolverOptions,
                   has_x0: bool = False) -> tuple:
        """The static signature an AOT executable serves.  Tolerance
        VALUES are runtime operands; only their non-zero-ness (which
        gates certify/track_diff branches statically) is part of the
        key.  The operator tier is part of the key (see :meth:`_tier`),
        and so is whether an initial guess rides the dispatch — an
        executable traced at ``x0=None`` and one traced with an x0
        operand are distinct cache entries (ISSUE 20 regression)."""
        return (kind, self.nparts, int(nrhs), self.dtype.name,
                self._tier(),
                o.maxits, o.check_every, o.replace_every,
                o.monitor_every, o.guard_nonfinite, o.sstep,
                o.pipeline_depth, o.halo_wire, o.precond, o.mg_levels,
                o.residual_atol > 0, o.residual_rtol > 0,
                o.diffatol > 0, o.diffrtol > 0, bool(has_x0))

    def _get_executable(self, kind: str, b, x0, o: SolverOptions):
        nrhs = b.shape[0] if np.ndim(b) == 2 else 1
        sig = self._signature(kind, nrhs, o, has_x0=x0 is not None)
        entry = self._exec.get(sig)
        if entry is not None:
            self.counters["executable"]["hits"] += 1
            _M_EXEC.labels(outcome="hit").inc()
            return entry
        with self.tracer.span("compile"):
            t0 = time.perf_counter()
            if self._ss is not None:
                from acg_tpu.solvers.cg_dist import aot_step as dist_aot

                entry = dist_aot(self._ss, b=np.asarray(b), x0=x0,
                                 options=o, solver=kind, fmt=self.fmt)
            else:
                from acg_tpu.solvers.cg import aot_step

                entry = aot_step(self._dev, b, x0=x0, options=o,
                                 dtype=self.dtype, fmt=self.fmt,
                                 mat_dtype=self.mat_dtype, solver=kind)
            compile_s = time.perf_counter() - t0
            self.counters["executable"]["compile_seconds"] += compile_s
            _M_COMPILE.observe(compile_s)
        self.counters["executable"]["misses"] += 1
        _M_EXEC.labels(outcome="miss").inc()
        self._exec[sig] = entry
        return entry

    def has_executable(self, solver: str, nrhs: int,
                       options: SolverOptions | None = None,
                       has_x0: bool = False) -> bool:
        """Whether this signature is already warm (no compile would run).
        The service layer records this per dispatch as the authoritative
        cache_hit bit."""
        o = options if options is not None else self.default_options
        kind = _normalize_solver(solver)
        if kind in ("cg-sstep", "cg-recycled") or o.segment_iters > 0:
            return False
        return self._signature(kind, nrhs, o, has_x0=has_x0) in self._exec

    def executable(self, *, solver: str = "cg", nrhs: int = 1,
                   options: SolverOptions | None = None):
        """The cached :class:`~acg_tpu.solvers.cg.AotSolve` for this
        signature, compiling on first use.  ``.compiled`` is the object
        :func:`acg_tpu.obs.hlo.audit_compiled` consumes — auditing it
        describes exactly the program every warm dispatch runs, which is
        how tests prove a warm Session issues zero recompiles."""
        o = options if options is not None else self.default_options
        kind = _normalize_solver(solver)
        if kind in ("cg-sstep", "cg-recycled"):
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           "the s-step/recycled family dispatches "
                           "through the ordinary solver functions "
                           "(no AOT entry)")
        n = self.nrows
        b = np.zeros((nrhs, n) if nrhs > 1 else (n,), dtype=self.dtype)
        with self._lock:
            return self._get_executable(kind, b, None, o)

    def audit(self, *, solver: str = "cg", nrhs: int = 1,
              options: SolverOptions | None = None):
        """CommAudit of the cached executable (compiles only on a cold
        signature — a warm audit touches no compiler at all)."""
        from acg_tpu.obs.hlo import audit_compiled

        return audit_compiled(
            self.executable(solver=solver, nrhs=nrhs,
                            options=options).compiled)

    # -- solving --------------------------------------------------------

    def solve(self, b, *, solver: str = "cg",
              options: SolverOptions | None = None, x0=None,
              stats=None, fault=None):
        """Solve against the prepared operator.  ``b`` of shape ``(n,)``
        or ``(B, n)`` (the coalesced batch).  Classic/pipelined/
        deep-pipelined solves dispatch through the cached AOT
        executable (the deep executable re-dispatches itself from the
        host on residual replacement — still one compiled program); the
        s-step family and segmented solves take the ordinary
        (jit-cached) solver functions and are counted as
        ``uncached_solves``.

        ``fault`` is a deterministic injection plan
        (:class:`~acg_tpu.robust.faults.FaultSpec`) — the chaos-drill
        surface (scripts/chaos_serve.py).  A faulted dispatch routes
        through the ordinary solver functions (the AOT executable was
        traced without an injection operand); the plan is DATA there,
        so every fault kind/iteration shares one jit cache entry."""
        o = options if options is not None else self.default_options
        kind = _normalize_solver(solver)
        with self._lock:
            if fault is not None and getattr(fault, "kind",
                                             None) == "replica-kill":
                # the replica dies AT this dispatch: the plan consumed,
                # the session marked dead, the batch failed with the
                # transient classification the fleet's failover path
                # keys on
                self.kill()
            if self.dead:
                raise AcgError(
                    Status.ERR_FAULT_DETECTED,
                    "replica session is dead (replica-kill): dispatch "
                    "failed — re-dispatch on a surviving replica")
            if self._closed:
                raise AcgError(Status.ERR_OVERLOADED,
                               "session is closed: dispatch refused")
            self.counters["solves"] += 1
            if kind in ("cg-sstep", "cg-recycled") \
                    or o.segment_iters > 0 \
                    or fault is not None:
                _M_SOLVES.labels(path="uncached").inc()
                return self._solve_uncached(kind, b, x0, o, stats,
                                            fault=fault)
            _M_SOLVES.labels(path="aot").inc()
            entry = self._get_executable(kind, b, x0, o)
            with self.tracer.span("solve"):
                # o rides along per dispatch: tolerance VALUES are
                # runtime operands of the cached executable (a request
                # at a tighter rtol must not inherit the compile-time
                # tolerances — only the static fields are baked)
                return entry.solve(b, x0=x0, stats=stats, options=o)

    def _solve_uncached(self, kind, b, x0, o, stats, fault=None):
        self.counters["uncached_solves"] += 1
        # spectral recycling (opt-in): the s-step and recycled kinds
        # read/write this operator's RecycleState — refined shift
        # schedules in, refined shift schedules out; the deflation
        # basis for cg-recycled.  fault injection never recycles (the
        # drill's solves must not feed the donor pool).
        extra = {}
        if self.recycle and fault is None \
                and kind in ("cg-sstep", "cg-recycled"):
            extra["recycle"] = self.recycle_state
        if kind == "cg-recycled":
            # the HOST operator's matvec (unpadded, unpermuted) — the
            # deflation projection is host-side SETUP work; the device
            # operator's padded matvec must never leak into it
            extra["matvec"] = (self.A.matvec
                               if hasattr(self.A, "matvec") else None)
        with self.tracer.span("solve"):
            if self._ss is not None:
                from acg_tpu.solvers.cg_dist import (
                    cg_dist, cg_pipelined_deep_dist, cg_pipelined_dist,
                    cg_recycled_dist, cg_sstep_dist)

                fn = {"cg": cg_dist, "cg-pipelined": cg_pipelined_dist,
                      "cg-pipelined-deep": cg_pipelined_deep_dist,
                      "cg-sstep": cg_sstep_dist,
                      "cg-recycled": cg_recycled_dist}[kind]
                return fn(self._ss, b, x0=x0, options=o, stats=stats,
                          fmt=self.fmt, fault=fault, **extra)
            from acg_tpu.solvers.cg import (cg, cg_pipelined,
                                            cg_pipelined_deep,
                                            cg_recycled, cg_sstep)

            fn = {"cg": cg, "cg-pipelined": cg_pipelined,
                  "cg-pipelined-deep": cg_pipelined_deep,
                  "cg-sstep": cg_sstep, "cg-recycled": cg_recycled}[kind]
            return fn(self._dev, b, x0=x0, options=o, dtype=self.dtype,
                      fmt=self.fmt, mat_dtype=self.mat_dtype,
                      stats=stats, fault=fault, **extra)

    # -- lifecycle ------------------------------------------------------

    def kill(self) -> None:
        """Mark this session DEAD (simulated replica death — the fleet
        drill's surface; also reachable via a ``replica-kill``
        :class:`~acg_tpu.robust.faults.FaultSpec` through
        ``solve(fault=)``).  Idempotent; every subsequent dispatch fails
        with a transient-classified ``ERR_FAULT_DETECTED``."""
        self.dead = True

    def close(self) -> None:
        """Release this session's executable cache (idempotent).  The
        prepared operator itself may be shared through the process-level
        cache (``share_prepared``) and is left to it; a closed session
        refuses further dispatches with a deterministic
        ``ERR_OVERLOADED`` (unlike a DEAD one, whose transient
        classification invites failover)."""
        with self._lock:
            self._exec.clear()
            self._closed = True

    # -- introspection --------------------------------------------------

    def stats(self) -> dict:
        """Session counters snapshot: cache traffic, compile/solve
        walls (from the span timeline), cached signatures.  The
        service layer merges queue/batch counters on top; the
        ``acg-tpu-stats/13`` ``session`` block is derived from this."""
        tr = self.tracer
        return {
            "nrows": int(self.nrows),
            "nparts": int(self.nparts),
            "dtype": self.dtype.name,
            "cache": {
                "executable": dict(self.counters["executable"]),
                "prepared": dict(self.counters["prepared"]),
                "prep": (self.prep_cache.stats()
                         if self.prep_cache is not None else None),
            },
            "signatures": len(self._exec),
            "solves": self.counters["solves"],
            "uncached_solves": self.counters["uncached_solves"],
            "recycle": (self._recycle_state.stats()
                        if self._recycle_state is not None else None),
            "walls": {name: tr.total(name)
                      for name in ("read", "partition", "operator-build",
                                   "compile", "solve")},
        }


def clear_prepared_cache() -> None:
    """Drop every prepared operator and its recycle state (tests; also
    frees device buffers the cache pins)."""
    with _PREPARED_LOCK:
        _PREPARED.clear()
        _RECYCLE.clear()
