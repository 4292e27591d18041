"""Machine-readable solver-stats export (``--output-stats-json``).

One JSON document per solve, carrying everything the reference prints in
its human-readable stats block (ref acg/cg.c:665-828 ``acgsolver_fwrite``)
plus the telemetry this port adds on top: the on-device convergence
history, the host phase-span timeline, and the capability matrix the
``--version`` action reports.  The schema is versioned
(``acg-tpu-stats/13``) and validated by :func:`validate_stats_document`
— the same validator ``scripts/check_stats_schema.py`` and the tests
import, so a document that passes the linter is by construction one a
dashboard can consume.

``bench.py``'s one-line benchmark record shares this module too
(:func:`bench_record` / :func:`validate_bench_record`): the ``parsed``
payload inside the ``BENCH_*.json`` trajectory files is exactly a bench
record, so the one schema linter covers both artifact families.

All floats are sanitized for strict JSON: non-finite values (the
``inf`` that means "criterion disabled" in :class:`SolveResult`)
serialize as ``null``.

SCHEMA VERSIONS: documents are written at ``acg-tpu-stats/13``.

- /13 extends /12 with the iteration-amortization layer (ISSUE 20,
  acg_tpu/serve/session.py ``RecycleState`` + service warm-start): a
  required nullable top-level ``warmstart`` object — ``null`` for a
  plain (non-serve) solve or a service without the feature exercised,
  else the per-request warm-start provenance: ``enabled`` (bool),
  ``source`` (``"client"`` / ``"recycled"`` / ``"none"`` — where the
  initial guess came from), nullable ``sketch_distance`` (RHS
  similarity-sketch distance to the donor), nullable
  ``iterations_saved`` (vs the session's cold-iterations EMA) and
  ``rejected`` (the certification guard refused the donor and the
  request was re-solved cold — status still reflects the problem,
  never the donor).
- /12 extends /11 with the elastic-fleet snapshot (ISSUE 19,
  acg_tpu/serve/fleet.py + acg_tpu/serve/autoscale.py): a non-null
  ``fleet`` block additionally carries ``resurrections`` and
  ``quarantined`` counts plus a nullable ``autoscaler`` sub-block
  (target width, last decision, its reason) — a plain fleet reports
  the zeros/null defaults, an ``elastic=True`` fleet threads its real
  :meth:`Fleet._fleet_state` snapshot through ``fleet_meta``.
- /11 extends /10 with the deep pipeline + compressed halo wire layer
  (ISSUE 17, acg_tpu/solvers/loops.py ``cg_pipelined_deep_while`` +
  acg_tpu/parallel/halo.py wire codecs): a required nullable
  ``introspection.halo_wire`` object — ``null`` when introspection was
  not requested (or the solve has no distributed halo), else the wire
  accounting of the halo exchange: ``wire`` (the
  ``SolverOptions.halo_wire`` spelling), ``dtype`` (the on-wire element
  dtype name), ``itemsize`` (bytes per value actually on the wire) and
  ``bytes_saved_ratio`` (fraction of the identity-wire payload the
  format saves; null/NaN-sanitized for single-chip solves).  The
  ``options`` block additionally carries ``pipeline_depth`` +
  ``halo_wire`` via ``options_to_dict`` (dataclass fields export
  automatically — no validator gate; depth 1 / "f32" for every
  pre-existing configuration).

- /10 extends /9 with the replica fleet (ISSUE 15,
  acg_tpu/serve/fleet.py): a required nullable top-level ``fleet``
  object — ``null`` for a plain solve or a bare (non-fleet)
  :class:`~acg_tpu.serve.service.SolverService` response, else the
  per-request replica provenance: ``replica_id`` (the replica that
  produced THIS response), ``failover_from`` (null, or the ordered
  list of replica ids whose deaths this request survived — a
  re-dispatched request's audit names every hop) and ``hops`` (the
  failover re-dispatch count, 0 for a first-attempt response).

- /9 extends /8 with the runtime telemetry spine (ISSUE 13,
  acg_tpu/obs/metrics.py + acg_tpu/obs/events.py): a required nullable
  top-level ``metrics`` object — ``null`` when the process metrics
  registry is disabled (the default; the zero-overhead clause), else a
  full registry snapshot (``enabled`` plus ``counters`` / ``gauges`` /
  ``histograms`` maps, each value list carrying labels and, for
  histograms, cumulative ``le`` buckets + sum + count) — and per-request
  trace-ID cross-links: ``session.trace_id`` and ``admission.trace_id``
  (nullable strings; for a serve response they carry the 16-hex trace
  ID minted at ``submit()`` that also names the request's
  flight-recorder timeline and its Chrome trace-event lane).

- /8 extends /7 with the serving admission-robustness layer (ISSUE 10,
  acg_tpu/serve/admission.py): a required nullable top-level
  ``admission`` object — ``null`` for a plain (non-serve) solve, else
  the per-request admission telemetry: ``deadline`` (budget /
  queue-split / remaining ms + the ``expired`` bit; null when no
  deadline was set), ``retries`` (``used``/``max`` plus the seeded
  ``backoff_ms`` schedule actually slept), ``breaker`` (per-signature
  circuit-breaker ``state`` CLOSED/HALF_OPEN/OPEN, ``signature``,
  ``trips``; null when no breaker is configured) and the ``shed`` /
  ``degraded`` / ``degraded_from`` outcome flags.  At /8 a non-null
  ``session`` block implies a non-null ``admission`` block — every
  serve response documents its admission path, shed and timed-out
  requests included.

- /7 extends /6 with the static contract layer (ISSUE 9,
  acg_tpu/analysis/): a required nullable top-level ``contract`` object
  — ``null`` when no contract was evaluated (``--explain`` off, or the
  solver has no declared contract), else the declared per-iteration
  collective model plus the verdict of checking it against the compiled
  program: ``name``, ``verdict`` (``"PASS"``/``"FAIL"``),
  ``violations`` (rule-coded, C1..C12) and ``declared`` (the
  ``SolverContract.as_dict()`` payload with the exact per-iteration
  rationals).

- /2 extends /1 with multi-RHS batching fields in ``result``: ``nrhs``
  (the system count; 1 for ordinary solves — full back-compat, every /1
  field keeps its meaning and shape) and, when ``nrhs > 1``, per-system
  ``iterations_per_system``/``rnrm2_per_system``/``converged_per_system``
  arrays plus a per-system ``residual_history`` (a list of ``nrhs``
  lists, each trimmed to that system's own ``iterations_i + 1`` samples
  — the active-mask freeze means systems stop recording at their own
  exit).
- /3 extends /2 with a required top-level ``introspection`` object
  carrying the static solver audit: ``comm_audit`` (the compiled-HLO
  collective/cost audit of acg_tpu/obs/hlo.py, as
  ``CommAudit.as_dict()``) and ``roofline`` (the analytic traffic model
  of acg_tpu/obs/roofline.py — ``RooflineModel.as_dict()`` plus, after
  the solve, ``measured_iters_per_sec`` and ``roofline_frac``).  Either
  member may be ``null`` (``--explain`` off, or a backend that cannot
  lower/compile the step).
- /6 extends /5 with the serve layer (ISSUE 8, acg_tpu/serve/): a
  required top-level ``session`` object — ``null`` for a plain CLI
  solve, or the per-request serving context: ``request_id``, ``cache``
  (``executable_hit`` for THIS dispatch plus cumulative executable /
  prepared-operator hit/miss counters), ``queue`` (``wait_seconds``,
  ``depth``) and ``batch`` (``size`` = real coalesced requests,
  ``bucket`` = padded dispatch size, ``occupancy``).  Every serve
  response carries one of these documents as its audit record.
- /5 extends /4 with the s-step solver family (ISSUE 7):
  ``options.sstep`` (the s-step block size; 0 for non-s-step solves)
  is required numeric, and a non-null ``introspection.comm_audit``
  carries ``iterations_per_body`` (solver iterations one while-body
  execution advances: s for cg-sstep, 1 otherwise) plus
  ``per_solver_iteration`` — the per-body collective counts divided
  through as exact rationals ("1/4"-style strings alongside floats),
  the recorded form of the "psums per iteration → 1/s" claim.
- /4 extends /3 with the resilience layer (acg_tpu/robust/): a required
  top-level ``resilience`` object — ``null`` for a plain solve, or the
  :class:`~acg_tpu.robust.supervisor.RecoveryReport` of a
  ``solve_resilient()`` run (``steps``/``restarts``/``fixed_by``/
  ``certified_relative_residual``/``final_status``) — and a required
  ``result.status`` string naming the first-class outcome
  classification (``SUCCESS``, ``ERR_NOT_CONVERGED``,
  ``ERR_NOT_CONVERGED_INDEFINITE_MATRIX``, ``ERR_FAULT_DETECTED``,
  ``ERR_NONFINITE``) — failed solves export too, which is exactly when
  the telemetry matters.

:func:`validate_stats_document` accepts ALL versions, so previously
captured /1../12 artifacts keep linting.
"""

from __future__ import annotations

import dataclasses
import json

SCHEMA_V1 = "acg-tpu-stats/1"
SCHEMA_V2 = "acg-tpu-stats/2"
SCHEMA_V3 = "acg-tpu-stats/3"
SCHEMA_V4 = "acg-tpu-stats/4"
SCHEMA_V5 = "acg-tpu-stats/5"
SCHEMA_V6 = "acg-tpu-stats/6"
SCHEMA_V7 = "acg-tpu-stats/7"
SCHEMA_V8 = "acg-tpu-stats/8"
SCHEMA_V9 = "acg-tpu-stats/9"
SCHEMA_V10 = "acg-tpu-stats/10"
SCHEMA_V11 = "acg-tpu-stats/11"
SCHEMA_V12 = "acg-tpu-stats/12"
SCHEMA = "acg-tpu-stats/13"
SCHEMAS = (SCHEMA_V1, SCHEMA_V2, SCHEMA_V3, SCHEMA_V4, SCHEMA_V5,
           SCHEMA_V6, SCHEMA_V7, SCHEMA_V8, SCHEMA_V9, SCHEMA_V10,
           SCHEMA_V11, SCHEMA_V12, SCHEMA)

# the seven per-op counter blocks of the reference's breakdown table
# (ref acg/cg.c:673-709); kept in sync with acg_tpu.utils.stats._OP_NAMES
# by a test rather than an import so this module stays importable without
# the solver stack
OP_NAMES = ("gemv", "dot", "nrm2", "axpy", "copy", "allreduce", "halo")


def _finite(v):
    """Non-finite floats become None (strict-JSON friendly)."""
    if isinstance(v, float) and not (v == v and abs(v) != float("inf")):
        return None
    return v


def sanitize_tree(obj):
    """Recursively map non-finite floats to None through dicts/lists —
    introspection payloads (roofline fracs against an absent measurement,
    degenerate ceilings) must stay strict-JSON serializable."""
    if isinstance(obj, dict):
        return {k: sanitize_tree(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [sanitize_tree(v) for v in obj]
    return _finite(obj)


def op_counters_to_dict(c) -> dict:
    return {"t": _finite(float(c.t)), "n": int(c.n),
            "bytes": int(c.bytes), "flops": int(c.flops)}


def stats_to_dict(st) -> dict:
    """Serialize a :class:`~acg_tpu.solvers.base.SolveStats`."""
    d = {"nsolves": int(st.nsolves),
         "ntotaliterations": int(st.ntotaliterations),
         "niterations": int(st.niterations),
         "nflops": int(st.nflops),
         "tsolve": _finite(float(st.tsolve)),
         "nhalomsgs": int(st.nhalomsgs),
         "iterations_per_sec": _finite(float(st.iterations_per_sec())),
         "per_op": {nm: op_counters_to_dict(getattr(st, nm))
                    for nm in OP_NAMES}}
    return d


def result_to_dict(res) -> dict:
    """Serialize a :class:`~acg_tpu.solvers.base.SolveResult` (without
    the solution vector — solutions go to ``--output-solution``).

    Multi-RHS results (``res.nrhs > 1``) add the per-system arrays and
    emit ``residual_history`` as one list per system, each trimmed to
    that system's own iteration count (schema /2)."""
    hist = getattr(res, "residual_history", None)
    nrhs = int(getattr(res, "nrhs", 1) or 1)
    d = {"converged": bool(res.converged),
         "niterations": int(res.niterations),
         "bnrm2": _finite(float(res.bnrm2)),
         "r0nrm2": _finite(float(res.r0nrm2)),
         "rnrm2": _finite(float(res.rnrm2)),
         "x0nrm2": _finite(float(res.x0nrm2)),
         "dxnrm2": _finite(float(res.dxnrm2)),
         "relative_residual": _finite(float(res.relative_residual)),
         "fpexcept": str(res.fpexcept),
         # the first-class outcome classification (schema /4); documents
         # predating SolveResult.status degrade to the converged bit
         "status": getattr(getattr(res, "status", None), "name", None)
         or ("SUCCESS" if res.converged else "ERR_NOT_CONVERGED"),
         "operator_format": str(res.operator_format),
         "kernel": str(res.kernel),
         "nrhs": nrhs}
    note = getattr(res, "kernel_note", "")
    if note:
        # why the kernel tier differs from the unconstrained auto choice
        # (e.g. "pipe2d disengaged: fault injection"); omitted when the
        # tier is the auto pick, so /1../4 documents stay byte-stable
        d["kernel_note"] = str(note)
    if nrhs > 1:
        iters = [int(v) for v in res.iterations_per_system]
        d["iterations_per_system"] = iters
        d["rnrm2_per_system"] = [_finite(float(v))
                                 for v in res.rnrm2_per_system]
        if getattr(res, "r0nrm2_per_system", None) is not None:
            d["r0nrm2_per_system"] = [_finite(float(v))
                                      for v in res.r0nrm2_per_system]
        d["converged_per_system"] = [bool(v)
                                     for v in res.converged_per_system]
        d["residual_history"] = (
            None if hist is None
            else [[_finite(float(v)) for v in hist[i][: iters[i] + 1]]
                  for i in range(nrhs)])
    else:
        if hist is not None and getattr(hist, "ndim", 1) == 2:
            # a (1, n) batched solve: one system, 2-D history row
            hist = hist[0]
        d["residual_history"] = (None if hist is None
                                 else [_finite(float(v)) for v in hist])
    return d


def options_to_dict(options) -> dict:
    return {k: _finite(v) for k, v in
            dataclasses.asdict(options).items()}


def capability_info() -> dict:
    """The capability matrix the ``--version`` action prints (the analog
    of the reference driver's feature report, cuda/acg-cuda.c:382-440),
    as data.  Every probe degrades to None/False rather than raising —
    this runs inside error paths too."""
    from acg_tpu import __version__

    info: dict = {"version": __version__, "jax": None, "jaxlib": None,
                  "platforms": [], "device_kinds": [], "ndevices": 0,
                  "processes": None, "x64": None,
                  "native_host_library": False, "scipy": None}
    try:
        import jax

        import jaxlib

        info["jax"] = jax.__version__
        info["jaxlib"] = jaxlib.__version__
        devs = jax.devices()
        info["platforms"] = sorted({d.platform for d in devs})
        info["device_kinds"] = sorted({d.device_kind for d in devs})
        info["ndevices"] = len(devs)
        info["processes"] = jax.process_count()
        info["x64"] = bool(jax.config.read("jax_enable_x64"))
    except Exception as e:   # report, don't crash, on backend issues
        info["backend_error"] = str(e)
    try:
        from acg_tpu.native import available as native_available

        info["native_host_library"] = bool(native_available())
    except Exception:
        pass
    try:
        import scipy

        info["scipy"] = scipy.__version__
    except ImportError:
        pass
    return info


def build_stats_document(*, solver: str, options, res, stats,
                         nunknowns: int | None = None, nparts: int = 1,
                         phases: list[dict] | None = None,
                         capabilities: dict | None = None,
                         introspection: dict | None = None,
                         resilience: dict | None = None,
                         session: dict | None = None,
                         contract: dict | None = None,
                         admission: dict | None = None,
                         metrics: dict | None = None,
                         fleet: dict | None = None,
                         warmstart: dict | None = None) -> dict:
    """Assemble the full ``acg-tpu-stats/13`` document for one solve.

    ``stats`` is the (already cross-process-reduced) SolveStats to
    export; ``phases`` a ``SpanTracer.as_dicts()`` timeline;
    ``introspection`` the ``--explain`` payload (``comm_audit`` +
    ``roofline`` — both null when introspection was not requested or
    could not run); ``resilience`` a ``RecoveryReport.as_dict()`` for
    ``--resilient`` solves (null for plain solves); ``session`` the
    serve layer's per-request block
    (``SolverService.session_block()`` — null for plain solves);
    ``contract`` the static-contract verdict block
    (``acg_tpu.analysis.contracts.contract_block()`` — null when no
    contract was evaluated); ``admission`` the serve layer's
    per-request admission-robustness telemetry
    (``AdmissionRecord.as_dict()``, acg_tpu/serve/admission.py — null
    for plain solves); ``metrics`` the process metrics-registry
    snapshot (``MetricsRegistry.snapshot()``, acg_tpu/obs/metrics.py —
    null when the registry is disabled, the default); ``fleet`` the
    replica-fleet provenance block (acg_tpu/serve/fleet.py —
    ``replica_id`` + ``failover_from`` + ``hops``; null outside a
    fleet); ``warmstart`` the iteration-amortization provenance block
    (acg_tpu/serve/service.py ``_warmstart_finish`` — donor source,
    sketch distance, iterations saved, rejection bit; null when the
    request had neither a client x0 nor warm-start serving)."""
    if introspection is None:
        introspection = {"comm_audit": None, "roofline": None,
                         "halo_wire": None}
    else:
        introspection = {"comm_audit": introspection.get("comm_audit"),
                         "roofline": introspection.get("roofline"),
                         "halo_wire": introspection.get("halo_wire")}
    return {
        "schema": SCHEMA,
        "solver": str(solver),
        "nunknowns": None if nunknowns is None else int(nunknowns),
        "nparts": int(nparts),
        "options": options_to_dict(options),
        "result": result_to_dict(res),
        "stats": stats_to_dict(stats),
        "phases": list(phases) if phases is not None else [],
        "capabilities": (capability_info() if capabilities is None
                         else capabilities),
        "introspection": introspection,
        "resilience": sanitize_tree(resilience),
        "session": sanitize_tree(session),
        "contract": sanitize_tree(contract),
        "admission": sanitize_tree(admission),
        "metrics": sanitize_tree(metrics),
        "fleet": sanitize_tree(fleet),
        "warmstart": sanitize_tree(warmstart),
    }


def write_stats_json(path: str, doc: dict) -> None:
    """Serialize ``doc`` to ``path`` (validating first — a document this
    module wrote must always pass its own linter)."""
    problems = validate_stats_document(doc)
    if problems:
        raise ValueError("refusing to write non-conforming stats "
                         "document: " + "; ".join(problems))
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, allow_nan=False)
        f.write("\n")


def load_stats_document(path: str) -> dict:
    """Round-trip helper: read + validate a ``--output-stats-json`` file.
    Raises ``ValueError`` on schema violations."""
    with open(path) as f:
        doc = json.load(f)
    problems = validate_stats_document(doc)
    if problems:
        raise ValueError(f"{path}: " + "; ".join(problems))
    return doc


def _check(problems, cond: bool, msg: str) -> None:
    if not cond:
        problems.append(msg)


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def validate_stats_document(doc) -> list[str]:
    """Validate a stats document; returns a list of problems (empty =
    conforming).  This is the ONE schema definition — the CLI's writer,
    the tests, and ``scripts/check_stats_schema.py`` all call it."""
    p: list[str] = []
    if not isinstance(doc, dict):
        return ["document is not a JSON object"]
    _check(p, doc.get("schema") in SCHEMAS,
           f"schema is {doc.get('schema')!r}, expected one of {SCHEMAS!r}")
    for key, typ in (("solver", str), ("nparts", int), ("options", dict),
                     ("result", dict), ("stats", dict), ("phases", list)):
        _check(p, isinstance(doc.get(key), typ),
               f"missing or mistyped top-level key {key!r}")
    if p:
        return p
    # version level: SCHEMAS is ordered /1../13, each version a superset
    # of the one before
    _lvl = SCHEMAS.index(doc["schema"]) + 1
    v2, v3, v4, v5 = _lvl >= 2, _lvl >= 3, _lvl >= 4, _lvl >= 5
    v6, v7, v8, v9 = _lvl >= 6, _lvl >= 7, _lvl >= 8, _lvl >= 9
    v10, v11, v12 = _lvl >= 10, _lvl >= 11, _lvl >= 12
    v13 = _lvl >= 13

    opts = doc["options"]
    for key in ("maxits", "diffatol", "diffrtol", "residual_atol",
                "residual_rtol", "check_every"):
        _check(p, _is_num(opts.get(key)),
               f"options.{key} missing or not numeric")

    res = doc["result"]
    _check(p, isinstance(res.get("converged"), bool),
           "result.converged missing or not bool")
    _check(p, isinstance(res.get("niterations"), int),
           "result.niterations missing or not int")
    for key in ("bnrm2", "r0nrm2", "rnrm2"):
        v = res.get(key, "missing")
        _check(p, v is None or _is_num(v),
               f"result.{key} missing or not numeric")
    nrhs = 1
    if v2:
        nrhs = res.get("nrhs", "missing")
        _check(p, isinstance(nrhs, int) and not isinstance(nrhs, bool)
               and nrhs >= 1, "result.nrhs missing or not a positive int")
        nrhs = nrhs if isinstance(nrhs, int) else 1
        if nrhs > 1:
            iters = res.get("iterations_per_system")

            def _arr_ok(key, pred):
                arr = res.get(key)
                if not isinstance(arr, list) or len(arr) != nrhs:
                    p.append(f"result.{key} missing or not a "
                             f"length-nrhs list")
                    return
                _check(p, all(pred(x) for x in arr),
                       f"result.{key} has mistyped entries")

            _arr_ok("iterations_per_system",
                    lambda x: isinstance(x, int)
                    and not isinstance(x, bool))
            _arr_ok("rnrm2_per_system", lambda x: x is None or _is_num(x))
            if "r0nrm2_per_system" in res:   # optional (device solvers)
                _arr_ok("r0nrm2_per_system",
                        lambda x: x is None or _is_num(x))
            _arr_ok("converged_per_system", lambda x: isinstance(x, bool))
            if isinstance(iters, list) and len(iters) == nrhs and \
                    all(isinstance(x, int) for x in iters):
                _check(p, isinstance(res.get("niterations"), int)
                       and res["niterations"] == max(iters),
                       "result.niterations != max(iterations_per_system)")
    hist = res.get("residual_history", "missing")
    _check(p, hist is None or isinstance(hist, list),
           "result.residual_history missing or not a list/null")
    if isinstance(hist, list) and nrhs > 1:
        # /2 batched shape: one trajectory per system, each trimmed to
        # that system's iterations_i + 1 samples
        _check(p, len(hist) == nrhs,
               f"residual_history has {len(hist)} rows, expected nrhs "
               f"= {nrhs}")
        iters = res.get("iterations_per_system")
        for i, row in enumerate(hist):
            if not isinstance(row, list):
                p.append(f"residual_history[{i}] is not a list")
                continue
            _check(p, all(x is None or _is_num(x) for x in row),
                   f"residual_history[{i}] has non-numeric entries")
            if isinstance(iters, list) and len(iters) == nrhs and \
                    isinstance(iters[i], int):
                _check(p, len(row) == iters[i] + 1,
                       f"residual_history[{i}] has {len(row)} entries, "
                       f"expected iterations_per_system[{i}]+1 = "
                       f"{iters[i] + 1}")
    elif isinstance(hist, list):
        _check(p, all(v is None or _is_num(v) for v in hist),
               "result.residual_history has non-numeric entries")
        if isinstance(res.get("niterations"), int):
            _check(p, len(hist) == res["niterations"] + 1,
                   f"residual_history has {len(hist)} entries, expected "
                   f"niterations+1 = {res['niterations'] + 1}")

    st = doc["stats"]
    for key in ("nsolves", "ntotaliterations", "niterations", "nflops"):
        _check(p, isinstance(st.get(key), int),
               f"stats.{key} missing or not int")
    per_op = st.get("per_op")
    _check(p, isinstance(per_op, dict), "stats.per_op missing")
    if isinstance(per_op, dict):
        for nm in OP_NAMES:
            blk = per_op.get(nm)
            if not isinstance(blk, dict):
                p.append(f"stats.per_op.{nm} missing")
                continue
            for f in ("t", "n", "bytes", "flops"):
                v = blk.get(f, "missing")
                _check(p, v is None or _is_num(v),
                       f"stats.per_op.{nm}.{f} missing or not numeric")

    for i, sp in enumerate(doc["phases"]):
        if not isinstance(sp, dict):
            p.append(f"phases[{i}] is not an object")
            continue
        _check(p, isinstance(sp.get("name"), str),
               f"phases[{i}].name missing")
        for f in ("start", "duration"):
            v = sp.get(f, "missing")
            _check(p, v is None or _is_num(v),
                   f"phases[{i}].{f} missing or not numeric")

    if v5:
        _check(p, _is_num(opts.get("sstep")),
               "options.sstep missing or not numeric (required at /5)")
    if v3:
        _validate_introspection(p, doc.get("introspection", "missing"),
                                v5=v5, v11=v11)
    if v4:
        _check(p, isinstance(res.get("status"), str),
               "result.status missing or not a string (required at /4)")
        _validate_resilience(p, doc.get("resilience", "missing"))
    if v6:
        _validate_session(p, doc.get("session", "missing"), v9=v9)
    if v7:
        _validate_contract_field(p, doc.get("contract", "missing"))
    if v8:
        _validate_admission(p, doc.get("admission", "missing"),
                            session=doc.get("session"), v9=v9)
    if v9:
        _validate_metrics(p, doc.get("metrics", "missing"))
    if v10:
        _validate_fleet(p, doc.get("fleet", "missing"), v12=v12)
    if v13:
        _validate_warmstart(p, doc.get("warmstart", "missing"))
    return p


def _validate_warmstart(p: list, ws) -> None:
    """Schema-/13 ``warmstart`` block (ISSUE 20): the key is required,
    its value null (plain solve, or a serve request that involved
    neither a client x0 nor warm-start serving) or the per-request
    iteration-amortization provenance: where the initial guess came
    from, how similar the donor RHS was, what it saved, and whether the
    true-residual certification guard rejected it."""
    if ws == "missing":
        p.append("warmstart missing (required at /13; null when the "
                 "request had no warm-start involvement)")
        return
    if ws is None:
        return
    if not isinstance(ws, dict):
        p.append("warmstart is neither null nor an object")
        return
    _check(p, isinstance(ws.get("enabled"), bool),
           "warmstart.enabled missing or not bool")
    src = ws.get("source")
    _check(p, src in ("client", "recycled", "none"),
           "warmstart.source not one of 'client'/'recycled'/'none'")
    d = ws.get("sketch_distance", "missing")
    _check(p, d is None or _is_num(d),
           "warmstart.sketch_distance missing or not numeric/null")
    sv = ws.get("iterations_saved", "missing")
    _check(p, sv is None or (isinstance(sv, int)
                             and not isinstance(sv, bool)),
           "warmstart.iterations_saved missing or not int/null")
    _check(p, isinstance(ws.get("rejected"), bool),
           "warmstart.rejected missing or not bool")


def _validate_fleet(p: list, fl, *, v12: bool = False) -> None:
    """Schema-/10 ``fleet`` block: the key is required, its value null
    (plain solve, or a serve response outside a replica fleet) or the
    per-request replica provenance (acg_tpu/serve/fleet.py): which
    replica produced the response and, for a failed-over request, the
    ordered chain of replicas whose deaths it survived.  Since /12 a
    non-null block also carries the elastic-fleet snapshot:
    ``resurrections``/``quarantined`` counts and the ``autoscaler``
    sub-block (null until the first resize; else target width, last
    decision and its reason)."""
    if fl == "missing":
        p.append("fleet missing (required at /10; null outside a "
                 "replica fleet)")
        return
    if fl is None:
        return
    if not isinstance(fl, dict):
        p.append("fleet is neither null nor an object")
        return
    _check(p, isinstance(fl.get("replica_id"), str),
           "fleet.replica_id missing or not a string")
    ff = fl.get("failover_from", "missing")
    _check(p, ff is None or (isinstance(ff, list)
                             and all(isinstance(v, str) for v in ff)),
           "fleet.failover_from missing or not a list of strings/null")
    hops = fl.get("hops", "missing")
    _check(p, isinstance(hops, int) and not isinstance(hops, bool)
           and hops >= 0,
           "fleet.hops missing or not a non-negative int")
    if isinstance(ff, list) and isinstance(hops, int):
        _check(p, len(ff) == hops,
               f"fleet.hops is {hops} but failover_from names "
               f"{len(ff)} hops")
    if v12:
        for key in ("resurrections", "quarantined"):
            v = fl.get(key, "missing")
            _check(p, isinstance(v, int) and not isinstance(v, bool)
                   and v >= 0,
                   f"fleet.{key} missing or not a non-negative int "
                   f"(required at /12)")
        a = fl.get("autoscaler", "missing")
        if a == "missing":
            p.append("fleet.autoscaler missing (required at /12; null "
                     "before the first resize)")
        elif a is not None:
            if not isinstance(a, dict):
                p.append("fleet.autoscaler is neither null nor an "
                         "object")
            else:
                t = a.get("target", "missing")
                _check(p, isinstance(t, int)
                       and not isinstance(t, bool) and t >= 1,
                       "fleet.autoscaler.target missing or not a "
                       "positive int")
                for key in ("decision", "reason"):
                    _check(p, isinstance(a.get(key), str),
                           f"fleet.autoscaler.{key} missing or not a "
                           f"string")


def _validate_metrics(p: list, m) -> None:
    """Schema-/9 ``metrics`` block: the key is required, its value null
    (registry disabled — the default) or a
    ``MetricsRegistry.snapshot()`` (acg_tpu/obs/metrics.py)."""
    if m == "missing":
        p.append("metrics missing (required at /9; null when the "
                 "registry is disabled)")
        return
    if m is None:
        return
    if not isinstance(m, dict):
        p.append("metrics is neither null nor an object")
        return
    _check(p, isinstance(m.get("enabled"), bool),
           "metrics.enabled missing or not bool")
    for fam in ("counters", "gauges", "histograms"):
        blk = m.get(fam)
        if not isinstance(blk, dict):
            p.append(f"metrics.{fam} missing or not an object")
            continue
        for name, entry in blk.items():
            if not isinstance(entry, dict) \
                    or not isinstance(entry.get("values"), list):
                p.append(f"metrics.{fam}.{name} missing its values list")
                continue
            for i, v in enumerate(entry["values"]):
                if not isinstance(v, dict) \
                        or not isinstance(v.get("labels"), dict):
                    p.append(f"metrics.{fam}.{name}.values[{i}] missing "
                             "labels")
                elif fam == "histograms":
                    _check(p, isinstance(v.get("buckets"), dict)
                           and _is_num(v.get("count", "missing")),
                           f"metrics.{fam}.{name}.values[{i}] missing "
                           "buckets/count")
                else:
                    _check(p, v.get("value") is None
                           or _is_num(v.get("value", "missing")),
                           f"metrics.{fam}.{name}.values[{i}].value "
                           "missing or not numeric")


_BREAKER_STATES = ("CLOSED", "HALF_OPEN", "OPEN")


def _validate_admission(p: list, adm, session=None,
                        v9: bool = False) -> None:
    """Schema-/8 ``admission`` block: the key is required, its value
    null (plain solve) or the serve layer's per-request admission
    telemetry (acg_tpu/serve/admission.py ``AdmissionRecord.as_dict()``).
    A serve response (non-null ``session``) must document its admission
    path — shed and timed-out requests are exactly when it matters.
    At /9 the block additionally carries the nullable ``trace_id``
    cross-link."""
    if adm == "missing":
        p.append("admission missing (required at /8; null for plain "
                 "solves)")
        return
    if adm is None:
        if session is not None:
            p.append("admission is null but session is not (a serve "
                     "response must carry its admission telemetry)")
        return
    if not isinstance(adm, dict):
        p.append("admission is neither null nor an object")
        return
    for f in ("shed", "degraded"):
        _check(p, isinstance(adm.get(f), bool),
               f"admission.{f} missing or not bool")
    if v9:
        _check(p, "trace_id" in adm
               and (adm["trace_id"] is None
                    or isinstance(adm["trace_id"], str)),
               "admission.trace_id missing or not a string/null "
               "(required at /9)")
    dfrom = adm.get("degraded_from", "missing")
    _check(p, dfrom is None or isinstance(dfrom, str),
           "admission.degraded_from missing or not a string/null")
    retries = adm.get("retries")
    if not isinstance(retries, dict):
        p.append("admission.retries missing or not an object")
    else:
        for f in ("used", "max"):
            _check(p, isinstance(retries.get(f), int)
                   and not isinstance(retries.get(f), bool),
                   f"admission.retries.{f} missing or not int")
        bo = retries.get("backoff_ms", "missing")
        _check(p, isinstance(bo, list)
               and all(_is_num(v) for v in bo),
               "admission.retries.backoff_ms missing or not a list of "
               "numbers")
    deadline = adm.get("deadline", "missing")
    if deadline == "missing":
        p.append("admission.deadline missing (null when no deadline "
                 "was configured)")
    elif deadline is not None:
        if not isinstance(deadline, dict):
            p.append("admission.deadline is neither null nor an object")
        else:
            _check(p, _is_num(deadline.get("budget_ms", "missing")),
                   "admission.deadline.budget_ms missing or not numeric")
            q = deadline.get("queue_ms", "missing")
            _check(p, q is None or _is_num(q),
                   "admission.deadline.queue_ms missing or not "
                   "numeric/null")
            rem = deadline.get("remaining_ms", "missing")
            _check(p, rem is None or _is_num(rem),
                   "admission.deadline.remaining_ms missing or not "
                   "numeric/null")
            _check(p, isinstance(deadline.get("expired"), bool),
                   "admission.deadline.expired missing or not bool")
    breaker = adm.get("breaker", "missing")
    if breaker == "missing":
        p.append("admission.breaker missing (null when no breaker is "
                 "configured)")
    elif breaker is not None:
        if not isinstance(breaker, dict):
            p.append("admission.breaker is neither null nor an object")
        else:
            _check(p, breaker.get("state") in _BREAKER_STATES,
                   f"admission.breaker.state not one of "
                   f"{_BREAKER_STATES}")
            sig = breaker.get("signature", "missing")
            _check(p, sig is None or isinstance(sig, str),
                   "admission.breaker.signature missing or not a "
                   "string/null")
            _check(p, isinstance(breaker.get("trips"), int)
                   and not isinstance(breaker.get("trips"), bool),
                   "admission.breaker.trips missing or not int")


def _validate_contract_field(p: list, contract) -> None:
    """Schema-/7 ``contract`` block: the key is required, its value null
    (no contract evaluated) or the static-contract verdict
    (acg_tpu/analysis/contracts.py ``contract_block()``)."""
    if contract == "missing":
        p.append("contract missing (required at /7; null when no "
                 "contract was evaluated)")
        return
    if contract is None:
        return
    if not isinstance(contract, dict):
        p.append("contract is neither null nor an object")
        return
    _check(p, isinstance(contract.get("name"), str),
           "contract.name missing or not a string")
    _check(p, contract.get("verdict") in ("PASS", "FAIL"),
           "contract.verdict missing or not PASS/FAIL")
    _validate_violations(p, contract.get("violations"), "contract")
    decl = contract.get("declared", "missing")
    _check(p, decl is None or isinstance(decl, dict),
           "contract.declared missing or not an object/null")
    viols = contract.get("violations")
    if contract.get("verdict") == "FAIL" and isinstance(viols, list):
        _check(p, len(viols) > 0,
               "contract.verdict is FAIL but violations is empty")


def _validate_violations(p: list, viols, where: str) -> None:
    """A rule-coded violation list (shared by the stats ``contract``
    block and the contracts-report cases)."""
    if not isinstance(viols, list):
        p.append(f"{where}.violations missing or not a list")
        return
    for i, v in enumerate(viols):
        if not isinstance(v, dict) or not isinstance(v.get("rule"), str) \
                or not isinstance(v.get("detail"), str):
            p.append(f"{where}.violations[{i}] missing rule/detail "
                     "strings")


def _validate_session(p: list, sess, v9: bool = False) -> None:
    """Schema-/6 ``session`` block: the key is required, its value null
    (plain solve) or the serve layer's per-request context
    (acg_tpu/serve/service.py ``SolverService.session_block()``).  At
    /9 the block additionally carries the nullable ``trace_id``
    cross-link into the flight recorder and Chrome trace export."""
    if sess == "missing":
        p.append("session missing (required at /6; null for plain "
                 "solves)")
        return
    if sess is None:
        return
    if not isinstance(sess, dict):
        p.append("session is neither null nor an object")
        return
    rid = sess.get("request_id", "missing")
    _check(p, rid is None or isinstance(rid, str),
           "session.request_id missing or not a string/null")
    if v9:
        _check(p, "trace_id" in sess
               and (sess["trace_id"] is None
                    or isinstance(sess["trace_id"], str)),
               "session.trace_id missing or not a string/null "
               "(required at /9)")
    cache = sess.get("cache")
    if not isinstance(cache, dict):
        p.append("session.cache missing or not an object")
    else:
        _check(p, isinstance(cache.get("executable_hit"), bool),
               "session.cache.executable_hit missing or not bool")
        for fam in ("executable", "prepared"):
            blk = cache.get(fam)
            if not isinstance(blk, dict):
                p.append(f"session.cache.{fam} missing or not an object")
                continue
            for f in ("hits", "misses"):
                _check(p, isinstance(blk.get(f), int)
                       and not isinstance(blk.get(f), bool),
                       f"session.cache.{fam}.{f} missing or not int")
    queue = sess.get("queue")
    if not isinstance(queue, dict):
        p.append("session.queue missing or not an object")
    else:
        _check(p, _is_num(queue.get("wait_seconds", "missing")),
               "session.queue.wait_seconds missing or not numeric")
        _check(p, isinstance(queue.get("depth"), int)
               and not isinstance(queue.get("depth"), bool),
               "session.queue.depth missing or not int")
    batch = sess.get("batch")
    if not isinstance(batch, dict):
        p.append("session.batch missing or not an object")
    else:
        for f in ("size", "bucket"):
            v = batch.get(f)
            _check(p, isinstance(v, int) and not isinstance(v, bool)
                   and v >= 1,
                   f"session.batch.{f} missing or not a positive int")
        occ = batch.get("occupancy", "missing")
        _check(p, _is_num(occ) and 0 <= occ <= 1,
               "session.batch.occupancy missing or not in [0, 1]")


def _validate_resilience(p: list, resil) -> None:
    """Schema-/4 ``resilience`` block: the key is required, its value is
    null (plain solve) or a RecoveryReport object
    (acg_tpu/robust/supervisor.py ``RecoveryReport.as_dict()``)."""
    if resil == "missing":
        p.append("resilience missing (required at /4; null for plain "
                 "solves)")
        return
    if resil is None:
        return
    if not isinstance(resil, dict):
        p.append("resilience is neither null nor an object")
        return
    steps = resil.get("steps")
    if not isinstance(steps, list):
        p.append("resilience.steps missing or not a list")
    else:
        for i, s in enumerate(steps):
            if not isinstance(s, dict) or not isinstance(
                    s.get("action"), str):
                p.append(f"resilience.steps[{i}] missing its action")
    for key in ("restarts", "max_restarts"):
        _check(p, isinstance(resil.get(key), int)
               and not isinstance(resil.get(key), bool),
               f"resilience.{key} missing or not int")
    _check(p, isinstance(resil.get("converged"), bool),
           "resilience.converged missing or not bool")
    _check(p, isinstance(resil.get("final_status"), str),
           "resilience.final_status missing or not a string")
    fx = resil.get("fixed_by", "missing")
    _check(p, fx is None or isinstance(fx, str),
           "resilience.fixed_by missing or not a string/null")
    crr = resil.get("certified_relative_residual", "missing")
    _check(p, crr is None or _is_num(crr),
           "resilience.certified_relative_residual missing or not "
           "numeric/null")
    faults = resil.get("faults", "missing")
    _check(p, isinstance(faults, list)
           and all(isinstance(f, str) for f in faults),
           "resilience.faults missing or not a list of strings")


def _validate_introspection(p: list, intro, v5: bool = False,
                            v11: bool = False) -> None:
    """Schema-/3 ``introspection`` block: ``comm_audit`` and ``roofline``
    keys required, each null or an object with the core numeric fields
    (acg_tpu/obs/hlo.py ``CommAudit.as_dict()`` /
    acg_tpu/obs/roofline.py ``RooflineModel.as_dict()``).  At /5 a
    non-null comm_audit additionally carries the per-SOLVER-iteration
    rational counts (the s-step 1/s claim as data).  At /11 a required
    nullable ``halo_wire`` object carries the on-wire halo accounting
    (wire spelling, element dtype, itemsize, bytes-saved ratio)."""
    if not isinstance(intro, dict):
        p.append("introspection missing or not an object (required at /3)")
        return
    for key in ("comm_audit", "roofline"):
        _check(p, key in intro, f"introspection.{key} missing")
    if v11:
        _check(p, "halo_wire" in intro,
               "introspection.halo_wire missing (required at /11)")
        hw = intro.get("halo_wire")
        if hw is not None and not isinstance(hw, dict):
            p.append("introspection.halo_wire is neither null nor an "
                     "object")
        elif isinstance(hw, dict):
            for f in ("wire", "dtype"):
                _check(p, isinstance(hw.get(f), str),
                       f"introspection.halo_wire.{f} missing or not a "
                       "string")
            _check(p, isinstance(hw.get("itemsize"), int)
                   and not isinstance(hw.get("itemsize"), bool),
                   "introspection.halo_wire.itemsize missing or not int")
            v = hw.get("bytes_saved_ratio", "missing")
            _check(p, v is None or _is_num(v),
                   "introspection.halo_wire.bytes_saved_ratio missing "
                   "or not numeric/null")
    audit = intro.get("comm_audit")
    if audit is not None and not isinstance(audit, dict):
        p.append("introspection.comm_audit is neither null nor an object")
    elif isinstance(audit, dict):
        per = audit.get("per_iteration")
        if not isinstance(per, dict):
            p.append("introspection.comm_audit.per_iteration missing")
        else:
            for cls in ("ppermute", "allreduce", "allgather"):
                blk = per.get(cls)
                if not isinstance(blk, dict):
                    p.append(f"comm_audit.per_iteration.{cls} missing")
                    continue
                for f in ("count", "bytes"):
                    _check(p, isinstance(blk.get(f), int)
                           and not isinstance(blk.get(f), bool),
                           f"comm_audit.per_iteration.{cls}.{f} missing "
                           "or not int")
        _check(p, isinstance(audit.get("nfusions"), int),
               "comm_audit.nfusions missing or not int")
        if v5:
            ipb = audit.get("iterations_per_body")
            _check(p, isinstance(ipb, int) and not isinstance(ipb, bool)
                   and ipb >= 1,
                   "comm_audit.iterations_per_body missing or not a "
                   "positive int (required at /5)")
            psi = audit.get("per_solver_iteration")
            if not isinstance(psi, dict):
                p.append("comm_audit.per_solver_iteration missing "
                         "(required at /5)")
            else:
                for cls in ("ppermute", "allreduce", "allgather"):
                    blk = psi.get(cls)
                    if not isinstance(blk, dict):
                        p.append(f"comm_audit.per_solver_iteration.{cls}"
                                 " missing")
                        continue
                    for f in ("count", "bytes"):
                        _check(p, _is_num(blk.get(f, "missing")),
                               f"per_solver_iteration.{cls}.{f} missing "
                               "or not numeric")
                    _check(p, isinstance(blk.get("count_rational"), str),
                           f"per_solver_iteration.{cls}.count_rational "
                           "missing or not a string")
        for f in ("flops", "bytes_accessed", "peak_hbm_bytes"):
            v = audit.get(f, "missing")
            _check(p, v is None or _is_num(v),
                   f"comm_audit.{f} missing or not numeric/null")
    roof = intro.get("roofline")
    if roof is not None and not isinstance(roof, dict):
        p.append("introspection.roofline is neither null nor an object")
    elif isinstance(roof, dict):
        for f in ("operator_bytes", "vector_bytes", "bytes_per_iter"):
            _check(p, _is_num(roof.get(f, "missing")),
                   f"roofline.{f} missing or not numeric")
        # null off the chip: no bandwidth to model against
        for f in ("hbm_gbps", "predicted_iters_per_sec"):
            v = roof.get(f, "missing")
            _check(p, v is None or _is_num(v),
                   f"roofline.{f} missing or not numeric/null")
        _check(p, isinstance(roof.get("nrhs", "missing"), int),
               "roofline.nrhs missing or not int")
        for f in ("measured_iters_per_sec", "roofline_frac"):
            if f in roof:
                v = roof[f]
                _check(p, v is None or _is_num(v),
                       f"roofline.{f} not numeric/null")


def bench_record(*, metric: str, value: float, unit: str,
                 vs_baseline: float | None = None, **extra) -> dict:
    """The one-line benchmark payload (bench.py; also the ``parsed``
    field of the driver's ``BENCH_*.json`` trajectory files).  Built
    here so bench.py and external dashboards share one schema."""
    rec = {"metric": str(metric), "value": _finite(float(value)),
           "unit": str(unit)}
    if vs_baseline is not None:
        rec["vs_baseline"] = _finite(float(vs_baseline))
    for k, v in extra.items():
        rec[k] = _finite(v) if isinstance(v, float) else v
    problems = validate_bench_record(rec)
    if problems:
        raise ValueError("; ".join(problems))
    return rec


CONTRACTS_SCHEMA = "acg-tpu-contracts/1"

_VERDICTS = ("PASS", "FAIL", "SKIP")


def validate_contracts_document(doc) -> list[str]:
    """Validate an ``acg-tpu-contracts/1`` report — the machine-readable
    output of ``scripts/check_contracts.py`` (the solver contract matrix
    swept against compiled HLO, acg_tpu/analysis/registry.py): per-case
    verdicts with rule-coded violations, the cross-B scaling pairs, and
    self-consistent summary counters."""
    p: list[str] = []
    if not isinstance(doc, dict):
        return ["contracts document is not a JSON object"]
    _check(p, doc.get("schema") == CONTRACTS_SCHEMA,
           f"schema is {doc.get('schema')!r}, expected "
           f"{CONTRACTS_SCHEMA!r}")
    _check(p, isinstance(doc.get("fast"), bool),
           "fast missing or not a bool")
    _check(p, isinstance(doc.get("ok"), bool), "ok missing or not a bool")
    for key in ("ncases", "failed", "skipped"):
        _check(p, isinstance(doc.get(key), int)
               and not isinstance(doc.get(key), bool),
               f"{key} missing or not an int")
    cases = doc.get("cases")
    if not isinstance(cases, list) or not cases:
        p.append("cases missing, not a list, or empty")
        return p
    nfail = nskip = 0
    for i, c in enumerate(cases):
        if not isinstance(c, dict):
            p.append(f"cases[{i}] is not an object")
            continue
        _check(p, isinstance(c.get("name"), str),
               f"cases[{i}].name missing")
        _check(p, isinstance(c.get("solver"), str),
               f"cases[{i}].solver missing")
        _check(p, isinstance(c.get("nparts"), int)
               and not isinstance(c.get("nparts"), bool),
               f"cases[{i}].nparts missing or not int")
        _check(p, isinstance(c.get("nrhs"), int)
               and not isinstance(c.get("nrhs"), bool),
               f"cases[{i}].nrhs missing or not int")
        _check(p, isinstance(c.get("dtype"), str),
               f"cases[{i}].dtype missing")
        verdict = c.get("verdict")
        _check(p, verdict in _VERDICTS,
               f"cases[{i}].verdict not one of {_VERDICTS}")
        _validate_violations(p, c.get("violations"), f"cases[{i}]")
        sr = c.get("skip_reason")
        _check(p, "skip_reason" in c
               and (sr is None or isinstance(sr, str)),
               f"cases[{i}].skip_reason missing or not a string/null")
        if verdict == "FAIL":
            nfail += 1
            if isinstance(c.get("violations"), list):
                _check(p, len(c["violations"]) > 0,
                       f"cases[{i}] FAILed with no violations")
        elif verdict == "SKIP":
            nskip += 1
            _check(p, isinstance(sr, str) and sr,
                   f"cases[{i}] SKIPped without a reason")
    pairs = doc.get("pairs")
    if not isinstance(pairs, list):
        p.append("pairs missing or not a list")
    else:
        for i, pr in enumerate(pairs):
            if not isinstance(pr, dict):
                p.append(f"pairs[{i}] is not an object")
                continue
            _check(p, isinstance(pr.get("name"), str),
                   f"pairs[{i}].name missing")
            _check(p, pr.get("verdict") in ("PASS", "FAIL"),
                   f"pairs[{i}].verdict not PASS/FAIL")
            _validate_violations(p, pr.get("violations"), f"pairs[{i}]")
            if pr.get("verdict") == "FAIL":
                nfail += 1
    if isinstance(doc.get("ncases"), int):
        _check(p, doc["ncases"] == len(cases),
               f"ncases is {doc['ncases']}, document has {len(cases)}")
    if isinstance(doc.get("failed"), int) and isinstance(pairs, list):
        _check(p, doc["failed"] == nfail,
               f"failed is {doc['failed']}, document counts {nfail}")
        _check(p, doc.get("ok") == (nfail == 0),
               "ok is inconsistent with the failure count")
    if isinstance(doc.get("skipped"), int):
        _check(p, doc["skipped"] == nskip,
               f"skipped is {doc['skipped']}, document counts {nskip}")
    return p


SEQBENCH_SCHEMA = "acg-tpu-seqbench/1"
SEQBENCH_SCHEMAS = (SEQBENCH_SCHEMA,)

_SEQ_STREAM_KEYS = ("iterations", "total_iterations", "wall_s",
                    "req_per_s", "all_certified")


def validate_seqbench_document(doc) -> list[str]:
    """Validate an ``acg-tpu-seqbench/1`` artifact — the output of
    ``scripts/bench_serve.py --sequence`` (ISSUE 20): a seeded
    correlated request stream (random-walk RHS) served twice through
    the SAME operator — once warm (x0 warm-start + recycling on) and
    once cold — with per-request iteration counts, aggregate
    throughput, and the certified-exit agreement between the two runs.

    Shape: ``schema``/``seed``/``config`` (solver, nparts, nrows,
    requests, sigma), a ``warm`` and a ``cold`` stream block (each:
    ``iterations`` per-request list, ``total_iterations``, nullable
    ``wall_s``/``req_per_s``, ``all_certified`` bool; ``warm`` adds
    ``served_warm``/``rejected`` counts), and a ``speedup`` block
    (``aggregate_iterations`` = cold/warm total-iteration ratio,
    nullable ``aggregate_req_per_s``)."""
    p: list[str] = []
    if not isinstance(doc, dict):
        return ["seqbench document is not a JSON object"]
    _check(p, doc.get("schema") in SEQBENCH_SCHEMAS,
           f"schema is {doc.get('schema')!r}, expected one of "
           f"{SEQBENCH_SCHEMAS!r}")
    _check(p, isinstance(doc.get("seed"), int)
           and not isinstance(doc.get("seed"), bool),
           "seed missing or not int")
    cfg = doc.get("config")
    if not isinstance(cfg, dict):
        p.append("config missing or not an object")
    else:
        _check(p, isinstance(cfg.get("solver"), str),
               "config.solver missing or not a string")
        for f in ("nparts", "nrows", "requests"):
            _check(p, isinstance(cfg.get(f), int)
                   and not isinstance(cfg.get(f), bool),
                   f"config.{f} missing or not int")
        _check(p, _is_num(cfg.get("sigma", "missing")),
               "config.sigma missing or not numeric")
    nreq = (cfg or {}).get("requests") if isinstance(cfg, dict) else None
    for blk_name in ("warm", "cold"):
        blk = doc.get(blk_name)
        if not isinstance(blk, dict):
            p.append(f"{blk_name} missing or not an object")
            continue
        its = blk.get("iterations")
        if not isinstance(its, list) or not all(
                isinstance(v, int) and not isinstance(v, bool)
                for v in its):
            p.append(f"{blk_name}.iterations missing or not a list of "
                     "ints")
        elif isinstance(nreq, int):
            _check(p, len(its) == nreq,
                   f"{blk_name}.iterations has {len(its)} entries, "
                   f"expected config.requests = {nreq}")
        ti = blk.get("total_iterations", "missing")
        _check(p, isinstance(ti, int) and not isinstance(ti, bool),
               f"{blk_name}.total_iterations missing or not int")
        if isinstance(its, list) and isinstance(ti, int) and all(
                isinstance(v, int) for v in its):
            _check(p, ti == sum(its),
                   f"{blk_name}.total_iterations != sum(iterations)")
        for f in ("wall_s", "req_per_s"):
            v = blk.get(f, "missing")
            _check(p, v is None or _is_num(v),
                   f"{blk_name}.{f} missing or not numeric/null")
        _check(p, isinstance(blk.get("all_certified"), bool),
               f"{blk_name}.all_certified missing or not bool")
    warm = doc.get("warm")
    if isinstance(warm, dict):
        for f in ("served_warm", "rejected"):
            v = warm.get(f, "missing")
            _check(p, isinstance(v, int) and not isinstance(v, bool)
                   and v >= 0,
                   f"warm.{f} missing or not a non-negative int")
    sp = doc.get("speedup")
    if not isinstance(sp, dict):
        p.append("speedup missing or not an object")
    else:
        _check(p, _is_num(sp.get("aggregate_iterations", "missing")),
               "speedup.aggregate_iterations missing or not numeric")
        v = sp.get("aggregate_req_per_s", "missing")
        _check(p, v is None or _is_num(v),
               "speedup.aggregate_req_per_s missing or not numeric/null")
    return p


SLO_SCHEMA_V1 = "acg-tpu-slo/1"
SLO_SCHEMA_V2 = "acg-tpu-slo/2"
SLO_SCHEMA_V3 = "acg-tpu-slo/3"
SLO_SCHEMA = "acg-tpu-slo/4"
SLO_SCHEMAS = (SLO_SCHEMA_V1, SLO_SCHEMA_V2, SLO_SCHEMA_V3,
               SLO_SCHEMA)

_SLO_LATENCY_KEYS = ("end_to_end", "queue_wait", "dispatch")
_SLO_PCT_KEYS = ("p50_ms", "p99_ms", "p999_ms", "mean_ms", "max_ms")
_SLO_RATE_KEYS = ("success", "shed", "timeout", "degraded")


def validate_slo_document(doc) -> list[str]:
    """Validate an ``acg-tpu-slo/1``.. ``/4`` artifact — the
    output of the sustained-load harness (``scripts/slo_report.py``): a
    seeded open-loop arrival process (Poisson + burst phases) driven
    against a live serve Session, summarized as p50/p99/p999 latency
    percentiles (end-to-end / queue-wait / dispatch), throughput,
    outcome rates and the final metrics-registry snapshot.

    /2 (ISSUE 15) adds a required nullable ``fleet`` block — null for a
    single-service run, else the replica-fleet load profile: ``replicas``
    (the fleet width), ``per_replica`` (replica id -> classified-response
    count), nullable ``kill`` (``{replica, at_s}`` — the seeded
    replica-kill event of the failover drill) and nullable ``failover``
    (``failed_over`` re-dispatched request count + the measured p99
    failover blip: end-to-end p99 before the kill, in the blip window
    after it, and after the window).

    /3 (ISSUE 16) adds a required nullable ``findings`` block — null
    when the run had no sentinel hub attached (``--findings`` off),
    else the :meth:`acg_tpu.obs.sentinel.SentinelHub.summary` counts
    (``total``/``worst``/``by_kind``/``by_severity``/``by_replica``)
    plus an optional ``items`` list of the finding records
    themselves.

    /4 (ISSUE 19) grows the non-null ``fleet`` block by a required
    nullable ``elastic`` sub-block — null for a fixed-width run, else
    the recovery story of the elastic drill: ``resurrections`` count,
    ``time_to_ready_s`` (the replacement's spawn-to-READY wall; null
    when nothing died), ``warm`` (did the replacement hit the
    prepared-operator cache; null when nothing died) and
    ``recovery_p99_ms`` (the ``{pre, during, post}`` e2e p99 around the
    kill; null when nothing died)."""
    p: list[str] = []
    if not isinstance(doc, dict):
        return ["slo document is not a JSON object"]
    _check(p, doc.get("schema") in SLO_SCHEMAS,
           f"schema is {doc.get('schema')!r}, expected one of "
           f"{SLO_SCHEMAS!r}")
    _check(p, isinstance(doc.get("seed"), int)
           and not isinstance(doc.get("seed"), bool),
           "seed missing or not int")
    _check(p, isinstance(doc.get("config"), dict),
           "config missing or not an object")
    cfg = doc.get("config")
    if isinstance(cfg, dict):
        _check(p, isinstance(cfg.get("solver"), str),
               "config.solver missing or not a string")
        for f in ("nparts", "nrows"):
            _check(p, isinstance(cfg.get(f), int)
                   and not isinstance(cfg.get(f), bool),
                   f"config.{f} missing or not int")
    load = doc.get("load")
    if not isinstance(load, dict):
        p.append("load missing or not an object")
    else:
        phases = load.get("phases")
        if not isinstance(phases, list) or not phases:
            p.append("load.phases missing, not a list, or empty")
        else:
            for i, ph in enumerate(phases):
                if not isinstance(ph, dict):
                    p.append(f"load.phases[{i}] is not an object")
                    continue
                _check(p, isinstance(ph.get("kind"), str),
                       f"load.phases[{i}].kind missing")
                for f in ("rate_rps", "duration_s"):
                    _check(p, _is_num(ph.get(f, "missing")),
                           f"load.phases[{i}].{f} missing or not "
                           "numeric")
        for f in ("submitted", "completed"):
            _check(p, isinstance(load.get(f), int)
                   and not isinstance(load.get(f), bool),
                   f"load.{f} missing or not int")
    lat = doc.get("latency_ms")
    if not isinstance(lat, dict):
        p.append("latency_ms missing or not an object")
    else:
        for key in _SLO_LATENCY_KEYS:
            blk = lat.get(key)
            if not isinstance(blk, dict):
                p.append(f"latency_ms.{key} missing or not an object")
                continue
            for f in _SLO_PCT_KEYS:
                v = blk.get(f, "missing")
                _check(p, v is None or _is_num(v),
                       f"latency_ms.{key}.{f} missing or not "
                       "numeric/null")
    tp = doc.get("throughput_rps", "missing")
    _check(p, tp is None or _is_num(tp),
           "throughput_rps missing or not numeric/null")
    rates = doc.get("rates")
    if not isinstance(rates, dict):
        p.append("rates missing or not an object")
    else:
        for f in _SLO_RATE_KEYS:
            v = rates.get(f, "missing")
            _check(p, _is_num(v) and 0 <= v <= 1,
                   f"rates.{f} missing or not a rate in [0, 1]")
    outcomes = doc.get("outcomes")
    _check(p, isinstance(outcomes, dict)
           and all(isinstance(k, str) and isinstance(v, int)
                   and not isinstance(v, bool)
                   for k, v in (outcomes or {}).items()),
           "outcomes missing or not a status -> count object")
    if "metrics" not in doc:
        p.append("metrics missing (the final registry snapshot; null "
                 "when the registry was disabled)")
    else:
        _validate_metrics(p, doc["metrics"])
    if doc.get("schema") in (SLO_SCHEMA_V2, SLO_SCHEMA_V3, SLO_SCHEMA):
        _validate_slo_fleet(p, doc.get("fleet", "missing"),
                            v4=doc.get("schema") == SLO_SCHEMA)
    if doc.get("schema") in (SLO_SCHEMA_V3, SLO_SCHEMA):
        _validate_findings_summary(p, doc.get("findings", "missing"),
                                   "findings",
                                   missing_hint="required at slo/3; "
                                   "null when no sentinel hub was "
                                   "attached")
    return p


def _validate_slo_fleet(p: list, fl, *, v4: bool = False) -> None:
    """SLO-/2 ``fleet`` block (see :func:`validate_slo_document`)."""
    if fl == "missing":
        p.append("fleet missing (required at slo/2; null for a "
                 "single-service run)")
        return
    if fl is None:
        return
    if not isinstance(fl, dict):
        p.append("fleet is neither null nor an object")
        return
    _check(p, isinstance(fl.get("replicas"), int)
           and not isinstance(fl.get("replicas"), bool)
           and fl.get("replicas") >= 1,
           "fleet.replicas missing or not a positive int")
    per = fl.get("per_replica")
    _check(p, isinstance(per, dict)
           and all(isinstance(k, str) and isinstance(v, int)
                   and not isinstance(v, bool)
                   for k, v in (per or {}).items()),
           "fleet.per_replica missing or not a replica -> count object")
    kill = fl.get("kill", "missing")
    if kill == "missing":
        p.append("fleet.kill missing (null when no replica was killed)")
    elif kill is not None:
        if not isinstance(kill, dict):
            p.append("fleet.kill is neither null nor an object")
        else:
            _check(p, isinstance(kill.get("replica"), str),
                   "fleet.kill.replica missing or not a string")
            _check(p, _is_num(kill.get("at_s", "missing")),
                   "fleet.kill.at_s missing or not numeric")
    fo = fl.get("failover", "missing")
    if fo == "missing":
        p.append("fleet.failover missing (null when no replica was "
                 "killed)")
    elif fo is not None:
        if not isinstance(fo, dict):
            p.append("fleet.failover is neither null nor an object")
        else:
            _check(p, isinstance(fo.get("failed_over"), int)
                   and not isinstance(fo.get("failed_over"), bool),
                   "fleet.failover.failed_over missing or not int")
            blip = fo.get("blip_p99_ms")
            if not isinstance(blip, dict):
                p.append("fleet.failover.blip_p99_ms missing or not an "
                         "object")
            else:
                for f in ("pre", "during", "post"):
                    v = blip.get(f, "missing")
                    _check(p, v is None or _is_num(v),
                           f"fleet.failover.blip_p99_ms.{f} missing or "
                           "not numeric/null")
    if v4:
        el = fl.get("elastic", "missing")
        if el == "missing":
            p.append("fleet.elastic missing (required at slo/4; null "
                     "for a fixed-width run)")
        elif el is not None:
            if not isinstance(el, dict):
                p.append("fleet.elastic is neither null nor an object")
                return
            n = el.get("resurrections", "missing")
            _check(p, isinstance(n, int) and not isinstance(n, bool)
                   and n >= 0,
                   "fleet.elastic.resurrections missing or not a "
                   "non-negative int")
            for f in ("time_to_ready_s",):
                v = el.get(f, "missing")
                _check(p, v is None or _is_num(v),
                       f"fleet.elastic.{f} missing or not numeric/null")
            w = el.get("warm", "missing")
            _check(p, w is None or isinstance(w, bool),
                   "fleet.elastic.warm missing or not a bool/null")
            rec = el.get("recovery_p99_ms", "missing")
            if rec == "missing":
                p.append("fleet.elastic.recovery_p99_ms missing (null "
                         "when nothing died)")
            elif rec is not None:
                if not isinstance(rec, dict):
                    p.append("fleet.elastic.recovery_p99_ms is neither "
                             "null nor an object")
                else:
                    for f in ("pre", "during", "post"):
                        v = rec.get(f, "missing")
                        _check(p, v is None or _is_num(v),
                               f"fleet.elastic.recovery_p99_ms.{f} "
                               "missing or not numeric/null")


_SEVERITIES = ("info", "warning", "critical")


def _validate_finding(p: list, f, where: str) -> None:
    """One sentinel :class:`~acg_tpu.obs.sentinel.Finding` dict."""
    if not isinstance(f, dict):
        p.append(f"{where} is not an object")
        return
    _check(p, isinstance(f.get("kind"), str),
           f"{where}.kind missing or not a string")
    _check(p, f.get("severity") in _SEVERITIES,
           f"{where}.severity not one of {_SEVERITIES!r}")
    _check(p, isinstance(f.get("summary"), str),
           f"{where}.summary missing or not a string")
    _check(p, isinstance(f.get("evidence"), dict),
           f"{where}.evidence missing or not an object")
    rid = f.get("replica_id", "missing")
    _check(p, rid is None or isinstance(rid, str),
           f"{where}.replica_id missing or not a string/null")


def _validate_findings_summary(p: list, s, where: str, *,
                               missing_hint: str) -> None:
    """A nullable ``SentinelHub.summary()`` block (+ optional
    ``items`` finding list) — the SLO-/3 ``findings`` key and the obs
    artifact's ``findings_summary``."""
    if s == "missing":
        p.append(f"{where} missing ({missing_hint})")
        return
    if s is None:
        return
    if not isinstance(s, dict):
        p.append(f"{where} is neither null nor an object")
        return
    _check(p, isinstance(s.get("total"), int)
           and not isinstance(s.get("total"), bool)
           and s.get("total") >= 0,
           f"{where}.total missing or not a non-negative int")
    worst = s.get("worst", "missing")
    _check(p, worst is None or worst in _SEVERITIES,
           f"{where}.worst missing or not a severity/null")
    for key in ("by_kind", "by_severity"):
        blk = s.get(key)
        _check(p, isinstance(blk, dict)
               and all(isinstance(k, str) and isinstance(v, int)
                       and not isinstance(v, bool)
                       for k, v in (blk or {}).items()),
               f"{where}.{key} missing or not a name -> count object")
    if "items" in s:
        items = s["items"]
        if not isinstance(items, list):
            p.append(f"{where}.items is not a list")
        else:
            for i, f in enumerate(items):
                _validate_finding(p, f, f"{where}.items[{i}]")


OBS_SCHEMA_V1 = "acg-tpu-obs/1"
OBS_SCHEMA_V2 = "acg-tpu-obs/2"
OBS_SCHEMA_V3 = "acg-tpu-obs/3"
OBS_SCHEMAS = (OBS_SCHEMA_V1, OBS_SCHEMA_V2, OBS_SCHEMA_V3)
# the historical name keeps pointing at /1 (documents built WITHOUT a
# history block stay at /1; /2 is the history-carrying superset, /3
# additionally carries the elastic-fleet keys in its fleet block)
OBS_SCHEMA = OBS_SCHEMA_V1


def _validate_history_points(p: list, series, where: str) -> None:
    """One ``{name: [{labels, points}]}`` family of sampled series."""
    if not isinstance(series, dict):
        p.append(f"{where} missing or not an object")
        return
    for name, entries in series.items():
        if not isinstance(entries, list):
            p.append(f"{where}.{name} is not a list")
            continue
        for i, s in enumerate(entries):
            if not isinstance(s, dict):
                p.append(f"{where}.{name}[{i}] is not an object")
                continue
            _check(p, isinstance(s.get("labels"), dict),
                   f"{where}.{name}[{i}].labels missing or not an "
                   "object")
            pts = s.get("points")
            if not isinstance(pts, list):
                p.append(f"{where}.{name}[{i}].points missing or not "
                         "a list")
                continue
            for j, pt in enumerate(pts):
                _check(p, isinstance(pt, list) and len(pt) == 2
                       and _is_num(pt[0])
                       and (pt[1] is None or _is_num(pt[1])),
                       f"{where}.{name}[{i}].points[{j}] is not a "
                       "[t, value] pair")
            ts = [pt[0] for pt in pts
                  if isinstance(pt, list) and len(pt) == 2
                  and _is_num(pt[0])]
            _check(p, ts == sorted(ts),
                   f"{where}.{name}[{i}].points not time-ordered")


def _validate_history_window(p: list, w, where: str) -> None:
    if not isinstance(w, dict):
        p.append(f"{where} missing or not an object")
        return
    _check(p, isinstance(w.get("samples"), int)
           and not isinstance(w.get("samples"), bool)
           and w.get("samples") >= 0,
           f"{where}.samples missing or not a non-negative int")
    _check(p, _is_num(w.get("dt_s", "missing"))
           and w.get("dt_s", -1) >= 0,
           f"{where}.dt_s missing or negative")
    for f in ("t0", "t1"):
        v = w.get(f, "missing")
        _check(p, v is None or _is_num(v),
               f"{where}.{f} missing or not numeric/null")


def validate_history_block(blk) -> list[str]:
    """Validate a ``MetricsHistory.as_block()`` document — the
    ``history`` block of an ``acg-tpu-obs/2`` artifact and the payload
    of the observability plane's ``GET /history?window=S`` (ISSUE 18):

    - sampler parameters (``interval_s``/``capacity``) and ring
      accounting (``samples`` held, ``evicted`` beyond capacity);
    - ``window`` — the span the queries actually covered;
    - ``series`` — per source, the raw sampled ``[t, value]`` point
      lists for counters, gauges and histogram observation counts;
    - ``queries`` — per source, the windowed derivatives the
      autoscaler consumes: counter ``rates`` (delta/per_sec), gauge
      ``min``/``mean``/``max``/``last`` and histogram window
      ``quantiles`` (count/per_sec/p50/p99).
    """
    p: list[str] = []
    if not isinstance(blk, dict):
        return ["history block is not a JSON object"]
    _check(p, _is_num(blk.get("interval_s", "missing"))
           and blk.get("interval_s", -1) >= 0,
           "history.interval_s missing or negative")
    for f in ("capacity", "samples", "evicted"):
        v = blk.get(f)
        _check(p, isinstance(v, int) and not isinstance(v, bool)
               and v >= 0,
               f"history.{f} missing or not a non-negative int")
    if isinstance(blk.get("capacity"), int) \
            and isinstance(blk.get("samples"), int):
        _check(p, blk["samples"] <= blk["capacity"],
               "history.samples exceeds capacity (the ring is not "
               "bounded)")
    _validate_history_window(p, blk.get("window"), "history.window")
    series = blk.get("series")
    if not isinstance(series, dict):
        p.append("history.series missing or not an object")
    else:
        for src, fams in series.items():
            if not isinstance(fams, dict):
                p.append(f"history.series.{src} is not an object")
                continue
            for fam in ("counters", "gauges", "histogram_counts"):
                _validate_history_points(
                    p, fams.get(fam), f"history.series.{src}.{fam}")
    q = blk.get("queries")
    if not isinstance(q, dict):
        p.append("history.queries missing or not an object")
        return p
    _validate_history_window(p, q.get("window"),
                             "history.queries.window")
    srcs = q.get("sources")
    if not isinstance(srcs, dict):
        p.append("history.queries.sources missing or not an object")
        return p
    for src, blk2 in srcs.items():
        where = f"history.queries.sources.{src}"
        if not isinstance(blk2, dict):
            p.append(f"{where} is not an object")
            continue
        _check(p, _is_num(blk2.get("window_s", "missing"))
               and blk2.get("window_s", -1) > 0,
               f"{where}.window_s missing or not positive")
        rates = blk2.get("rates")
        if not isinstance(rates, dict):
            p.append(f"{where}.rates missing or not an object")
        else:
            for name, series2 in rates.items():
                for i, s in enumerate(series2
                                      if isinstance(series2, list)
                                      else []):
                    _check(p, isinstance(s, dict)
                           and isinstance(s.get("labels"), dict)
                           and _is_num(s.get("per_sec", "missing"))
                           and _is_num(s.get("delta", "missing")),
                           f"{where}.rates.{name}[{i}] missing "
                           "labels/delta/per_sec")
        gauges = blk2.get("gauges")
        if not isinstance(gauges, dict):
            p.append(f"{where}.gauges missing or not an object")
        else:
            for name, series2 in gauges.items():
                for i, s in enumerate(series2
                                      if isinstance(series2, list)
                                      else []):
                    _check(p, isinstance(s, dict)
                           and isinstance(s.get("labels"), dict)
                           and all(_is_num(s.get(k, "missing"))
                                   for k in ("min", "mean", "max",
                                             "last")),
                           f"{where}.gauges.{name}[{i}] missing "
                           "labels/min/mean/max/last")
        quants = blk2.get("quantiles")
        if not isinstance(quants, dict):
            p.append(f"{where}.quantiles missing or not an object")
        else:
            for name, series2 in quants.items():
                for i, s in enumerate(series2
                                      if isinstance(series2, list)
                                      else []):
                    if not isinstance(s, dict):
                        p.append(f"{where}.quantiles.{name}[{i}] is "
                                 "not an object")
                        continue
                    _check(p, isinstance(s.get("labels"), dict)
                           and _is_num(s.get("count", "missing"))
                           and _is_num(s.get("per_sec", "missing")),
                           f"{where}.quantiles.{name}[{i}] missing "
                           "labels/count/per_sec")
                    for qq in ("p50", "p99"):
                        v = s.get(qq, "missing")
                        _check(p, v is None or _is_num(v),
                               f"{where}.quantiles.{name}[{i}].{qq} "
                               "missing or not numeric/null")
    return p


def validate_obs_document(doc) -> list[str]:
    """Validate an ``acg-tpu-obs/1``..``/3`` fleet-observatory
    artifact (the output of ``scripts/fleet_top.py --once``, built by
    :func:`acg_tpu.obs.aggregate.build_obs_document`):

    - ``window`` — the rollup window the snapshot ring covered
      (``t0``/``t1``/``dt_s``/``samples``);
    - ``merged`` — ONE replica-labeled fleet metrics snapshot in
      ``MetricsRegistry.snapshot()`` shape (every series carries a
      ``replica`` label), validated through the shared metrics-block
      rules;
    - ``rollups`` — per-replica windowed derivatives: counter
      ``rates`` (delta & per-second) and histogram window
      ``quantiles`` (count, per-second, interpolated p50/p99);
    - ``fleet`` — nullable: the :meth:`Fleet.observe` block (replica
      state/routing/health/findings);
    - ``findings`` + ``findings_summary`` — the sentinel records and
      their :meth:`SentinelHub.summary` counts;
    - ``history`` (/2 and up, required there) — the
      :meth:`MetricsHistory.as_block` sampled-series + windowed-query
      embed, validated by :func:`validate_history_block`;
    - at /3 (ISSUE 19) a non-null ``fleet`` block additionally carries
      the elastic snapshot: ``resurrections``/``quarantined`` counts
      and the nullable ``autoscaler`` sub-block (target width, last
      decision, its reason).
    """
    p: list[str] = []
    if not isinstance(doc, dict):
        return ["obs document is not a JSON object"]
    _check(p, doc.get("schema") in OBS_SCHEMAS,
           f"schema is {doc.get('schema')!r}, expected one of "
           f"{OBS_SCHEMAS!r}")
    if doc.get("schema") in (OBS_SCHEMA_V2, OBS_SCHEMA_V3):
        p.extend(validate_history_block(doc.get("history")))
    elif "history" in doc:
        p.append("history block present on a /1 document (a "
                 "history-carrying artifact must declare "
                 f"{OBS_SCHEMA_V2!r} or {OBS_SCHEMA_V3!r})")
    _check(p, _is_num(doc.get("generated_unix", "missing")),
           "generated_unix missing or not numeric")
    w = doc.get("window")
    if not isinstance(w, dict):
        p.append("window missing or not an object")
    else:
        _check(p, isinstance(w.get("samples"), int)
               and not isinstance(w.get("samples"), bool)
               and w.get("samples") >= 0,
               "window.samples missing or not a non-negative int")
        _check(p, _is_num(w.get("dt_s", "missing"))
               and w.get("dt_s", -1) >= 0,
               "window.dt_s missing or negative")
        for f in ("t0", "t1"):
            v = w.get(f, "missing")
            _check(p, v is None or _is_num(v),
                   f"window.{f} missing or not numeric/null")
    merged = doc.get("merged")
    if not isinstance(merged, dict):
        p.append("merged missing or not an object (the replica-"
                 "labeled fleet snapshot)")
    else:
        _validate_metrics(p, merged)
        for fam in ("counters", "gauges", "histograms"):
            for name, entry in (merged.get(fam) or {}).items():
                if not isinstance(entry, dict):
                    continue
                for i, v in enumerate(entry.get("values") or []):
                    if isinstance(v, dict) \
                            and isinstance(v.get("labels"), dict):
                        _check(p, "replica" in v["labels"],
                               f"merged.{fam}.{name}.values[{i}] "
                               "missing the replica label")
    roll = doc.get("rollups")
    if not isinstance(roll, dict):
        p.append("rollups missing or not an object")
    else:
        for rid, blk in roll.items():
            if not isinstance(blk, dict):
                p.append(f"rollups.{rid} is not an object")
                continue
            _check(p, _is_num(blk.get("window_s", "missing"))
                   and blk.get("window_s", -1) > 0,
                   f"rollups.{rid}.window_s missing or not positive")
            rates = blk.get("rates")
            if not isinstance(rates, dict):
                p.append(f"rollups.{rid}.rates missing or not an "
                         "object")
            else:
                for name, series in rates.items():
                    for i, s in enumerate(series
                                          if isinstance(series, list)
                                          else []):
                        _check(p, isinstance(s, dict)
                               and isinstance(s.get("labels"), dict)
                               and _is_num(s.get("per_sec", "missing"))
                               and _is_num(s.get("delta", "missing")),
                               f"rollups.{rid}.rates.{name}[{i}] "
                               "missing labels/delta/per_sec")
            quants = blk.get("quantiles")
            if not isinstance(quants, dict):
                p.append(f"rollups.{rid}.quantiles missing or not an "
                         "object")
            else:
                for name, series in quants.items():
                    for i, s in enumerate(series
                                          if isinstance(series, list)
                                          else []):
                        if not isinstance(s, dict):
                            p.append(f"rollups.{rid}.quantiles."
                                     f"{name}[{i}] is not an object")
                            continue
                        _check(p, isinstance(s.get("labels"), dict)
                               and _is_num(s.get("count", "missing"))
                               and _is_num(s.get("per_sec", "missing")),
                               f"rollups.{rid}.quantiles.{name}[{i}] "
                               "missing labels/count/per_sec")
                        for q in ("p50", "p99"):
                            v = s.get(q, "missing")
                            _check(p, v is None or _is_num(v),
                                   f"rollups.{rid}.quantiles."
                                   f"{name}[{i}].{q} missing or not "
                                   "numeric/null")
    fl = doc.get("fleet", "missing")
    if fl == "missing":
        p.append("fleet missing (null when the scrape had no fleet "
                 "block)")
    elif fl is not None:
        if not isinstance(fl, dict):
            p.append("fleet is neither null nor an object")
        else:
            _check(p, isinstance(fl.get("status"), str),
                   "fleet.status missing or not a string")
            reps = fl.get("replicas")
            if not isinstance(reps, dict):
                p.append("fleet.replicas missing or not an object")
            else:
                for rid, r in reps.items():
                    if not isinstance(r, dict):
                        p.append(f"fleet.replicas.{rid} is not an "
                                 "object")
                        continue
                    _check(p, isinstance(r.get("state"), str),
                           f"fleet.replicas.{rid}.state missing")
                    _check(p, isinstance(r.get("findings"), list),
                           f"fleet.replicas.{rid}.findings missing "
                           "or not a list")
            if doc.get("schema") == OBS_SCHEMA_V3:
                for key in ("resurrections", "quarantined"):
                    v = fl.get(key, "missing")
                    _check(p, isinstance(v, int)
                           and not isinstance(v, bool) and v >= 0,
                           f"fleet.{key} missing or not a non-negative "
                           f"int (required at /3)")
                a = fl.get("autoscaler", "missing")
                if a == "missing":
                    p.append("fleet.autoscaler missing (required at "
                             "/3; null before the first resize)")
                elif a is not None and not isinstance(a, dict):
                    p.append("fleet.autoscaler is neither null nor an "
                             "object")
    fnd = doc.get("findings")
    if not isinstance(fnd, list):
        p.append("findings missing or not a list")
    else:
        for i, f in enumerate(fnd):
            _validate_finding(p, f, f"findings[{i}]")
    _validate_findings_summary(p, doc.get("findings_summary",
                                          "missing"),
                               "findings_summary",
                               missing_hint="the SentinelHub.summary "
                               "counts; required")
    return p


PARTBENCH_SCHEMA = "acg-tpu-partbench/1"


def validate_partbench_document(doc) -> list[str]:
    """Validate an ``acg-tpu-partbench/1`` wrapper (the preprocessing
    benchmark trajectory, scripts/bench_partition.py): a round index
    ``n`` plus a ``records`` list of ordinary bench records, each
    validated through :func:`validate_bench_record` so the perf gate
    can compare them like any other metric.

    Round 7 adds OPTIONAL fields — all absent in earlier rounds, which
    must keep validating: ``config.threads`` (the resolved
    ACG_NATIVE_THREADS count), ``config.rss_mode`` (how per-stage peak
    RSS was sampled), and per-record ``stage`` (which prep stage a
    ``prep-rss-*`` row measured) / ``reuse`` (the cache tier an
    incremental ``reprep-*`` round exercised)."""
    p: list[str] = []
    if not isinstance(doc, dict):
        return ["partbench document is not a JSON object"]
    _check(p, doc.get("schema") == PARTBENCH_SCHEMA,
           f"schema is {doc.get('schema')!r}, expected "
           f"{PARTBENCH_SCHEMA!r}")
    _check(p, isinstance(doc.get("n"), int), "n missing or not an int")
    cfg = doc.get("config")
    if cfg is not None:
        if not isinstance(cfg, dict):
            p.append("config is not a JSON object")
        else:
            if "threads" in cfg:
                _check(p, isinstance(cfg["threads"], int)
                       and cfg["threads"] >= 1,
                       "config.threads not a positive int")
            if "rss_mode" in cfg:
                _check(p, cfg["rss_mode"] in ("vmhwm", "ru_maxrss"),
                       f"config.rss_mode {cfg.get('rss_mode')!r} not "
                       "one of ('vmhwm', 'ru_maxrss')")
    recs = doc.get("records")
    if not isinstance(recs, list) or not recs:
        p.append("records missing, not a list, or empty")
        return p
    for i, rec in enumerate(recs):
        p += [f"records[{i}]: {msg}" for msg in validate_bench_record(rec)]
        if not isinstance(rec, dict):
            continue
        if "stage" in rec:
            _check(p, isinstance(rec["stage"], str)
                   and isinstance(rec.get("metric"), str)
                   and rec["stage"] in rec["metric"],
                   f"records[{i}]: stage not a substring of its metric")
        if "reuse" in rec:
            _check(p, rec["reuse"] in ("structure", "full"),
                   f"records[{i}]: reuse {rec.get('reuse')!r} not one "
                   "of ('structure', 'full')")
    return p


def validate_bench_record(rec) -> list[str]:
    """Validate a bench payload; returns a list of problems."""
    p: list[str] = []
    if not isinstance(rec, dict):
        return ["bench record is not a JSON object"]
    _check(p, isinstance(rec.get("metric"), str), "metric missing")
    v = rec.get("value", "missing")
    _check(p, v is None or _is_num(v), "value missing or not numeric")
    _check(p, isinstance(rec.get("unit"), str), "unit missing")
    if "vs_baseline" in rec:
        v = rec["vs_baseline"]
        _check(p, v is None or _is_num(v), "vs_baseline not numeric")
    if "psums_per_iter" in rec:
        # the collective-count model of the measured solver, recorded as
        # an exact rational ("2/1" classic, "1/1" pipelined, "1/s"
        # s-step) so the perf-gate trajectory can track the s-step
        # communication claim alongside the rates
        v = rec["psums_per_iter"]
        ok = (isinstance(v, str) and len(v.split("/")) == 2
              and all(x.isdigit() for x in v.split("/")))
        _check(p, ok, "psums_per_iter not an 'N/D' rational string")
    return p
