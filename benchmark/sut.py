"""The system under test: the program's entries, as a traffic mix names
them.

``"entry"`` in a traffic file picks one of:

- a single-chip solver (``cg``, ``cg_pipelined``, ``cg_sstep``) over the
  device operator that the program builds from the configuration's DIA
  bands (``fmt`` and ``mat_dtype`` from the configuration);
- a distributed solver (``cg_dist``, ``cg_pipelined_dist``,
  ``cg_sstep_dist``) over ``build_sharded`` with the configuration's
  CSR matrix, the block-grid partition ``parts`` and the ``halo`` tier;
- ``serve``: a ``SolverService`` over a ``Session``, with the traffic's
  ``service`` keyword arguments.

Every entry is built in set-up, its preparation timed apart (``prep_s``),
and driven through the same call the program's users make.  The
benchmark's host annotations (``bench.*``) wrap each call so that the
trace reduction can name what the host was doing in an idle gap.
"""

from __future__ import annotations

import importlib

import numpy as np
from jax.profiler import TraceAnnotation

from benchmark.traffic import Outcome, clock

SINGLE_CHIP = ("cg", "cg_pipelined", "cg_sstep")
DISTRIBUTED = ("cg_dist", "cg_pipelined_dist", "cg_sstep_dist")


def solver_options(cfg: dict, traffic: dict):
    from acg_tpu.config import SolverOptions

    return SolverOptions(maxits=cfg["maxits"], residual_rtol=cfg["rtol"],
                         **traffic.get("options", {}))


def _outcome_of(index: int, fn):
    """Run ``fn()`` -> SolveResult; an AcgError is a failed outcome."""
    from acg_tpu.errors import AcgError

    try:
        res = fn()
    except AcgError as e:
        return Outcome(index, 0.0, 0.0, 0.0, ok=False,
                       error=f"{e.status.name}: {e}")
    return Outcome(index, 0.0, 0.0, 0.0, ok=bool(res.converged),
                   x=res.x, niterations=int(res.niterations),
                   error=None if res.converged else "not converged")


def _block(obj) -> None:
    """Wait until every array that ``obj`` holds is on the device."""
    import jax

    jax.block_until_ready(jax.tree_util.tree_leaves(vars(obj)))


class SingleChipSolver:
    """A single-chip solver over a device operator built once."""

    def __init__(self, cfg: dict, traffic: dict, operator):
        cg_mod = importlib.import_module("acg_tpu.solvers.cg")

        self.fn = getattr(cg_mod, traffic["entry"])
        self.options = solver_options(cfg, traffic)
        self.dtype = np.dtype(cfg["dtype"])
        A = operator.program_matrix(cfg, "dia")
        self.n = A.nrows
        self.dev = cg_mod.build_device_operator(
            A, dtype=self.dtype, fmt=cfg["fmt"], mat_dtype=cfg["mat_dtype"])
        # the program's padded layout, reused for every upload (the
        # upload is waited for before the buffer is written again)
        self.staging = np.zeros(self.dev.nrows_padded, dtype=self.dtype)

    def ready(self) -> None:
        _block(self.dev)

    def solve(self, index: int, b) -> Outcome:
        import jax

        with TraceAnnotation("bench.rhs_upload"):
            self.staging[:self.n] = b
            b_dev = jax.device_put(self.staging)
            b_dev.block_until_ready()
        with TraceAnnotation("bench.solve_call"):
            return _outcome_of(index, lambda: self.fn(
                self.dev, b_dev, options=self.options, dtype=self.dtype))

    def close(self) -> None:
        self.dev = None


class DistributedSolver:
    """A distributed solver over a sharded system built once."""

    def __init__(self, cfg: dict, traffic: dict, operator):
        from acg_tpu.config import HaloMethod
        dist_mod = importlib.import_module("acg_tpu.solvers.cg_dist")

        self.fn = getattr(dist_mod, traffic["entry"])
        self.options = solver_options(cfg, traffic)
        A = operator.program_matrix(cfg, "csr")
        part = operator.program_partition(cfg, traffic["parts"])
        self.ss = dist_mod.build_sharded(
            A, part=part, dtype=np.dtype(cfg["dtype"]),
            method=HaloMethod(traffic["halo"]), fmt=cfg["fmt"],
            mat_dtype=cfg["mat_dtype"])

    def ready(self) -> None:
        import jax

        jax.block_until_ready([a for a in self.ss.local_op_arrays()
                               if a is not None])

    def solve(self, index: int, b) -> Outcome:
        with TraceAnnotation("bench.solve_call"):
            return _outcome_of(index, lambda: self.fn(
                self.ss, b, options=self.options))

    def close(self) -> None:
        self.ss = None


class Service:
    """A SolverService over a Session of the configuration's operator."""

    def __init__(self, cfg: dict, traffic: dict, operator):
        from acg_tpu.serve.service import SolverService
        from acg_tpu.serve.session import Session

        kw = dict(traffic["service"])
        self.max_batch = int(kw.get("max_batch", 8))
        self.session = Session(operator.program_matrix(cfg, "dia"),
                               dtype=np.dtype(cfg["dtype"]),
                               fmt=cfg["fmt"], mat_dtype=cfg["mat_dtype"],
                               options=solver_options(cfg, traffic),
                               prep_cache=None, nrhs=self.max_batch)
        self.service = SolverService(self.session, **kw)

    def ready(self) -> None:
        _block(self.session.operator)

    def warm(self, rhs) -> None:
        """Send one batch of every bucket the queue can dispatch through
        the service: the batch fills on the waiter's first response call,
        or at once when it reaches ``max_batch``."""
        for bucket in self.service.queue.policy.buckets:
            reqs = [self.service.submit(rhs[i % len(rhs)])
                    for i in range(bucket)]
            for r in reqs:
                r.response()

    def request(self, index: int, b, wait_until: float) -> Outcome:
        with TraceAnnotation("bench.submit"):
            req = self.service.submit(b)
        with TraceAnnotation("bench.response_wait"):
            resp = req.response(timeout=max(0.0, wait_until - clock()))
        res = resp.result
        ok = bool(resp.ok and resp.status == "SUCCESS" and res is not None)
        return Outcome(index, 0.0, 0.0, 0.0, ok=ok,
                       x=None if res is None else res.x,
                       niterations=0 if res is None else int(res.niterations),
                       error=None if ok else f"{resp.status}: {resp.error}",
                       extra={"queue_wait": float(resp.queue_wait),
                              "batch_size": int(resp.batch_size),
                              "bucket": int(resp.bucket)})

    def close(self) -> None:
        self.service.close()
        self.session.close()


def build(cfg: dict, traffic: dict, operator):
    entry = traffic["entry"]
    if entry in SINGLE_CHIP:
        return SingleChipSolver(cfg, traffic, operator)
    if entry in DISTRIBUTED:
        return DistributedSolver(cfg, traffic, operator)
    if entry == "serve":
        return Service(cfg, traffic, operator)
    raise ValueError(f"unknown entry {entry!r}: one of "
                     f"{SINGLE_CHIP + DISTRIBUTED} or serve")

