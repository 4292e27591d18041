"""Whole runs with the timed path broken underneath must come out not
correct: each fault that a cell can have, planted in the program at a
small size on the CPU (the harness's look for a chip is left out)."""

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness

from benchmark_roots import make_root

# the package re-exports these functions under the modules' own names
sharded = importlib.import_module("acg_tpu.parallel.sharded")
session = importlib.import_module("acg_tpu.serve.session")
cg_mod = importlib.import_module("acg_tpu.solvers.cg")
dist_mod = importlib.import_module("acg_tpu.solvers.cg_dist")
ENTRY = {"p7-256-cg": (cg_mod, "cg"),
         "p7-256-pipelined": (cg_mod, "cg_pipelined"),
         "p7-128-cg-4chip": (dist_mod, "cg_dist")}


@pytest.fixture(scope="module")
def busy_root(tmp_path_factory):
    """A tiny root whose served mix is fast enough to coalesce batches."""
    return make_root(tmp_path_factory.mktemp("faults"), rate_per_s=1000.0)


def broken_result(res, fault: str):
    """The result as a faulty step would leave it."""
    x = np.array(res.x, copy=True)
    if fault == "unchanged":              # x never left x0 = 0
        x[...] = 0.0
    elif fault == "altered":              # an answer changed where made
        x *= 1.001
    elif fault == "half_batch" and x.ndim == 2:
        x[x.shape[0] // 2:] = 0.0         # half the batch left out
    res.x = x
    return res


def plant(monkeypatch, workload: str, fault: str) -> None:
    if fault == "exchange":
        orig = sharded.ShardedSystem.shard_halo_fn

        def no_exchange(self, wire="f32"):
            halo = orig(self, wire)
            return lambda *a: jnp.zeros_like(halo(*a))

        monkeypatch.setattr(sharded.ShardedSystem, "shard_halo_fn",
                            no_exchange)
    elif workload == "p7-128-serve":
        orig = session.Session.solve
        monkeypatch.setattr(
            session.Session, "solve",
            lambda self, b, **kw: broken_result(orig(self, b, **kw), fault))
    else:
        mod, name = ENTRY[workload]
        orig = getattr(mod, name)
        monkeypatch.setattr(
            mod, name, lambda *a, **kw: broken_result(orig(*a, **kw), fault))


def run(root, workload: str):
    cell = harness.find_cell(workload, root=root)
    return harness.run_cell(cell, 2**31 + 77, 0.25, False, time.perf_counter(),
                            jax.devices(), log=None)


@pytest.mark.parametrize("workload,fault", [
    ("p7-256-cg", "unchanged"), ("p7-256-cg", "altered"),
    ("p7-256-pipelined", "unchanged"), ("p7-256-pipelined", "altered"),
    ("p7-128-cg-4chip", "unchanged"), ("p7-128-cg-4chip", "exchange"),
    ("p7-128-cg-4chip", "altered"),
    ("p7-128-serve", "unchanged"), ("p7-128-serve", "half_batch"),
    ("p7-128-serve", "altered"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, busy_root,
                                            workload, fault):
    plant(monkeypatch, workload, fault)
    line = run(busy_root, workload)
    assert line["correct"] is False
    chk = line["checks"]
    assert (chk["failed"]["value"] > 0
            or not chk["true_relres_max"]["value"]
            <= chk["true_relres_max"]["limit"])


def test_the_served_mix_coalesces_so_half_a_batch_can_be_lost(
        monkeypatch, busy_root):
    orig = session.Session.solve
    widths = []

    def spy(self, b, **kw):
        widths.append(np.shape(b)[0] if np.ndim(b) == 2 else 1)
        return orig(self, b, **kw)

    monkeypatch.setattr(session.Session, "solve", spy)
    assert run(busy_root, "p7-128-serve")["correct"] is True
    assert max(widths) > 1
