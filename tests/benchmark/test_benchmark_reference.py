"""The plain reference, the seeded right-hand sides, and the control that
the correctness check has to fail, at small sizes on the CPU."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from acg_tpu.sparse.poisson import poisson3d_7pt
from benchmark import reference, traffic
from benchmark.operators import poisson7

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def limit_of(config: str) -> float:
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        return json.load(f)["certified_relres"]


@pytest.mark.parametrize("grid", [(5, 5, 5), (4, 6, 7)])
def test_reference_matches_program_operator(grid):
    A = poisson3d_7pt(*grid, dtype=np.float64)
    x = np.random.default_rng(1).standard_normal(A.nrows)
    np.testing.assert_allclose(reference.poisson7_apply(x, grid),
                               A.matvec(x), rtol=0, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(reference.poisson7_apply_jnp(grid)(jnp.asarray(x))),
        A.matvec(x), rtol=0, atol=1e-12)


def test_true_relres_reads_zero_at_the_solution_and_one_at_zero():
    grid = (6, 6, 6)
    apply = lambda v: reference.poisson7_apply(v, grid)   # noqa: E731
    x = np.random.default_rng(2).standard_normal(216)
    b = apply(x)
    assert reference.true_relres(apply, b, x) < 1e-15
    assert reference.true_relres(apply, b, np.zeros(216)) == 1.0


@pytest.mark.parametrize("grid", [[6, 6, 6], [4, 6, 6]])
def test_transforms_commute_with_the_operator(grid):
    cfg = {"grid": grid}
    v = np.random.default_rng(3).standard_normal(int(np.prod(grid)))
    seen = set()
    for code in range(poisson7.transforms(cfg)):
        moved = poisson7.transform(v, cfg, code)
        np.testing.assert_allclose(
            reference.poisson7_apply(moved, tuple(grid)),
            poisson7.transform(reference.poisson7_apply(v, tuple(grid)),
                               cfg, code), atol=1e-12)
        seen.add(moved.tobytes())
    assert len(seen) == poisson7.transforms(cfg)


def test_rhs_set_same_seed_same_inputs_other_seed_same_norms():
    cfg = {"grid": [6, 6, 6]}
    a = traffic.rhs_set(poisson7, cfg, 2**31 + 7, 20)
    b = traffic.rhs_set(poisson7, cfg, 2**31 + 7, 20)
    c = traffic.rhs_set(poisson7, cfg, 2**31 + 8, 20)
    assert a.dtype == np.float32 and a.shape == (20, 216)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # one draw, moved by a symmetry: the same work for every seed
    np.testing.assert_allclose(np.linalg.norm(a, axis=1),
                               np.linalg.norm(c, axis=1), rtol=1e-6)


@pytest.mark.parametrize("config", ["poisson7-256", "poisson7-128"])
def test_control_one_precision_below_fails_the_limit(config):
    """The reference CG in bfloat16 in the program's place fails the
    configuration's limit; in float32 it meets it."""
    grid = (16, 16, 16)
    cfg = {"grid": list(grid)}
    apply_np = lambda v: reference.poisson7_apply(v, grid)   # noqa: E731
    apply_jnp = reference.poisson7_apply_jnp(grid)
    limit = limit_of(config)
    for b in traffic.rhs_set(poisson7, cfg, 11, 3):
        x, _ = reference.cg_reference(apply_jnp, b, 1e-5, 5000, jnp.bfloat16)
        assert reference.true_relres(apply_np, b, x) > 10 * limit
        x, _ = reference.cg_reference(apply_jnp, b, 1e-5, 5000, jnp.float32)
        assert reference.true_relres(apply_np, b, x) < limit
