"""Operator ``poisson7``: the 3-D 7-point Laplacian of a configuration's
``grid``.

The program's matrices come from the program's own generators (they
are part of the system under test); the reference that judges its
answers is :mod:`benchmark.reference`, which imports nothing of the
program.
"""

from __future__ import annotations

import itertools

import numpy as np

from benchmark import reference


def program_matrix(cfg: dict, form: str):
    """The host matrix the program is given: ``"dia"`` bands (the
    single-chip path) or ``"csr"`` (the partitioned path)."""
    from acg_tpu.sparse.poisson import poisson3d_7pt, poisson3d_7pt_dia

    dtype = np.dtype(cfg["dtype"])
    if form == "dia":
        return poisson3d_7pt_dia(*cfg["grid"], dtype=dtype)
    if form == "csr":
        return poisson3d_7pt(*cfg["grid"], dtype=dtype)
    raise ValueError(f"poisson7 has no {form!r} form (dia, csr)")


def program_partition(cfg: dict, parts) -> np.ndarray:
    """Part id of every unknown for a block grid of ``parts`` (z-slabs
    for ``[4, 1, 1]``), from the program's exact grid partitioner."""
    from acg_tpu.sparse.poisson import grid_partition_vector

    return grid_partition_vector(tuple(cfg["grid"]), tuple(parts))


def _axis_orders(grid) -> list:
    """The axis permutations that map the grid onto itself."""
    return [p for p in itertools.permutations(range(3))
            if all(grid[p[i]] == grid[i] for i in range(3))]


def transforms(cfg: dict) -> int:
    """How many symmetries :func:`transform` knows: axis permutations
    that keep the grid, times the 8 reflections, times the sign."""
    return len(_axis_orders(cfg["grid"])) * 16


def transform(v, cfg: dict, code: int) -> np.ndarray:
    """Symmetry ``code`` of the operator applied to the flat vector v.
    The Laplacian commutes with each of them, so a solve of the moved
    right-hand side does the same work as one of the original."""
    grid = tuple(cfg["grid"])
    sign, code = code % 2, code // 2
    flips, order = code % 8, code // 8
    g = np.transpose(np.asarray(v).reshape(grid), _axis_orders(grid)[order])
    g = np.flip(g, axis=[a for a in range(3) if flips >> a & 1])
    return (-g if sign else g).reshape(-1)


def reference_apply(cfg: dict):
    grid = tuple(cfg["grid"])
    return lambda x: reference.poisson7_apply(x, grid)


def reference_apply_jnp(cfg: dict):
    return reference.poisson7_apply_jnp(tuple(cfg["grid"]))
