"""Operator ``hpcg27``: HPCG's 27-point operator on a configuration's
``grid``: 26 on the diagonal and -1 for each of the 26 neighbours of the
3x3x3 box that lie inside the grid (Dirichlet boundaries).

The program's matrices come from the program's own generators; the
reference that judges its answers, :func:`reference_apply`, is this
file's own and imports nothing of the program: A = 27 I - B, where B is
the zero-padded 3x3x3 box sum, taken separably as three passes of
3-point sums.  The axis permutations that keep the grid and the
reflections commute with the box, as with the 7-point operator.
"""

from __future__ import annotations

import numpy as np

from benchmark.operators.poisson7 import (program_partition, transform,
                                          transforms)

__all__ = ["program_matrix", "program_partition", "transforms",
           "transform", "reference_apply", "reference_apply_jnp"]


def program_matrix(cfg: dict, form: str):
    """The host matrix the program is given: ``"dia"`` bands (the
    single-chip path, built in band form) or ``"csr"``."""
    from acg_tpu.sparse.poisson import poisson3d_27pt, poisson3d_27pt_dia

    dtype = np.dtype(cfg["dtype"])
    if form == "dia":
        return poisson3d_27pt_dia(*cfg["grid"], dtype=dtype)
    if form == "csr":
        return poisson3d_27pt(*cfg["grid"], dtype=dtype)
    raise ValueError(f"hpcg27 has no {form!r} form (dia, csr)")


def _box_sum(v, xp):
    """The 3x3x3 box sum of the 3-D array v with zero padding, in the
    array module ``xp`` (numpy or jax.numpy)."""
    for axis in range(3):
        n = v.shape[axis]
        pad = [(0, 0)] * 3
        pad[axis] = (1, 1)
        w = xp.moveaxis(xp.pad(v, pad), axis, 0)
        v = xp.moveaxis(w[:n] + w[1:n + 1] + w[2:], 0, axis)
    return v


def reference_apply(cfg: dict):
    """``A x`` in float64 for flat vectors."""
    grid = tuple(cfg["grid"])

    def apply(x):
        v = np.asarray(x, np.float64).reshape(grid)
        return (27.0 * v - _box_sum(v, np)).reshape(-1)
    return apply


def reference_apply_jnp(cfg: dict):
    """``A x`` in ``jax.numpy`` at the dtype of ``x`` (flat vectors)."""
    import jax.numpy as jnp

    grid = tuple(cfg["grid"])

    def apply(x):
        v = x.reshape(grid)
        return (27.0 * v - _box_sum(v, jnp)).reshape(-1).astype(x.dtype)
    return apply
