"""Plain float64 NumPy reference of HPCG's multigrid-preconditioned CG.

Follows the HPCG reference code (``ComputeMG_ref``,
``ComputeSYMGS_ref``, ``ComputeRestriction_ref``,
``ComputeProlongation_ref``, ``CG_ref``) and imports nothing of the
device code: a stencil is given as its ``arms``, ``(digits,
coefficient)`` pairs with digits in {-1, 0, 1}^3, on a 3-D grid with
Dirichlet truncation (unknowns in C order).

- :func:`vcycle_host`: one V-cycle over ``levels`` grids, each halved
  in every dimension and carrying the same stencil; on each level but
  the coarsest a symmetric Gauss-Seidel sweep from zero, ``A x``, the
  residual injected at ``[2i, 2j, 2k]``, the coarse V-cycle, its
  correction added back at ``[2i, 2j, 2k]``, and a second sweep; on the
  coarsest one sweep from zero.
- :func:`symgs_host`: the 8-colour symmetric sweep the device runs
  (colour = parity of (i, j, k), forward over colours 0..7 in
  ``itertools.product`` order, then backward).
- :func:`symgs_host_lexicographic`: the spec's own row order, by sparse
  triangular solves, for the record.
- :func:`pcg_host`: preconditioned CG with the device loop's stopping
  rule (``|r|^2 < rtol^2 |r0|^2``).
"""

from __future__ import annotations

import functools
import itertools

import numpy as np

COLOURS = tuple(itertools.product((0, 1), repeat=3))
CENTRE = (0, 0, 0)


def _view(xp: np.ndarray, d, start=(0, 0, 0), step: int = 1,
          shape=None) -> np.ndarray:
    """The neighbours along arm ``d`` of the points ``start + step * I``
    of the grid, read from ``xp`` (the grid zero-padded by one)."""
    shape = xp.shape if shape is None else shape
    return xp[tuple(slice(1 + s + g, 1 + n + g, step)
                    for s, g, n in zip(start, d, shape))]


def stencil_apply(x: np.ndarray, arms) -> np.ndarray:
    """``A x`` for the 3-D array ``x``."""
    xp = np.pad(np.asarray(x, np.float64), 1)
    y = np.zeros(x.shape)
    for d, c in arms:
        y += c * _view(xp, d, shape=x.shape)
    return y


def symgs_host(x: np.ndarray, r: np.ndarray, arms) -> np.ndarray:
    """One symmetric 8-colour Gauss-Seidel sweep on ``A x = r`` from
    ``x``; returns the new x."""
    diag = dict(arms)[CENTRE]
    xp = np.pad(np.asarray(x, np.float64), 1)
    shape = x.shape
    inner = tuple(slice(1, 1 + n) for n in shape)
    for colour in COLOURS + COLOURS[::-1]:
        off = sum(c * _view(xp, d, colour, 2, shape)
                  for d, c in arms if d != CENTRE)
        rc = r[tuple(slice(a, None, 2) for a in colour)]
        xp[inner][tuple(slice(a, None, 2) for a in colour)] = \
            (rc - off) / diag
    return xp[inner].copy()


@functools.lru_cache(maxsize=8)
def _triangles(shape: tuple, arms: tuple):
    """(D + L, D + U, L, U) of the stencil on ``shape`` as CSR."""
    import scipy.sparse as sp

    n = int(np.prod(shape))
    coords = np.unravel_index(np.arange(n), shape)
    strides = (shape[1] * shape[2], shape[2], 1)
    rows, cols, vals = [], [], []
    for d, c in arms:
        ok = np.ones(n, bool)
        for ax, g in enumerate(d):
            ok &= (coords[ax] + g >= 0) & (coords[ax] + g < shape[ax])
        i = np.nonzero(ok)[0]
        rows.append(i)
        cols.append(i + sum(g * s for g, s in zip(d, strides)))
        vals.append(np.full(len(i), c))
    A = sp.csr_matrix((np.concatenate(vals),
                       (np.concatenate(rows), np.concatenate(cols))),
                      shape=(n, n))
    L, U = sp.tril(A, -1, "csr"), sp.triu(A, 1, "csr")
    D = sp.diags(A.diagonal())
    return (D + L).tocsr(), (D + U).tocsr(), L, U


def symgs_host_lexicographic(x: np.ndarray, r: np.ndarray,
                             arms) -> np.ndarray:
    """HPCG's ``ComputeSYMGS_ref``: forward then backward Gauss-Seidel
    in lexicographic row order."""
    from scipy.sparse.linalg import spsolve_triangular

    DL, DU, L, U = _triangles(tuple(x.shape), tuple(arms))
    v, b = x.reshape(-1).astype(np.float64), r.reshape(-1)
    v = spsolve_triangular(DL, b - U @ v, lower=True)
    v = spsolve_triangular(DU, b - L @ v, lower=False)
    return v.reshape(x.shape)


def vcycle_host(r: np.ndarray, arms, levels: int,
                symgs=symgs_host) -> np.ndarray:
    """z = M r for the 3-D ``r`` (float64)."""
    r = np.asarray(r, np.float64)
    x = symgs(np.zeros_like(r), r, arms)
    if levels == 1:
        return x
    inj = (slice(None, None, 2),) * 3
    rc = (r - stencil_apply(x, arms))[inj]
    x[inj] += vcycle_host(rc, arms, levels - 1, symgs)
    return symgs(x, r, arms)


def pcg_host(apply, precond, b: np.ndarray, rtol: float, maxits: int):
    """Preconditioned CG from x0 = 0; ``apply`` and ``precond`` map flat
    float64 vectors.  Returns ``(x, iterations)``."""
    b = np.asarray(b, np.float64)
    x = np.zeros_like(b)
    r = b.copy()
    rr0 = rr = r @ r
    z = precond(r)
    rz = r @ z
    p = z.copy()
    k = 0
    while k < maxits and not rr < rtol ** 2 * rr0:
        t = apply(p)
        alpha = rz / (p @ t)
        x += alpha * p
        r -= alpha * t
        rr = r @ r
        z = precond(r)
        rz, rz_old = r @ z, rz
        p = z + (rz / rz_old) * p
        k += 1
    return x, k
