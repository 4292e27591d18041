"""The plain reference for the 7-point 3-D Laplacian, independent of acg_tpu.

The operator is the finite-difference Laplacian on an ``nx x ny x nz``
grid with Dirichlet boundaries outside it: 6 on the diagonal, -1 for each
of the six neighbours that lie inside the grid.  Unknowns are numbered in
C order over ``(x, y, z)``, z fastest.

- :func:`poisson7_apply` applies it in float64 NumPy; :func:`true_relres`
  gives ``|b - A x| / |b|`` with it.  This decides ``correct``.
- :func:`poisson7_apply_jnp` and :func:`cg_reference` are plain CG in
  ``jax.numpy`` at a chosen dtype: computed one precision below the
  configuration's (bfloat16 for float32), it is the control that the
  comparison has to fail.
"""

from __future__ import annotations

import numpy as np


def poisson7_apply(x, grid) -> np.ndarray:
    """``A x`` in float64 for the flat vector ``x`` on ``grid``."""
    x = np.asarray(x, np.float64).reshape(grid)
    y = 6.0 * x
    y[1:] -= x[:-1]
    y[:-1] -= x[1:]
    y[:, 1:] -= x[:, :-1]
    y[:, :-1] -= x[:, 1:]
    y[:, :, 1:] -= x[:, :, :-1]
    y[:, :, :-1] -= x[:, :, 1:]
    return y.reshape(-1)


def true_relres(apply, b, x) -> float:
    """``|b - apply(x)| / |b|`` in float64 (``apply`` maps float64 to
    float64)."""
    b64 = np.asarray(b, np.float64)
    r = b64 - apply(x)
    return float(np.linalg.norm(r) / np.linalg.norm(b64))


def poisson7_apply_jnp(grid):
    """``A x`` in ``jax.numpy`` at the dtype of ``x`` (flat vectors)."""
    import jax.numpy as jnp

    def apply(x):
        v = x.reshape(grid)
        y = 6.0 * v
        for axis in range(3):
            lo = [slice(None)] * 3
            hi = [slice(None)] * 3
            lo[axis] = slice(None, -1)
            hi[axis] = slice(1, None)
            before = [(0, 0)] * 3
            after = [(0, 0)] * 3
            before[axis] = (1, 0)
            after[axis] = (0, 1)
            y = y - jnp.pad(v[tuple(lo)], before)   # neighbour at i - 1
            y = y - jnp.pad(v[tuple(hi)], after)    # neighbour at i + 1
        return y.reshape(-1).astype(x.dtype)

    return apply


def cg_reference(apply, b, rtol: float, maxits: int, dtype):
    """Textbook CG from x0 = 0 with every vector and scalar in ``dtype``;
    stops when the recurred residual norm falls under ``rtol * |b|`` or
    after ``maxits`` iterations.  Returns ``(x, iterations)`` on the
    host, x as float64."""
    import jax
    import jax.numpy as jnp

    def solve(b):
        x = jnp.zeros_like(b)
        r = b
        p = b
        rr = jnp.vdot(r, r)
        stop = (rtol ** 2) * rr

        def cond(c):
            _x, _r, _p, rr, k = c
            return (rr > stop) & (k < maxits)

        def body(c):
            x, r, p, rr, k = c
            q = apply(p)
            alpha = rr / jnp.vdot(p, q)
            x = x + alpha * p
            r = r - alpha * q
            rr_new = jnp.vdot(r, r)
            p = r + (rr_new / rr) * p
            return x, r, p, rr_new, k + 1

        x, _r, _p, _rr, k = jax.lax.while_loop(
            cond, body, (x, r, p, rr, jnp.asarray(0, jnp.int32)))
        return x, k

    x, k = jax.jit(solve)(jnp.asarray(np.asarray(b), dtype=dtype))
    return np.asarray(x, np.float64), int(k)
