"""Work counts of HPCG's multigrid-preconditioned CG, computed from a
configuration (its grid and vector dtype) and the V-cycle's level count,
never from the program.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.counts import cg_iteration_min_bytes


def mg_pcg_iteration_min_bytes(cfg: dict, levels: int) -> int:
    """Least HBM traffic of one MG-preconditioned CG iteration whose
    V-cycle has ``levels`` grids (the traffic's ``mg_levels``; HPCG's
    is 4), each halving every dimension, at the vector itemsize s; the
    operator is constant-coefficient, so no band is read:

    - the plain CG floor (``counts.cg_iteration_min_bytes``, 4 s per
      unknown: r and p each read and written) and s more for reading
      z = M r into the direction update p = z + beta p;
    - on each level of n / 8^l unknowns but the coarsest: 4 symmetric
      Gauss-Seidel half-sweeps (two before the coarse correction, two
      after), each reading r and x and writing x, 3 s; and 4 s for the
      residual (read x and r) and the prolongation (read and write x);
    - on the coarsest level: 2 half-sweeps of 3 s.

    At 256^3 in float32 and 4 levels that is about 93 bytes per unknown."""
    s = np.dtype(cfg["dtype"]).itemsize
    grids = [[n >> lvl for n in cfg["grid"]] for lvl in range(levels)]
    vcycle = sum((4 * 3 * s + 4 * s) * math.prod(g) for g in grids[:-1])
    vcycle += 2 * 3 * s * math.prod(grids[-1])
    return cg_iteration_min_bytes(cfg) + s * math.prod(cfg["grid"]) + vcycle
