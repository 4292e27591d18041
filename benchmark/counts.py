"""Work counts computed from a configuration, never from the program.

A kernel change in the program cannot move these: they depend only on
the configuration's grid and vector dtype.
"""

from __future__ import annotations

import math

import numpy as np


def unknowns(cfg: dict) -> int:
    return math.prod(cfg["grid"])


def cg_iteration_min_bytes(cfg: dict) -> int:
    """Least HBM traffic of one CG iteration: the residual r and the
    search direction p, each read and written once at the vector dtype
    (16 bytes per unknown in float32).  Every classic or pipelined CG
    iteration updates both, and on a grid whose vectors do not fit in
    VMEM each update passes through HBM."""
    return 4 * np.dtype(cfg["dtype"]).itemsize * unknowns(cfg)
