"""Pallas ELL SpMV: the gather-form kernel for unstructured operators.

The reference's crown-jewel kernel for these matrices is the merge-based
load-balanced CSR SpMV (reference acg/cg-kernels-cuda.cu:340-441
``csrgemv_merge``: binary-searched row starts, shared-memory staging, warp
row reduction).  On TPU the load balancing already happened on the host —
rows are padded to a rectangle (acg_tpu/sparse/ell.py) — so the kernel's
only job is streaming vals/colidx once and gathering x.  This kernel keeps
the whole padded x resident in VMEM and processes one (tile, W) block of
vals/colidx per grid step, accumulating the width-axis reduction
in-register.

Whether the in-kernel gather beats XLA's fused gather formulation
(acg_tpu/ops/spmv.py ``ell_matvec``) is an empirical, chip-generation
question: Mosaic's VMEM gather support is the limiting factor.  The kernel
is probe-gated (compile-and-match once per process, group "ell" —
acg_tpu/ops/pallas_kernels.py) and OUT of automatic routing: on TPU v5e
Mosaic refuses its 1-D vector gather ("Only 2D gather is supported", PR
21), so ``DeviceEll.matvec`` runs the XLA path and the kernel runs only
through :func:`ell_matvec_forced`, which raises where the probe failed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from acg_tpu.ops.pallas_kernels import _VMEM_BUDGET, pallas_call


def _ell_kernel(x_ref, vals_ref, cols_ref, y_ref):
    """One grid step = one (tile, W) block of rows.

    ``x_ref``: full padded x in VMEM, shape (1, n).  ``vals_ref`` may be a
    narrow storage dtype (bf16; upcast in-register).  The gather
    ``x[cols]`` is expressed as a 2D fancy index — Mosaic lowers it to
    vector gathers where the generation supports them; the probe rejects
    the kernel otherwise."""
    cols = cols_ref[:, :]
    xg = x_ref[0, :][cols]                      # (tile, W) gather of x
    v = vals_ref[:, :].astype(y_ref.dtype)
    y_ref[:, :] = jnp.sum(v * xg, axis=1, keepdims=False).reshape(
        y_ref.shape)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def ell_matvec_pallas(vals, colidx, x, tile: int = 512,
                      interpret: bool = False):
    """y = ELL(vals, colidx) @ x via one Pallas kernel.

    ``vals``/``colidx``: (n_pad, W); ``x``: (n_pad,) with n_pad a multiple
    of ``tile``.  Returns (n_pad,).  Same contract as
    acg_tpu.ops.spmv.ell_matvec (colidx pad lanes point at column 0 with
    value 0)."""
    n, W = vals.shape
    assert n % tile == 0, "n_pad must be a multiple of the tile size"
    xp = x.reshape(1, n)
    y = pallas_call(
        _ell_kernel,
        name="acg_ell",
        out_shape=jax.ShapeDtypeStruct((n, 1), x.dtype),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, W), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, W), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(xp, vals, colidx)
    return y.reshape(n)


def pallas_ell_fits(n: int, width: int, vec_dtype, mat_dtype,
                    tile: int) -> bool:
    """VMEM bound for the resident-x ELL kernel: full x + double-buffered
    (tile, W) val/col blocks + y tiles; f64 unsupported by Mosaic."""
    vb = np.dtype(vec_dtype).itemsize
    mb = np.dtype(mat_dtype).itemsize
    if vb > 4 or mb > 4:
        return False
    tile_bytes = tile * width * (mb + 4) + tile * vb
    return n * vb + 2 * tile_bytes <= _VMEM_BUDGET


_ELL_TILES = (1024, 512, 256, 128)      # every tile the probe validates


def _pick_ell_tile(n: int) -> int | None:
    # floor at 128: smaller tiles violate Mosaic sublane tiling for narrow
    # storage dtypes and are never faster than the XLA fallback anyway.
    # Only tiles from _ELL_TILES may be returned — the probe compiles each
    # of them, so a probe pass guarantees the selected shape compiles.
    for t in _ELL_TILES:
        if n % t == 0:
            return t
    return None


def ell_matvec_forced(vals, colidx, x, interpret: bool = False):
    """y = ELL(vals, colidx) @ x through the Pallas kernel, forced.

    The kernel is out of automatic routing (pallas_kernels.OUT_OF_AUTO:
    Mosaic on TPU v5e refuses its 1-D vector gather; ``DeviceEll.matvec``
    always runs the XLA gather formulation).  A forced call raises
    ERR_NOT_SUPPORTED where the "ell" probe failed or the shape does not
    fit, rather than quietly running another path.  ``interpret`` skips
    the probe (CPU tests run the interpret kernel)."""
    from acg_tpu.errors import AcgError, Status
    from acg_tpu.ops.pallas_kernels import (pallas_spmv_available,
                                            probe_errors)

    n, W = vals.shape
    tile = _pick_ell_tile(n)
    if (tile is None or x.ndim != 1 or x.shape[0] != n
            or not pallas_ell_fits(n, W, x.dtype, vals.dtype, tile)):
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       f"the Pallas ELL kernel does not fit this shape "
                       f"(n={n}, W={W}, x {x.shape} {x.dtype})")
    if not interpret and not pallas_spmv_available("ell"):
        raise AcgError(Status.ERR_NOT_SUPPORTED,
                       "the Pallas ELL kernel was forced but its probe "
                       f"failed: {probe_errors().get('ell')}")
    return ell_matvec_pallas(vals, colidx, x, tile=tile,
                             interpret=interpret)
