"""Device SpMV for the padded ELL layout.

The XLA formulation: a (rows, width) gather of x by column index, an
elementwise multiply, and a width-axis reduction.  XLA fuses this into one
pass over the operator (vals + colidx streamed once from HBM, x gathered),
which is the TPU-native replacement for the reference's merge-based
load-balanced CSR kernel (reference acg/cg-kernels-cuda.cu:340-441
``csrgemv_merge``) — the load balancing already happened on the host when
rows were padded to a rectangle (see acg_tpu/sparse/ell.py).

A Pallas kernel for the same contract lives in acg_tpu/ops/pallas_spmv.py,
out of automatic routing since PR 21 (Mosaic on TPU v5e refuses its 1-D
vector gather; it runs only when forced, ``ell_matvec_forced``); the
unstructured tier on the chip is sgell (acg_tpu/ops/sgell.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceEll:
    """Device-resident ELL operator (analog of the device CSR uploaded at
    solver init, reference acg/cgcuda.c:259-308).

    ``vals``/``colidx`` have shape (nrows_padded, width); padding lanes have
    value 0 and column 0, so matvec needs no masking.
    """

    vals: jax.Array
    colidx: jax.Array
    nrows: int = dataclasses.field(metadata=dict(static=True), default=0)
    ncols: int = dataclasses.field(metadata=dict(static=True), default=0)
    nnz: int = dataclasses.field(metadata=dict(static=True), default=0)
    vec_dtype: str = dataclasses.field(metadata=dict(static=True),
                                       default="float32")

    @classmethod
    def from_ell(cls, E, dtype=None, mat_dtype="auto") -> "DeviceEll":
        from acg_tpu.ops.dia import resolve_mat_dtype

        vdt = np.dtype(dtype if dtype is not None else E.vals.dtype)
        mdt = resolve_mat_dtype(E.vals, mat_dtype, vdt)
        host = E.vals if E.vals.dtype == vdt else E.vals.astype(vdt)
        host = host.astype(np.dtype(mdt)) if np.dtype(mdt) != vdt else host
        vals = jnp.asarray(host)
        return cls(vals=vals, colidx=jnp.asarray(E.colidx),
                   nrows=E.nrows, ncols=E.ncols, nnz=E.nnz,
                   vec_dtype=np.dtype(vdt).name)

    @property
    def mat_itemsize(self) -> int:
        return self.vals.dtype.itemsize

    def operator_stream_bytes(self) -> int:
        """Per-SpMV HBM bytes of the operator stream: the padded
        value rectangle at its storage width plus the column-index
        rectangle (the index traffic DIA avoids) — charged once per
        iteration by the roofline model (acg_tpu/obs/roofline.py)."""
        return (int(self.vals.size) * self.mat_itemsize
                + int(self.colidx.size) * self.colidx.dtype.itemsize)

    @property
    def nrows_padded(self) -> int:
        return self.vals.shape[0]

    @property
    def width(self) -> int:
        return self.vals.shape[1]

    def matvec(self, x: jax.Array) -> jax.Array:
        return ell_matvec(self.vals, self.colidx, x)


def ell_matvec(vals: jax.Array, colidx: jax.Array, x: jax.Array) -> jax.Array:
    """y[i] = sum_l vals[i,l] * x[colidx[i,l]].

    ``x`` is ``(n,)`` or batched ``(B, n)`` (multi-RHS: one pass over
    vals/colidx serves every system; the gather broadcasts over the
    leading axis).  ``x`` must have length >= nrows_padded when the
    operator is square and padded (callers pad x with zeros to the padded
    row count so y and x are shape-compatible for the CG vector updates).
    Narrow-stored vals (mixed-precision operator, see acg_tpu/ops/dia.py)
    upcast in-register.
    """
    # the ELL tier IS the gather formulation — the one place a hot-loop
    # gather is the design, priced by the tier economics (ops/dia.py)
    return jnp.sum(vals.astype(x.dtype) * x[..., colidx],  # acg: allow-gather
                   axis=-1)


def pad_vector(x: np.ndarray, nrows_padded: int):
    """Zero-pad a host vector (last axis; a leading batch axis passes
    through) to the operator's padded row count.  The pad region stays
    identically zero through CG (all-zero padded rows), so reductions
    need no mask on a single chip."""
    x = np.asarray(x)
    if x.shape[-1] == nrows_padded:
        return x
    out = np.zeros(x.shape[:-1] + (nrows_padded,), dtype=x.dtype)
    out[..., : x.shape[-1]] = x
    return out
