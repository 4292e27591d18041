"""Microbenchmark the CG hot ops on the attached chip (dev tool)."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.ops.dia import DeviceDia, DiaMatrix, dia_matvec
from acg_tpu.ops.pallas_kernels import dia_matvec_pallas_2d
from acg_tpu.sparse import poisson3d_7pt

GRID = 128
REPS = 200

from acg_tpu.utils.backend import require_tpu

dev = require_tpu()[0]
print("device_kind:", dev.device_kind)

dtype = np.float32
A = poisson3d_7pt(GRID, dtype=dtype)
D = DiaMatrix.from_csr(A)
op = DeviceDia.from_dia(D, dtype=dtype, mat_dtype=None)  # full-width streams
n = op.nrows_padded
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal(n).astype(dtype))


def timeit(name, fn, *args, bytes_per_rep=None):
    f = jax.jit(fn)
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = f(*args)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    per = dt / REPS
    bw = (bytes_per_rep / per / 1e9) if bytes_per_rep else 0.0
    print(f"{name:34s} {per*1e6:9.1f} us/rep   {bw:8.1f} GB/s")
    return per


B = dtype().itemsize

# pure streaming: y = a*x + y  (read 2n, write n)
def axpy_loop(x, y):
    def body(i, c):
        x, y = c
        return x, y + 1.001 * x
    return jax.lax.fori_loop(0, REPS, body, (x, y))[1]

timeit("axpy (3n streams)", axpy_loop, x, jnp.zeros_like(x),
       bytes_per_rep=3 * n * B)

# copy: read n write n
def copy_loop(x):
    def body(i, y):
        return y * 1.0000001
    return jax.lax.fori_loop(0, REPS, body, x)

timeit("scale in-place (2n streams)", copy_loop, x, bytes_per_rep=2 * n * B)

# dot
def dot_loop(x, y):
    def body(i, acc):
        return acc + jnp.vdot(x, y + acc * 0)
    return jax.lax.fori_loop(0, REPS, body, jnp.asarray(0.0, dtype))

timeit("vdot (2n reads)", dot_loop, x, x * 0.5, bytes_per_rep=2 * n * B)

# SpMV XLA
def spmv_loop(bands, x):
    def body(i, y):
        return dia_matvec(bands, op.offsets, y) * 1e-3
    return jax.lax.fori_loop(0, REPS, body, x)

timeit("DIA SpMV xla (9n model)", spmv_loop, op.bands, x,
       bytes_per_rep=9 * n * B)

# SpMV pallas
def spmv_pl_loop(bands, x):
    def body(i, y):
        return dia_matvec_pallas_2d(bands, op.offsets, y) * 1e-3
    return jax.lax.fori_loop(0, REPS, body, x)

try:
    timeit("DIA SpMV pallas (9n model)", spmv_pl_loop, op.bands, x,
           bytes_per_rep=9 * n * B)
except Exception as e:
    print("pallas spmv FAILED:", repr(e))

# one full classic CG iteration body (as in loops.cg_while)
def cg_iter_loop(bands, x0, r0, p0):
    def body(i, c):
        x, r, p, rr = c
        t = dia_matvec(bands, op.offsets, p)
        ptap = jnp.vdot(p, t)
        alpha = rr / ptap
        x = x + alpha * p
        r = r - alpha * t
        rr_new = jnp.vdot(r, r)
        beta = rr_new / rr
        p = r + beta * p
        return (x, r, p, rr_new)
    return jax.lax.fori_loop(0, REPS, body,
                             (x0, r0, p0, jnp.vdot(r0, r0)))

timeit("classic CG iter (88n model)", cg_iter_loop, op.bands, x,
       x * 0.5, x * 0.25, bytes_per_rep=88 * n // 4 * B)
