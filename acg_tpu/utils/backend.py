"""Backend selection for the entry points: the chip, or the CPU test mesh.

One process holds the chip.  Entry points that measure on it call
:func:`require_tpu` and fail when JAX finds no TPU; nothing falls back
to the CPU.  Tests and dry runs pin the virtual CPU mesh with
:func:`force_cpu_mesh`.  :func:`enable_compile_cache` places JAX's
persistent compilation cache before the first compile.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class NoTpuError(RuntimeError):
    """JAX found no TPU where an entry point needs one."""


def require_tpu():
    """``jax.devices()`` when they are TPUs; else raise NoTpuError."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoTpuError(
            f"this entry point measures on a TPU, but JAX's devices are "
            f"{devs[0].platform} ({len(devs)} x {devs[0].device_kind})")
    return devs


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else at the fixed
    ``<repo>/.jax_cache``; return the directory.  Call before the first
    compile.  The path is never derived from a pid, a time or a temporary
    name: the directory is part of the cache key."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_cpu_mesh(n: int = 8) -> None:
    """Pin JAX to an ``n``-device virtual CPU mesh (tests, fuzzing, dry
    runs).  Call before the first backend use: XLA_FLAGS is read when
    the CPU client initializes."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def f64_report() -> dict:
    """Does float64 on the default device keep float64 precision?  Three
    checks: 1e-60 (below float32's range) survives a computed round trip,
    1 + 2**-40 stays above 1, and a float64 dot of 4096 terms agrees with
    NumPy's to 1e-12 of its magnitude."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    with jax.enable_x64(True):
        one = jnp.asarray(1.0, jnp.float64)
        tiny = float(jax.jit(lambda v, s: v * s)(
            jnp.asarray(1e-60, jnp.float64), one))
        eps = float(jax.jit(lambda v, e: (v + e) - v)(
            one, jnp.asarray(2.0 ** -40, jnp.float64)))
        rng = np.random.default_rng(0)
        a, b = rng.standard_normal((2, 4096))
        got = float(jax.jit(jnp.dot)(jnp.asarray(a), jnp.asarray(b)))
    rel = abs(got - float(a @ b)) / float(np.abs(a) @ np.abs(b))
    return {"tiny_1e-60_kept": tiny == 1e-60,
            "eps_2^-40_kept": eps == 2.0 ** -40,
            "dot_rel_err": rel,
            "f64_ok": tiny == 1e-60 and eps == 2.0 ** -40 and rel < 1e-12}
