"""Test configuration: an 8-device virtual CPU mesh and fp64.

The JAX analog of the reference's oversubscribed ``mpirun -np N`` testing
(SURVEY §4.4): multi-device code paths are exercised on one host via
``--xla_force_host_platform_device_count`` (BASELINE.md milestone configs).
fp64 is enabled so the host/CPU paths match the reference's double precision.
The persistent compilation cache is off: tests leave no cache behind.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from acg_tpu.utils.backend import force_cpu_mesh  # noqa: E402

force_cpu_mesh(8)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
jax.config.update("jax_enable_compilation_cache", False)
