"""Multi-host (multi-process) bootstrap and data movement.

The reference scales across nodes with MPI: ``MPI_Init_thread`` at driver
entry (reference cuda/acg-cuda.c:891), rank-to-device binding
(:1014-1041), root-based scatter of submatrices (acg/graph.c:1731-1809)
and collective stats reduction (acg/cg.c:720).  The TPU-native equivalents:

- :func:`init_multihost` — ``jax.distributed.initialize``: one controller
  process per host, after which ``jax.devices()`` spans the whole slice
  and XLA collectives ride ICI within a slice and DCN across slices.
  This is the MPI_Init + NCCL/NVSHMEM-bootstrap analog
  (cuda/acg-cuda.c:1110-1139) collapsed into one call.
- :func:`make_global_array` — build a globally-sharded array where each
  process materializes ONLY its addressable shards
  (``jax.make_array_from_callback``).  This replaces the reference's
  root-based MPI scatter: instead of rank 0 sending submatrices, every
  host constructs its own shards from the (host-side, replicated or
  memory-mapped) partition description.
- :func:`gather_to_host` — fetch a sharded array to every process
  (``multihost_utils.process_allgather`` when multi-process), the analog
  of the collective solution write (cuda/acg-cuda.c:2388-2425).

Single-process behavior is identical (the callbacks see all shards), so
every code path here is exercised by the 8-device CPU-mesh tests.
"""

from __future__ import annotations

import jax
import numpy as np


def _cluster_size_from_env(env) -> int:
    """Processes the environment says this job spans (1 when it names
    none): the TPU worker list (GKE / Cloud TPU: ``TPU_PROCESS_ADDRESSES``
    or ``TPU_WORKER_HOSTNAMES``, times ``MEGASCALE_NUM_SLICES``), Slurm's
    ``SLURM_NTASKS`` or Open MPI's ``OMPI_COMM_WORLD_SIZE``."""
    hosts = (env.get("TPU_PROCESS_ADDRESSES")
             or env.get("TPU_WORKER_HOSTNAMES") or "")
    n = max(len([h for h in hosts.split(",") if h.strip()]), 1)
    n *= max(int(env.get("MEGASCALE_NUM_SLICES") or 1), 1)
    for key in ("SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE"):
        n = max(n, int(env.get(key) or 1))
    return n


def init_multihost(coordinator_address: str | None = None,
                   num_processes: int | None = None,
                   process_id: int | None = None) -> None:
    """Initialize the JAX distributed runtime.

    MUST be the first JAX call of the process (``jax.distributed.initialize``
    precedes any backend use — the same contract as MPI_Init, reference
    cuda/acg-cuda.c:891).  The cluster is given explicitly, by the
    arguments or by ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID``, and a given cluster that fails to connect raises.
    Without one, a host the environment names as single
    (:func:`_cluster_size_from_env`) runs single-process and this is a
    no-op; an environment naming several processes raises, because
    silently degrading a pod run to N independent single-host runs would
    produce wrong results with no diagnostic.  JAX's own autodetection
    is never used: its Cloud TPU probe queries the GCE metadata server
    (jax/_src/clusters/cloud_tpu_cluster.py ``GceTpuCluster.
    is_env_present`` -> ``get_metadata``), a network call that a sealed
    host cannot answer (PR 21: the chip machines have no metadata
    server)."""
    import os

    from jax._src import distributed as _dist

    from acg_tpu.errors import AcgError, Status

    if _dist.global_state.client is not None:
        return                  # already initialized
    env = os.environ
    coordinator_address = (coordinator_address
                           or env.get("JAX_COORDINATOR_ADDRESS"))
    if not coordinator_address:
        n = _cluster_size_from_env(env)
        if n > 1:
            raise AcgError(Status.ERR_NOT_SUPPORTED,
                           f"the environment names a {n}-process job but "
                           f"no coordinator: set JAX_COORDINATOR_ADDRESS, "
                           f"JAX_NUM_PROCESSES and JAX_PROCESS_ID (or pass "
                           f"them) so every host joins one runtime")
        return
    if num_processes is None and env.get("JAX_NUM_PROCESSES"):
        num_processes = int(env["JAX_NUM_PROCESSES"])
    if process_id is None and env.get("JAX_PROCESS_ID"):
        process_id = int(env["JAX_PROCESS_ID"])
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def make_global_array(global_shape, sharding, fill_shard) -> jax.Array:
    """Globally-sharded device array from per-shard host data.

    ``fill_shard(index)`` receives the global index (a tuple of slices)
    of one addressable shard and returns its host values.  Each process
    touches only its own shards — no global host array, no root scatter.
    """
    return jax.make_array_from_callback(tuple(global_shape), sharding,
                                        fill_shard)


def gather_to_host(x: jax.Array) -> np.ndarray:
    """Full host copy of a (possibly cross-process) sharded array on
    every process."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(
            x, tiled=True))
    return np.asarray(jax.device_get(x))
