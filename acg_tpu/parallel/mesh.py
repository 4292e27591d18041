"""Device mesh helpers.

The reference binds one MPI rank to one GPU by shared-communicator rank
(reference cuda/acg-cuda.c:1014-1041) and bootstraps one of four comm
backends on top (acg/comm.h:84-92).  On TPU all of that is one object: a
1-D ``jax.sharding.Mesh`` over the chips, with XLA collectives riding
ICI/DCN.  The solver's row-partition axis maps directly onto this mesh axis
(SURVEY §2: the reference's parallelism is 1-D domain decomposition).
"""

from __future__ import annotations

import jax
import numpy as np

from acg_tpu.errors import AcgError, Status

PARTS_AXIS = "parts"


def make_mesh(nparts: int, devices=None) -> jax.sharding.Mesh:
    """1-D mesh with ``nparts`` devices on axis "parts".

    When ``nparts`` equals the full device count, the device order comes
    from ``mesh_utils.create_device_mesh``, which lays the 1-D axis along
    an ICI ring/line of the physical topology — neighbour halo ``ppermute``
    traffic then rides single-hop ICI links instead of arbitrary routes
    (on multi-host slices, consecutive parts stay host-local first, so
    only the block boundaries cross DCN).  Otherwise the first ``nparts``
    of ``jax.devices()`` are used (globally consistent across processes —
    the analog of the reference's identical-communicator requirement,
    reference cuda/acg-cuda.c:1014-1041).
    """
    if devices is None:
        devices = jax.devices()
    if nparts > len(devices):
        raise AcgError(
            Status.ERR_MESH,
            f"need {nparts} devices for {nparts} parts, have {len(devices)}")
    if nparts == len(devices) and nparts > 1:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh((nparts,), devices=devices)
        return jax.sharding.Mesh(arr, (PARTS_AXIS,))
    return jax.sharding.Mesh(np.asarray(devices[:nparts]), (PARTS_AXIS,))
