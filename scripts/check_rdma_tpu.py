"""Compile + execute the device-initiated RDMA halo on real TPU hardware.

The rdma halo tier (acg_tpu/parallel/rdma_halo.py — the NVSHMEM
put+signal analog, ref acg/cg-kernels-cuda.cu:734-746) cannot run on the
CPU interpreter, so CI only trace-tests it.  This script is the missing
hardware evidence, sized to the one attached chip: a 1-device mesh where
every slot's partner is the device itself — the remote-DMA program
(put, send/recv semaphores, wait) compiles under Mosaic and executes as
a loopback transfer whose payload must round-trip bit-exactly.  On a
multi-chip mesh the identical program moves the same slots between
chips; run with more devices when a real mesh is available.

The solver refuses the rdma tier (ERR_NOT_SUPPORTED) until a multi-chip
run proves it; this loopback check is not that proof.

Usage: python scripts/check_rdma_tpu.py   (needs a TPU)
Prints one JSON line {"rdma_loopback": "ok", ...} on success.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    from acg_tpu.utils.backend import require_tpu

    devs = require_tpu()

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from acg_tpu.parallel.mesh import PARTS_AXIS, make_mesh
    from acg_tpu.parallel.rdma_halo import rdma_exchange

    ndev = len(devs)
    mesh = make_mesh(ndev)
    R, S = 3, 256
    rng = np.random.default_rng(0)
    sendbuf = jnp.asarray(
        rng.standard_normal((ndev, R, S)).astype(np.float32))
    # every slot targets the shard itself (loopback on 1 chip; on a real
    # mesh replace with the edge-colored partner table)
    def shard(buf):
        me = jax.lax.axis_index(PARTS_AXIS)
        devices = jnp.full((R,), me, jnp.int32)
        return rdma_exchange(buf[0], devices, nrounds=R)[None]

    fn = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=(P(PARTS_AXIS),),
                               out_specs=P(PARTS_AXIS), check_vma=False))
    out = np.asarray(jax.block_until_ready(fn(sendbuf)))
    ok = np.array_equal(out, np.asarray(sendbuf))
    print(json.dumps({"rdma_loopback": "ok" if ok else "PAYLOAD MISMATCH",
                      "devices": ndev, "rounds": R, "slot_values": S,
                      "device_kind": devs[0].device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
