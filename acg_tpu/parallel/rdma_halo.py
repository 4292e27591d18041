"""Device-initiated halo exchange: Pallas remote DMA (experimental).

The literal TPU analog of the reference's NVSHMEM device-initiated
communication — ``nvshmemx_double_put_signal_nbi_block`` per neighbour from
inside the solver kernel, then ``nvshmem_signal_wait_until`` before the
interface SpMV (reference acg/cg-kernels-cuda.cu:734-746, 876-887; host-
initiated variant acg/halo.cu:181-242).  Here each shard issues
``pltpu.make_async_remote_copy`` puts for ALL its neighbour messages at
once (no edge-coloring serialization — messages are in flight
simultaneously, like the reference's non-blocking puts) and then waits on
the receive semaphores, which play exactly the role of NVSHMEM signal
variables.

Message slots reuse the edge-colored (round, partner) tables of
acg_tpu/parallel/halo.py: the coloring is symmetric, so slot r on the
sender pairs with slot r on the receiver — the rendezvous the reference
establishes with its putdispls/putranks handshake (acg/halo.c:904-951) is
here a property of the shared schedule.  Slots without a partner self-copy
(device_id = own index); their payload is dropped by the pad scatter
indices.

Status: NOT SUPPORTED.  ``build_sharded`` refuses ``HaloMethod.RDMA``
with ERR_NOT_SUPPORTED on every platform: the puts target a peer's VMEM
with no barrier handshake that the peer's kernel is running (Pallas'
remote-copy protocol wants a barrier semaphore and a ``collective_id``),
and the kernel has never run across chips.  A PR that adds the handshake
and proves it on four chips (``chip_smoke.py --chips 4``) lifts the gate.
The transport moves (R, S) message blocks; placing them into ghost slots
stays in XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from acg_tpu.ops.pallas_kernels import pallas_call
from acg_tpu.parallel.halo import ghost_buffer, place_round


def _rdma_kernel(nrounds, dev_ref, sendbuf_ref, recvbuf_ref,
                 send_sem, recv_sem):
    """Issue all puts non-blocking, then wait all — NVSHMEM put+signal
    semantics (see module docstring).  ``dev_ref`` (SMEM) holds the target
    logical device per slot (own index for inactive slots)."""
    rdmas = []
    for r in range(nrounds):
        rdma = pltpu.make_async_remote_copy(
            src_ref=sendbuf_ref.at[r],
            dst_ref=recvbuf_ref.at[r],
            send_sem=send_sem.at[r],
            recv_sem=recv_sem.at[r],
            device_id=dev_ref[r],
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdmas.append(rdma)
    for rdma in rdmas:
        rdma.wait()


@functools.partial(jax.jit, static_argnames=("nrounds",))
def rdma_exchange(sendbuf: jax.Array, devices: jax.Array,
                  nrounds: int) -> jax.Array:
    """Exchange (R, S) message blocks with per-slot partner devices.

    Must be called inside ``shard_map``.  ``sendbuf[r]`` is delivered into
    the returned array's slot r on device ``devices[r]``.

    Slots are staged as (8, S'/8) 2-D blocks behind a leading slot axis:
    Mosaic requires ``.at[r]`` memref slices to land on sublane-tile
    boundaries, so a flat (R, S) buffer with small R is rejected.
    """
    R, S = sendbuf.shape
    assert R == nrounds
    Sp = -(-S // 1024) * 1024
    sb = jnp.pad(sendbuf, ((0, 0), (0, Sp - S))).reshape(R, 8, Sp // 8)
    out = pallas_call(
        functools.partial(_rdma_kernel, nrounds),
        name="acg_rdma_halo",
        out_shape=jax.ShapeDtypeStruct((R, 8, Sp // 8), sendbuf.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((R,)),
            pltpu.SemaphoreType.DMA((R,)),
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(devices, sb)
    return out.reshape(R, Sp)[:, :S]


def halo_rdma(x_own, send_idx, recv_idx, partner_row, nghost_max: int,
              axis_name: str):
    """Per-shard halo via device-initiated remote DMA.

    Same contract as ``halo_ppermute`` (acg_tpu/parallel/halo.py):
    ``send_idx``/``recv_idx`` are this shard's (R, S) tables,
    ``partner_row`` its (R,) partner ids (-1 = inactive slot).
    """
    R = send_idx.shape[0]
    with jax.named_scope("acg.halo.pack"):
        me = jax.lax.axis_index(axis_name)
        devices = jnp.where(partner_row >= 0, partner_row,
                            me).astype(jnp.int32)
        sendbuf = x_own[jnp.clip(send_idx, 0, None)]      # (R, S)
    with jax.named_scope("acg.halo.exchange"):
        recvbuf = rdma_exchange(sendbuf, devices, nrounds=R)
    with jax.named_scope("acg.halo.unpack"):
        ghosts = ghost_buffer(x_own, send_idx.shape[-1], nghost_max)
        for r in range(R):
            ghosts = place_round(ghosts, recvbuf[r], recv_idx[r],
                                 nghost_max)
        return jax.lax.slice_in_dim(ghosts, 0, nghost_max, axis=-1)
