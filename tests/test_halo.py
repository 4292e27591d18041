"""Device halo-exchange tests vs the host oracle (SURVEY §7.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from acg_tpu.config import HaloMethod
from acg_tpu.parallel.halo import build_halo_tables, edge_color
from acg_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from acg_tpu.parallel.sharded import ShardedSystem
from acg_tpu.partition import partition_graph, partition_system
from acg_tpu.sparse import poisson2d_5pt, poisson3d_7pt


def _system(nparts, n=6, gen=poisson2d_5pt):
    A = gen(n)
    part = partition_graph(A, nparts)
    return A, partition_system(A, part)


def test_edge_color_is_matching():
    _, ps = _system(8, n=8, gen=poisson3d_7pt)
    nrounds, partner = edge_color(ps)
    assert nrounds >= 1
    for r in range(nrounds):
        # each round is a matching: partner of partner is self
        for p in range(ps.nparts):
            q = partner[p, r]
            if q >= 0:
                assert partner[q, r] == p
    # every neighbour edge is scheduled in exactly one round
    for p in ps.parts:
        for q in p.neighbors:
            assert (partner[p.part] == int(q)).sum() == 1


@pytest.mark.parametrize("method", [HaloMethod.PPERMUTE, HaloMethod.ALLGATHER])
@pytest.mark.parametrize("nparts", [2, 4, 8])
def test_device_halo_matches_host(method, nparts):
    A, ps = _system(nparts, n=8)
    ss = ShardedSystem.build(ps, method=method)
    x = np.random.default_rng(0).standard_normal(A.nrows)

    # host oracle
    locs = ps.scatter_vector(x)
    full = ps.exchange_halo(locs)

    halo_fn = ss.shard_halo_fn()

    def shard(x_own, sidx, ridx, ptnr, pidx, gsp, gpp):
        ghosts = halo_fn(x_own[0], sidx[0], ridx[0], ptnr[0], pidx[0],
                         gsp[0], gpp[0])
        return ghosts[None]

    ghosts = jax.jit(jax.shard_map(
        shard, mesh=ss.mesh, in_specs=(P(PARTS_AXIS),) * 7,
        out_specs=P(PARTS_AXIS), check_vma=False))(
            ss.to_sharded(x), ss.send_idx, ss.recv_idx, ss.partner,
            ss.pack_idx, ss.ghost_src_part, ss.ghost_src_pos)
    ghosts = np.asarray(ghosts)
    for i, p in enumerate(ps.parts):
        np.testing.assert_allclose(ghosts[i, : p.nghost],
                                   full[i][p.nown:], rtol=1e-14)


@pytest.mark.parametrize("method", [HaloMethod.PPERMUTE, HaloMethod.ALLGATHER])
def test_distributed_device_matvec(method):
    A, ps = _system(8, n=6, gen=poisson3d_7pt)
    ss = ShardedSystem.build(ps, method=method)
    x = np.random.default_rng(1).standard_normal(A.nrows)
    y_expect = A.matvec(x)

    from acg_tpu.ops.spmv import ell_matvec
    halo_fn = ss.shard_halo_fn()
    local_mv = ss.local_matvec_fn()

    def shard(lops, iv, ic, sidx, ridx, ptnr, pidx, gsp, gpp, x_own):
        xo = x_own[0]
        ghosts = halo_fn(xo, sidx[0], ridx[0], ptnr[0], pidx[0], gsp[0],
                         gpp[0])
        y = (local_mv(xo, tuple(a[0] for a in lops))
             + ell_matvec(iv[0], ic[0], ghosts))
        return y[None]

    y = jax.jit(jax.shard_map(
        shard, mesh=ss.mesh, in_specs=(P(PARTS_AXIS),) * 10,
        out_specs=P(PARTS_AXIS), check_vma=False))(
            ss.local_op_arrays(), ss.ivals, ss.icols, ss.send_idx,
            ss.recv_idx, ss.partner, ss.pack_idx, ss.ghost_src_part,
            ss.ghost_src_pos, ss.to_sharded(x))
    np.testing.assert_allclose(ss.from_sharded(y), y_expect, rtol=1e-12)


def test_rdma_halo_traces():
    """The RDMA halo (device-initiated tier) must at least trace/abstract-
    eval cleanly; Mosaic remote DMA cannot execute on the CPU interpreter,
    so execution is exercised only on real multi-chip TPU."""
    from acg_tpu.parallel.rdma_halo import halo_rdma

    _, ps = _system(4, n=6)
    ss = ShardedSystem.build(ps, method=HaloMethod.PPERMUTE)

    def shard(x_own, sidx, ridx, ptnr):
        return halo_rdma(x_own[0], sidx[0], ridx[0], ptnr[0],
                         ss.nghost_max, PARTS_AXIS)[None]

    mapped = jax.shard_map(shard, mesh=ss.mesh,
                           in_specs=(P(PARTS_AXIS),) * 4,
                           out_specs=P(PARTS_AXIS), check_vma=False)
    x = ss.zeros_sharded()
    # abstract evaluation only (no device execution)
    out = jax.eval_shape(mapped, x, ss.send_idx, ss.recv_idx, ss.partner)
    assert out.shape == (4, ss.nghost_max)


def test_place_round_writes_runs_without_scatter():
    """A round's receive slots are one contiguous run (pad = G): the
    masked dynamic_update_slice places it in any round order, leaves
    inactive rounds out, and lowers without a scatter (contract C5)."""
    from acg_tpu.parallel.halo import ghost_buffer, place_round

    G, S = 6, 3
    g = ghost_buffer(jnp.zeros(4), S, G)
    rounds = [(jnp.array([4., 5., 9.]), jnp.array([4, 5, G])),
              (jnp.array([7., 7., 7.]), jnp.array([G, G, G])),
              (jnp.array([1., 2., 3.]), jnp.array([1, 2, 3]))]
    for rbuf, rows in rounds:
        g = place_round(g, rbuf, rows.astype(jnp.int32), G)
    np.testing.assert_array_equal(np.asarray(g[:G]), [0, 1, 2, 3, 4, 5])
    txt = jax.jit(lambda g, r, i: place_round(g, r, i, G)).lower(
        g, rounds[0][0], rounds[0][1].astype(jnp.int32)).as_text()
    assert "scatter" not in txt
