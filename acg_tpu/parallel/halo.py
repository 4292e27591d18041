"""Device halo exchange: static collective schedules over the mesh.

Replaces all four reference communication backends (GPU-aware MPI
persistent requests, NCCL grouped send/recv, NVSHMEM host- and
device-initiated put+signal — reference acg/halo.c:1272-1327,
acg/halo.cu:181-242, acg/cg-kernels-cuda.cu:734-746) with XLA collectives
compiled into the solve loop.  The pattern is frozen at preprocessing time,
exactly as the reference freezes it at ``acghaloexchange_init`` — but here
"persistent requests" become a *compiled schedule*: a fixed sequence of
``ppermute`` rounds whose permutations are baked into the executable.

Two methods (config ``HaloMethod``):

- **ppermute**: the neighbour graph's edges are greedily edge-colored on the
  host; each color is one round, a matching, executed as one
  ``lax.ppermute`` whose pairs are that round's (src, dst) edges in both
  directions.  Traffic is neighbour-to-neighbour over ICI; rounds =
  chromatic index ≈ max neighbour degree (Vizing).  Per round each shard
  gathers its send buffer by a padded index table and scatters the received
  buffer into ghost slots with drop-mode padding.
- **allgather**: each shard packs the union of its border values once;
  one ``all_gather`` replicates all packs; each shard gathers its ghosts
  from (owner, position) tables.  One collective, more bandwidth — the
  robust fallback (and often optimal for small packs on ICI-all-to-all
  topologies).

All tables are built in :func:`build_halo_tables` from the host-side
:class:`~acg_tpu.partition.graph.PartitionedSystem`.  The exchange's ops
carry the scopes ``acg.halo.pack`` (send-buffer gather, wire encode),
``acg.halo.exchange`` (the collective) and ``acg.halo.unpack`` (wire
decode, ghost placement).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.partition.graph import PartitionedSystem


def edge_color(ps: PartitionedSystem) -> tuple[int, np.ndarray]:
    """Greedy edge coloring of the neighbour graph.

    Returns (nrounds, partner[P, nrounds]) with partner[p, r] = the part p
    exchanges with in round r, or -1.  Each round is a matching, so the
    per-round ppermute pairs form a valid permutation.
    """
    P = ps.nparts
    edges = sorted({(p.part, int(q)) for p in ps.parts
                    for q in p.neighbors if p.part < int(q)})
    colors: dict[tuple[int, int], int] = {}
    used: list[set] = [set() for _ in range(P)]
    for e in edges:
        c = 0
        while c in used[e[0]] or c in used[e[1]]:
            c += 1
        colors[e] = c
        used[e[0]].add(c)
        used[e[1]].add(c)
    nrounds = max(colors.values()) + 1 if colors else 0
    partner = np.full((P, max(nrounds, 1)), -1, dtype=np.int32)
    for (a, b), c in colors.items():
        partner[a, c] = b
        partner[b, c] = a
    return nrounds, partner


@dataclasses.dataclass(frozen=True)
class HaloTables:
    """Padded, device-ready halo schedule (host-built, static per matrix).

    Shapes: P parts, R rounds, S = max values per message, B = max pack
    size, G = max ghost count.  Index -1 = padding (dropped on scatter,
    or index 0 on gather with the result unused).
    """

    nrounds: int
    # ppermute schedule
    perms: tuple                  # per round: tuple of (src, dst) pairs
    partner: np.ndarray           # (P, R) partner part per round, -1 none
    send_idx: np.ndarray          # (P, R, S) into owned vector, -1 pad
    recv_idx: np.ndarray          # (P, R, S) into ghost vector, G pad (OOB)
    # allgather tables
    pack_idx: np.ndarray          # (P, B) into owned vector, -1 pad
    ghost_src_part: np.ndarray    # (P, G) owner part id, 0 pad
    ghost_src_pos: np.ndarray     # (P, G) position in owner's pack, 0 pad
    nghost_max: int
    total_send_values: int        # sum of per-part send counts (for stats)

    @property
    def max_msg(self) -> int:
        return self.send_idx.shape[2]


def build_halo_tables(ps: PartitionedSystem, nghost_max: int | None = None,
                      ) -> HaloTables:
    P = ps.nparts
    nrounds, partner = edge_color(ps)
    R = max(nrounds, 1)
    S = 1
    for p in ps.parts:
        if len(p.send_counts):
            S = max(S, int(p.send_counts.max()))
    G = nghost_max if nghost_max is not None else max(
        max((p.nghost for p in ps.parts), default=1), 1)

    # recv pad = G (one past the ghost region, into place_round's slack):
    # each row is one contiguous run start..start+cnt, then pads.
    send_idx = np.full((P, R, S), -1, dtype=np.int32)
    recv_idx = np.full((P, R, S), G, dtype=np.int32)
    for p in ps.parts:
        sd, rd = p.send_displs, p.recv_displs
        for qi, q in enumerate(p.neighbors):
            q = int(q)
            r = int(np.nonzero(partner[p.part] == q)[0][0])
            cnt = int(p.send_counts[qi])
            send_idx[p.part, r, :cnt] = p.send_idx[sd[qi]: sd[qi + 1]]
            rcnt = int(p.recv_counts[qi])
            recv_idx[p.part, r, :rcnt] = np.arange(rd[qi], rd[qi + 1])

    # allgather pack: union of all border nodes each part ever sends,
    # sorted by global id (deduplicated — a border node adjacent to two
    # neighbours is packed once)
    B = 1
    packs = []
    for p in ps.parts:
        uniq = np.unique(p.send_idx) if len(p.send_idx) else np.empty(
            0, dtype=np.int64)
        packs.append(uniq)
        B = max(B, len(uniq))
    pack_idx = np.full((P, B), -1, dtype=np.int32)
    for p, u in zip(ps.parts, packs):
        pack_idx[p.part, : len(u)] = u

    # position of each ghost's global id inside its owner's pack: ONE
    # global gid -> pack-position map filled from every part's pack (each
    # node is packed by at most its one owner), then a single gather per
    # part.  Replaces a per-(part, neighbour) O(nrows) g2l rebuild that
    # dominated halo-table time at 9M rows (O(P² · n)).
    ghost_src_part = np.zeros((P, G), dtype=np.int32)
    ghost_src_pos = np.zeros((P, G), dtype=np.int32)
    pack_pos = np.zeros(ps.nrows, dtype=np.int32)
    for q, u in zip(ps.parts, packs):
        if len(u):
            pack_pos[q.owned_global[u]] = np.arange(len(u), dtype=np.int32)
    for p in ps.parts:
        if p.nghost == 0:
            continue
        ghost_src_part[p.part, : p.nghost] = p.ghost_owner
        ghost_src_pos[p.part, : p.nghost] = pack_pos[p.ghost_global]

    perms = []
    for r in range(R):
        pairs = tuple((a, int(partner[a, r])) for a in range(P)
                      if partner[a, r] >= 0)
        perms.append(pairs)

    total = sum(int(p.send_counts.sum()) for p in ps.parts)
    return HaloTables(nrounds=nrounds, perms=tuple(perms),
                      partner=partner[:, :R],
                      send_idx=send_idx, recv_idx=recv_idx,
                      pack_idx=pack_idx, ghost_src_part=ghost_src_part,
                      ghost_src_pos=ghost_src_pos, nghost_max=G,
                      total_send_values=total)


def halo_describe(ps: PartitionedSystem, tables: HaloTables | None = None,
                  ) -> str:
    """Render the communication pattern, one block per part — the
    ``acghalo_fwrite`` debug dump (reference acg/halo.c:356-389: recipients
    with sendcounts/sdispls, senders with recvcounts/rdispls) plus the
    compiled schedule summary (rounds/colors) that replaces the reference's
    per-neighbour message list."""
    if tables is None:
        tables = build_halo_tables(ps)
    lines = [f"halo exchange pattern: {ps.nparts} parts, "
             f"{tables.nrounds} ppermute rounds, "
             f"{tables.total_send_values} total values/exchange"]
    for p in ps.parts:
        nb = [int(q) for q in p.neighbors]
        lines.append(f"part {p.part}: nown {p.nown} (interior "
                     f"{p.nown - p.nborder}, border {p.nborder}), "
                     f"ghost {p.nghost}")
        lines.append(f"  recipients: {nb}")
        lines.append(f"  sendcounts: {[int(c) for c in p.send_counts]}")
        lines.append(f"  sdispls: {[int(d) for d in p.send_displs]}")
        lines.append(f"  senders: {nb}")
        lines.append(f"  recvcounts: {[int(c) for c in p.recv_counts]}")
        lines.append(f"  rdispls: {[int(d) for d in p.recv_displs]}")
        rounds = [(r, int(q)) for r, q in enumerate(tables.partner[p.part])
                  if q >= 0]
        lines.append(f"  schedule (round, partner): {rounds}")
    return "\n".join(lines)


#: Wire encodings accepted by ``SolverOptions.halo_wire``.  "f32" is the
#: identity (the message goes out at the vector dtype); the compressed
#: formats halve the on-wire payload and decode to the vector dtype
#: BEFORE any arithmetic touches the values (accumulation is always
#: full precision — only the wire is narrow).
HALO_WIRES = ("f32", "bf16", "int16-delta")

# int16-delta prepends a 4-value int16 header per message carrying the
# bitcast (offset, scale) f32 pair the receiver decodes with.  The
# header rides INSIDE the same collective — adding a second tiny
# ppermute for two scalars would change the per-iteration collective
# COUNT the contracts pin (analysis/contracts.py C1-C3).
_I16_HDR = 4


def wire_itemsize(wire: str, vec_dtype) -> int:
    """Bytes per value actually on the wire for one halo message.

    The honest-accounting hook for roofline/CommAudit byte models
    (obs/roofline.py): "f32" sends at the vector dtype's width; both
    compressed formats send 2-byte values (int16-delta additionally
    carries a constant 8-byte header per message, amortized away here)."""
    if wire == "f32":
        return int(np.dtype(vec_dtype).itemsize)
    if wire in ("bf16", "int16-delta"):
        return 2
    raise ValueError(f"unknown halo wire format {wire!r}")


def wire_encode(buf, wire: str):
    """Encode one halo message for the wire.  ``buf`` is ([B,] m) at the
    vector dtype; per-system scaling for int16-delta runs along the last
    axis (one (offset, scale) pair per message per system)."""
    if wire == "f32":
        return buf
    if wire == "bf16":
        # ship the bf16 BIT PATTERN as u16: backend legalization passes
        # widen unsupported-dtype collectives back to f32 (XLA:CPU's
        # bf16 normalization does exactly that), which would silently
        # undo the compression; no pass rewrites an integer payload
        return jax.lax.bitcast_convert_type(buf.astype(jnp.bfloat16),
                                            jnp.uint16)
    if wire == "int16-delta":
        b32 = buf.astype(jnp.float32)
        lo = b32.min(axis=-1, keepdims=True)
        hi = b32.max(axis=-1, keepdims=True)
        off = 0.5 * (hi + lo)
        # smallest-normal floor: a constant message still round-trips
        # (q == 0 everywhere, decode == off == the constant)
        scale = jnp.maximum((hi - lo) / 65534.0, jnp.float32(1.2e-38))
        q = jnp.round((b32 - off) / scale).astype(jnp.int16)
        hdr = jax.lax.bitcast_convert_type(
            jnp.concatenate([off, scale], axis=-1), jnp.int16)
        return jnp.concatenate(
            [hdr.reshape(buf.shape[:-1] + (_I16_HDR,)), q], axis=-1)
    raise ValueError(f"unknown halo wire format {wire!r}")


def wire_decode(buf, wire: str, dtype):
    """Decode one received halo message back to ``dtype`` (full-width)
    values — the "f32 accumulation on unpack" half of the contract:
    everything downstream of this point is ordinary-width arithmetic."""
    if wire == "f32":
        return buf
    if wire == "bf16":
        return jax.lax.bitcast_convert_type(buf, jnp.bfloat16).astype(dtype)
    if wire == "int16-delta":
        raw = jax.lax.slice_in_dim(buf, 0, _I16_HDR, axis=-1)
        hdr = jax.lax.bitcast_convert_type(
            raw.reshape(buf.shape[:-1] + (2, 2)),
            jnp.float32)              # (..., 2): [offset, scale]
        off = jax.lax.slice_in_dim(hdr, 0, 1, axis=-1)
        scale = jax.lax.slice_in_dim(hdr, 1, 2, axis=-1)
        body = jax.lax.slice_in_dim(buf, _I16_HDR, buf.shape[-1],
                                    axis=-1)
        return (body.astype(jnp.float32) * scale + off).astype(dtype)
    raise ValueError(f"unknown halo wire format {wire!r}")


def halo_ppermute(x_own, send_idx, recv_idx, perms, nghost_max: int,
                  axis_name: str, wire: str = "f32"):
    """Per-shard halo via edge-colored ppermute rounds.

    ``x_own``: (nown_max,) owned values of this shard.  ``send_idx``/
    ``recv_idx``: this shard's (R, S) tables.  Returns ghosts (nghost_max,).
    The reference analog is the per-neighbour put+signal loop
    (acg/halo.cu:181-242); signals/ordering are the collective's semantics.

    Batched ``x_own`` of shape (B, nown_max) exchanges ALL B systems'
    border values in the SAME ppermute rounds — (B, S) message blocks,
    so the per-iteration collective COUNT is independent of B (the
    multi-RHS amortization of collective latency; ghosts come back
    (B, nghost_max)).

    ``wire`` != "f32" encodes each round's message before the ppermute
    and decodes after (wire_encode/wire_decode): same round count, same
    collective count, ~2x narrower payload.  "f32" takes the original
    code path untouched — the traced program is bit-identical to one
    built before the wire option existed (the zero-overhead clause).
    """
    with jax.named_scope("acg.halo.unpack"):
        ghosts = ghost_buffer(x_own, send_idx.shape[-1], nghost_max)
    for r, perm in enumerate(perms):
        if not perm:
            continue
        with jax.named_scope("acg.halo.pack"):
            # pad gathers 0; the send-pack gather is the halo design
            idx = jnp.clip(send_idx[r], 0, None)
            sbuf = x_own[..., idx]  # acg: allow-gather
            if wire != "f32":
                sbuf = wire_encode(sbuf, wire)
        with jax.named_scope("acg.halo.exchange"):
            rbuf = jax.lax.ppermute(sbuf, axis_name, perm)
        with jax.named_scope("acg.halo.unpack"):
            if wire != "f32":
                rbuf = wire_decode(rbuf, wire, x_own.dtype)
            ghosts = place_round(ghosts, rbuf, recv_idx[r], nghost_max)
    with jax.named_scope("acg.halo.unpack"):
        return jax.lax.slice_in_dim(ghosts, 0, nghost_max, axis=-1)


def ghost_buffer(x_own, max_msg: int, nghost_max: int):
    """Zero ghost vector with ``max_msg`` slots of slack behind it, so a
    message placed by :func:`place_round` never runs off the end."""
    return jnp.zeros(x_own.shape[:-1] + (nghost_max + max_msg,),
                     dtype=x_own.dtype)


def place_round(ghosts, rbuf, recv_row, nghost_max: int):
    """Write one round's message into its ghost slots.  A round's
    receive slots are one contiguous run (build_halo_tables numbers each
    neighbour's ghosts consecutively; pad = ``nghost_max``), so a masked
    dynamic_update_slice at the run's start places it: no scatter in the
    solver's loop (contract C5)."""
    start = recv_row[0]
    cur = jax.lax.dynamic_slice_in_dim(ghosts, start, rbuf.shape[-1],
                                       axis=-1)
    new = jnp.where(recv_row < nghost_max, rbuf, cur)
    return jax.lax.dynamic_update_slice_in_dim(ghosts, new, start, axis=-1)


def halo_allgather(x_own, pack_idx, ghost_src_part, ghost_src_pos,
                   axis_name: str, wire: str = "f32"):
    """Per-shard halo via one all_gather of packed border values.
    Batched ``x_own`` (B, nown_max) packs (B, pack) blocks — still ONE
    collective for all B systems — and returns (B, nghost) ghosts.
    ``wire`` != "f32" gathers the encoded pack and decodes every part's
    replica before the (owner, position) gather, so the position tables
    are untouched by the int16-delta header offset."""
    with jax.named_scope("acg.halo.pack"):
        pack = x_own[..., jnp.clip(pack_idx, 0, None)]  # acg: allow-gather
        if wire != "f32":
            pack = wire_encode(pack, wire)
    with jax.named_scope("acg.halo.exchange"):
        allpacks = jax.lax.all_gather(pack, axis_name)  # (P, [B,] pack)
    with jax.named_scope("acg.halo.unpack"):
        if wire != "f32":
            allpacks = wire_decode(allpacks, wire, x_own.dtype)
        if x_own.ndim == 2:
            # gather (owner, position) per ghost, then put the system
            # axis back in front: (G, B) -> (B, G)
            return jnp.moveaxis(
                allpacks[ghost_src_part, :, ghost_src_pos], 0, -1)
        return allpacks[ghost_src_part, ghost_src_pos]
