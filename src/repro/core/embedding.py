"""DalorexEmbedding: vocab-routed, data-local embedding lookup.

The paper's placement + routing applied to LM embedding tables: the table is
scattered across the ``model`` axis by **low-order bits of the vocab id**
(``owner(v) = v mod M``, ``local(v) = v div M`` — the exact arithmetic of
Section III-A), and token ids are *routed to the data* with one all_to_all;
gathered rows ride one all_to_all back.  Compare: the naive sharded lookup
all-gathers a ``V x d`` table (nemotron: 256k x 6144 x 2B = 3.1 GB) per step;
the routed lookup moves ``4·tokens`` bytes of ids + ``2·tokens·d`` bytes of
rows — independent of V.

Overflow semantics follow the paper's channel queues: per-destination slots
are a static ``capacity``; tokens that do not fit get a zero row and are
*counted* (telemetry).  With low-order placement and natural token streams
the per-shard load is near-uniform, so the default slack never overflows in
our tests — the capacity-sweep test exercises the counter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.queues import occurrence_index
from repro.parallel.sharding import current_mesh, current_rules


def padded_vocab(vocab: int, shards: int) -> int:
    return ((vocab + shards - 1) // shards) * shards


def place_table(table_rows, num_shards: int):
    """Host helper: (V_pad, d) vocab-order -> placed order (chunked by owner).

    placed[(v % M) * chunk + v // M] = rows[v]."""
    import numpy as np
    v_pad = table_rows.shape[0]
    chunk = v_pad // num_shards
    ids = np.arange(v_pad)
    place = (ids % num_shards) * chunk + ids // num_shards
    out = np.empty_like(table_rows)
    out[place] = table_rows
    return out


def _routed_lookup_local(table_shard, ids, capacity: int, axis: str, M: int):
    """Per-device body (inside shard_map).  table_shard: (V_pad/M, d);
    ids: (n,) int32 local token ids.  Returns (emb (n, d), overflow count).
    """
    n = ids.shape[0]
    owner = ids % M                      # low-order placement = the route
    local_row = ids // M
    valid = ids >= 0
    occ = occurrence_index(owner, valid, M)
    fits = valid & (occ < capacity)
    slot = jnp.where(fits, owner * capacity + occ, M * capacity)
    # send buffer of local row indices; -1 marks empty (headerless validity)
    send = jnp.full((M * capacity + 1,), -1, jnp.int32).at[slot].set(local_row)
    send = send[:-1]
    got = jax.lax.all_to_all(send, axis, 0, 0, tiled=True)  # (M*capacity,)
    rvalid = got >= 0
    rows = jnp.take(table_shard, jnp.maximum(got, 0), axis=0)
    rows = jnp.where(rvalid[:, None], rows, 0)
    back = jax.lax.all_to_all(rows, axis, 0, 0, tiled=True)  # (M*cap, d)
    # result for slot owner*cap+occ returns to the same position (a2a is an
    # involution on the tiled block layout)
    emb = jnp.take(back, jnp.minimum(slot, M * capacity - 1), axis=0)
    emb = jnp.where(fits[:, None], emb, 0)
    overflow = (valid & ~fits).sum(dtype=jnp.int32)
    return emb, overflow


def routed_embed(table, ids, *, model_axis: str = "model",
                 batch_axes=("data",), seq_shard: bool = True,
                 capacity_factor: float = 2.0):
    """Routed lookup as a shard_map island inside a jit region.

    table: (V_pad, d) in *placed* layout, sharded P(model_axis, None).
    ids:   (B, S) int32, sharded P(batch_axes, model_axis if seq_shard).
    Returns (emb (B, S, d) with the same sharding as ids + trailing d,
    overflow scalar).
    """
    mesh = current_mesh()
    if mesh is None:  # single-device path: plain placed-order gather
        M = 1
        emb = jnp.take(table, ids, axis=0)
        return emb, jnp.zeros((), jnp.int32)
    M = mesh.shape[model_axis]
    B, S = ids.shape
    # drop non-divisible shardings (e.g. batch=1 long-context decode)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    if B % dp != 0 or B < dp:
        batch_axes, dp = (), 1
    if S % M != 0 or S < M:
        seq_shard = False
    bspec = (tuple(batch_axes) if len(batch_axes) > 1
             else batch_axes[0] if batch_axes else None)
    sspec = model_axis if seq_shard else None
    n_local = (B // dp) * (S // (M if seq_shard else 1))
    capacity = max(1, int(n_local * capacity_factor) // M)

    def body(table_shard, ids_blk):
        flat = ids_blk.reshape(-1)
        emb, ovf = _routed_lookup_local(table_shard, flat, capacity,
                                        model_axis, M)
        emb = emb.reshape(ids_blk.shape + (table_shard.shape[1],))
        return emb, jax.lax.psum(ovf, model_axis)

    out_emb_spec = P(bspec, sspec, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(model_axis, None), P(bspec, sspec)),
        out_specs=(out_emb_spec, P()), check_vma=False)
    return fn(table, ids)


def embed_lookup(table, ids, routed: bool, **kw):
    """Entry point used by the models: routed (Dalorex) or replicated."""
    if routed:
        return routed_embed(table, ids, **kw)
    emb = jnp.take(table, ids, axis=0)
    return emb, jnp.zeros((), jnp.int32)
