"""The Dalorex task-routing primitive.

This is the JAX-native analogue of the paper's headerless NoC (Section
III-E/F).  A *task message* is a fixed-width row of int32 flits whose first
flit is a **global array index**; ownership of that index under the static
equal-chunk distribution *is* the route — no metadata is sent, exactly like
the paper's head-flit encoding.  We take the idea one step further: slot
*emptiness* is also encoded in the head flit (index < 0), so a routing round
exchanges exactly one buffer — no side-band validity traffic.

This module is the single-exchange *primitive*; the engine routes through
the pluggable :mod:`repro.noc` subsystem, whose ``IdealAllToAll`` backend
is exactly one :func:`route_tasks` round and whose physical backends
(mesh / torus / ruche) compose :func:`bin_by_owner` + ``comm.a2a`` into
dimension-ordered per-axis exchanges with per-link backpressure.

``route_tasks`` performs one network round:

1. each device bins its outgoing messages by destination shard
   (``owner = idx // chunk`` in placed space — the paper's head encoder),
2. claims per-destination slots up to ``capacity`` (the channel-queue bound;
   the paper's routers stall, we *spill* and replay — same backpressure
   semantics, no loss),
3. exchanges the binned buffer with ONE ``all_to_all`` (the vectorized
   wormhole transfer), and
4. returns the received messages plus the spilled ones for local re-queueing.

Slot claiming is FIFO per destination (:func:`bin_by_owner`), matching the
in-order per-channel delivery of the paper's wormhole NoC.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

EMPTY = jnp.int32(-1)  # head-flit value marking an empty network slot


class Routed(NamedTuple):
    """Result of one routing round (all shapes static).

    recv:        (T*capacity, W) int32 — received messages, grouped by source
                 device; empty slots have head flit < 0.
    recv_valid:  (T*capacity,) bool — decoded from the head flit.
    spill:       (N, W) int32 — local copies of messages that did not fit.
    spill_valid: (N,) bool.
    sent:        () int32 — number of messages actually sent by this device.
    """

    recv: jax.Array
    recv_valid: jax.Array
    spill: jax.Array
    spill_valid: jax.Array
    sent: jax.Array


def bin_by_owner(msgs, valid, dest, num_shards, capacity):
    """Pack ``msgs`` into per-destination slots of a (T*capacity, W) buffer.

    Returns (send_buf, spill_msgs, spill_valid, n_sent).  Rows
    ``[d*capacity:(d+1)*capacity]`` of ``send_buf`` are addressed to shard
    ``d``; empty slots have head flit -1.  FIFO order within each destination
    is preserved; messages beyond ``capacity`` for a destination are returned
    as spill (masked in place).

    One stable sort by destination carries the messages as payload, so
    each destination's messages sit contiguously in FIFO order: slot
    ``(d, c)`` is the ``c``-th of them, read by one slice per destination.
    A second sort brings the fit flags back to message order.  No row is
    scattered or gathered on its own (a TPU runs those one row at a time).
    """
    n, w = msgs.shape
    ar = jnp.arange(n, dtype=jnp.int32)
    d = jnp.where(valid, dest, num_shards).astype(jnp.int32)  # invalid last
    d_s, ar_s, *cols = jax.lax.sort(
        (d, ar) + tuple(msgs[:, j] for j in range(w)), num_keys=1,
        is_stable=True)
    # FIFO rank inside each destination's run of the sorted order
    new_grp = jnp.concatenate([jnp.ones((1,), bool), d_s[1:] != d_s[:-1]])
    occ = ar - jax.lax.associative_scan(jnp.maximum,
                                        jnp.where(new_grp, ar, 0))
    fits_s = (d_s < num_shards) & (occ < capacity)
    # where each destination's run starts, and how many of it fit
    start = jnp.searchsorted(d_s, jnp.arange(num_shards + 1, dtype=jnp.int32),
                             side="left", method="scan_unrolled")
    start = start.astype(jnp.int32)
    fill = jnp.minimum(start[1:] - start[:-1], capacity)
    sorted_msgs = jnp.concatenate(
        [jnp.stack(cols, axis=1), jnp.full((capacity, w), EMPTY, jnp.int32)])
    blocks = jax.vmap(lambda s0: jax.lax.dynamic_slice_in_dim(
        sorted_msgs, s0, capacity))(start[:-1])      # (T, capacity, W)
    live = jnp.arange(capacity, dtype=jnp.int32)[None, :] < fill[:, None]
    buf = jnp.where(live[..., None], blocks, EMPTY)
    _, fits = jax.lax.sort((ar_s, fits_s), num_keys=1)
    spill_valid = valid & ~fits
    n_sent = fits_s.sum(dtype=jnp.int32)
    return buf.reshape(num_shards * capacity, w), msgs, spill_valid, n_sent


def route_tasks(comm, msgs: jax.Array, valid: jax.Array, dest: jax.Array,
                capacity: int) -> Routed:
    """One Dalorex network round over ``comm`` (AxisComm or LocalComm).

    Under ``LocalComm`` every array carries a leading T axis and local stages
    are vmapped; under ``AxisComm`` this runs inside shard_map per device.
    """
    T = comm.size

    def local_bin(_me, m, v, d):
        return bin_by_owner(m, v, d, T, capacity)

    buf, spill, spill_valid, n_sent = comm.run(local_bin, msgs, valid, dest)
    recv = comm.a2a(buf)
    recv_valid = recv[..., 0] >= 0
    return Routed(recv, recv_valid, spill, spill_valid, n_sent)
