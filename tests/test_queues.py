"""Property tests (hypothesis) for the queue/routing invariants.

The system invariants under test:
  * queues preserve FIFO order and never lose accepted entries,
  * occurrence_index assigns FIFO per-destination slot ranks,
  * route_tasks conserves messages: sent + spilled == valid, and every
    message arrives at the shard that owns its head index.
"""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="dev extra (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core.comm import LocalComm
from repro.core.queues import (occurrence_index, queue_make, queue_push,
                               queue_take_front)
from repro.core.routing import route_tasks


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 1), min_size=1, max_size=40), st.data())
def test_queue_push_take_fifo(mask_list, data):
    n = len(mask_list)
    cap = data.draw(st.integers(1, 50))
    q = queue_make(cap, 2)
    rows = jnp.stack([jnp.arange(n, dtype=jnp.int32),
                      jnp.arange(n, dtype=jnp.int32) * 10], axis=1)
    mask = jnp.asarray(mask_list, bool)
    q, dropped = queue_push(q, rows, mask)
    expect = [i for i, m in enumerate(mask_list) if m][:cap]
    assert int(q.count) == len(expect)
    assert int(dropped) == sum(mask_list) - len(expect)
    taken, tvalid, q2 = queue_take_front(q, jnp.int32(len(expect)), cap)
    got = np.asarray(taken[np.asarray(tvalid)])[:, 0].tolist()
    assert got == expect
    assert int(q2.count) == 0


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.booleans()),
                min_size=1, max_size=64))
def test_occurrence_index_is_fifo_rank(items):
    dest = jnp.asarray([d for d, _ in items], jnp.int32)
    valid = jnp.asarray([v for _, v in items], bool)
    occ = np.asarray(occurrence_index(dest, valid, 4))
    seen = {}
    for i, (d, v) in enumerate(items):
        if v:
            assert occ[i] == seen.get(d, 0)
            seen[d] = seen.get(d, 0) + 1
        else:
            assert occ[i] >= len(items)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.integers(1, 8), st.data())
def test_route_conserves_messages(T, capacity, data):
    n = data.draw(st.integers(1, 32))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    # message head = global index in a T*chunk space; dest = owner
    chunk = 16
    idx = rng.integers(0, T * chunk, size=(T, n))
    payload = rng.integers(0, 1000, size=(T, n))
    valid = rng.random((T, n)) < 0.8
    msgs = jnp.stack([jnp.asarray(idx, jnp.int32),
                      jnp.asarray(payload, jnp.int32)], axis=2)
    dest = jnp.asarray(idx // chunk, jnp.int32)
    comm = LocalComm(T)
    r = route_tasks(comm, msgs, jnp.asarray(valid), dest, capacity)
    sent = int(np.asarray(r.per_dest).sum())
    spilled = int(np.asarray(r.spill_valid).sum())
    assert sent + spilled == int(valid.sum())
    # delivery: each device receives exactly the sent messages it owns
    recv = np.asarray(r.recv)
    rvalid = np.asarray(r.recv_valid)
    assert rvalid.sum() == sent
    for t in range(T):
        got = recv[t][rvalid[t]]
        assert (got[:, 0] // chunk == t).all()
    # multiset of delivered (idx, payload) pairs == multiset of sent pairs
    sent_rows = []
    spill = np.asarray(r.spill)
    spillv = np.asarray(r.spill_valid)
    for t in range(T):
        for i in range(n):
            if valid[t, i] and not spillv[t, i]:
                sent_rows.append((idx[t, i], payload[t, i]))
    got_rows = [tuple(x) for t in range(T) for x in recv[t][rvalid[t]]]
    assert sorted(sent_rows) == sorted(got_rows)


def test_route_fifo_per_destination():
    """In-order per-channel delivery (wormhole property)."""
    T = 4
    comm = LocalComm(T)
    n = 12
    # all devices send to device 0, increasing payloads
    idx = np.zeros((T, n), np.int64)  # global index 0 -> owner 0 (chunk 4)
    payload = np.arange(n)[None, :].repeat(T, 0)
    msgs = jnp.stack([jnp.asarray(idx, jnp.int32),
                      jnp.asarray(payload, jnp.int32)], axis=2)
    dest = jnp.zeros((T, n), jnp.int32)
    r = route_tasks(comm, msgs, jnp.ones((T, n), bool), dest, capacity=8)
    recv = np.asarray(r.recv[0])
    rvalid = np.asarray(r.recv_valid[0])
    for t in range(T):
        block = recv[t * 8:(t + 1) * 8]
        bv = rvalid[t * 8:(t + 1) * 8]
        pays = block[bv][:, 1]
        assert (np.diff(pays) > 0).all()  # FIFO order preserved
        assert len(pays) == 8  # capacity slots filled
    assert int(np.asarray(r.spill_valid).sum()) == T * (n - 8)


def _bin_loop(msgs, valid, dest, num_shards, capacity):
    """The row-by-row slot claim that ``bin_by_owner`` vectorizes: each
    valid message takes the next free slot of its destination, or spills.
    Returns the buffer, the spill mask and the slots used per destination."""
    n, w = msgs.shape
    buf = np.full((num_shards * capacity, w), -1, np.int64)
    used = [0] * num_shards
    spill = np.zeros(n, bool)
    for i in range(n):
        if not valid[i]:
            continue
        d = int(dest[i])
        if used[d] < capacity:
            buf[d * capacity + used[d]] = msgs[i]
            used[d] += 1
        else:
            spill[i] = True
    return buf, spill, np.asarray(used)


@pytest.mark.parametrize("T,capacity,n,w", [
    (1, 3, 10, 2), (4, 2, 24, 3), (4, 0, 8, 2), (3, 5, 0, 1),
    (6, 1, 40, 4), (2, 16, 12, 2)])
def test_bin_by_owner_matches_row_by_row_claim(T, capacity, n, w):
    from repro.core.routing import bin_by_owner
    rng = np.random.default_rng(T * 100 + n)
    msgs = rng.integers(0, 1000, (n, w))
    dest = rng.integers(0, T, n)
    valid = rng.random(n) < 0.7
    buf, spill_msgs, spill_valid, per_dest = bin_by_owner(
        jnp.asarray(msgs, jnp.int32), jnp.asarray(valid),
        jnp.asarray(dest, jnp.int32), T, capacity)
    want_buf, want_spill, want_used = _bin_loop(msgs, valid, dest, T,
                                                capacity)
    np.testing.assert_array_equal(np.asarray(buf), want_buf)
    np.testing.assert_array_equal(np.asarray(spill_valid), want_spill)
    np.testing.assert_array_equal(np.asarray(spill_msgs), msgs)
    np.testing.assert_array_equal(np.asarray(per_dest), want_used)
    assert int(np.asarray(per_dest).sum()) == int(valid.sum()
                                                  - want_spill.sum())
