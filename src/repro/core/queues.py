"""Fixed-capacity, jit-compatible FIFO queues.

These are the software analogue of Dalorex's circular FIFO task queues,
which the paper implements inside each tile's scratchpad (Section III-E).
A queue is a pytree ``(data, count)`` where ``data`` is a ``(cap, width)``
array whose first ``count`` rows are valid, stored in FIFO order and always
compacted to the front. Every operation is static-shape (jit/scan/while_loop
safe).  Pushes compact with a sort and pops shift with a slice, so no
operation scatters or gathers row by row.

All queues store int32; float payloads are bitcast via :func:`f2i`/:func:`i2f`
so a single dtype flows through the network buffers — mirroring the paper's
32-bit flits ("A 32-bit Dalorex can process graphs of up to 2^32 edges").
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.mem import alloc


class Queue(NamedTuple):
    data: jax.Array  # (cap, width) int32
    count: jax.Array  # () int32


def f2i(x: jax.Array) -> jax.Array:
    """Bitcast float32 -> int32 (a 32-bit flit)."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def i2f(x: jax.Array) -> jax.Array:
    """Bitcast int32 -> float32."""
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def queue_make(cap: int, width: int, space: str = "vmem",
               label: str = "queue") -> Queue:
    """Allocate a queue in its declared memory space (``repro.mem``) —
    the registry rejects spaces that cannot hold queue buffers (HBM holds
    only bulk edge shards) at config time, naming ``label``."""
    data = alloc(space, "queue", (cap, width), jnp.int32, label=label)
    return Queue(data, jnp.zeros((), jnp.int32))


def queue_free(q: Queue) -> jax.Array:
    return q.data.shape[0] - q.count


def queue_clear(q: Queue) -> Queue:
    """An emptied queue of the same shape (and zeroed storage, so cleared
    queues compare bit-equal to freshly made ones).  Used by the serving
    front end's lane recycling: a finished query's channel queues are
    reset in place for the next admitted query, without reallocating."""
    return Queue(jnp.zeros_like(q.data), jnp.zeros_like(q.count))


def queue_push(q: Queue, rows: jax.Array, mask: jax.Array) -> tuple[Queue, jax.Array]:
    """Append ``rows[mask]`` (preserving row order) to the queue tail.

    Rows that would exceed capacity are dropped and counted — callers are
    expected to have reserved space (credit/budget) so that ``dropped == 0``;
    the counter exists so tests and production monitors can assert the
    backpressure invariant, like the paper's "CQ full" check.

    The masked rows are compacted to the front by a stable sort that
    carries them as payload, and the compacted block is written at the
    tail with one slice update: no per-row scatter, which a TPU runs one
    row at a time.  Only the pushed rows change.

    Returns (new_queue, n_dropped).
    """
    cap, w = q.data.shape
    n = rows.shape[0]
    m = mask.astype(jnp.int32)
    n_valid = m.sum(dtype=jnp.int32)
    n_push = jnp.minimum(n_valid, cap - q.count)
    cols = jax.lax.sort((1 - m,) + tuple(rows[:, j] for j in range(w)),
                        num_keys=1, is_stable=True)[1:]
    ext = jnp.concatenate([q.data, jnp.zeros((n, w), jnp.int32)], 0)
    ext = jax.lax.dynamic_update_slice_in_dim(
        ext, jnp.stack(cols, axis=1), q.count, axis=0)[:cap]
    slot = jnp.arange(cap, dtype=jnp.int32)[:, None]
    pushed = (slot >= q.count) & (slot < q.count + n_push)
    return (Queue(jnp.where(pushed, ext, q.data), q.count + n_push),
            n_valid - n_push)


def queue_take_front(q: Queue, n: jax.Array, max_n: int) -> tuple[jax.Array, jax.Array, Queue]:
    """Pop the first ``min(n, count)`` entries (FIFO). ``max_n`` is the static
    bound on n; the returned buffer has shape (min(max_n, cap), width).

    The queue is compacted to the front, so the pop is a slice and the
    rest moves down by one dynamic slice (rows at or past the new count
    are don't-cares)."""
    cap, w = q.data.shape
    eff = min(max_n, cap)
    n = jnp.minimum(jnp.minimum(n, q.count), eff).astype(jnp.int32)
    taken = q.data[:eff]
    taken_valid = jnp.arange(eff, dtype=jnp.int32) < n
    ext = jnp.concatenate([q.data, jnp.zeros((eff, w), jnp.int32)], 0)
    rest = jax.lax.dynamic_slice_in_dim(ext, n, cap, axis=0)
    return taken, taken_valid, Queue(rest, q.count - n)


def occurrence_index(dest: jax.Array, valid: jax.Array, num_dest: int) -> jax.Array:
    """For each valid element, its 0-based occurrence rank among earlier valid
    elements with the same ``dest``. Invalid elements get rank >= cap.

    This is the vectorized equivalent of the paper's per-channel slot
    assignment: element i may claim slot ``occ[i]`` of channel ``dest[i]``.
    """
    cap = dest.shape[0]
    ar = jnp.arange(cap, dtype=jnp.int32)
    d = jnp.where(valid, dest, num_dest)  # invalid -> trash group
    order = jnp.argsort(d * jnp.int32(cap) + ar)  # unique keys: group, then FIFO
    ds = d[order]
    new_grp = jnp.concatenate([jnp.ones((1,), bool), ds[1:] != ds[:-1]])
    grp_start = jax.lax.associative_scan(jnp.maximum, jnp.where(new_grp, ar, 0))
    occ_sorted = ar - grp_start
    occ = jnp.zeros((cap,), jnp.int32).at[order].set(occ_sorted)
    return jnp.where(valid, occ, jnp.int32(cap))


def histogram(dest: jax.Array, valid: jax.Array, num_dest: int) -> jax.Array:
    """Per-destination counts of valid elements."""
    return jnp.zeros((num_dest,), jnp.int32).at[
        jnp.where(valid, dest, num_dest - 1)
    ].add(valid.astype(jnp.int32))
