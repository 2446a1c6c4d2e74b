"""Pallas tile-grid execution backend for the Dalorex engine round.

One grid program = one Dalorex tile.  The engine's per-round hot path —
the queue->scan->route->fold legs of ``engine.make_round`` — is re-expressed
here as Pallas kernels whose *block* is the tile's VMEM-resident
vertex/edge shard.  Under ``LocalComm`` the engine vmaps per-tile stages,
and Pallas's batching rule turns the vmapped tile axis into a leading grid
dimension — literally one grid program per tile; under ``AxisComm``
(shard_map SPMD) each device *is* one tile and the kernels run gridless on
its shard.  The query-lane axis of ``repro.serve`` (the round vmapped over
``(B,)`` concurrent traversals) rides the same batching rule as one more
leading grid dimension — a ``(B, T)`` grid of programs, no kernel changes.
See DESIGN.md "Pallas backend" for the tile-grid mapping, the per-tile
VMEM budget, and the TPU (non-interpret) caveats.

Two granularities share one set of *pure bodies* (:func:`frontier_take`,
:func:`fifo_turn`, :func:`queue_append`, :func:`segment_gather`,
:func:`scatter_body` — plain jnp value->value functions):

* **Standalone kernels** — :func:`frontier_pop`, :func:`queue_push_pop`,
  :func:`edge_scan_gather`, :func:`fold_scatter` wrap one body each in its
  own ``pallas_call`` (PR4's four-launch leg, kept as the
  ``pallas_fuse=False`` legacy path and for the kernel-twin tests).
* **The fused leg** — :func:`fused_leg_call` runs a *whole* engine channel
  leg (frontier-pop -> FIFO turn -> transform -> spill re-queue ->
  split-remainder re-push -> segment-gather -> scatter-fold, whatever the
  stage composes) as ONE ``pallas_call``: the per-tile stage function
  itself becomes the kernel body, every intermediate lives in
  VMEM-resident registers/scratch of that single launch, and the XLA glue
  that used to run *between* kernels (the mid-round spill re-queue and the
  split-remainder re-push) is absorbed into the same body via the pure
  queue bodies.  ``Ctx.fused`` routes the building blocks of
  ``core/program.py`` to the pure bodies so a fused leg never nests a
  ``pallas_call``.  The fold stays the in-kernel ``.at[]`` scatter idiom
  of ``kernels/scatter_update`` (owner-local, atomic-free) rather than the
  one-hot matmul alternative — bit-identical to XLA in interpret mode; on
  a real TPU a scatter-add drains in-order per row, so add folds may drift
  by the last ulp vs XLA's unspecified reduction order (DESIGN.md).

Every ``pallas_call`` dispatch is *counted*: the public wrappers call
:func:`repro.kernels.engine.launches.record` at trace time, the engine
brackets its round with :func:`..launches.tally`, and the per-round total
surfaces as ``Stats.launches`` (fig11's ``launches_per_round`` column —
one launch per leg fused, vs 4+ standalone launches plus XLA glue before).

Interpret mode is decided in one place, from the platform
(:func:`interpret_mode`): the kernels run through the Pallas interpreter on
the CPU, so CPU CI executes the very same kernel bodies, and are compiled
everywhere else.  The TPU compiler refuses the bodies today
(:data:`TPU_REFUSALS`), so on a TPU ``Program.validate`` rejects a
``backend="pallas"`` run at config time instead of interpreting it.  Every
kernel is **bit-identical** to its XLA twin in ``core/program.py`` /
``core/queues.py`` (the backend equivalence contract
``tests/test_backend_pallas.py`` + ``tests/test_fused_leg.py`` enforce).
``pad_lanes=True`` additionally pads every fused-leg operand block out to
the TPU's (8, 128) sublane x lane f32 tile (sliced back to logical shape
inside the body), so the same harness lands aligned blocks when compiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.engine.launches import record

# float32 max as a python float (pallas kernels cannot capture traced
# consts); must equal core.program.INF so the fold's neutral element is the
# same "unreached" sentinel the XLA legs use.
_INF = 3.4028234663852886e38

# What the TPU compiler refuses in the pure bodies below, found by
# compiling each standalone kernel for a described v5e (the fused leg
# composes the same bodies).
TPU_REFUSALS = (
    "frontier_pop, queue_push_pop: unimplemented primitive cumsum "
    "(frontier_take, fifo_turn, queue_append)",
    "fold_scatter: unimplemented primitives scatter-min, scatter-add "
    "(scatter_body)",
    "edge_scan_gather, edge_scan_stream: only 2D gather is supported "
    "(segment_gather, segment_stream)",
)


def interpret_mode() -> bool:
    """Whether the engine kernels run through the Pallas interpreter: on
    the CPU, and nowhere else."""
    return jax.default_backend() == "cpu"


def require_runnable():
    """Raise unless the engine kernels can run on this platform: they are
    interpreted on the CPU, and compiled elsewhere, where the TPU compiler
    refuses their bodies (:data:`TPU_REFUSALS`)."""
    if not interpret_mode():
        raise NotImplementedError(
            f"backend='pallas' cannot run on {jax.default_backend()!r}: the "
            "engine kernels are interpreted only on the CPU, and the TPU "
            "compiler refuses them (" + "; ".join(TPU_REFUSALS)
            + "). Use backend='xla'.")


# ==========================================================================
# Pure bodies: value -> value, shared by the standalone kernels and the
# fused leg (Ctx.fused routes core/program.py's building blocks here).
# ==========================================================================

def frontier_take(mask: jax.Array, k: jax.Array, k_max: int):
    """Body of :func:`frontier_pop`: first ``min(k, popcount)`` set bits,
    FIFO by position, compacted with a cumsum-rank scatter (no sort).
    Returns (idx (k_max,) i32, valid (k_max,) bool, cleared_mask)."""
    n = mask.shape[0]
    ar = jnp.arange(n, dtype=jnp.int32)
    mi = mask.astype(jnp.int32)
    rank = jnp.cumsum(mi) - mi            # 0-based rank among set bits
    take = mask & (rank < k)
    # rank < k <= k_max for every taken bit, so the scatter stays in-bounds;
    # slot k_max is the trash slot for the rest.
    slot = jnp.where(take, rank, jnp.int32(k_max))
    idx = jnp.zeros((k_max + 1,), jnp.int32).at[slot].set(ar)
    n_take = take.sum(dtype=jnp.int32)
    valid = jnp.arange(k_max, dtype=jnp.int32) < n_take
    return idx[:k_max], valid, mask & ~take


def fifo_turn(data: jax.Array, count: jax.Array, rows: jax.Array,
              valid: jax.Array, n: jax.Array, max_n: int):
    """Body of :func:`queue_push_pop`: one circular-FIFO turn — append the
    valid fresh rows at the tail (cumsum slot claim, overflow -> drops),
    then pop ``min(n, count')`` off the front with a single shift.

    Returns (taken (min(max_n, cap), w), taken_valid, new_data (cap, w),
    new_count () i32, drops () i32).  The taken buffer is clamped to the
    capacity exactly like the XLA ``queue_take_front`` slice — which makes
    the zero-capacity degenerate (a cap-0 spill-only channel) an explicit
    early-out here: nothing can be stored, so the pop is the empty (0, w)
    buffer and every offered row is a counted drop, reproducing XLA's
    empty-slice behavior instead of relying on it.
    """
    cap, w = data.shape
    if cap == 0:
        drops = valid.sum(dtype=jnp.int32)
        return (jnp.zeros((0, w), jnp.int32), jnp.zeros((0,), bool),
                data, count + 0, drops)
    data2, count2, drops = queue_append(data, count, rows, valid)
    eff = min(max_n, cap)
    n_pop = jnp.minimum(n, count2)
    taken = data2[:eff]
    tvalid = jnp.arange(eff, dtype=jnp.int32) < n_pop
    src = jnp.minimum(jnp.arange(cap, dtype=jnp.int32) + n_pop, cap - 1)
    return taken, tvalid, data2[src], count2 - n_pop, drops


def queue_append(data: jax.Array, count: jax.Array, rows: jax.Array,
                 valid: jax.Array):
    """Push-only FIFO tail append — the in-kernel twin of
    ``core.queues.queue_push`` (same cumsum slot claim, same trash-slot
    scatter, bit-identical), used by the fused leg to absorb the mid-round
    spill re-queue and the split-remainder re-push that previously ran as
    XLA glue between kernels.  Returns (new_data, new_count, drops)."""
    cap, w = data.shape
    mi = valid.astype(jnp.int32)
    offs = count + jnp.cumsum(mi) - mi
    ok = valid & (offs < cap)
    slot = jnp.where(ok, offs, jnp.int32(cap))  # cap = trash slot
    ext = jnp.concatenate([data, jnp.zeros((1, w), jnp.int32)], axis=0)
    data2 = ext.at[slot].set(rows)[:cap]
    n_push = ok.sum(dtype=jnp.int32)
    return data2, count + n_push, mi.sum() - n_push


def segment_gather(edge_dst: jax.Array, edge_val: jax.Array,
                   start: jax.Array, stop: jax.Array, rv: jax.Array,
                   max_t2: int):
    """Body of :func:`edge_scan_gather`: the T2 ragged segment gather out
    of the tile's edge shard.  Returns (nb, w, jvalid), each (R, max_t2)."""
    e_chunk = edge_dst.shape[0]
    length = jnp.where(rv, stop - start, 0)
    local0 = jnp.where(rv, start % e_chunk, 0)
    j = jnp.arange(max_t2, dtype=jnp.int32)[None, :]
    eidx = local0[:, None] + j                    # (R, MAX_T2)
    jvalid = rv[:, None] & (j < length[:, None])
    eidx_c = jnp.minimum(eidx, e_chunk - 1)
    nb = edge_dst[eidx_c]
    return nb, edge_val[eidx_c], jvalid & (nb >= 0)


def segment_stream(edge_dst: jax.Array, edge_val: jax.Array,
                   start: jax.Array, stop: jax.Array, rv: jax.Array,
                   max_t2: int, window: int):
    """Body of :func:`edge_scan_stream`: T2 over an **HBM-resident** edge
    shard, consumed through double-buffered segment DMA windows.

    The prefetched head flit of each delivered range message carries the
    global edge index — the true scalar-prefetch form of T2: the local
    offset ``start % e_chunk`` is known *before* the edge data is touched,
    so the engine issues the window fetch for message ``r`` while message
    ``r-1`` computes.  This body is the value-exact emulation of that
    discipline: each message stages the two consecutive ``window``-sized
    DMA windows covering its segment (``base = (local0 // window) *
    window``; the aligned window plus its successor — the double buffer),
    then gathers its ``<= max_t2`` edges out of the staging buffer only.

    Bit-identity with the VMEM-direct :func:`segment_gather`: upstream
    ``range_split`` bounds every message at the chunk border and at
    MAX_T2, and :func:`repro.mem.resolve_window` guarantees ``window >=
    max_t2`` — so a segment starting anywhere in the aligned window ends
    strictly inside the next one, and every *valid* lane reads the same
    shard element either way (invalid lanes are don't-cares masked by
    ``jvalid`` at every consumer).  The engine counts 2 windows per
    delivered message into ``Stats.hbm_windows`` and prices the streamed
    words at ``t_hbm``/``e_hbm`` (DESIGN.md "Memory spaces").

    Returns (nb, w, jvalid), each (R, max_t2) — same contract as
    :func:`segment_gather`.
    """
    e_chunk = edge_dst.shape[0]
    length = jnp.where(rv, stop - start, 0)
    local0 = jnp.where(rv, start % e_chunk, 0)
    base = (local0 // window) * window        # aligned window start
    # Stage the double buffer: 2*window consecutive elements from base.
    k = jnp.arange(2 * window, dtype=jnp.int32)[None, :]
    sidx = jnp.minimum(base[:, None] + k, e_chunk - 1)  # (R, 2*window)
    stage_dst = edge_dst[sidx]
    stage_val = edge_val[sidx]
    # Gather the segment out of the staging buffer only.
    j = jnp.arange(max_t2, dtype=jnp.int32)[None, :]
    jvalid = rv[:, None] & (j < length[:, None])
    off = jnp.minimum((local0 - base)[:, None] + j, 2 * window - 1)
    nb = jnp.take_along_axis(stage_dst, off, axis=1)
    w = jnp.take_along_axis(stage_val, off, axis=1)
    return nb, w, jvalid & (nb >= 0)


def scatter_body(target: jax.Array, lidx: jax.Array, vals: jax.Array,
                 valid: jax.Array, op: str):
    """Body of :func:`fold_scatter`: the T3 owner-local scatter-min /
    scatter-add (``lidx`` maps invalid rows to the ``v_chunk`` trash slot).
    The ``kernels/scatter_update`` in-kernel scatter idiom — atomic-free
    because every write targets the tile's own shard."""
    v_chunk = target.shape[0]
    neutral = _INF if op == "min" else 0.0
    ext = jnp.concatenate(
        [target, jnp.full((1,), neutral, jnp.float32)])
    masked = jnp.where(valid, vals, jnp.float32(neutral))
    if op == "min":
        ext = ext.at[lidx].min(masked)
    else:
        ext = ext.at[lidx].add(masked)
    return ext[:v_chunk]


# ==========================================================================
# The fused leg: one pallas_call per engine channel leg.
# ==========================================================================

def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _lane_pad(shape: tuple) -> tuple:
    """The (8, 128) f32 tile rule: last dim to a lane multiple, second-last
    (when present) to a sublane multiple.  Scalars ride as (1,) unpadded
    (they belong in SMEM on real hardware, not a lane tile)."""
    s = list(shape)
    s[-1] = _ceil_to(s[-1], 128)
    if len(s) >= 2:
        s[-2] = _ceil_to(s[-2], 8)
    return tuple(s)


def fused_leg_call(fn, *operands, pad_lanes: bool = False):
    """Run the per-tile stage ``fn(*operands)`` as ONE Pallas launch.

    ``fn`` is a pure pytree -> pytree function (an engine channel leg:
    state in, state + messages out).  The harness flattens the operand
    pytrees into kernel refs, makes the *stage itself* the kernel body —
    so every intermediate of the chained frontier-pop -> FIFO turn ->
    spill re-queue -> remainder re-push -> segment-gather -> scatter-fold
    stays resident in the launch (VMEM on a TPU) — and unflattens the
    outputs, shaped via ``jax.eval_shape``.  Leaf plumbing:

    * () scalars ride as (1,) refs and are restored inside the body;
    * zero-size leaves (e.g. a cap-0 queue's data) bypass the launch —
      materialized as zeros on each side, since a 0-element ref is
      meaningless;
    * ``pad_lanes=True`` pads every non-scalar block to the (8, 128)
      sublane x lane f32 tile on the way in (zeros) and slices each ref
      back to its logical shape inside the body, so TPU-aligned blocks
      and the interpret path compute the identical values.

    Under ``LocalComm`` the engine vmaps this call and the batching rule
    turns the tile axis into the Pallas grid (one grid program per tile);
    a serving lane axis batches the same way.  Counts as one launch with
    :mod:`repro.kernels.engine.launches`.
    """
    flat_in, in_tree = jax.tree.flatten(operands)
    flat_in = [jnp.asarray(x) for x in flat_in]
    out_avals = jax.eval_shape(fn, *operands)
    flat_out, out_tree = jax.tree.flatten(out_avals)
    in_specs = [(tuple(x.shape), x.dtype) for x in flat_in]
    out_specs = [(tuple(a.shape), a.dtype) for a in flat_out]

    def live(shape):
        return int(np.prod(shape, dtype=np.int64)) > 0 or shape == ()

    def to_call(x):
        shape = tuple(x.shape)
        if shape == ():
            return x.reshape(1)
        tgt = _lane_pad(shape) if pad_lanes else shape
        if tgt != shape:
            x = jnp.pad(x, [(0, t - s) for s, t in zip(shape, tgt)])
        return x

    def from_ref(ref, shape):
        v = ref[...]
        if shape == ():
            return v[0]
        return v[tuple(slice(0, s) for s in shape)]

    n_in = sum(live(s) for s, _ in in_specs)

    def kernel(*refs):
        it = iter(refs[:n_in])
        vals = []
        for shape, dtype in in_specs:
            if not live(shape):
                vals.append(jnp.zeros(shape, dtype))
            else:
                vals.append(from_ref(next(it), shape))
        outs = jax.tree.leaves(fn(*jax.tree.unflatten(in_tree, vals)))
        ot = iter(refs[n_in:])
        for o, (shape, _) in zip(outs, out_specs):
            if not live(shape):
                continue
            ref = next(ot)
            if shape == ():
                ref[...] = o.reshape(1)
            else:
                tgt = _lane_pad(shape) if pad_lanes else shape
                if tgt != shape:
                    o = jnp.pad(o, [(0, t - s) for s, t in zip(shape, tgt)])
                ref[...] = o

    call_ins = [to_call(x) for x, (s, _) in zip(flat_in, in_specs)
                if live(s)]
    out_shape = tuple(
        jax.ShapeDtypeStruct(
            (1,) if s == () else (_lane_pad(s) if pad_lanes else s), d)
        for s, d in out_specs if live(s))
    record()
    raw = pl.pallas_call(kernel, out_shape=out_shape,
                         interpret=interpret_mode())(*call_ins)
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    it = iter(raw)
    restored = []
    for shape, dtype in out_specs:
        if not live(shape):
            restored.append(jnp.zeros(shape, dtype))
        elif shape == ():
            restored.append(next(it)[0])
        else:
            restored.append(
                next(it)[tuple(slice(0, s) for s in shape)])
    return jax.tree.unflatten(out_tree, restored)


# ==========================================================================
# Standalone kernels (PR4's four-launch leg; the pallas_fuse=False path and
# the kernel-twin test surface).  Each wraps one pure body in a pallas_call;
# the plain public wrappers record the launch, then dispatch to an inner
# jitted impl (a jit cache hit would skip a record placed inside).
# ==========================================================================

# --------------------------------------------------------------------------
# T4: fused frontier pop (take_first_k as one kernel).
# --------------------------------------------------------------------------

def _frontier_pop_kernel(k_ref, mask_ref, idx_ref, valid_ref, rem_ref):
    idx, valid, rem = frontier_take(mask_ref[...], k_ref[0],
                                    idx_ref.shape[0])
    idx_ref[...] = idx
    valid_ref[...] = valid
    rem_ref[...] = rem


@functools.partial(jax.jit, static_argnames=("k_max",))
def _frontier_pop(mask, k, k_max):
    n = mask.shape[0]
    return pl.pallas_call(
        _frontier_pop_kernel,
        out_shape=(jax.ShapeDtypeStruct((k_max,), jnp.int32),
                   jax.ShapeDtypeStruct((k_max,), jnp.bool_),
                   jax.ShapeDtypeStruct((n,), jnp.bool_)),
        interpret=interpret_mode(),
    )(jnp.asarray(k, jnp.int32).reshape(1), mask)


def frontier_pop(mask: jax.Array, k: jax.Array, k_max: int):
    """Pop the first ``min(k, popcount)`` set bits of the tile's frontier
    bitmap, FIFO by position — the Pallas twin of
    :func:`repro.core.program.take_first_k`.

    mask: (n,) bool; k: () int32 dynamic budget (<= k_max).
    Returns (idx (k_max,) i32, valid (k_max,) bool, cleared_mask (n,) bool).
    Invalid slots of ``idx`` hold 0 (the XLA twin holds unpopped positions
    there); both are don't-cares masked by ``valid`` everywhere downstream.
    """
    record()
    return _frontier_pop(mask, k, k_max)


# --------------------------------------------------------------------------
# Fused circular-FIFO turn: push fresh tasks, pop the TSU budget.
# --------------------------------------------------------------------------

def _queue_push_pop_kernel(n_ref, data_ref, count_ref, rows_ref, pvalid_ref,
                           taken_ref, tvalid_ref, ndata_ref, ncount_ref,
                           drops_ref):
    taken, tvalid, ndata, ncount, drops = fifo_turn(
        data_ref[...], count_ref[0], rows_ref[...], pvalid_ref[...],
        n_ref[0], taken_ref.shape[0])
    taken_ref[...] = taken
    tvalid_ref[...] = tvalid
    ndata_ref[...] = ndata
    ncount_ref[0] = ncount
    drops_ref[0] = drops


@functools.partial(jax.jit, static_argnames=("max_n",))
def _queue_push_pop(data, count, rows, valid, n, max_n):
    return pl.pallas_call(
        _queue_push_pop_kernel,
        out_shape=(jax.ShapeDtypeStruct((max_n, data.shape[1]), jnp.int32),
                   jax.ShapeDtypeStruct((max_n,), jnp.bool_),
                   jax.ShapeDtypeStruct(data.shape, jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        interpret=interpret_mode(),
    )(jnp.asarray(n, jnp.int32).reshape(1), data,
      jnp.asarray(count, jnp.int32).reshape(1), rows, valid)


def queue_push_pop(data: jax.Array, count: jax.Array, rows: jax.Array,
                   valid: jax.Array, n: jax.Array, max_n: int):
    """One fused FIFO turn: ``queue_push(rows[valid])`` then
    ``queue_take_front(min(n, count'))`` in a single kernel.

    data: (cap, w) i32 queue buffer whose first ``count`` rows are live;
    rows/valid: the fresh tasks; n: () i32 dynamic pop budget (<= max_n).
    Returns (taken (max_n, w), taken_valid (max_n,), new_data (cap, w),
    new_count () i32, drops () i32).  Live rows (< new_count) and the taken
    buffer are bit-identical to the two-call XLA path; rows at or beyond
    the live count are unobservable garbage in both backends.

    The zero-capacity degenerate (a cap-0 spill-only channel) is an
    explicit early-out — no kernel is launched, the pop is the empty
    ``(0, w)`` buffer, every offered row counts as a drop — matching the
    shapes XLA's empty ``queue_take_front`` slice produces instead of
    relying on them.
    """
    cap = data.shape[0]
    if cap == 0:
        return fifo_turn(data, count, rows, valid, n, max_n)
    assert max_n <= cap, f"pop budget bound {max_n} > queue capacity {cap}"
    record()
    taken, tvalid, ndata, ncount, drops = _queue_push_pop(
        data, count, rows, valid, n, max_n)
    return taken, tvalid, ndata, ncount[0], drops[0]


# --------------------------------------------------------------------------
# T2: segment gather over the tile's edge shard.
# --------------------------------------------------------------------------

def _edge_scan_kernel(edge_dst_ref, edge_val_ref, start_ref, stop_ref,
                      rv_ref, nb_ref, w_ref, jvalid_ref):
    nb, w, jvalid = segment_gather(
        edge_dst_ref[...], edge_val_ref[...], start_ref[...], stop_ref[...],
        rv_ref[...], nb_ref.shape[1])
    nb_ref[...] = nb
    w_ref[...] = w
    jvalid_ref[...] = jvalid


@functools.partial(jax.jit, static_argnames=("max_t2",))
def _edge_scan_gather(edge_dst, edge_val, start, stop, rv, max_t2):
    r = start.shape[0]
    return pl.pallas_call(
        _edge_scan_kernel,
        out_shape=(jax.ShapeDtypeStruct((r, max_t2), jnp.int32),
                   jax.ShapeDtypeStruct((r, max_t2), jnp.float32),
                   jax.ShapeDtypeStruct((r, max_t2), jnp.bool_)),
        interpret=interpret_mode(),
    )(edge_dst, edge_val, start, stop, rv)


def edge_scan_gather(edge_dst: jax.Array, edge_val: jax.Array,
                     start: jax.Array, stop: jax.Array, rv: jax.Array,
                     max_t2: int):
    """The T2 segment gather: for each delivered range message, read its
    ``[start, stop)`` slice (bounded by MAX_T2 upstream) out of the tile's
    VMEM-resident edge shard.

    The head flit is the address — the received global edge index maps to a
    local offset (``start % e_chunk``) and indexes straight into the shard,
    the ragged-CSR analogue of ``kernels/spmv``'s scalar-prefetched column
    index.  Returns (nb (R, max_t2) i32, w (R, max_t2) f32,
    jvalid (R, max_t2) bool), bit-identical to the inline XLA gather in
    :func:`repro.core.program.edge_scan`.
    """
    record()
    return _edge_scan_gather(edge_dst, edge_val, start, stop, rv, max_t2)


# --------------------------------------------------------------------------
# T2 over an HBM-resident shard: double-buffered segment-DMA stream.
# --------------------------------------------------------------------------

def _edge_stream_kernel(edge_dst_ref, edge_val_ref, start_ref, stop_ref,
                        rv_ref, nb_ref, w_ref, jvalid_ref, *, window):
    nb, w, jvalid = segment_stream(
        edge_dst_ref[...], edge_val_ref[...], start_ref[...], stop_ref[...],
        rv_ref[...], nb_ref.shape[1], window)
    nb_ref[...] = nb
    w_ref[...] = w
    jvalid_ref[...] = jvalid


@functools.partial(jax.jit, static_argnames=("max_t2", "window"))
def _edge_scan_stream(edge_dst, edge_val, start, stop, rv, max_t2, window):
    r = start.shape[0]
    return pl.pallas_call(
        functools.partial(_edge_stream_kernel, window=window),
        out_shape=(jax.ShapeDtypeStruct((r, max_t2), jnp.int32),
                   jax.ShapeDtypeStruct((r, max_t2), jnp.float32),
                   jax.ShapeDtypeStruct((r, max_t2), jnp.bool_)),
        interpret=interpret_mode(),
    )(edge_dst, edge_val, start, stop, rv)


def edge_scan_stream(edge_dst: jax.Array, edge_val: jax.Array,
                     start: jax.Array, stop: jax.Array, rv: jax.Array,
                     max_t2: int, window: int):
    """The T2 segment scan when the tile's edge shard is declared in HBM:
    each delivered range message stages its two covering DMA windows into
    VMEM (the double buffer) and gathers its segment from the staging
    buffer — never word-random from the shard (:func:`segment_stream` is
    the body; the fused leg calls it directly via ``Ctx.fused``).

    Bit-identical in every valid lane to :func:`edge_scan_gather` on the
    same shard (the space-equivalence contract,
    ``tests/test_memspace.py``); requires ``window >= max_t2``
    (:func:`repro.mem.resolve_window`).
    """
    record()
    return _edge_scan_stream(edge_dst, edge_val, start, stop, rv, max_t2,
                             window)


# --------------------------------------------------------------------------
# T3: CQ drain + owner-local scatter fold.
# --------------------------------------------------------------------------

def _fold_scatter_kernel(target_ref, lidx_ref, vals_ref, valid_ref, out_ref,
                         *, op):
    out_ref[...] = scatter_body(target_ref[...], lidx_ref[...],
                                vals_ref[...], valid_ref[...], op)


@functools.partial(jax.jit, static_argnames=("op",))
def _fold_scatter(target, lidx, vals, valid, op):
    return pl.pallas_call(
        functools.partial(_fold_scatter_kernel, op=op),
        out_shape=jax.ShapeDtypeStruct(target.shape, jnp.float32),
        interpret=interpret_mode(),
    )(target, lidx, vals, valid)


def fold_scatter(target: jax.Array, lidx: jax.Array, vals: jax.Array,
                 valid: jax.Array, op: str = "min"):
    """The T3 fold: drain a delivered CQ buffer into the tile's owned
    ``(v_chunk,)`` slice — scatter-min for relaxations, scatter-add for
    accumulations.  Atomic-free: all writes land in this tile's own shard
    (Section III-A), so the kernel needs no synchronization.

    target: (v_chunk,) f32; lidx: (R,) i32 local indices with ``v_chunk``
    as the trash slot for invalid rows; vals/valid: the drained payloads.
    Bit-identical to the XLA ``ext.at[lidx].min/add`` twin in
    :func:`repro.core.program.scatter_fold`.
    """
    assert op in ("min", "add"), op
    record()
    return _fold_scatter(target, lidx, vals, valid, op)
