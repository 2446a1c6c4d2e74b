"""The Dalorex task-based programming model (paper contribution 2).

The paper's Listing 1 (BFS as T1/T2/T3 tasks) is *one program* in a general
model: arbitrary tasks execute at the tile that owns their target data, and
each task type has its own network channel with per-destination channel
queues (CQs).  This module is that model, lifted out of the engine:

* :class:`TaskSpec` — one task channel: message payload width, the owner
  function that decodes the destination tile from the head flit (headerless
  routing), the handler that runs at the owner (reads/writes the local shard
  slice and emits successor messages), CQ capacity, and local task-queue
  capacity/budget knobs.
* :class:`Program` — an ordered chain of task channels executed once per
  engine round (a DAG unrolled in channel order), plus the *source* that
  turns local frontier bits into the first channel's tasks (the paper's
  T4/T1 head).  ``engine.make_round`` iterates the channels generically:
  one ``queue -> budget -> route -> handler -> spill`` leg per channel.

Two queue disciplines exist, both from the paper:

* ``queued=True`` — a real task queue (the paper's IQ/OQ pair): fresh tasks
  are pushed in, the TSU budget pops them, and a ``transform`` turns each
  popped task into a bounded network message (the T1 range split of Listing
  1, ``MAX_T2``), re-pushing the remainder.  Spilled messages replay through
  the same queue; the split is idempotent on already-bounded messages.
* ``queued=False`` — a spill/replay queue only (the paper's "CQ full ->
  retry next invocation"): fresh messages go straight to the network behind
  the replayed backlog.

The five seed workloads compile to the classic 3-task program (T1 range
split -> T2 edge scan -> T3 fold) via :func:`classic_program`; k-core
peeling reuses the shape with a threshold fold; 2-hop triangle counting is
a 4-channel chain (range -> wedge -> second range at the neighbor's owner
-> intersection-count fold) that the old hard-wired pipeline could not
express.

Everything here is backend-agnostic: handlers are pure per-tile functions,
identical under ``LocalComm`` (vmap emulation) and ``AxisComm``
(shard_map SPMD).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.queues import f2i, i2f
from repro.kernels.engine import (edge_scan_gather, edge_scan_stream,
                                  fold_scatter, frontier_pop, frontier_take,
                                  require_runnable, scatter_body,
                                  segment_gather, segment_stream)
from repro.mem import check_alloc, check_budgets, resolve_window

INF = jnp.float32(np.finfo(np.float32).max)


class Ctx(NamedTuple):
    """Static per-run context threaded to sources/transforms/handlers.

    ``backend`` is the execution backend of the *current* leg, resolved by
    ``engine.make_round`` from ``EngineConfig.backend`` and the channel's
    :attr:`TaskSpec.backend` hint — "xla" runs the building blocks inline,
    "pallas" dispatches them to the :mod:`repro.kernels.engine` tile-grid
    kernels (bit-identical by contract; see DESIGN.md "Pallas backend").

    ``fused`` means the *whole leg* is already executing inside one Pallas
    launch (``engine.make_round`` wrapped the stage in
    :func:`repro.kernels.engine.fused_leg_call`): the building blocks then
    call the pure kernel *bodies* inline — same ops, same bits — instead
    of nesting a ``pallas_call`` per block.

    ``edge_space`` is the resolved memory space of the tile's edge shard
    (``repro.mem``; "vmem" = word-random resident, "hbm" = consumed
    through double-buffered segment-DMA windows of ``hbm_window``
    elements) — resolved by ``engine.make_round`` from
    ``EngineConfig.edge_space`` and the Program's own pin via
    :func:`resolve_edge_space`; the :func:`edge_scan` building block
    dispatches on it.
    """

    cfg: object   # EngineConfig (static dataclass)
    T: int
    e_chunk: int
    v_chunk: int
    backend: str = "xla"
    fused: bool = False
    edge_space: str = "vmem"
    hbm_window: int = 0


# --------------------------------------------------------------------------
# Legacy algorithm specifications (kept as the high-level front-end for the
# five paper workloads; they compile to Programs via classic_program).
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AlgSpec:
    """How values flow through the classic T1/T2/T3 pipeline.

    ``emit``   — T2's payload: f(parent_value, edge_value) for a neighbor.
    ``kind``   — T3's fold: "min" (relaxation; improvements re-enter the
                 frontier) or "add" (accumulation into ``acc``; single epoch).
    ``parent`` — what T1 loads from the local shard for a frontier vertex.
    """

    name: str
    kind: str  # "min" | "add"
    emit: str  # "plus1" | "plus_w" | "copy" | "times_w"
    parent: str = "value"  # "value" | "value_over_deg"


BFS = AlgSpec("bfs", "min", "plus1")
SSSP = AlgSpec("sssp", "min", "plus_w")
WCC = AlgSpec("wcc", "min", "copy")
PAGERANK = AlgSpec("pagerank", "add", "copy", parent="value_over_deg")
SPMV = AlgSpec("spmv", "add", "times_w")

# Name registry of the classic single-program workloads — the one place a
# front end (repro.serve, examples, benchmarks) resolves an app string to
# its spec.  The min-kind entries are the *point-query* apps: a single
# source vertex fully determines the run, which is what makes them
# servable as batched query lanes (repro.serve.lanes).
CLASSIC = {a.name: a for a in (BFS, SSSP, WCC, PAGERANK, SPMV)}
POINT_QUERY_APPS = tuple(sorted(n for n, a in CLASSIC.items()
                                if a.kind == "min" and n != "wcc"))


def _emit(alg: AlgSpec, parent: jax.Array, w: jax.Array) -> jax.Array:
    if alg.emit == "plus1":
        return parent + 1.0
    if alg.emit == "plus_w":
        return parent + w
    if alg.emit == "copy":
        return parent
    if alg.emit == "times_w":
        return parent * w
    raise ValueError(alg.emit)


# --------------------------------------------------------------------------
# TaskSpec / Program.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TaskSpec:
    """One task channel of a Program.

    ``owner`` decodes the destination tile from the head flit: the strings
    "edge" / "vertex" select the equal-chunk owner of a placed edge / vertex
    index (``idx // chunk``); a callable ``owner(ctx)`` may return any
    ``msgs -> dest`` function for custom routings.

    ``knobs`` selects which EngineConfig triple supplies the default CQ
    capacity, queue capacity and pop budget ("range" -> ``cap_route_range``
    / ``cap_rangeq`` / ``r_pop``; "update" -> ``cap_route_update`` /
    ``cap_updq`` / ``u_pop``); the explicit ``cap_route`` / ``queue_cap`` /
    ``pop`` fields override them per channel.

    ``handler(ctx, me, sh, st, recv, recv_valid) -> (st, rows, valid, work)``
    runs at the owner tile on this channel's delivered messages and emits
    rows for the *next* channel (the last channel's rows are ignored).
    ``work`` is a per-tile scalar attributed to Stats by the ``work`` tag
    ("edges" -> edges_scanned/work_max, "updates" -> updates_applied).

    ``emit_factor`` bounds handler fan-out per received message (the int, or
    "max_t2" for edge scans) — it feeds the worst-case inflow formula of
    ``Program.min_caps`` that sizes the successor channel's queue.

    ``backend`` is the per-channel execution-backend hint: ``None`` inherits
    ``EngineConfig.backend``; "xla" / "pallas" pin this channel's queue and
    handler legs regardless of the config (e.g. a channel whose handler has
    no kernel form can stay on "xla" while the rest of the program runs on
    the tile-grid kernels).  Handlers built from the dispatching building
    blocks below (``frontier_source`` / ``edge_scan`` / ``scatter_fold``)
    honor the resolved backend via ``Ctx.backend``.

    ``space`` declares the memory space of this channel's task/spill
    queue (``repro.mem``): ``None`` defaults to "vmem" — the queue is the
    tile's working set, the paper's scratchpad FIFO.  The registry
    validates the declaration at allocation time (HBM holds only bulk
    edge shards), and ``Program.validate`` charges it against the
    space's per-tile budget.
    """

    name: str
    width: int
    owner: Union[str, Callable] = "vertex"
    knobs: str = "update"
    handler: Optional[Callable] = None
    queued: bool = False
    transform: Optional[Callable] = None
    emit_factor: Union[int, str] = 1
    work: str = ""
    cap_route: Optional[int] = None
    queue_cap: Optional[int] = None
    pop: Optional[int] = None
    backend: Optional[str] = None
    space: Optional[str] = None

    def resolve_space(self, cfg) -> str:
        """The declared memory space of this channel's queue buffer."""
        s = self.space if self.space is not None else "vmem"
        check_alloc(s, "queue", f"queue[{self.name}]")
        return s

    def resolve_backend(self, cfg) -> str:
        """The execution backend of this channel's legs under ``cfg``."""
        b = self.backend if self.backend is not None else \
            getattr(cfg, "backend", "xla")
        assert b in ("xla", "pallas"), f"unknown backend {b!r}"
        return b

    def route_cap(self, cfg) -> int:
        if self.cap_route is not None:
            return self.cap_route
        return (cfg.cap_route_range if self.knobs == "range"
                else cfg.cap_route_update)

    def qcap(self, cfg) -> int:
        if self.queue_cap is not None:
            return self.queue_cap
        return cfg.cap_rangeq if self.knobs == "range" else cfg.cap_updq

    def pop_budget(self, cfg) -> int:
        if self.pop is not None:
            return self.pop
        return cfg.r_pop if self.knobs == "range" else cfg.u_pop

    def emit_bound(self, cfg) -> int:
        f = cfg.max_t2 if self.emit_factor == "max_t2" else self.emit_factor
        return int(f)

    def owner_fn(self, ctx: Ctx) -> Callable:
        if callable(self.owner):
            return self.owner(ctx)
        chunk = ctx.e_chunk if self.owner == "edge" else ctx.v_chunk
        return lambda m: m[..., 0] // chunk


@dataclasses.dataclass(frozen=True)
class Program:
    """An ordered chain of task channels plus the frontier source.

    Per round the engine runs ``source`` (T4: frontier bits -> channel-0
    tasks) and then each channel's generic leg in order; channel ``i``'s
    handler output feeds channel ``i+1``.  Feedback edges (a fold re-arming
    the frontier) close the DAG *across* rounds through the frontier bitmap,
    exactly like the paper's T3 -> T1 loop.

    Per-buffer memory-space declarations (``repro.mem``): ``edge_space``
    is the tile's edge shard — ``None`` leaves it configurable
    (``EngineConfig.edge_space`` picks "vmem" or "hbm" at run time); a
    program whose handlers need word-random access to the shard (e.g.
    triangles' closing binary search) *pins* it to "vmem", and asking the
    config for "hbm" anyway is a :func:`resolve_edge_space` error.
    ``state_space`` is the vertex state (value/acc/frontier bitmaps +
    ptr/deg) — always the tile's working set, so "vmem".  Channel queues
    declare their own space on each :class:`TaskSpec`.
    """

    name: str
    channels: tuple
    source: Optional[Callable] = None
    edge_space: Optional[str] = None
    state_space: str = "vmem"

    def round_inflow(self, cfg, T: int) -> tuple:
        """Per-channel worst-case one-round queue inflow.

        Queued channels absorb fresh tasks plus their own re-pushed split
        remainders; spill-only channels absorb the predecessor handler's
        full burst behind the replay budget.  Physical NoCs additionally
        spill mid-route messages into *waypoint* queues, so every inbound
        CQ slot of the leg must fit too.  The TSU stops a channel's
        producers while its queue has less room than this
        (``engine._budgets``)."""
        physical = cfg.noc != "ideal"
        out = []
        for i, ch in enumerate(self.channels):
            cap_i = ch.route_cap(cfg)
            pop_i = ch.pop_budget(cfg)
            if i == 0:
                feed = cfg.f_pop
            else:
                prev = self.channels[i - 1]
                feed = T * prev.route_cap(cfg) * prev.emit_bound(cfg)
            inflow = feed + pop_i
            if physical:
                inflow += pop_i + T * cap_i if ch.queued else T * cap_i
            out.append(inflow)
        return tuple(out)

    def min_caps(self, cfg, T: int) -> tuple:
        """Per-channel queue capacity needed so that no queue drops: one
        round's worst-case inflow (:meth:`round_inflow`) fits.
        ``EngineConfig.validate`` keeps the closed-form twin of this for
        the classic program shape.
        """
        physical = cfg.noc != "ideal"
        deep = len(self.channels) > 2
        needs = []
        for i, (ch, inflow) in enumerate(zip(self.channels,
                                             self.round_inflow(cfg, T))):
            cap_i = ch.route_cap(cfg)
            pop_i = ch.pop_budget(cfg)
            if i == 0 and ch.queued:
                # the frontier source clamps itself to the queue's free
                # space, so the legacy 2x margin suffices.
                need = 2 * cfg.f_pop
                if physical:
                    need += 2 * pop_i + T * cap_i
            elif deep:
                # Mid-chain inflow is unclamped (routed messages must be
                # absorbed) and the TSU's congestion throttle only engages
                # the round *after* occupancy crosses the 3/4 threshold —
                # so the top quarter must hold a full one-round inflow:
                # cap >= 3/4*cap + inflow  <=>  cap >= 4*inflow.
                need = 4 * inflow
            else:
                # classic shape: one round's inflow fits, and the TSU's
                # credit stop holds the producers back while it would not
                # (EngineConfig.min_caps keeps the closed form).
                need = inflow
            needs.append(need)
        return tuple(needs)

    def validate(self, cfg, T: int, e_chunk: Optional[int] = None,
                 v_chunk: Optional[int] = None):
        """No-drop invariant (every task queue must absorb its worst-case
        one-round inflow, even under static scheduling) and — when the
        shard chunks are known — the per-tile memory budget: the total
        declared buffer footprint of each memory space must fit its
        capacity (:func:`repro.mem.check_budgets`), replacing what would
        otherwise surface as an opaque allocation failure mid-trace with
        a config-time error naming the offending buffer and space.

        A channel on the pallas backend is also checked against the
        platform: off the CPU the kernels would have to compile, which
        the TPU compiler refuses today, so that is an error here too."""
        if any(ch.resolve_backend(cfg) == "pallas" for ch in self.channels):
            require_runnable()
        for ch, need in zip(self.channels, self.min_caps(cfg, T)):
            cap = ch.qcap(cfg)
            assert cap >= need, (
                f"program {self.name!r} channel {ch.name!r}: queue cap "
                f"{cap} < worst-case inflow {need}")
        if e_chunk is not None and v_chunk is not None:
            check_budgets(self.name, self.tile_decls(cfg, T, e_chunk,
                                                     v_chunk),
                          getattr(cfg, "vmem_limit_bytes", 0))

    def tile_decls(self, cfg, T: int, e_chunk: int, v_chunk: int) -> list:
        """Per-tile buffer declarations, one ``(label, space, bytes)``
        triple per engine buffer — the budget math of DESIGN.md "Memory
        spaces":

        * each channel's task/spill queue: ``qcap * width`` i32 words in
          the channel's declared space;
        * the vertex state: value/acc (f32), frontier/next_frontier
          (bool) and ptr_start/deg (i32) — 18 bytes per owned vertex in
          ``state_space``;
        * the edge shard: dst (i32) + val (f32) — 8 bytes per placed edge
          in the resolved edge space;
        * when the shard streams from HBM, the VMEM double-buffer the
          scan unit gathers through: 2 windows of 8-byte edge words per
          scan channel, charged against VMEM.  (A tile's scan unit
          drains one range message at a time, so the architectural
          staging is one double buffer per channel — the emulator's
          wider batch is a host-side artifact and is not charged.)
        """
        edge_space = resolve_edge_space(self, cfg)
        decls = [(f"queue[{ch.name}]", ch.resolve_space(cfg),
                  ch.qcap(cfg) * ch.width * 4) for ch in self.channels]
        decls.append(("vertex-state", self.state_space, 18 * v_chunk))
        decls.append((f"edge-shard[{self.name}]", edge_space, 8 * e_chunk))
        if edge_space == "hbm":
            window = resolve_window(getattr(cfg, "hbm_window", 0),
                                    cfg.max_t2)
            for ch in self.channels:
                if ch.work == "edges":
                    decls.append((f"dma-staging[{ch.name}]", "vmem",
                                  2 * window * 8))
        return decls


def resolve_edge_space(prog: Program, cfg) -> str:
    """The memory space of the tile's edge shard under ``cfg``.

    A program-level pin (``Program.edge_space``) wins: triangles pins
    "vmem" because its closing fold binary-searches the resident local
    adjacency word-random — asking the config for "hbm" anyway is a
    config error, not a silent de-optimization.  Unpinned programs take
    ``EngineConfig.edge_space``; the registry validates that the space
    can hold edge shards at all.
    """
    want = getattr(cfg, "edge_space", "vmem")
    if prog.edge_space is not None:
        if want not in ("vmem", prog.edge_space):
            raise ValueError(
                f"program {prog.name!r} pins its edge shard to "
                f"{prog.edge_space!r} (a handler needs word-random access "
                f"to the resident shard), but cfg.edge_space={want!r}")
        space = prog.edge_space
    else:
        space = want
    check_alloc(space, "edge", f"edge-shard[{prog.name}]")
    return space


def sized_cfg(cfg, program: Program, T: int):
    """Return ``cfg`` with ``cap_rangeq``/``cap_updq`` raised (next pow2)
    to satisfy ``program.validate`` — for programs whose channel inflow
    exceeds the classic defaults (e.g. triangles' second range channel).

    For deep chains (> 2 channels) ``min_caps`` already demands 4x the
    one-round inflow, so the TSU's stop-producers throttle has a full
    burst of headroom above its 3/4 congestion threshold.
    """
    rangeq, updq = cfg.cap_rangeq, cfg.cap_updq
    for ch, need in zip(program.channels, program.min_caps(cfg, T)):
        if ch.queue_cap is not None:
            continue
        need = 1 << (max(int(need), 1) - 1).bit_length()
        if ch.knobs == "range":
            rangeq = max(rangeq, need)
        else:
            updq = max(updq, need)
    return dataclasses.replace(cfg, cap_rangeq=rangeq, cap_updq=updq)


# --------------------------------------------------------------------------
# Reusable building blocks: frontier source, range split, edge scan, folds.
# --------------------------------------------------------------------------

def take_first_k(mask: jax.Array, k: jax.Array, k_max: int):
    """Indices of the first ``min(k, popcount)`` set bits, FIFO by position
    (``k <= k_max``).

    The bitmap is read as rows of 128 bits: a prefix sum over the row
    popcounts finds the row holding the i-th set bit, and a prefix sum
    inside that row (one row gather per query) finds its column, so no
    prefix sum or search runs over the whole bitmap.  The cleared mask
    keeps the set bits after the last one taken.  Invalid slots hold
    clamped positions (don't-cares, masked by ``valid`` downstream).

    Returns (idx (k_max,) i32, valid (k_max,) bool, cleared_mask)."""
    n = mask.shape[0]
    width = 128
    nrow = -(-n // width)
    bits = jnp.pad(mask.astype(jnp.int32),
                   (0, nrow * width - n)).reshape(nrow, width)
    row_csum = jnp.cumsum(bits.sum(axis=1))
    taken = jnp.minimum(k, row_csum[-1])
    want = jnp.arange(1, k_max + 1, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(row_csum, want, side="left",
                                       method="compare_all"), nrow - 1)
    rank = want - jnp.where(row > 0, row_csum[row - 1], 0)
    col = jnp.argmax(jnp.cumsum(bits[row], axis=1) >= rank[:, None],
                     axis=1)
    idx = jnp.minimum(row * width + col, n - 1).astype(jnp.int32)
    last = jnp.where(taken > 0, idx[jnp.maximum(taken - 1, 0)], -1)
    return (idx, want <= taken,
            mask & (jnp.arange(n, dtype=jnp.int32) > last))


def frontier_source(payload: Callable) -> Callable:
    """T4: pop up to the TSU budget of frontier bits into channel-0 tasks
    ``(edge_start, edge_end, *payload)``.

    ``payload(ctx, me, sh, st, vidx, deg)`` returns the task's payload
    column(s) — (k,) or (k, P) int32 — e.g. the bitcast parent value for the
    classic workloads, or the placed vertex id for triangle counting.
    """

    def source(ctx: Ctx, me, sh, st, budget):
        if ctx.backend == "pallas":
            if ctx.fused:  # already inside the leg's single pallas_call
                vidx, vvalid, frontier = frontier_take(
                    st.frontier, budget, ctx.cfg.f_pop)
            else:
                vidx, vvalid, frontier = frontier_pop(
                    st.frontier, budget, ctx.cfg.f_pop)
        else:
            vidx, vvalid, frontier = take_first_k(st.frontier, budget,
                                                  ctx.cfg.f_pop)
        deg = sh.deg[vidx]
        start = sh.ptr_start[vidx]
        pay = payload(ctx, me, sh, st, vidx, deg)
        if pay.ndim == 1:
            pay = pay[:, None]
        vvalid = vvalid & (deg > 0)
        rows = jnp.concatenate([start[:, None], (start + deg)[:, None], pay],
                               axis=1)
        return st._replace(frontier=frontier), rows, vvalid

    return source


def range_split(ctx: Ctx, taken: jax.Array, tvalid: jax.Array):
    """Listing 1's T1: bound each popped range task at the chunk border and
    at MAX_T2; re-push the remainder.  Payload columns ride along, and the
    split is a no-op on already-bounded (spilled-and-replayed) messages."""
    t_start, t_end = taken[:, 0], taken[:, 1]
    boundary = (t_start // ctx.e_chunk + 1) * ctx.e_chunk
    stop = jnp.minimum(jnp.minimum(t_end, boundary),
                       t_start + ctx.cfg.max_t2)
    pay = taken[:, 2:]
    msgs = jnp.concatenate([t_start[:, None], stop[:, None], pay], axis=1)
    rem = jnp.concatenate([stop[:, None], t_end[:, None], pay], axis=1)
    return msgs, tvalid, rem, tvalid & (stop < t_end)


def edge_rows(edge: jax.Array, max_t2: int, fill) -> jax.Array:
    """A tile's edge array (leading axes kept) as ``(rows, width)`` with
    ``width`` the larger of 128 and ``max_t2`` (a power of two), padded
    with ``fill`` to one whole row past its end: the layout
    :func:`segment_windows` reads.  Built once per engine run."""
    width = max(128, 1 << (max(max_t2, 1) - 1).bit_length())
    n = edge.shape[-1]
    nrow = -(-n // width) + 1
    pad = [(0, 0)] * (edge.ndim - 1) + [(0, nrow * width - n)]
    return jnp.pad(edge, pad, constant_values=fill).reshape(
        edge.shape[:-1] + (nrow, width))


def segment_windows(dst_rows: jax.Array, val_rows: jax.Array,
                    local0: jax.Array, max_t2: int):
    """``(R, max_t2)`` windows ``edge_*[local0 + j]`` of the tile's shard,
    exact wherever ``local0 + j`` is inside it (every lane of a segment
    that ``range_split`` bounded at the chunk border).

    The shard is read in the :func:`edge_rows` layout: the two whole rows
    that cover each segment are gathered, and shifted left by the
    segment's offset in its row with a barrel shifter.  A TPU gathers
    whole rows quickly, but serves a slice at an unaligned offset, or a
    single word, as one access at a time."""
    width = dst_rows.shape[-1]
    row, shift = local0 // width, local0 % width

    def windows(rows):
        win = jnp.concatenate([rows[row], rows[row + 1]], axis=1)
        # shift by the high bits first; after bit b only the first
        # max_t2 + b - 1 columns can still reach the window
        b = width // 2
        win = win[:, :max_t2 + 2 * b - 1]
        while b:
            moved = jnp.concatenate([win[:, b:], win[:, :b]], axis=1)
            win = jnp.where(((shift & b) != 0)[:, None], moved,
                            win)[:, :max_t2 + b - 1]
            b //= 2
        return win

    return windows(dst_rows), windows(val_rows)


def edge_scan(emit_rows: Callable) -> Callable:
    """T2 skeleton: scan the local edge chunk for each received range
    message ``(start, stop, *payload)``.

    ``emit_rows(ctx, recv, nb, w, jvalid)`` maps the (R, MAX_T2) neighbor /
    weight grids to output rows (R, MAX_T2, W') and validity — the only
    part that differs between workloads.
    """

    def handler(ctx: Ctx, me, sh, st, recv, rv):
        r_start, r_stop = recv[:, 0], recv[:, 1]
        if ctx.backend == "pallas" and ctx.edge_space == "hbm":
            # HBM-resident shard: the kernels consume it through the
            # double-buffered segment-DMA stream.
            if ctx.fused:  # already inside the leg's single pallas_call
                nb, w, jvalid = segment_stream(
                    sh.edge_dst, sh.edge_val, r_start, r_stop, rv,
                    ctx.cfg.max_t2, ctx.hbm_window)
            else:
                nb, w, jvalid = edge_scan_stream(
                    sh.edge_dst, sh.edge_val, r_start, r_stop, rv,
                    ctx.cfg.max_t2, ctx.hbm_window)
        elif ctx.backend == "pallas":
            if ctx.fused:  # already inside the leg's single pallas_call
                nb, w, jvalid = segment_gather(
                    sh.edge_dst, sh.edge_val, r_start, r_stop, rv,
                    ctx.cfg.max_t2)
            else:
                nb, w, jvalid = edge_scan_gather(
                    sh.edge_dst, sh.edge_val, r_start, r_stop, rv,
                    ctx.cfg.max_t2)
        else:
            # Either space: every valid lane reads the shard element the
            # HBM stream would stage for it (segment_stream's contract), so
            # the xla path reads each segment directly, as one contiguous
            # window per message (see segment_windows).
            length = jnp.where(rv, r_stop - r_start, 0)
            local0 = jnp.where(rv, r_start % ctx.e_chunk, 0)
            j = jnp.arange(ctx.cfg.max_t2, dtype=jnp.int32)[None, :]
            jvalid = rv[:, None] & (j < length[:, None])
            nb, w = segment_windows(*sh.edge_rows, local0, ctx.cfg.max_t2)
            jvalid = jvalid & (nb >= 0)
        rows, ov = emit_rows(ctx, recv, nb, w, jvalid)
        edges = jvalid.sum(dtype=jnp.int32)
        return st, rows.reshape(-1, rows.shape[-1]), ov.reshape(-1), edges

    return handler


def scatter_fold(ctx: Ctx, target: jax.Array, lidx: jax.Array,
                 vals: jax.Array, valid: jax.Array, op: str) -> jax.Array:
    """T3 scatter primitive shared by every fold: min/add ``vals[valid]``
    into ``target`` at local indices ``lidx`` (which must already map
    invalid rows to the trash slot ``target.shape[0]``).  Dispatches to the
    :func:`repro.kernels.engine.fold_scatter` kernel on the pallas backend;
    both paths are bit-identical (owner-local, atomic-free writes)."""
    if ctx.backend == "pallas":
        if ctx.fused:  # already inside the leg's single pallas_call
            return scatter_body(target, lidx, vals, valid, op)
        return fold_scatter(target, lidx, vals, valid, op=op)
    neutral = INF if op == "min" else jnp.float32(0.0)
    ext = jnp.concatenate([target, jnp.full((1,), neutral, jnp.float32)])
    masked = jnp.where(valid, vals, neutral)
    ext = ext.at[lidx].min(masked) if op == "min" else \
        ext.at[lidx].add(masked)
    return ext[:target.shape[0]]


def min_fold(ctx: Ctx, me, sh, st, recv, rv):
    """T3 for relaxations: scatter-min into ``value``; improved vertices
    re-enter the live (async) or next-epoch (BSP) frontier."""
    nb, vb = recv[:, 0], recv[:, 1]
    lidx = jnp.where(rv, nb % ctx.v_chunk, ctx.v_chunk)  # pad -> trash slot
    val = i2f(vb)
    applied = rv.sum(dtype=jnp.int32)
    after = scatter_fold(ctx, st.value, lidx, val, rv, "min")
    improved = after < st.value
    if ctx.cfg.mode == "async":
        st = st._replace(value=after, frontier=st.frontier | improved)
    else:
        st = st._replace(value=after,
                         next_frontier=st.next_frontier | improved)
    return st, None, None, applied


def add_fold(ctx: Ctx, me, sh, st, recv, rv):
    """T3 for accumulations: scatter-add into ``acc`` (atomic-free: this
    tile is the only owner)."""
    nb, vb = recv[:, 0], recv[:, 1]
    lidx = jnp.where(rv, nb % ctx.v_chunk, ctx.v_chunk)
    val = i2f(vb)
    applied = rv.sum(dtype=jnp.int32)
    acc = scatter_fold(ctx, st.acc, lidx, val, rv, "add")
    return st._replace(acc=acc), None, None, applied


# --------------------------------------------------------------------------
# The classic 3-task program (all five seed workloads).
# --------------------------------------------------------------------------

@lru_cache(maxsize=None)
def classic_program(alg: AlgSpec) -> Program:
    """Compile an AlgSpec to the paper's Listing-1 program: T1 range split
    -> T2 edge scan (routed to the edge owner) -> T3 fold (routed to the
    neighbor's vertex owner).  Cached so jit sees one Program per AlgSpec."""

    if alg.parent == "value_over_deg":
        def payload(ctx, me, sh, st, vidx, deg):
            return f2i(st.value[vidx]
                       / jnp.maximum(deg, 1).astype(jnp.float32))
    else:
        def payload(ctx, me, sh, st, vidx, deg):
            return f2i(st.value[vidx])

    def emit_rows(ctx, recv, nb, w, jvalid):
        out = jnp.broadcast_to(_emit(alg, i2f(recv[:, 2])[:, None], w),
                               nb.shape)
        return jnp.stack([nb, f2i(out)], axis=-1), jvalid

    fold = min_fold if alg.kind == "min" else add_fold
    return Program(
        name=alg.name,
        source=frontier_source(payload),
        channels=(
            TaskSpec("range", width=3, owner="edge", knobs="range",
                     queued=True, transform=range_split,
                     handler=edge_scan(emit_rows), emit_factor="max_t2",
                     work="edges"),
            TaskSpec("update", width=2, owner="vertex", knobs="update",
                     handler=fold, work="updates"),
        ))


def as_program(alg) -> Program:
    """AlgSpec -> Program (cached); Programs pass through."""
    if isinstance(alg, Program):
        return alg
    return classic_program(alg)


# --------------------------------------------------------------------------
# k-core peeling: the classic shape with a threshold fold (different T3).
# --------------------------------------------------------------------------

@lru_cache(maxsize=None)
def kcore_program(k: int) -> Program:
    """Peel the k-core: removed vertices emit one decrement per out-edge;
    the fold subtracts and re-arms the frontier when a still-alive vertex
    drops below k (``acc`` is the removed flag).  Requires a symmetrized,
    deduplicated graph; converges to the same fixed point in async and BSP
    modes (peeling is order-independent)."""
    kf = float(k)
    one = np.int32(np.float32(1.0).view(np.int32))

    def payload(ctx, me, sh, st, vidx, deg):
        return jnp.full(vidx.shape, one, jnp.int32)

    def emit_rows(ctx, recv, nb, w, jvalid):
        dec = jnp.full(nb.shape, one, jnp.int32)
        return jnp.stack([nb, dec], axis=-1), jvalid

    def fold(ctx, me, sh, st, recv, rv):
        nb, vb = recv[:, 0], recv[:, 1]
        lidx = jnp.where(rv, nb % ctx.v_chunk, ctx.v_chunk)
        dec = i2f(vb)
        applied = rv.sum(dtype=jnp.int32)
        after = scatter_fold(ctx, st.value, lidx, -dec, rv, "add")
        newly = (st.acc == 0.0) & (after < jnp.float32(kf))
        acc = jnp.where(newly, jnp.float32(1.0), st.acc)
        if ctx.cfg.mode == "async":
            st = st._replace(value=after, acc=acc,
                             frontier=st.frontier | newly)
        else:
            st = st._replace(value=after, acc=acc,
                             next_frontier=st.next_frontier | newly)
        return st, None, None, applied

    return Program(
        name=f"kcore{k}",
        source=frontier_source(payload),
        channels=(
            TaskSpec("range", width=3, owner="edge", knobs="range",
                     queued=True, transform=range_split,
                     handler=edge_scan(emit_rows), emit_factor="max_t2",
                     work="edges"),
            TaskSpec("decrement", width=2, owner="vertex", knobs="update",
                     handler=fold, work="updates"),
        ))


# --------------------------------------------------------------------------
# 2-hop triangle counting: a 4-channel chain the fixed pipeline could not
# express (range -> wedge -> second range at the neighbor's owner ->
# intersection-count fold).
# --------------------------------------------------------------------------

def _segment_contains(edge_dst: jax.Array, lo, deg, target):
    """Vectorized bounded binary search: is ``target`` in the sorted local
    segment ``edge_dst[lo : lo+deg]``?  Static log2(e_chunk)+1 steps."""
    e_chunk = edge_dst.shape[0]
    left, right = lo, lo + deg
    for _ in range(max(1, int(e_chunk).bit_length())):
        has = left < right
        mid = (left + right) // 2
        val = edge_dst[jnp.clip(mid, 0, e_chunk - 1)]
        go = has & (val < target)
        left = jnp.where(go, mid + 1, left)
        right = jnp.where(has & ~go, mid, right)
    at = edge_dst[jnp.clip(left, 0, e_chunk - 1)]
    return (left < lo + deg) & (at == target)


def _make_triangles_program() -> Program:
    """Count each triangle once at its placed-minimum vertex: wedges
    v -> u -> w with v < u < w (placed order) close iff w is in adj(v).

    Requires a ``prepare_triangles`` partition: vertex-aligned edges (each
    tile owns its vertices' full adjacency) with per-vertex segments sorted
    by placed destination, so the closing check is a local binary search.
    """

    def payload(ctx, me, sh, st, vidx, deg):
        return me * ctx.v_chunk + vidx  # placed vertex id

    def scan1_rows(ctx, recv, nb, w, jvalid):
        v = recv[:, 2][:, None]
        rows = jnp.stack([nb, jnp.broadcast_to(v, nb.shape)], axis=-1)
        return rows, jvalid & (nb > v)

    def wedge_to_range(ctx, me, sh, st, recv, rv):
        # At u's owner: look up u's adjacency range, emit the second-hop
        # range task (start, end, v, u).
        u, v = recv[:, 0], recv[:, 1]
        lidx = jnp.where(rv, u % ctx.v_chunk, 0)
        start = sh.ptr_start[lidx]
        deg = sh.deg[lidx]
        rows = jnp.stack([start, start + deg, v, u], axis=1)
        return st, rows, rv & (deg > 0), jnp.zeros((), jnp.int32)

    def scan2_rows(ctx, recv, nb, w, jvalid):
        v = recv[:, 2][:, None]
        u = recv[:, 3][:, None]
        rows = jnp.stack([jnp.broadcast_to(v, nb.shape), nb], axis=-1)
        return rows, jvalid & (nb > u)

    def close_fold(ctx, me, sh, st, recv, rv):
        # At v's owner: does the closing edge (v, w) exist?  v's full
        # adjacency is local (vertex-aligned) and sorted (prepare).
        v, w = recv[:, 0], recv[:, 1]
        lidx = jnp.where(rv, v % ctx.v_chunk, 0)
        lo = sh.ptr_start[lidx] % ctx.e_chunk
        deg = sh.deg[lidx]
        found = _segment_contains(sh.edge_dst, lo, deg, w) & rv
        slot = jnp.where(rv, lidx, ctx.v_chunk)
        acc = scatter_fold(ctx, st.acc, slot, found.astype(jnp.float32),
                           rv, "add")
        return (st._replace(acc=acc), None, None,
                found.sum(dtype=jnp.int32))

    return Program(
        name="triangles",
        source=frontier_source(payload),
        # close_fold binary-searches the resident local adjacency
        # word-random — the shard must stay VMEM-resident (pinned; a
        # cfg.edge_space="hbm" request is a resolve_edge_space error).
        edge_space="vmem",
        channels=(
            TaskSpec("range", width=3, owner="edge", knobs="range",
                     queued=True, transform=range_split,
                     handler=edge_scan(scan1_rows), emit_factor="max_t2",
                     work="edges"),
            TaskSpec("wedge", width=2, owner="vertex", knobs="update",
                     handler=wedge_to_range, emit_factor=1),
            TaskSpec("range2", width=4, owner="edge", knobs="range",
                     queued=True, transform=range_split,
                     handler=edge_scan(scan2_rows), emit_factor="max_t2",
                     work="edges"),
            TaskSpec("close", width=2, owner="vertex", knobs="update",
                     handler=close_fold, work="updates"),
        ))


TRIANGLES = _make_triangles_program()
