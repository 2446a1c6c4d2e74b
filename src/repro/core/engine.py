"""The Dalorex execution engine: data-local task-flow over a device grid.

The engine executes a :class:`repro.core.program.Program` — an ordered chain
of task channels (the paper's task-based programming model, Section II) —
one *round* at a time (the vectorized analogue of a window of machine
cycles).  Per round, every device runs:

  source   pop local frontier bits -> channel-0 tasks (the paper's T4/T1
           head: one (edge_start, edge_end, payload) task per vertex)
  per channel, in program order (one generic leg each):
           queue -> TSU budget -> transform (e.g. the T1 range split at
           chunk borders and MAX_T2, Listing 1)
           --- route by owner(head flit) over the NoC backend ---
           handler at the owner tile (edge scan, fold, ...) -> successor
           messages for the next channel; spills -> local queue.

The classic workloads (BFS, SSSP, PageRank, WCC, SpMV) compile to the
3-task program T1 range split -> T2 edge scan -> T3 fold
(:func:`repro.core.program.classic_program`); k-core peeling swaps the
fold; triangle counting runs a 4-channel chain.  The engine itself is
workload-agnostic: it only iterates channels.

The fabric between channels is a pluggable :mod:`repro.noc` Network
selected by ``EngineConfig.noc``: the ideal crossbar, a physical mesh /
torus / ruche grid, or the multi-die ``hier`` composition (an
``ndies_y x ndies_x`` array of intra-die grids joined by DIE-class express
links), all with dimension-ordered routing, per-link capacities, and
per-link telemetry (``Stats.flits_per_link``, ``Stats.die_crossings``
etc.).

Backpressure: routing capacity is finite (endpoint slots *and*, for the
physical NoCs, per-link flits); overflow *spills* back into the channel's
local queue — of whichever tile the message is stranded at, since routes
are re-derived from the head flit — and is replayed next round, the
software form of the paper's "CQ full -> early exit, resume next
invocation".  Nothing is ever dropped: the TSU's credit stop (below)
keeps every queue from overflowing, and the host drivers raise on a run
with ``Stats.drops > 0``.

Scheduling: per-round budgets are chosen per device by a generic arbiter
over the N channel queue occupancies plus the NoC's fed-back link occupancy
— the Task Scheduling Unit's traffic-aware priorities (Section III-E),
adapted from per-cycle arbitration to per-round budget allocation.  The
drain-consumers-first / throttle-producers ordering falls out of the
channel DAG: the deepest consumer always drains in full, and a channel's
budget is quartered while any *downstream* queue (or the fabric) is
congested; the frontier source stops entirely.  ``policy="static"``
reproduces the paper's round-robin arbitration rung of the Fig. 5 ablation.
Under both, a producer stops while any downstream queue on any tile lacks
room for one round's worst-case inflow: end-to-end credit, since a tile's
inflow comes from other tiles' pops.

Synchronization: ``mode="async"`` is barrierless Dalorex — vertices
re-armed by a fold re-enter the *live* frontier immediately.  ``mode="bsp"``
defers them to a next-epoch frontier swapped in only when the whole grid is
quiescent (the paper's per-epoch global barrier, driven by the same idle
signal).

Termination is the paper's hierarchical idle wire: a psum of local pending
work (queue occupancies + frontier population); the loop exits when it hits
zero.  The whole traversal runs inside ONE ``lax.while_loop`` — on real
meshes there is no host round-trip per round.

Each round is also *priced* by the :mod:`repro.perf` cost model
(``EngineConfig.perf``): the slowest tile's compute plus the busiest
link's serialization accumulate into ``Stats.cycles``, and the round's
counters into ``Stats.energy_pj`` — so benchmarks report modeled time /
GTEPS / joules, not just rounds (DESIGN.md "Performance model").

The per-tile legs themselves execute on ``EngineConfig.backend``: "xla"
traces them inline, "pallas" dispatches to the tile-grid kernels of
:mod:`repro.kernels.engine` (one grid program = one tile, shard resident
in VMEM) — bit-identical by contract, per-channel overridable via
``TaskSpec.backend`` (DESIGN.md "Pallas backend").

Each leg of a round runs inside a ``jax.named_scope`` named in
:data:`ROUND_LEGS`, so a profile of the compiled engine charges every
device operation to the innermost leg it came from
(:func:`repro.core.algorithms.engine_leg_map`; DESIGN.md "Device legs and
host spans").  Scopes are compile-time metadata: they change no value,
and the optimized program only in its instructions' names.

Everything here is single-query; the serving subsystem
(:mod:`repro.serve`) vmaps the round built by :func:`make_round` over a
leading *query-lane* axis so a batch of B traversals shares the resident
graph, the rounds and the fabric, freezing each lane with
:func:`lane_select` when its own :func:`pending_work` signal hits zero
(DESIGN.md "Query serving").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import AxisComm, LocalComm
from repro.core.graph import PartitionedGraph
from repro.core.program import (BFS, PAGERANK, SPMV, SSSP,  # noqa: F401
                                WCC, AlgSpec, Ctx, INF, Program, TaskSpec,
                                as_program, edge_rows, resolve_edge_space)
from repro.core.queues import (Queue, f2i, i2f, queue_make, queue_push,
                               queue_take_front)
from repro.kernels.engine import (fifo_turn, fused_leg_call, queue_append,
                                  queue_push_pop, tally)
from repro.mem import resolve_window
from repro.noc import make_network
from repro.noc.topology import N_LINK_CLASSES
from repro.perf import (PerfParams, link_cost_vectors, round_energy_pj,
                        tile_compute_cycles)
from repro.trace.buffer import record_round, zero_trace


# The legs of one engine round, as the jax.named_scope names they run in:
#   control     credit check, TSU budgets, idle-wire reductions, BSP swap,
#               the round loop's condition
#   source      the frontier pop (Program.source)
#   queue       channel queue turns and spill re-queues (ingest, requeue)
#   route       binning by owner and the exchange (Network.route)
#   link_count  per-link / per-hop / per-die telemetry histograms inside
#               Network.route (the TSU's net_pressure input)
#   scan        handlers of "edges" channels (the edge scan)
#   fold        every other handler (the vertex-owner folds)
#   telemetry   the round's counters, the cycle/energy model, Stats
# Work before the loop runs in "init"; the flight recorder's block in
# "recorder" (only with cfg.trace).
ROUND_LEGS = ("control", "source", "queue", "route", "link_count", "scan",
              "fold", "telemetry")


def _leg(name: str):
    """Decorator: trace the function inside the named scope ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args):
            with jax.named_scope(name):
                return fn(*args)
        return scoped
    return wrap


def handler_leg(ch: TaskSpec) -> str:
    """The leg of a channel's handler: "scan" for edge scans, "fold" for
    the rest (vertex-owner handlers, triangles' wedge among them)."""
    return "scan" if ch.work == "edges" else "fold"


# --------------------------------------------------------------------------
# Engine configuration and state.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static knobs.  Sizes are per device; all shapes they imply are static.

    The queue/budget names mirror the paper:  ``cap_route_*`` are the channel
    queue (CQ) capacities *per destination*, ``max_t2`` is Listing 1's MAX_T2
    (edge-scan length bound per message), the ``*_pop`` budgets are the TSU's
    per-invocation drain amounts.  These are the *defaults* for a Program's
    channels, selected by each TaskSpec's ``knobs`` tag ("range" /
    "update"); a TaskSpec can override them per channel.
    """

    f_pop: int = 32          # frontier bits popped per round (T4 drain)
    r_pop: int = 32          # "range"-knob queue entries popped per round
    u_pop: int = 64          # "update"-knob spilled entries replayed
    max_t2: int = 32         # edge-scan bound per range message (MAX_T2)
    cap_route_range: int = 16    # CQ slots per destination, "range" channels
    cap_route_update: int = 64   # CQ slots per destination, "update" channels
    cap_rangeq: int = 2048   # local task-queue capacity, "range" channels
    cap_updq: int = 16384    # local spill-queue capacity, "update" channels
    policy: str = "traffic"  # "traffic" | "static"
    mode: str = "async"      # "async" (barrierless) | "bsp"
    max_rounds: int = 100_000
    # --- execution backend of the per-tile round legs ---
    # "xla" traces the queue/scan/fold legs inline; "pallas" dispatches them
    # to the repro.kernels.engine tile-grid kernels (one grid program = one
    # tile, shard resident in VMEM).  Results are bit-identical by contract
    # (tests/test_backend_pallas.py).  A TaskSpec.backend hint overrides
    # this per channel.  The kernels run through the Pallas interpreter on
    # the CPU; elsewhere Program.validate rejects them, because the TPU
    # compiler refuses their bodies (repro.kernels.engine.TPU_REFUSALS).
    backend: str = "xla"     # "xla" | "pallas"
    # ``pallas_fuse=True`` (the default) runs each channel leg whose
    # channels all resolved to "pallas" as ONE pallas_call — the whole
    # per-tile stage (frontier pop, FIFO turn, spill re-queue, remainder
    # re-push, scan, fold) becomes the kernel body with VMEM-resident
    # intermediates (repro.kernels.engine.fused_leg_call).  False keeps
    # the legacy one-kernel-per-building-block dispatch (4+ launches per
    # leg plus XLA glue); both are bit-identical to "xla".
    # ``pallas_pad_lanes`` pads every fused-leg operand block to the TPU's
    # (8, 128) sublane x lane f32 tile (sliced back inside the body) so
    # a compiled kernel lands aligned blocks; value-neutral.
    # ``Stats.launches`` counts the pallas_call dispatches per round.
    pallas_fuse: bool = True
    pallas_pad_lanes: bool = False
    # --- memory spaces (repro.mem) ---
    # ``edge_space`` declares where the tile's edge shard lives: "vmem"
    # (word-random resident, the default) or "hbm" (the shard streams
    # through double-buffered segment-DMA windows of ``hbm_window``
    # elements; 0 auto-sizes to the next pow2 >= max_t2).  Programs may
    # pin their own shard space (e.g. triangles pins "vmem"); see
    # program.resolve_edge_space.  ``vmem_limit_bytes`` overrides the
    # registry's per-tile VMEM capacity for Program.validate's
    # config-time footprint check (0 = the registry default) — the knob
    # that models a smaller tile, and the error that replaced the old
    # implicit "everything fits in VMEM" assumption.
    edge_space: str = "vmem"
    hbm_window: int = 0
    vmem_limit_bytes: int = 0
    # --- NoC backend (repro.noc) ---
    noc: str = "ideal"       # "ideal" | "mesh" | "torus" | "ruche" | "hier"
    noc_rows: int = 0        # grid rows; 0 = near-square factorization of T
    link_cap: int = 0        # flits per directed link per routing leg (a
                             # round has one leg per channel); 0 = off
    ruche_factor: int = 2    # tiles skipped by a ruche channel (noc="ruche")
    # hier (die-of-dies) geometry: the grid is cut into ndies_y x ndies_x
    # equal dies wired internally as hier_base ("mesh" | "torus") and
    # joined by DIE-class express links; ndies_x = ndies_y = 1 with a mesh
    # base is bit-identical to noc="mesh" (tests/test_hier.py)
    ndies_x: int = 1         # die columns (noc="hier")
    ndies_y: int = 1         # die rows (noc="hier")
    hier_base: str = "mesh"  # intra-die wiring (noc="hier")
    # --- cycle/energy cost model (repro.perf) ---
    perf: PerfParams = PerfParams()
    # --- flight recorder (repro.trace) ---
    # ``trace=True`` carries a TraceBuf ring through the round loop,
    # recording per-round series (per-channel msgs/spills/queue depth,
    # per-tile busy cycles + critical-path tile, per-link-class flits,
    # TSU budget grants, HBM windows, frontier/pending) every
    # ``trace_every``-th round into a bounded ``trace_rounds``-slot ring
    # (oldest rounds overwritten).  Contract: trace=False is
    # byte-identical to a build without the recorder; trace=True never
    # perturbs values or Stats (tests/test_trace.py).
    trace: bool = False
    trace_every: int = 1
    trace_rounds: int = 512
    # --- telemetry-driven adaptive placement (repro.place) ---
    # ``adapt=True`` lets the epoch-boundary repartitioner run: between
    # engine epochs (host-driven, e.g. PageRank's) or between serving
    # queries, a migration plan derived from the flight recorder's
    # per-tile busy series / the partition's die-affinity is applied as a
    # pure relabeling (repro.place.apply_plan).  ``adapt_every`` is the
    # epoch/batch cadence; ``adapt_budget`` caps migrated vertices per
    # adaptation.  The engine round loop itself never migrates — plans
    # apply only at quiescent boundaries, so converged values stay
    # bit-identical to the unmigrated run (tests/test_place.py).
    adapt: bool = False
    adapt_every: int = 1
    adapt_budget: int = 64

    def min_caps(self, T: int) -> tuple[int, int]:
        """Worst-case per-round queue inflow for the *classic* program
        shape: (rangeq_need, updq_need).  The generic, per-channel version
        is :meth:`repro.core.program.Program.min_caps`; this closed form is
        kept because benchmarks size their queues from it."""
        burst = T * self.cap_route_range * self.max_t2 + self.u_pop
        rangeq_need = 2 * self.f_pop
        if self.noc != "ideal":
            burst += T * self.cap_route_update
            rangeq_need += 2 * self.r_pop + T * self.cap_route_range
        return rangeq_need, burst

    def validate(self, T: int):
        # queues must absorb a full worst-case burst so the no-drop
        # invariant holds even under static scheduling.
        rangeq_need, burst = self.min_caps(T)
        assert self.cap_updq >= burst, (
            f"cap_updq={self.cap_updq} < worst-case T2 burst {burst}")
        assert self.cap_rangeq >= rangeq_need, (
            f"cap_rangeq={self.cap_rangeq} < worst-case inflow {rangeq_need}")


class EngineState(NamedTuple):
    value: jax.Array      # (v_chunk,) f32 — dist / label / rank / x / degree
    acc: jax.Array        # (v_chunk,) f32 — accumulator / removed flag
    frontier: jax.Array   # (v_chunk,) bool — local bitmap frontier (live)
    next_frontier: jax.Array  # (v_chunk,) bool — BSP-deferred frontier
    queues: tuple         # one Queue per program channel
    net_pressure: jax.Array  # () i32 — last round's occupancy on own links


class Stats(NamedTuple):
    rounds: jax.Array
    epochs: jax.Array           # BSP frontier swaps (0 in async mode)
    msgs: jax.Array             # (K,) messages delivered per task channel
    spills: jax.Array           # (K,) spill-and-replay events per channel
    edges_scanned: jax.Array    # work of "edges"-tagged handlers (scans)
    updates_applied: jax.Array  # work of "updates"-tagged handlers (folds)
    drops: jax.Array            # MUST be 0 — backpressure invariant
    work_max: jax.Array         # max per-device edges_scanned (balance)
    # --- NoC telemetry (shapes fixed by the Network backend) ---
    flits_per_link: jax.Array       # (num_links,) cumulative flit traversals
    max_link_occupancy: jax.Array   # () peak per-round per-link occupancy
    hop_histogram: jax.Array        # (max_hops+1,) injections by hop count
    die_crossings: jax.Array        # (max_die_crossings+1,) injections by
                                    # die boundaries crossed (bin 0 only,
                                    # on single-die fabrics)
    # --- cycle/energy model (repro.perf; f32 — magnitudes exceed int32,
    # and the in-loop accumulation is Kahan-compensated so small per-round
    # increments survive far past f32's 2^24 integer ceiling) ---
    cycles: jax.Array               # () modeled cycles, per-round critical
                                    # path summed over rounds
    energy_pj: jax.Array            # () modeled energy, linear in counters
    # --- launch accounting (repro.kernels.engine.launches) ---
    launches: jax.Array             # () pallas_call dispatches, summed over
                                    # rounds (0 on the xla backend; counted
                                    # at trace time, identical across comm
                                    # backends — intentionally NOT part of
                                    # the cross-backend equivalence
                                    # contract)
    # --- per-space traffic (repro.mem; 0 unless the edge shard resolved
    # to "hbm" — stats_row omits the columns when zero, the same additive
    # convention as ``launches``, so pre-memspace baseline rows stay
    # byte-stable.  NOT part of the vmem-vs-hbm space-equivalence
    # contract, by design: they are what *differs* between spaces) ---
    hbm_windows: jax.Array          # () DMA windows fetched (2 per
                                    # delivered range message: the double
                                    # buffer)
    hbm_edges: jax.Array            # () edge words streamed from HBM
                                    # (windows * window size), priced at
                                    # t_hbm / e_hbm
    # --- adaptive-placement migration accounting (repro.place; 0 unless
    # a migration plan was applied between epochs/queries — stats_row
    # omits the columns when zero, the same additive convention as
    # ``launches``, so pre-adaptive baseline rows stay byte-stable.
    # Added host-side by repro.place.price_migration at the quiescent
    # boundary the plan applied at; the in-loop round accumulator only
    # carries them through) ---
    migrated_vertices: jax.Array    # () vertices moved by applied plans
    migration_cycles: jax.Array     # () modeled cycles of the moves (also
                                    # folded into ``cycles``)
    migration_pj: jax.Array         # () modeled energy of the moves (also
                                    # folded into ``energy_pj``; kept so
                                    # energy_from_totals reconciles)

    # Legacy scalar views: the classic program's two channels.
    @property
    def msgs_range(self):
        return self.msgs[..., 0]

    @property
    def msgs_update(self):
        return self.msgs[..., -1]

    @property
    def spills_range(self):
        return self.spills[..., 0]

    @property
    def spills_update(self):
        return self.spills[..., -1]

    @staticmethod
    def zero(num_links: int = 1, max_hops: int = 1, num_channels: int = 2,
             max_die_crossings: int = 0):
        z = jnp.zeros((), jnp.int32)
        zf = jnp.zeros((), jnp.float32)
        return Stats(z, z,
                     jnp.zeros((num_channels,), jnp.int32),
                     jnp.zeros((num_channels,), jnp.int32),
                     z, z, z, z,
                     jnp.zeros((num_links,), jnp.int32), z,
                     jnp.zeros((max_hops + 1,), jnp.int32),
                     jnp.zeros((max_die_crossings + 1,), jnp.int32),
                     zf, zf, z, z, z, z, zf, zf)


def zero_stats(cfg: EngineConfig, T: int, alg=BFS) -> Stats:
    """A Stats zero whose telemetry shapes match the NoC backend ``cfg``
    selects and whose channel counters match the program — safe to
    accumulate with real runs (the ``Stats.zero()`` defaults are not)."""
    prog = as_program(alg)
    net = make_network(cfg, T)
    return Stats.zero(net.num_links, net.max_hops, len(prog.channels),
                      net.max_die_crossings)


class GraphShard(NamedTuple):
    """One device's chunk of the four dataset arrays (placed space), plus
    the xla edge scan's row layout of the two edge arrays
    (``program.edge_rows``), which :func:`make_round` builds once per run
    for the legs that read it."""
    ptr_start: jax.Array  # (v_chunk,) i32 global placed edge index
    deg: jax.Array        # (v_chunk,) i32
    edge_dst: jax.Array   # (e_chunk,) i32 placed dst (-1 pad)
    edge_val: jax.Array   # (e_chunk,) f32
    edge_rows: tuple = ()  # (dst rows, val rows), or () before make_round


# --------------------------------------------------------------------------
# The TSU: a generic arbiter over N channel occupancies + fabric pressure.
# --------------------------------------------------------------------------

def _budgets(cfg: EngineConfig, prog: Program, qcaps, pops, st: EngineState,
             plimit: int, full):
    """Per-round budgets from the channel queue occupancies AND the NoC's
    per-link occupancy fed back from last round (Section III-E).

    Priorities derive from the program DAG: the deepest consumer always
    drains (its IQ filling up is the main source of endpoint contention);
    a producer channel is throttled to 1/4 budget while any *downstream*
    queue is congested (> 3/4 full) or the fabric is hot; the frontier
    source stops entirely while channel 0 is half full or anything
    downstream is congested.  Returns (source_budget, (K,) channel pops).

    Under either policy two credit rules keep ``drops`` at 0 (the local
    throttle alone does not):

    * ``full`` is the grid-wide check, ``(K,)`` i32: channel ``j``'s
      queue on some tile has less room than one round's worst-case
      inflow.  Every producer upstream of such a channel pops nothing,
      since the messages it would deliver could land on that tile.
    * A queued channel 0 re-pushes the split remainder of every task it
      pops, and the spilled message too, so popping can grow it.  Its pop
      is clamped to the room the source leaves, but never below its
      per-destination capacity: that many messages always fit the ideal
      fabric, so the queue cannot grow from them.
    """
    K = len(prog.channels)
    occ = [st.queues[i].count for i in range(K)]
    free0 = jnp.int32(qcaps[0]) - occ[0]
    # stopped[i]: some channel downstream of i is out of credit
    stopped = [full[i + 1:].any() for i in range(K - 1)] + [False]

    def clamp_first(f_pop, chan_pops):
        if prog.channels[0].queued:
            room = jnp.maximum(free0 - f_pop,
                               prog.channels[0].route_cap(cfg))
            chan_pops[0] = jnp.minimum(chan_pops[0], room)
        return f_pop, jnp.stack(chan_pops)

    if cfg.policy == "static":
        f_pop = jnp.minimum(jnp.int32(cfg.f_pop), jnp.maximum(free0, 0))
        return clamp_first(f_pop, [jnp.where(stopped[i], 0, jnp.int32(p))
                                   for i, p in enumerate(pops)])
    net_hot = st.net_pressure > jnp.int32(max(plimit, 1))
    congested = [occ[i] > (3 * qcaps[i]) // 4 for i in range(K)]
    chan_pops = [None] * K
    down = jnp.zeros((), bool)          # any congested queue downstream
    for i in reversed(range(K)):
        if i == K - 1:
            chan_pops[i] = jnp.int32(pops[i])
        else:
            # classic 2-channel shape: quarter the producer (the paper's
            # throttle rung).  Deeper chains amplify (each channel fans out
            # again), so a quartered producer can still outrun the last
            # channel's drain — stop producers outright until the backlog
            # clears; the last channel always drains, so this cannot
            # deadlock.
            throttled = pops[i] // 4 if K == 2 else 0
            chan_pops[i] = jnp.where(
                stopped[i], jnp.int32(0),
                jnp.where(down | net_hot, jnp.int32(throttled),
                          jnp.int32(pops[i])))
        down = down | congested[i]
    down_of_source = net_hot
    for i in range(1, K):
        down_of_source = down_of_source | congested[i]
    half0 = occ[0] > qcaps[0] // 2
    f_pop = jnp.where(
        half0 | down_of_source, jnp.int32(0),
        jnp.minimum(jnp.int32(cfg.f_pop),
                    jnp.maximum(free0 - 2 * cfg.f_pop, 0)))
    return clamp_first(f_pop, chan_pops)


def pending_work(me, st: EngineState):
    """Per-device pending work (frontier population + queue occupancies) —
    the local contribution to the paper's hierarchical idle wire.  Public
    because the serving lane runner (:mod:`repro.serve.lanes`) computes a
    *per-query* idle signal from the same definition."""
    p = st.frontier.sum(dtype=jnp.int32)
    for q in st.queues:
        p = p + q.count
    return p


_pending = pending_work


def lane_select(active: jax.Array, old, new):
    """Per-lane masked select over matching lane-led pytrees.

    ``active`` is a ``(B,)`` bool vector; every leaf of ``old``/``new`` is
    lane-led ``(B, ...)``.  Returns ``new`` where the lane is active and
    ``old`` where it is frozen — the query-lane analogue of BSP's
    do-nothing round: a finished query's state, Stats and Kahan
    compensation stop evolving the round its pending work hits zero, which
    is what keeps each lane's trajectory bit-identical to a solo run
    (tests/test_serve.py).
    """
    def sel(o, n):
        m = active.reshape(active.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, old, new)


def _next_pending(me, st: EngineState):
    return st.next_frontier.sum(dtype=jnp.int32)


def _bsp_swap(me, st: EngineState, do_swap):
    frontier = jnp.where(do_swap, st.frontier | st.next_frontier, st.frontier)
    nxt = jnp.where(do_swap, jnp.zeros_like(st.next_frontier),
                    st.next_frontier)
    return st._replace(frontier=frontier, next_frontier=nxt)


def _set_queue(st: EngineState, i: int, q: Queue) -> EngineState:
    return st._replace(queues=st.queues[:i] + (q,) + st.queues[i + 1:])


# --------------------------------------------------------------------------
# The generic round + driver, parametric over the comm backend.
# --------------------------------------------------------------------------

def make_round(comm, net, cfg: EngineConfig, prog: Program, e_chunk: int,
               v_chunk: int, shard: GraphShard):
    """Build the per-round function ``(state, stats, kahan_comp, tbuf) ->
    (state, stats, kahan_comp, tbuf, pending)`` where ``kahan_comp`` is
    the ``(cycles, energy)`` f32 compensation pair of the perf model's
    in-loop summation (threaded through the ``while_loop`` carry, never
    surfaced) and ``tbuf`` is the flight recorder's ring
    (:mod:`repro.trace`) when ``cfg.trace`` — an empty pytree ``()``
    otherwise, so the trace-off carry is byte-identical to a build
    without the recorder.

    One generic ``queue -> budget -> transform -> net.route -> handler ->
    spill`` leg per program channel, with the destination decoded from the
    head flit (the paper's headerless routing).  ``net`` is a
    :mod:`repro.noc` Network backend; every leg goes through it.

    Each leg executes on the backend resolved from ``cfg.backend`` and the
    channel's ``TaskSpec.backend`` hint: "xla" inline, or "pallas" via the
    :mod:`repro.kernels.engine` tile-grid kernels (the fused queue turn
    here; the scan/fold kernels inside the dispatching handlers).  The TSU,
    the NoC, and the perf model are backend-agnostic — they only ever see
    the legs' (bit-identical) outputs.
    """
    # Memory space of the edge shard (repro.mem): "hbm" switches the T2
    # building blocks to the double-buffered segment-DMA stream and turns
    # on per-space traffic accounting below.
    edge_space = resolve_edge_space(prog, cfg)
    window = resolve_window(cfg.hbm_window, cfg.max_t2) \
        if edge_space == "hbm" else 0
    ctx = Ctx(cfg, comm.size, e_chunk, v_chunk,
              edge_space=edge_space, hbm_window=window)
    chans = prog.channels
    K = len(chans)
    backends = tuple(ch.resolve_backend(cfg) for ch in chans)
    # Leg fusion (pallas_fuse): legs are indexed 0 (stage_first: channel
    # 0's source + ingest), 1..K-1 (make_mid(i): channel i-1's handler +
    # channel i's ingest) and K (stage_last: channel K-1's handler).  A
    # leg runs as ONE pallas_call iff every channel it spans resolved to
    # "pallas" — a per-channel "xla" pin de-fuses just the legs it touches.
    fuse = cfg.pallas_fuse
    leg_fused = ((fuse and backends[0] == "pallas",)
                 + tuple(fuse and backends[i - 1] == "pallas"
                         and backends[i] == "pallas"
                         for i in range(1, K))
                 + (fuse and backends[K - 1] == "pallas",))

    def leg_ctx(chan_i, leg_i):
        """The Ctx a building block of channel ``chan_i`` sees inside leg
        ``leg_i`` — fused legs route the blocks to the pure kernel bodies
        (no nested pallas_call)."""
        return ctx._replace(backend=backends[chan_i],
                            fused=leg_fused[leg_i])

    # The xla edge scan reads the shard in row layout; a fused leg reads
    # the flat arrays inside its kernel, so only unfused legs carry it.
    shard_rows = shard._replace(edge_rows=comm.run(_leg("init")(
        lambda me, sh: (edge_rows(sh.edge_dst, cfg.max_t2, -1),
                        edge_rows(sh.edge_val, cfg.max_t2, 0.0))), shard))

    def leg_shard(leg_i):
        return shard if leg_fused[leg_i] else shard_rows

    def wrap_leg(stage, leg_i):
        """Fused legs: the whole per-tile stage becomes one pallas_call
        body (intermediates VMEM-resident), via fused_leg_call."""
        if not leg_fused[leg_i]:
            return stage

        def fused_stage(me, *args):
            return fused_leg_call(stage, me, *args,
                                  pad_lanes=cfg.pallas_pad_lanes)
        return fused_stage

    caps = tuple(ch.route_cap(cfg) for ch in chans)
    pops = tuple(ch.pop_budget(cfg) for ch in chans)
    qcaps = tuple(ch.qcap(cfg) for ch in chans)
    inflow = prog.round_inflow(cfg, comm.size)

    @_leg("control")
    def out_of_credit(me, st):
        """(K,) i32: which of this tile's queues could overflow in one
        round of worst-case inflow (the input of _budgets' ``full``)."""
        return jnp.stack([(jnp.int32(qcaps[j]) - st.queues[j].count
                           < inflow[j]).astype(jnp.int32)
                          for j in range(K)])
    owners = tuple(ch.owner_fn(ctx) for ch in chans)
    plimit = net.pressure_limit(cfg, caps)
    pp = cfg.perf
    t_hop, e_hop = link_cost_vectors(pp, net)
    tracing = cfg.trace
    if tracing:
        # static (C, num_links) one-hot splitting per-link flits by cost
        # class for the recorder's per-class series
        _cls = np.asarray(net.link_classes)
        cls_onehot = jnp.asarray(
            (_cls[None, :] == np.arange(N_LINK_CLASSES)[:, None])
            .astype(np.int32))

    @_leg("queue")
    def requeue(st, i, sp, spv, cx):
        """Spill re-queue into channel i's local queue.  Inside a fused leg
        this is the in-kernel :func:`queue_append` body (bit-identical to
        ``queue_push``) — the XLA glue the single launch absorbs."""
        q = st.queues[i]
        if cx.fused:
            qdata, qcount, d = queue_append(q.data, q.count, sp, spv)
            q = Queue(qdata, qcount)
        else:
            q, d = queue_push(q, sp, spv)
        return _set_queue(st, i, q), d

    @_leg("queue")
    def ingest(i, st, rows, valid, pop_i, cx):
        """Feed fresh rows into channel i and produce its network messages.

        Queued channels (real task queues) push fresh tasks, pop up to the
        budget, and bound each popped task via the channel transform
        (re-pushing remainders).  Spill-only channels replay their backlog
        ahead of the fresh messages.

        Also returns this tile's queue-op counts for the cycle model:
        ``npop`` entries dequeued and ``npush`` entries enqueued (fresh
        tasks + re-pushed split remainders) this round.

        On the pallas backend the push+pop pair is one fused FIFO turn
        (spill-only channels turn with an empty fresh batch): the
        standalone :func:`repro.kernels.engine.queue_push_pop` kernel when
        the leg is unfused, or the in-kernel :func:`fifo_turn` body when
        the whole leg is already a single pallas_call (``cx.fused``), in
        which case the split-remainder re-push is absorbed in-kernel too
        via :func:`queue_append`.
        """
        q = st.queues[i]
        if chans[i].queued:
            if cx.backend == "pallas":
                turn = fifo_turn if cx.fused else queue_push_pop
                taken, tvalid, qdata, qcount, d0 = turn(
                    q.data, q.count, rows, valid, pop_i, pops[i])
                q = Queue(qdata, qcount)
            else:
                q, d0 = queue_push(q, rows, valid)
                taken, tvalid, q = queue_take_front(q, pop_i, pops[i])
            msgs, mvalid, rem, remv = chans[i].transform(cx, taken, tvalid)
            if cx.fused:
                qdata, qcount, d1 = queue_append(q.data, q.count, rem, remv)
                q = Queue(qdata, qcount)
            else:
                q, d1 = queue_push(q, rem, remv)
            drops = d0 + d1
            npop = tvalid.sum(dtype=jnp.int32)
            npush = (valid.sum(dtype=jnp.int32)
                     + remv.sum(dtype=jnp.int32))
        else:
            if cx.backend == "pallas":
                none = jnp.zeros((1,), bool)
                pad = jnp.zeros((1, q.data.shape[1]), jnp.int32)
                turn = fifo_turn if cx.fused else queue_push_pop
                replay, rvalid, qdata, qcount, _ = turn(
                    q.data, q.count, pad, none, pop_i, pops[i])
                q = Queue(qdata, qcount)
            else:
                replay, rvalid, q = queue_take_front(q, pop_i, pops[i])
            msgs = jnp.concatenate([replay, rows], axis=0)
            mvalid = jnp.concatenate([rvalid, valid], axis=0)
            drops = jnp.zeros((), jnp.int32)
            npop = rvalid.sum(dtype=jnp.int32)
            npush = jnp.zeros((), jnp.int32)
        return _set_queue(st, i, q), msgs, mvalid, drops, npop, npush

    cx_first = leg_ctx(0, 0)

    def stage_first(me, sh, st, full):
        with jax.named_scope("control"):
            f_pop, dyn_pops = _budgets(cfg, prog, qcaps, pops, st, plimit,
                                       full)
        with jax.named_scope("source"):
            st, rows, valid = prog.source(cx_first, me, sh, st, f_pop)
        st, msgs, mvalid, drops, npop, npush = ingest(
            0, st, rows, valid, dyn_pops[0], cx_first)
        return st, msgs, mvalid, drops, dyn_pops, npop, npush

    stage_first = wrap_leg(stage_first, 0)

    def make_mid(i):
        cx_h = leg_ctx(i - 1, i)  # channel i-1's handler under this leg
        cx_q = leg_ctx(i, i)      # channel i's ingest under this leg

        def stage(me, sh, st, recv, rv, sp, spv, dyn_pops):
            st, d0 = requeue(st, i - 1, sp, spv, cx_h)
            with jax.named_scope(handler_leg(chans[i - 1])):
                st, rows, valid, work = chans[i - 1].handler(
                    cx_h, me, sh, st, recv, rv)
            st, msgs, mvalid, d1, npop, npush = ingest(
                i, st, rows, valid, dyn_pops[i], cx_q)
            with jax.named_scope("telemetry"):
                nspill = spv.sum(dtype=jnp.int32)
                return st, msgs, mvalid, d0 + d1, work, npop, npush, nspill
        return wrap_leg(stage, i)

    cx_last = leg_ctx(K - 1, K)

    def stage_last(me, sh, st, recv, rv, sp, spv):
        st, d0 = requeue(st, K - 1, sp, spv, cx_last)
        with jax.named_scope(handler_leg(chans[K - 1])):
            st, _, _, work = chans[K - 1].handler(cx_last, me, sh, st, recv,
                                                  rv)
        with jax.named_scope("telemetry"):
            return st, d0, work, spv.sum(dtype=jnp.int32)

    stage_last = wrap_leg(stage_last, K)

    def kahan_add(total, comp, inc):
        """Compensated f32 accumulation: (new_total, new_comp)."""
        y = inc - comp
        t = total + y
        return t, (t - total) - y

    def rnd(st: EngineState, stats: Stats, kcomp, tbuf=()):
        st0, round_ix = st, stats.rounds  # pre-round views (trace only)
        # The round body is traced exactly once per compile, so the
        # pallas_call dispatches recorded while tracing the stages below
        # ARE this round's launch count (repro.kernels.engine.launches) —
        # a Python int folded into Stats.launches, identical under
        # LocalComm/vmap, shard_map and the serving-lane vmap.
        with jax.named_scope("control"):
            full = comm.pmax(comm.run(out_of_credit, st))

        def route(i, msgs, mvalid):
            with jax.named_scope("route"):
                return net.route(comm, msgs, mvalid, caps[i], owners[i])

        with tally() as launch_tally:
            st, msgs, mvalid, drops, dyn_pops, n_pop, n_push = comm.run(
                stage_first, leg_shard(0), st, full)
            routed = route(0, msgs, mvalid)
            link_round = routed.link_flits
            hop_round = routed.hop_hist
            die_round = routed.die_hist
            sents = [routed.sent]
            spillv = [routed.spill_valid]
            with jax.named_scope("telemetry"):
                edges = jnp.zeros_like(drops)
                applied = jnp.zeros_like(drops)
                n_replay = jnp.zeros_like(drops)
                hbm_win = jnp.zeros_like(drops)

            def count_windows(acc, rvalid):
                # Per-tile DMA accounting of the streamed T2: each range
                # message delivered to an "edges" handler fetches its two
                # covering windows (the double buffer) — what the machine
                # transfers, independent of the emulation's vectorized
                # staging.
                with jax.named_scope("telemetry"):
                    return acc + comm.run(
                        lambda me, v: 2 * v.sum(dtype=jnp.int32), rvalid)

            def add_work(ch, edges, applied, work):
                if ch.work == "edges":
                    edges = edges + work
                elif ch.work == "updates":
                    applied = applied + work
                return edges, applied

            for i in range(1, K):
                if edge_space == "hbm" and chans[i - 1].work == "edges":
                    hbm_win = count_windows(hbm_win, routed.recv_valid)
                st, msgs, mvalid, d, work, npop, npush, nspill = comm.run(
                    make_mid(i), leg_shard(i), st, routed.recv,
                    routed.recv_valid,
                    routed.spill, routed.spill_valid, dyn_pops)
                with jax.named_scope("telemetry"):
                    drops = drops + d
                    n_pop = n_pop + npop
                    n_push = n_push + npush
                    n_replay = n_replay + nspill
                    edges, applied = add_work(chans[i - 1], edges, applied,
                                              work)
                routed = route(i, msgs, mvalid)
                with jax.named_scope("telemetry"):
                    link_round = link_round + routed.link_flits
                    hop_round = hop_round + routed.hop_hist
                    die_round = die_round + routed.die_hist
                sents.append(routed.sent)
                spillv.append(routed.spill_valid)
            if edge_space == "hbm" and chans[K - 1].work == "edges":
                hbm_win = count_windows(hbm_win, routed.recv_valid)
            st, d, work, nspill = comm.run(stage_last, leg_shard(K), st,
                                           routed.recv, routed.recv_valid,
                                           routed.spill,
                                           routed.spill_valid)
        with jax.named_scope("telemetry"):
            drops = drops + d
            n_replay = n_replay + nspill
            edges, applied = add_work(chans[K - 1], edges, applied, work)

            # NoC telemetry: global per-link occupancy of this round, and
            # the per-tile pressure fed back into next round's TSU budgets.
            link_round = comm.psum(link_round)
            hop_round = comm.psum(hop_round)
            die_round = comm.psum(die_round)
            st = st._replace(net_pressure=comm.run(
                lambda me, lf: net.pressure(me, lf), link_round))

        glob = comm.to_global
        with jax.named_scope("control"):
            pending = comm.psum(comm.run(_pending, st))
            nxt = comm.psum(comm.run(_next_pending, st))
            if cfg.mode == "bsp":
                do_swap = (pending == 0) & (nxt > 0)
                st = comm.run(_bsp_swap, st, _bcast(comm, do_swap))
                epochs_inc = do_swap
                pending = pending + nxt
            else:
                epochs_inc = jnp.zeros_like(pending)

        with jax.named_scope("telemetry"):
            msgs_vec = jnp.stack([glob(comm.psum(s)) for s in sents])
            spills_vec = jnp.stack([
                glob(comm.psum(comm.run(
                    lambda me, v: v.sum(dtype=jnp.int32), sv)))
                for sv in spillv])
            link_g = glob(link_round)
            edges_g = glob(comm.psum(edges))
            applied_g = glob(comm.psum(applied))

            # Cycle/energy model (repro.perf): the round costs its slowest
            # tile's compute plus the busiest link's serialization, each
            # link priced by its class (local / ruche express / torus
            # wrap).  An HBM-resident shard additionally pays t_hbm/e_hbm
            # per streamed edge word (the per-space pricing split; the
            # terms are absent — not zero-multiplied — on all-VMEM runs,
            # keeping them bit-stable with the pre-memspace model).
            streaming = edge_space == "hbm"
            hbm_edges_tile = hbm_win * jnp.int32(window) if streaming \
                else None
            hw_g = glob(comm.psum(hbm_win))
            he_g = hw_g * jnp.int32(window) if streaming else hw_g
            comp = tile_compute_cycles(pp, n_pop, n_push, n_replay, edges,
                                       applied, hbm_edges=hbm_edges_tile)
            cyc_round = (jnp.float32(pp.t_round) + glob(comm.pmax(comp))
                         + (link_g.astype(jnp.float32) * t_hop).max())
            energy_round = round_energy_pj(
                pp, comm.size, edges_g, applied_g, msgs_vec.sum(),
                spills_vec.sum(), link_g, e_hop, cyc_round,
                hbm_edges_g=he_g if streaming else None)
            cycles_acc, c_cyc = kahan_add(stats.cycles, kcomp[0], cyc_round)
            energy_acc, c_en = kahan_add(stats.energy_pj, kcomp[1],
                                         energy_round)

            stats = Stats(
                rounds=stats.rounds + 1,
                epochs=stats.epochs + glob(epochs_inc),
                msgs=stats.msgs + msgs_vec,
                spills=stats.spills + spills_vec,
                edges_scanned=stats.edges_scanned + edges_g,
                updates_applied=stats.updates_applied + applied_g,
                drops=stats.drops + glob(comm.psum(drops)),
                work_max=stats.work_max + glob(comm.pmax(edges)),
                flits_per_link=stats.flits_per_link + link_g,
                max_link_occupancy=jnp.maximum(stats.max_link_occupancy,
                                               link_g.max()),
                hop_histogram=stats.hop_histogram + glob(hop_round),
                die_crossings=stats.die_crossings + glob(die_round),
                cycles=cycles_acc,
                energy_pj=energy_acc,
                launches=stats.launches + jnp.int32(launch_tally.n),
                hbm_windows=stats.hbm_windows + hw_g,
                hbm_edges=stats.hbm_edges + he_g,
                migrated_vertices=stats.migrated_vertices,
                migration_cycles=stats.migration_cycles,
                migration_pj=stats.migration_pj,
            )
        if tracing:
            with jax.named_scope("recorder"):
                # Flight recorder (repro.trace): pure reads of telemetry
                # the round already computed, plus trace-only reductions —
                # nothing here feeds back into state, values or Stats (the
                # invariance contract).  All recorded values are
                # global/replicated, like Stats, so shard_map carries an
                # identical ring per device.
                comp_all = comm.to_global(comm.all_gather(comp))  # (T,) f32
                occ = comm.run(
                    lambda me, s: jnp.stack([q.count for q in s.queues]), st)
                # the TSU's source grant, recomputed from the same pre-round
                # state stage_first arbitrated on (same integer math)
                src_grant = comm.run(
                    lambda me, s, f: _budgets(cfg, prog, qcaps, pops, s,
                                              plimit, f)[0], st0, full)
                tbuf = record_round(tbuf, dict(
                    cyc=cyc_round,
                    cyc_total=cycles_acc,
                    tile_busy=comp_all,
                    crit_tile=jnp.argmax(comp_all).astype(jnp.int32),
                    msgs=msgs_vec,
                    spills=spills_vec,
                    qdepth=glob(comm.psum(occ)),
                    qdepth_max=glob(comm.pmax(occ)),
                    chan_budget=glob(comm.psum(dyn_pops)),
                    src_budget=glob(comm.psum(src_grant)),
                    link_cls=(cls_onehot * link_g[None, :]).sum(axis=1),
                    launches=jnp.int32(launch_tally.n),
                    hbm_windows=hw_g,
                    frontier=glob(comm.psum(comm.run(
                        lambda me, s: s.frontier.sum(dtype=jnp.int32), st))),
                    pending=glob(pending),
                ), round_ix, cfg.trace_every)
        with jax.named_scope("control"):
            return st, stats, (c_cyc, c_en), tbuf, glob(pending)

    return rnd


def _bcast(comm, x):
    """Broadcast a global scalar back to per-device shape for comm.run."""
    if isinstance(comm, LocalComm):
        return jnp.broadcast_to(x, (comm.size,))
    return x


def init_state(comm, cfg: EngineConfig, v_chunk: int, value, frontier,
               alg=BFS, acc=None) -> EngineState:
    """value/frontier/acc: (T, v_chunk) under LocalComm, (v_chunk,) under
    Axis.  ``alg`` (AlgSpec or Program) fixes the channel queue shapes."""
    prog = as_program(alg)
    lead = (comm.size,) if isinstance(comm, LocalComm) else ()

    def mk_queue(ch):
        # allocated through the memory-space registry (repro.mem): the
        # channel's declared space is validated at config time.
        q = queue_make(ch.qcap(cfg), ch.width, space=ch.resolve_space(cfg),
                       label=f"queue[{ch.name}]")
        if lead:
            return Queue(jnp.broadcast_to(q.data, lead + q.data.shape),
                         jnp.broadcast_to(q.count, lead))
        return q

    if acc is None:
        acc = jnp.zeros(lead + (v_chunk,), jnp.float32)
    return EngineState(
        value=value,
        acc=acc,
        frontier=frontier,
        next_frontier=jnp.zeros(lead + (v_chunk,), bool),
        queues=tuple(mk_queue(ch) for ch in prog.channels),
        net_pressure=jnp.zeros(lead, jnp.int32),
    )


def run_engine(comm, cfg: EngineConfig, alg, shard: GraphShard,
               st: EngineState, e_chunk: int, v_chunk: int):
    """Run rounds until the global idle signal fires (or max_rounds).

    ``alg`` is an AlgSpec (compiled via ``classic_program``) or any
    :class:`repro.core.program.Program`.  Returns ``(state, stats,
    trace, pending)`` — ``trace`` is the captured
    :class:`repro.trace.TraceBuf` ring when ``cfg.trace``, ``None``
    otherwise (the trace-off carry is an empty pytree: byte-identical to
    a build without the recorder); ``pending`` is the global work left
    when the loop stopped, nonzero only when ``max_rounds`` cut the run
    (the host drivers raise on it, :func:`check_finished`).
    """
    prog = as_program(alg)
    prog.validate(cfg, comm.size, e_chunk, v_chunk)
    net = make_network(cfg, comm.size)
    rnd = make_round(comm, net, cfg, prog, e_chunk, v_chunk, shard)
    tbuf0 = zero_trace(cfg, comm.size, prog) if cfg.trace else ()

    def cond(carry):
        _, _, _, _, pending, r = carry
        with jax.named_scope("control"):
            return (pending > 0) & (r < cfg.max_rounds)

    def body(carry):
        st, stats, kcomp, tbuf, _, r = carry
        st, stats, kcomp, tbuf, pending = rnd(st, stats, kcomp, tbuf)
        with jax.named_scope("control"):
            return st, stats, kcomp, tbuf, pending, r + 1

    with jax.named_scope("init"):
        pending0 = comm.to_global(comm.psum(comm.run(_pending, st)))
    zf = jnp.zeros((), jnp.float32)
    st, stats, _, tbuf, pending, _ = jax.lax.while_loop(
        cond, body,
        (st, Stats.zero(net.num_links, net.max_hops, len(prog.channels),
                        net.max_die_crossings),
         (zf, zf), tbuf0, pending0, jnp.int32(0)))
    return st, stats, (tbuf if cfg.trace else None), pending


def check_finished(cfg: EngineConfig, left, what: str, unit: str = "tasks",
                   drops=0):
    """Raise when a run's values are not its result: it stopped at
    ``cfg.max_rounds`` with ``left`` work (pending tasks, or unfinished
    query lanes) still to do, or a full queue dropped ``drops``
    messages.  Returning either as a result would hide it."""
    left, drops = int(left), int(drops)
    if left:
        raise RuntimeError(
            f"{what} stopped at max_rounds={cfg.max_rounds} with {left} "
            f"{unit} left: its values are partial (raise "
            f"EngineConfig.max_rounds)")
    if drops:
        raise RuntimeError(
            f"{what} dropped {drops} messages at a full queue: its values "
            f"are wrong (raise cap_rangeq/cap_updq)")
