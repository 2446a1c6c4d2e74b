"""Serving front end: request queue -> batches -> lanes -> latency rows.

The machine side (:mod:`repro.serve.lanes`) answers a fixed batch of B
sources; this module is the *service* wrapped around it: a request queue
admits sources as they arrive, forms fixed-width batches (padding partial
batches with idle lanes), drives the batched round loop, and streams back
per-query results with latency accounted on the perf model's cycle clock
— every timestamp below is modeled machine cycles, not host wall time.

Latency accounting (per query)::

    enqueue_cycle   the request arrives (the arrival process)
    admit_cycle     its batch forms / its lane is recycled to it
    complete_cycle  its lane's pending work hits zero (batch clock)

    wait    = admit - enqueue      (queueing delay)
    latency = complete - enqueue   (what the client sees)

Two batching policies:

* ``"static"`` — classic fixed batches: admit up to ``width`` arrived
  requests, run the batch TO COMPLETION, advance the clock by the batch
  makespan, repeat.  Stragglers hold the whole batch (the head-of-line
  blocking fig12's latency columns expose).  Works on both comm backends
  (LocalComm and shard_map SPMD).
* ``"continuous"`` — continuous batching: the round loop is run in
  *segments* that stop the moment any lane finishes; the freed lane is
  immediately recycled to the next queued request (state re-initialized in
  place, its channel queues reset with :func:`repro.core.queues.
  queue_clear`, its Stats slice zeroed) while the other lanes keep their
  in-flight traversals.  LocalComm only (the host sits in the admit loop).

Both policies price time on the shared *batch clock* of
:mod:`repro.serve.lanes` (lanes time-multiplex the tiles; the fixed round
overhead is paid once per round), so a wider batch amortizes rounds and a
recycled lane never waits for its cohort.
"""
from __future__ import annotations

import dataclasses
from collections import deque
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import LocalComm
from repro.core.engine import EngineConfig, EngineState, check_finished
from repro.core.graph import PartitionedGraph
from repro.core.program import CLASSIC, as_program
from repro.core.queues import Queue, queue_clear
from repro.serve.lanes import (GraphShard, LaneCarry, batch_min_state,
                               lane_carry, lane_state, lane_values,
                               local_lanes_segment, multi_source)


def arrival_cycles(n: int, pattern: str = "burst", gap: float = 0.0,
                   seed: int = 0) -> np.ndarray:
    """Enqueue timestamps (modeled cycles) for ``n`` requests.

    ``pattern``: "burst" (all at cycle 0 — an offline batch), "uniform"
    (one every ``gap`` cycles — a paced open loop), or "poisson"
    (exponential interarrivals with mean ``gap`` — an open loop with
    bursts).  Deterministic at a fixed ``seed``.
    """
    if pattern == "burst":
        return np.zeros(n, np.float64)
    if gap <= 0:
        raise ValueError(f"{pattern!r} arrivals need gap > 0 cycles")
    if pattern == "uniform":
        return gap * np.arange(n, dtype=np.float64)
    if pattern == "poisson":
        rng = np.random.default_rng(seed)
        return np.cumsum(rng.exponential(gap, size=n))
    raise ValueError(f"unknown arrival pattern {pattern!r}")


@dataclasses.dataclass
class QueryRecord:
    """One served query, timestamps in modeled cycles."""

    qid: int
    source: int
    enqueue_cycle: float
    admit_cycle: float
    complete_cycle: float
    rounds: int     # the lane's own rounds (== its solo run's rounds)
    edges: int      # the lane's edges_scanned
    values: np.ndarray = None  # (V,) f64 result, original vertex order

    @property
    def wait(self) -> float:
        return self.admit_cycle - self.enqueue_cycle

    @property
    def latency(self) -> float:
        return self.complete_cycle - self.enqueue_cycle


@dataclasses.dataclass
class ServeReport:
    """Aggregate of one serving run; throughput on the modeled clock."""

    app: str
    policy: str
    width: int
    arrival: str
    records: list
    batches: int
    total_cycles: float      # serving makespan (batch clock + idle gaps)
    total_energy_pj: float
    total_rounds: int        # shared rounds actually executed
    seq_rounds: int          # what solo runs would have cost (sum of
                             # per-lane rounds — each lane == its solo run)
    drops: int = 0           # summed over lanes; MUST be 0 (backpressure)
    f_ghz: float = 1.0
    migrated_vertices: int = 0  # vertices moved by between-batch adaptation

    @property
    def queries(self) -> int:
        return len(self.records)

    @property
    def time_s(self) -> float:
        return self.total_cycles / (self.f_ghz * 1e9)

    @property
    def qps(self) -> float:
        return self.queries / self.time_s if self.time_s > 0 else 0.0

    @property
    def j_per_query(self) -> float:
        return (self.total_energy_pj * 1e-12 / self.queries
                if self.queries else 0.0)

    @property
    def edges_total(self) -> int:
        return sum(r.edges for r in self.records)

    @property
    def gteps(self) -> float:
        return (self.edges_total / self.time_s / 1e9
                if self.time_s > 0 else 0.0)

    def latency_cycles(self, q: float) -> float:
        """Latency percentile (0..100) over the served queries, cycles."""
        return float(np.percentile([r.latency for r in self.records], q))

    def row(self) -> dict:
        row = {
            "app": self.app, "policy": self.policy, "width": self.width,
            "arrival": self.arrival, "queries": self.queries,
            "batches": self.batches, "rounds": self.total_rounds,
            "seq_rounds": self.seq_rounds,
            "cycles": int(round(self.total_cycles)),
            "energy_pj": round(self.total_energy_pj, 1),
            "drops": self.drops,
            "qps": round(self.qps, 1),
            "gteps": round(self.gteps, 6),
            "j_per_query": round(self.j_per_query * 1e12, 1),  # pJ/query
            "lat_p50": int(round(self.latency_cycles(50))),
            "lat_p95": int(round(self.latency_cycles(95))),
            "lat_max": int(round(self.latency_cycles(100))),
        }
        if self.migrated_vertices:  # additive: pre-adaptive rows unchanged
            row["migrated_vertices"] = self.migrated_vertices
        return row


@jax.jit
def _recycle(carry: LaneCarry, lane, value, frontier) -> LaneCarry:
    """Re-initialize ONE lane of the carry in place for a fresh query:
    min-app value/frontier set, acc and BSP frontier zeroed, channel
    queues reset (:func:`queue_clear` — bit-equal to freshly made ones),
    Stats slice and Kahan compensation zeroed, pending recomputed, and the
    segment ``halt`` flag cleared so the loop resumes."""
    st = carry.st
    cleared = tuple(queue_clear(Queue(q.data[lane], q.count[lane]))
                    for q in st.queues)
    st = EngineState(
        value=st.value.at[lane].set(value),
        acc=st.acc.at[lane].set(0.0),
        frontier=st.frontier.at[lane].set(frontier),
        next_frontier=st.next_frontier.at[lane].set(False),
        queues=tuple(Queue(q.data.at[lane].set(c.data),
                           q.count.at[lane].set(c.count))
                     for q, c in zip(st.queues, cleared)),
        net_pressure=st.net_pressure.at[lane].set(0))
    stats = jax.tree.map(lambda s: s.at[lane].set(jnp.zeros_like(s[lane])),
                         carry.stats)
    kcomp = jax.tree.map(lambda k: k.at[lane].set(0.0), carry.kcomp)
    # the recycled lane's flight-recorder ring starts over too (cursor 0,
    # every slot marked empty) so its trace is the fresh query's alone
    trace = carry.trace
    if len(trace):  # a lane-led TraceBuf (cfg.trace)
        trace = jax.tree.map(
            lambda s: s.at[lane].set(jnp.zeros_like(s[lane])), trace)
        trace = trace._replace(
            round_id=trace.round_id.at[lane].set(-1))
    # fresh lane: queues empty, so pending is the frontier population
    pend = frontier.sum(dtype=jnp.int32)
    return carry._replace(
        st=st, stats=stats, kcomp=kcomp, trace=trace,
        pending=carry.pending.at[lane].set(pend),
        done_round=carry.done_round.at[lane].set(-1),
        done_cycle=carry.done_cycle.at[lane].set(0.0),
        halt=jnp.zeros((), bool))


class Frontend:
    """The serving loop over one resident partitioned graph.

    >>> fe = Frontend(pg, app="bfs", cfg=cfg, width=8)
    >>> report = fe.serve(sources, arrival="poisson", gap=5e4)
    """

    def __init__(self, pg: PartitionedGraph, app: str = "bfs",
                 cfg: EngineConfig = EngineConfig(), width: int = 8,
                 policy: str = "static", mesh=None, graph=None):
        if app not in ("bfs", "sssp"):
            raise ValueError(f"servable point-query apps: bfs/sssp, "
                             f"got {app!r}")
        if policy not in ("static", "continuous"):
            raise ValueError(f"unknown policy {policy!r}")
        if policy == "continuous" and mesh is not None:
            raise ValueError("continuous batching is LocalComm-only "
                             "(the host drives the admit loop)")
        if width < 1:
            raise ValueError("width must be >= 1")
        if cfg.adapt and graph is None:
            raise ValueError("cfg.adapt needs graph= (the host CSR) to "
                             "re-deal edge segments between batches")
        if cfg.adapt and policy != "static":
            raise ValueError("between-batch adaptation is static-policy "
                             "only (continuous lanes are never quiescent)")
        self.pg = pg
        self.app = app
        self.cfg = cfg
        self.width = width
        self.policy = policy
        self.mesh = mesh
        self.graph = graph          # host CSR; needed when cfg.adapt
        self.migrated_vertices = 0  # total moved by between-batch plans
        self.prog = as_program(CLASSIC[app])
        self.prog.validate(cfg, pg.T, pg.e_chunk, pg.v_chunk)

    # -- public ------------------------------------------------------------

    def serve(self, sources, arrival: str = "burst", gap: float = 0.0,
              seed: int = 0) -> ServeReport:
        """Serve ``sources`` (original vertex ids) arriving per
        ``arrival``/``gap`` (see :func:`arrival_cycles`); returns the
        aggregate report with one :class:`QueryRecord` per query."""
        sources = np.asarray(sources, np.int64)
        enq = arrival_cycles(len(sources), arrival, gap, seed)
        queue = deque(
            (i, int(s), float(t)) for i, (s, t) in enumerate(zip(sources,
                                                                 enq)))
        serve = (self._serve_static if self.policy == "static"
                 else self._serve_continuous)
        migrated0 = self.migrated_vertices
        records, batches, cyc, en, rounds, seq, drops = serve(queue)
        records.sort(key=lambda r: r.qid)
        return ServeReport(
            app=self.app, policy=self.policy, width=self.width,
            arrival=arrival, records=records, batches=batches,
            total_cycles=cyc, total_energy_pj=en, total_rounds=rounds,
            seq_rounds=seq, drops=drops, f_ghz=self.cfg.perf.f_ghz,
            migrated_vertices=self.migrated_vertices - migrated0)

    # -- between-batch adaptation (repro.place) ----------------------------

    def _maybe_adapt(self, res):
        """Relabel the resident partition from the finished batch's
        telemetry (lane-led trace rings summed into one busy vector; the
        planner's static in-degree fallback when tracing is off).  The
        batch boundary is the serving quiescent point — every lane has
        drained — so the migration is a pure relabeling and later queries
        see bit-identical values.  Returns the priced ``(cycles, pJ)`` of
        the move, charged to the serving clock by the caller."""
        from repro.perf.model import migration_cost
        from repro.place import (adapt_partition, cfg_tile_die,
                                 migration_words, score_tiles)
        busy = None
        if res.trace is not None:
            from repro.trace.export import lane_trace
            busy = sum(score_tiles(lane_trace(res.trace, lane))
                       for lane in range(self.width))
        old = self.pg
        pg2, plan = adapt_partition(self.graph, old, self.cfg, busy=busy)
        if not plan.num_pairs:
            return 0.0, 0.0
        tile_die = cfg_tile_die(self.cfg, old.T)
        wi, wc = migration_words(old, plan, tile_die)
        cyc, pj = migration_cost(self.cfg.perf, wi, wc)
        self.migrated_vertices += plan.moved_vertices(old)
        self.pg = pg2
        # e_chunk can change in the aligned edge modes: re-check sizing
        self.prog.validate(self.cfg, pg2.T, pg2.e_chunk, pg2.v_chunk)
        return cyc, pj

    # -- static batches ----------------------------------------------------

    def _serve_static(self, queue):
        records, batches = [], 0
        now = 0.0
        energy = 0.0
        rounds = seq = drops = 0
        while queue:
            # the batch forms when its first request has arrived
            now = max(now, queue[0][2])
            batch = []
            while queue and len(batch) < self.width and queue[0][2] <= now:
                batch.append(queue.popleft())
            srcs = [s for _, s, _ in batch] + [-1] * (self.width -
                                                      len(batch))
            res = multi_source(self.pg, self.app, srcs, self.cfg, self.mesh)
            lane_rounds = np.asarray(res.stats.rounds)
            lane_edges = np.asarray(res.stats.edges_scanned)
            for lane, (qid, s, t_enq) in enumerate(batch):
                records.append(QueryRecord(
                    qid=qid, source=s, enqueue_cycle=t_enq,
                    admit_cycle=now,
                    complete_cycle=now + float(res.done_cycle[lane]),
                    rounds=int(lane_rounds[lane]),
                    edges=int(lane_edges[lane]),
                    values=res.values[lane]))
            now += res.batch_cycles
            energy += res.batch_energy_pj
            rounds += res.total_rounds
            seq += res.seq_rounds
            drops += int(np.asarray(res.stats.drops).sum())
            batches += 1
            if (self.cfg.adapt and queue
                    and batches % max(self.cfg.adapt_every, 1) == 0):
                mig_cyc, mig_pj = self._maybe_adapt(res)
                now += mig_cyc
                energy += mig_pj
        return records, batches, now, energy, rounds, seq, drops

    # -- continuous batching (lane recycling) ------------------------------

    def _serve_continuous(self, queue):
        pg, cfg, W = self.pg, self.cfg, self.width
        shard = GraphShard(pg.ptr_start, pg.deg, pg.edge_dst, pg.edge_val)
        comm = LocalComm(pg.T)
        from repro.noc import make_network
        net = make_network(cfg, pg.T)

        # born idle: W padding lanes; the admit loop below fills them
        value, frontier = batch_min_state(pg, [-1] * W)
        st = lane_state(comm, cfg, pg.v_chunk, value, frontier, self.prog)
        carry = lane_carry(comm, net, cfg, self.prog, st)
        lane_qid = [-1] * W          # qid in flight per lane (-1 = idle)
        lane_meta = [None] * W       # (qid, source, enqueue, admit)
        records, batches = [], 0
        drops = 0
        now = 0.0                    # absolute serving clock (cycles)

        def admit():
            nonlocal carry, batches, now
            pending = np.asarray(carry.pending)
            idle = [i for i in range(W) if lane_qid[i] < 0]
            # a fully idle machine fast-forwards to the next arrival
            if queue and len(idle) == W and queue[0][2] > now:
                now = queue[0][2]
            admitted = 0
            for lane in idle:
                if not queue or queue[0][2] > now:
                    break
                assert pending[lane] == 0
                qid, s, t_enq = queue.popleft()
                v1, f1 = batch_min_state(pg, [s])
                carry = _recycle(carry, jnp.int32(lane), v1[0], f1[0])
                lane_qid[lane] = qid
                lane_meta[lane] = (qid, s, t_enq, now)
                admitted += 1
            if admitted:
                batches += 1  # here: one lane-refill event
            return admitted

        admit()
        while any(q >= 0 for q in lane_qid):
            prev_clock = float(carry.clock)
            # clear the segment stop flag even when nothing was admitted
            # (no arrival yet): the remaining in-flight lanes must resume
            carry = carry._replace(halt=jnp.zeros((), bool))
            carry = local_lanes_segment(self.prog, cfg, pg.T, pg.e_chunk,
                                        pg.v_chunk, shard, carry)
            now += float(carry.clock) - prev_clock
            pending = np.asarray(carry.pending)
            # a segment ends when a lane finishes (halt), when every lane
            # is idle, or when max_rounds cuts it
            lane_drops = np.asarray(carry.stats.drops)
            check_finished(cfg, 0 if bool(carry.halt) else
                           (pending > 0).sum(),
                           f"continuous {self.app} serving", "query lanes",
                           drops=lane_drops.sum())
            lane_rounds = np.asarray(carry.stats.rounds)
            lane_edges = np.asarray(carry.stats.edges_scanned)
            for lane in range(W):
                if lane_qid[lane] >= 0 and pending[lane] == 0:
                    qid, s, t_enq, t_admit = lane_meta[lane]
                    records.append(QueryRecord(
                        qid=qid, source=s, enqueue_cycle=t_enq,
                        admit_cycle=t_admit, complete_cycle=now,
                        rounds=int(lane_rounds[lane]),
                        edges=int(lane_edges[lane]),
                        values=lane_values(pg, carry.st.value[lane])))
                    drops += int(lane_drops[lane])
                    lane_qid[lane] = -1
            admit()
        total_rounds = int(carry.rounds)
        # each lane is bit-identical to its solo run, so the sequential
        # cost is just the sum of the per-record round counts
        seq = sum(r.rounds for r in records)
        return (records, batches, now, float(carry.energy), total_rounds,
                seq, drops)
