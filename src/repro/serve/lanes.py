"""Query lanes: a batch of B traversals vmapped through the engine round.

The Dalorex machine of :mod:`repro.core.engine` runs ONE program over one
resident graph.  A serving deployment answers many point queries (BFS /
SSSP sources) against that same graph; PIUMA's answer to small-message
underutilization is many concurrent threads sharing one memory system, and
the software analogue here is a *query-lane axis*: the per-round function
built by :func:`repro.core.engine.make_round` is ``jax.vmap``-ed over a
leading ``(B,)`` axis, so B independent traversals share the resident
graph shard, the round loop, the NoC and the TSU.

Bit-identity contract.  Each lane's trajectory is EXACTLY the solo run's:

* ``vmap`` preserves per-lane computation (the graph ``shard`` is closed
  over by the round, broadcast — never stacked);
* a lane whose own pending-work signal (:func:`repro.core.engine.
  pending_work`) hits zero is *frozen* by :func:`repro.core.engine.
  lane_select` — its state, Stats and Kahan compensation stop evolving,
  exactly as if its solo ``while_loop`` had exited.

So per-lane values AND every per-lane Stats field (rounds, msgs, cycles,
energy, link telemetry, ...) are bit-identical to B separate single-query
runs, on both execution backends (xla / pallas — the Pallas kernels take
the extra lane axis through ``pallas_call``'s batching rule as a grid
dimension) and both comm backends (LocalComm / shard_map).  The batch
finishes in ``max_i rounds_i`` shared rounds instead of ``sum_i rounds_i``
sequential ones — the whole point (tests/test_serve.py pins both).

Batch clock.  Lanes time-multiplex the tiles, so the *batch* makespan is
priced per round as the fixed round overhead paid once plus every active
lane's marginal work::

    cyc_round = t_round + sum over active lanes of (d_cyc_lane - t_round)

and batch energy re-apportions static leakage onto that shared makespan
(each lane's accumulator priced leakage over its own ``d_cyc``; the batch
pays it once over ``cyc_round``)::

    en_round = sum(d_en_lane - leak_pj(T, d_cyc_lane)) + leak_pj(T, cyc_round)

At B=1 both degenerate to the solo accumulators; at B>1 the batch clock
grows sublinearly in B — the amortization fig12 measures.  Both are
Kahan-compensated like the engine's own accumulators.

``done_round`` / ``done_cycle`` record, per lane, the shared round index
and batch-clock value at which the lane finished — the completion side of
the front end's enqueue -> admit -> complete latency accounting
(:mod:`repro.serve.frontend`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import AxisComm, LocalComm
from repro.core.engine import (EngineConfig, EngineState, GraphShard, Stats,
                               check_finished, init_state, lane_select,
                               make_round, pending_work)
from repro.core.graph import PartitionedGraph
from repro.core.program import CLASSIC, as_program
from repro.noc import make_network
from repro.perf import leak_pj
from repro.trace.buffer import zero_trace


class LaneCarry(NamedTuple):
    """The batched round-loop carry: everything lane-led ``(B, ...)``
    except the shared batch counters (scalar)."""

    st: EngineState       # lane-led engine state
    stats: Stats          # lane-led per-query Stats
    kcomp: tuple          # ((B,) f32, (B,) f32) per-lane Kahan compensation
    pending: jax.Array    # (B,) i32 — per-lane global pending work
    rounds: jax.Array     # () i32 — shared batch rounds so far
    clock: jax.Array      # () f32 — batch makespan, modeled cycles
    clock_c: jax.Array    # () f32 — Kahan compensation of `clock`
    energy: jax.Array     # () f32 — batch energy, pJ
    energy_c: jax.Array   # () f32 — Kahan compensation of `energy`
    done_round: jax.Array  # (B,) i32 — batch round a lane finished at
                           # (-1 = still running / never finished)
    done_cycle: jax.Array  # (B,) f32 — batch clock at lane completion
    halt: jax.Array       # () bool — segment stop flag (continuous mode)
    trace: tuple = ()     # lane-led (B, ...) TraceBuf when cfg.trace,
                          # else the empty pytree (no extra carry leaves)


def lane_state(comm, cfg: EngineConfig, v_chunk: int, value, frontier, alg,
               acc=None) -> EngineState:
    """Vmapped :func:`repro.core.engine.init_state` over the leading lane
    axis: ``value``/``frontier`` are ``(B, T, v_chunk)`` under LocalComm,
    ``(B, v_chunk)`` under AxisComm."""
    prog = as_program(alg)
    if acc is None:
        acc = jnp.zeros_like(value)
    return jax.vmap(
        lambda v, f, a: init_state(comm, cfg, v_chunk, v, f, prog, a)
    )(value, frontier, acc)


def lane_carry(comm, net, cfg: EngineConfig, prog, st: EngineState
               ) -> LaneCarry:
    """A fresh carry for a lane-led state: per-lane pending computed with
    the engine's own :func:`pending_work` definition, zero Stats broadcast
    to the lane axis, batch clocks at zero.  Lanes that start with no
    pending work (padding lanes) are born finished: ``done_round = 0``."""
    prog = as_program(prog)
    pend0 = jax.vmap(
        lambda s: comm.to_global(comm.psum(comm.run(pending_work, s))))(st)
    B = pend0.shape[0]
    z = Stats.zero(net.num_links, net.max_hops, len(prog.channels),
                   net.max_die_crossings)
    stats = jax.tree.map(lambda x: jnp.broadcast_to(x, (B,) + x.shape), z)
    zf = jnp.zeros((B,), jnp.float32)
    z0 = jnp.zeros((), jnp.float32)
    trace = ()
    if cfg.trace:  # each lane records its own ring, frozen when the lane is
        tb = zero_trace(cfg, comm.size, prog)
        trace = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (B,) + x.shape), tb)
    return LaneCarry(
        st=st, stats=stats, kcomp=(zf, zf), pending=pend0,
        rounds=jnp.zeros((), jnp.int32),
        clock=z0, clock_c=z0, energy=z0, energy_c=z0,
        done_round=jnp.where(pend0 > 0, jnp.int32(-1), jnp.int32(0)),
        done_cycle=jnp.zeros((B,), jnp.float32),
        halt=jnp.zeros((), bool), trace=trace)


def lane_loop(comm, net, cfg: EngineConfig, prog, e_chunk: int, v_chunk: int,
              shard: GraphShard, carry: LaneCarry,
              stop_on_finish: bool = False) -> LaneCarry:
    """Run the batched round loop until every lane is idle (or max_rounds).

    One shared ``lax.while_loop`` drives ``jax.vmap(rnd)``; finished lanes
    are frozen by :func:`lane_select` so their trajectories stay
    bit-identical to solo runs.  With ``stop_on_finish=True`` the loop
    additionally exits the round ANY active lane completes — the
    continuous-batching segment runner: the host then recycles the freed
    lane(s) and resumes with the same carry (``halt`` is cleared by
    :func:`recycle_lanes`).
    """
    prog = as_program(prog)
    rnd = make_round(comm, net, cfg, prog, e_chunk, v_chunk, shard)
    vrnd = jax.vmap(rnd)
    pp = cfg.perf
    T = comm.size

    def kahan(total, comp, inc):
        y = inc - comp
        t = total + y
        return t, (t - total) - y

    def cond(c: LaneCarry):
        return ((c.pending > 0).any() & (c.rounds < cfg.max_rounds)
                & ~c.halt)

    def body(c: LaneCarry):
        active = c.pending > 0
        st2, stats2, kcomp2, tbuf2, pend2 = vrnd(c.st, c.stats, c.kcomp,
                                                 c.trace)
        st = lane_select(active, c.st, st2)
        stats = lane_select(active, c.stats, stats2)
        kcomp = lane_select(active, c.kcomp, kcomp2)
        trace = lane_select(active, c.trace, tbuf2)
        pending = jnp.where(active, pend2, c.pending)
        rounds = c.rounds + 1
        # batch clock: realized per-lane increments (0 for frozen lanes);
        # the shared round pays t_round once, then each active lane's
        # marginal cost on top.
        d_cyc = stats.cycles - c.stats.cycles
        d_en = stats.energy_pj - c.stats.energy_pj
        tr = jnp.float32(pp.t_round)
        cyc_round = tr + (d_cyc - jnp.where(active, tr, 0.0)).sum()
        en_round = ((d_en - leak_pj(pp, T, d_cyc)).sum()
                    + leak_pj(pp, T, cyc_round))
        clock, clock_c = kahan(c.clock, c.clock_c, cyc_round)
        energy, energy_c = kahan(c.energy, c.energy_c, en_round)
        newly = active & (pending == 0)
        done_round = jnp.where(newly, rounds, c.done_round)
        done_cycle = jnp.where(newly, clock, c.done_cycle)
        halt = newly.any() if stop_on_finish else c.halt
        return LaneCarry(st, stats, kcomp, pending, rounds, clock, clock_c,
                         energy, energy_c, done_round, done_cycle, halt,
                         trace)

    return jax.lax.while_loop(cond, body, carry)


# --------------------------------------------------------------------------
# Jitted entry points: LocalComm emulation and shard_map SPMD.
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("prog", "cfg", "T", "e_chunk", "v_chunk"))
def local_lanes_call(prog, cfg: EngineConfig, T: int, e_chunk: int,
                     v_chunk: int, shard: GraphShard, value, frontier, acc
                     ) -> LaneCarry:
    """Full batched run under LocalComm: ``(B, T, v_chunk)`` value /
    frontier / acc in, final :class:`LaneCarry` out."""
    comm = LocalComm(T)
    net = make_network(cfg, T)
    st = lane_state(comm, cfg, v_chunk, value, frontier, prog, acc)
    carry = lane_carry(comm, net, cfg, prog, st)
    return lane_loop(comm, net, cfg, prog, e_chunk, v_chunk, shard, carry)


@partial(jax.jit, static_argnames=("prog", "cfg", "T", "e_chunk", "v_chunk",
                                   "stop_on_finish"))
def local_lanes_segment(prog, cfg: EngineConfig, T: int, e_chunk: int,
                        v_chunk: int, shard: GraphShard, carry: LaneCarry,
                        stop_on_finish: bool = True) -> LaneCarry:
    """Resume a batched run from an existing carry, stopping at the first
    round any active lane finishes — the continuous-batching segment."""
    comm = LocalComm(T)
    net = make_network(cfg, T)
    return lane_loop(comm, net, cfg, prog, e_chunk, v_chunk, shard, carry,
                     stop_on_finish=stop_on_finish)


def spmd_lanes_call(pg: PartitionedGraph, prog, cfg: EngineConfig, value,
                    frontier, mesh, axis: str = "x", acc=None):
    """The batched run as true SPMD under shard_map: the tile axis is
    sharded over ``axis`` of ``mesh``, the lane axis is replicated (every
    device runs all B lanes of its own tile row — the same layout a real
    grid would use, queries resident on every tile).

    ``value``/``frontier``/``acc``: ``(B, T, v_chunk)``.  Returns
    ``(values (B, T, v_chunk), stats lane-led, rounds, clock, energy,
    done_round, done_cycle, trace)`` — ``trace`` is the lane-led
    :class:`repro.trace.TraceBuf` when ``cfg.trace``, else ``None``.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    T = pg.T
    prog = as_program(prog)
    prog.validate(cfg, T, pg.e_chunk, pg.v_chunk)
    comm = AxisComm(axis, T)
    net = make_network(cfg, T)
    if acc is None:
        acc = jnp.zeros_like(value)
    spec2 = P(axis, None)
    spec3 = P(None, axis, None)

    def body(ptr_start, deg, edge_dst, edge_val, value, frontier, acc):
        shard = GraphShard(ptr_start[0], deg[0], edge_dst[0], edge_val[0])
        st = lane_state(comm, cfg, pg.v_chunk, value[:, 0], frontier[:, 0],
                        prog, acc[:, 0])
        carry = lane_carry(comm, net, cfg, prog, st)
        out = lane_loop(comm, net, cfg, prog, pg.e_chunk, pg.v_chunk, shard,
                        carry)
        return (out.st.value[:, None], out.stats, out.rounds, out.clock,
                out.energy, out.done_round, out.done_cycle, out.trace)

    stats_spec = jax.tree.map(lambda _: P(), Stats.zero())
    # lane-led trace rings hold only globals — replicated, like Stats
    trace_spec = jax.tree.map(lambda _: P(), zero_trace(cfg, T, prog)) \
        if cfg.trace else ()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec2,) * 4 + (spec3,) * 3,
        out_specs=(spec3, stats_spec, P(), P(), P(), P(), P(), trace_spec),
        check_vma=False)
    args = [jax.device_put(a, NamedSharding(mesh, spec2)) for a in
            (pg.ptr_start, pg.deg, pg.edge_dst, pg.edge_val)]
    args += [jax.device_put(a, NamedSharding(mesh, spec3)) for a in
             (value, frontier, acc)]
    return jax.jit(fn)(*args)


# --------------------------------------------------------------------------
# Host-side batch construction and the one-shot multi-source driver.
# --------------------------------------------------------------------------

def batch_min_state(pg: PartitionedGraph, sources):
    """``(B, T, v_chunk)`` value/frontier for a batch of min-app sources.

    ``sources[i] < 0`` makes lane i a *padding lane*: all-INF values and an
    empty frontier, so it is born idle, frozen from round 0, and costs the
    batch nothing — the front end pads partial batches with these.
    """
    B = len(sources)
    value = np.full((B, pg.T, pg.v_chunk),
                    np.float32(np.finfo(np.float32).max), np.float32)
    frontier = np.zeros((B, pg.T, pg.v_chunk), bool)
    for i, s in enumerate(sources):
        if s < 0:
            continue
        p = int(pg.place[int(s)])
        t, l = divmod(p, pg.v_chunk)
        value[i, t, l] = 0.0
        frontier[i, t, l] = True
    return jnp.asarray(value), jnp.asarray(frontier)


def lane_values(pg: PartitionedGraph, value) -> np.ndarray:
    """One lane's ``(T, v_chunk)`` placed-space values -> ``(V,)`` f64 in
    original vertex order, unreached slots mapped to +inf (the min-app
    convention of :func:`repro.core.algorithms.bfs`)."""
    flat = np.asarray(value).reshape(-1)
    out = flat[np.asarray(pg.place)].astype(np.float64)
    out[out >= np.float32(np.finfo(np.float32).max)] = np.inf
    return out


@dataclasses.dataclass
class BatchResult:
    """One batched multi-source run, host-side."""

    values: np.ndarray       # (B, V) f64 in original vertex order
    stats: Stats             # lane-led (B, ...) per-query Stats
    total_rounds: int        # shared batch rounds (== max over lane rounds)
    batch_cycles: float      # batch-clock makespan, modeled cycles
    batch_energy_pj: float   # batch energy on the shared makespan
    done_round: np.ndarray   # (B,) i32
    done_cycle: np.ndarray   # (B,) f32
    sources: np.ndarray      # (B,) the admitted sources (-1 = padding)
    trace: object = None     # lane-led (B, ...) TraceBuf when cfg.trace

    @property
    def seq_rounds(self) -> int:
        """What B sequential solo runs would have cost in rounds (valid
        because each lane's Stats are bit-identical to its solo run)."""
        return int(np.asarray(self.stats.rounds).sum())


def multi_source(pg: PartitionedGraph, app: str, sources,
                 cfg: EngineConfig = EngineConfig(), mesh=None
                 ) -> BatchResult:
    """Answer a batch of point queries (``app`` in "bfs" / "sssp") over the
    resident graph in one shared batched run.

    ``mesh=None`` runs the LocalComm emulation; a mesh runs shard_map SPMD.
    Per-query results are bit-identical to solo :func:`repro.core.
    algorithms.bfs` / ``sssp`` runs at the same ``cfg``.  A batch that
    ``cfg.max_rounds`` cut before every lane finished, or that dropped
    messages, raises.
    """
    if app not in ("bfs", "sssp"):
        raise ValueError(f"multi_source serves point queries (bfs/sssp), "
                         f"got {app!r}")
    alg_spec = CLASSIC[app]
    sources = np.asarray(sources, np.int64)
    value, frontier = batch_min_state(pg, sources)
    if mesh is None:
        shard = GraphShard(pg.ptr_start, pg.deg, pg.edge_dst, pg.edge_val)
        prog = as_program(alg_spec)
        prog.validate(cfg, pg.T, pg.e_chunk, pg.v_chunk)
        out = local_lanes_call(prog, cfg, pg.T, pg.e_chunk, pg.v_chunk,
                               shard, value, frontier,
                               jnp.zeros_like(value))
        vals, stats = out.st.value, out.stats
        rounds, clock, energy = out.rounds, out.clock, out.energy
        done_round, done_cycle = out.done_round, out.done_cycle
        trace = out.trace if cfg.trace else None
    else:
        (vals, stats, rounds, clock, energy, done_round, done_cycle,
         trace) = spmd_lanes_call(pg, alg_spec, cfg, value, frontier, mesh)
        if not cfg.trace:
            trace = None
    check_finished(cfg, (np.asarray(done_round) < 0).sum(),
                   f"{app} batch of {len(sources)}", "query lanes",
                   drops=np.asarray(stats.drops).sum())
    B = len(sources)
    flat = np.asarray(vals).reshape(B, -1)
    values = flat[:, np.asarray(pg.place)].astype(np.float64)
    values[values >= np.float32(np.finfo(np.float32).max)] = np.inf
    return BatchResult(
        values=values, stats=jax.tree.map(np.asarray, stats),
        total_rounds=int(rounds), batch_cycles=float(clock),
        batch_energy_pj=float(energy),
        done_round=np.asarray(done_round), done_cycle=np.asarray(done_cycle),
        sources=sources, trace=trace)
