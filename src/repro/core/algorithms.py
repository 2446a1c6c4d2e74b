"""Host drivers for the paper workloads on the Dalorex engine.

Each driver: (1) initializes per-shard value/acc/frontier state in *placed*
space, (2) runs its :class:`repro.core.program.Program` on the generic
engine (barrierless or BSP) over a comm backend, and (3) maps results back
to original vertex IDs.

The five seed workloads (BFS, SSSP, PageRank, WCC, SpMV) compile to the
classic 3-task program; :func:`kcore` runs the peel program (threshold
fold); :func:`triangles` runs the 4-channel 2-hop chain over a
vertex-aligned, sorted partition (:func:`prepare_triangles`).

Two execution paths share all engine code:

* ``comm=LocalComm(T)`` — T emulated tiles on one device (tests/benchmarks).
* ``comm=AxisComm(axis, T)`` via :func:`spmd_engine_call` — real shard_map
  SPMD over a device mesh (the production / dry-run path).

The drivers emit the host spans of :data:`HOST_SPANS` with
``jax.profiler.TraceAnnotation``, on the profiler's clock beside the
device operations; :func:`engine_leg_map` names the round leg
(``engine.ROUND_LEGS``) of each device operation of the engine program.
A span costs well under a microsecond when no profile is being taken
(DESIGN.md "Device legs and host spans").
"""
from __future__ import annotations

import collections
import contextvars
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.core.comm import AxisComm, LocalComm
from repro.core.engine import (BFS, PAGERANK, ROUND_LEGS, SPMV, SSSP, WCC,
                               AlgSpec, EngineConfig, EngineState,
                               GraphShard, INF, Stats, check_finished,
                               init_state, run_engine, zero_stats)
from repro.core.graph import CSRGraph, PartitionedGraph, partition_graph
from repro.core.program import (TRIANGLES, as_program, kcore_program,
                                sized_cfg)
from repro.trace.buffer import zero_trace

# The host spans of the drivers (jax.profiler.TraceAnnotation names):
#   engine_call      _call as a whole; its arguments name the program and,
#                    for PageRank, the epoch
#   engine_dispatch  the jitted engine call returning (inside engine_call)
#   engine_wait      the blocking read of pending work and drops
#                    (check_finished, inside engine_call)
#   init_state       building a run's placed state (init_*_state)
#   epoch_update     PageRank's rank update between epochs
#   to_original      mapping a result back to original vertex ids
HOST_SPANS = ("engine_call", "engine_dispatch", "engine_wait", "init_state",
              "epoch_update", "to_original")


# --------------------------------------------------------------------------
# State initialization in placed space.
# --------------------------------------------------------------------------

def real_mask(pg: PartitionedGraph) -> np.ndarray:
    """(T, v_chunk) bool — slots that hold a real (non-padding) vertex."""
    return (pg.inv >= 0).reshape(pg.T, pg.v_chunk)


@partial(annotate_function, name="init_state")
def init_min_state(pg: PartitionedGraph, roots: list[int]):
    """value=+inf except roots (=0); frontier = roots."""
    value = np.full((pg.T, pg.v_chunk), np.float32(np.finfo(np.float32).max))
    frontier = np.zeros((pg.T, pg.v_chunk), bool)
    for r in roots:
        p = int(pg.place[r])
        t, l = p // pg.v_chunk, p % pg.v_chunk
        value[t, l] = 0.0
        frontier[t, l] = True
    return jnp.asarray(value), jnp.asarray(frontier)


@partial(annotate_function, name="init_state")
def init_wcc_state(pg: PartitionedGraph):
    """Label = original vertex id; every real vertex starts in the frontier."""
    inv = pg.inv.reshape(pg.T, pg.v_chunk)
    value = np.where(inv >= 0, inv, np.float32(np.finfo(np.float32).max))
    frontier = inv >= 0
    return jnp.asarray(value, jnp.float32), jnp.asarray(frontier)


@partial(annotate_function, name="init_state")
def init_add_state(pg: PartitionedGraph, x: np.ndarray):
    """value = x scattered to placed slots; frontier = real vertices with
    out-edges (vertices with deg 0 emit nothing)."""
    flat = np.zeros(pg.T * pg.v_chunk, np.float32)
    flat[pg.place] = x.astype(np.float32)
    value = flat.reshape(pg.T, pg.v_chunk)
    deg = np.asarray(pg.deg)
    frontier = real_mask(pg) & (deg > 0)
    return jnp.asarray(value), jnp.asarray(frontier)


@partial(annotate_function, name="init_state")
def init_kcore_state(pg: PartitionedGraph, k: int):
    """value = remaining degree; acc = removed flag (1 = out of the core);
    the initially-dead vertices (deg < k, and padding) seed the frontier so
    their decrements propagate."""
    real = real_mask(pg)
    deg = np.asarray(pg.deg)
    value = np.where(real, deg, 0).astype(np.float32)
    dead0 = real & (deg < k)
    acc = np.where(real & ~dead0, 0.0, 1.0).astype(np.float32)
    return jnp.asarray(value), jnp.asarray(dead0), jnp.asarray(acc)


@partial(annotate_function, name="to_original")
def to_original(pg: PartitionedGraph, arr) -> np.ndarray:
    """(T, v_chunk) placed-space array -> (V,) original order."""
    flat = np.asarray(arr).reshape(-1)
    return flat[pg.place]


# --------------------------------------------------------------------------
# Engine invocation: local emulation and SPMD shard_map.
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("prog", "cfg", "T", "e_chunk", "v_chunk"))
def _local_call(prog, cfg: EngineConfig, T: int, e_chunk: int,
                v_chunk: int, shard: GraphShard, value, frontier, acc):
    comm = LocalComm(T)
    with jax.named_scope("init"):
        st = init_state(comm, cfg, v_chunk, value, frontier, prog, acc)
    st, stats, trace, pending = run_engine(comm, cfg, prog, shard, st,
                                           e_chunk, v_chunk)
    return st.value, st.acc, stats, trace, pending


def local_engine_call(pg: PartitionedGraph, alg, cfg: EngineConfig,
                      value, frontier, acc=None):
    """One engine run under LocalComm: ``(value, acc, stats, trace,
    pending)``, with ``pending`` nonzero when ``max_rounds`` cut it."""
    prog = as_program(alg)
    shard = GraphShard(pg.ptr_start, pg.deg, pg.edge_dst, pg.edge_val)
    if acc is None:
        acc = jnp.zeros_like(value)
    return _local_call(prog, cfg, pg.T, pg.e_chunk, pg.v_chunk, shard,
                       value, frontier, acc)


def spmd_engine_call(pg: PartitionedGraph, alg, cfg: EngineConfig,
                     value, frontier, mesh, axis: str = "x", acc=None):
    """Run the engine as true SPMD under shard_map over ``axis`` of ``mesh``.

    Arrays keep the (T, chunk) layout; the leading axis is sharded so each
    device owns one tile row.  Inside, blocks are squeezed to per-device
    shards and the identical engine code runs with ``AxisComm``.  Returns
    the same ``(value, acc, stats, trace, pending)`` as
    :func:`local_engine_call`.
    """
    if acc is None:
        acc = jnp.zeros_like(value)
    fn, sharding = _spmd_program(pg, as_program(alg), cfg, mesh, axis)
    args = [jax.device_put(a, sharding) for a in
            (pg.ptr_start, pg.deg, pg.edge_dst, pg.edge_val, value,
             frontier, acc)]
    return fn(*args)


def _spmd_program(pg: PartitionedGraph, prog, cfg: EngineConfig, mesh,
                  axis: str):
    """The jitted shard_map engine of :func:`spmd_engine_call`, and the
    sharding of its seven (T, chunk) operands."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    T = pg.T
    comm = AxisComm(axis, T)
    spec2 = P(axis, None)

    def body(ptr_start, deg, edge_dst, edge_val, value, frontier, acc):
        with jax.named_scope("init"):
            shard = GraphShard(ptr_start[0], deg[0], edge_dst[0],
                               edge_val[0])
            st = init_state(comm, cfg, pg.v_chunk, value[0], frontier[0],
                            prog, acc[0])
        st, stats, trace, pending = run_engine(comm, cfg, prog, shard, st,
                                               pg.e_chunk, pg.v_chunk)
        return st.value[None], st.acc[None], stats, trace, pending

    # the recorder's ring holds only global (replicated) series, so its
    # out_spec is P() everywhere, exactly like Stats (None when trace off)
    trace_spec = jax.tree.map(lambda _: P(), zero_trace(cfg, T, prog)) \
        if cfg.trace else None
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec2,) * 7,
        out_specs=(spec2, spec2, jax.tree.map(lambda _: P(), Stats.zero()),
                   trace_spec, P()),
        check_vma=False)
    return jax.jit(fn), NamedSharding(mesh, spec2)


# The engine's named scopes: the round legs, the work before the round
# loop, and the flight recorder's block.
ENGINE_SCOPES = ROUND_LEGS + ("init", "recorder")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^ ]+) .*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^ ]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^ ,}]+)")
_REF = re.compile(r"%([^\s,(){}]+)")
_TRANSFORM = re.compile(r"^(?:\w+\()+|\)+$")


def op_leg(op_name: str) -> str:
    """The innermost engine scope in an HLO ``op_name`` (a name stack such
    as ``jit(_local_call)/while/body/vmap(route)/link_count/scatter-add``,
    where a transformation wraps a scope as ``vmap(route)``), or
    "unscoped"."""
    for part in reversed(op_name.split("/")):
        part = _TRANSFORM.sub("", part)
        if part in ENGINE_SCOPES:
            return part
    return "unscoped"


def hlo_legs(hlo_text: str) -> dict[str, str]:
    """{instruction name: leg} of every instruction of an HLO module's
    text.  An instruction's leg is :func:`op_leg` of its
    ``metadata={op_name=...}``; where that is "unscoped":

    * a fusion takes the leg of its fused computation (that of the
      instruction nearest the root that has one): the compiler drops a
      fusion's metadata where it rewrites the root, as for a scatter;
    * any other instruction the compiler added or lowered without the
      name stack (a copy, a prefetch, a reduce-window of ``cumsum`` on
      the CPU) takes the leg of the nearest instruction it feeds that has
      one, else of the nearest that feeds it: the leg it serves;
    * failing both, an instruction of a called computation (the body of
      a loop the compiler made) takes the scope of the innermost
      instruction calling it that has one.
    """
    scoped, calls, comps, refs = {}, {}, {}, {}
    body = None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m and body is not None:
            name = m.group(1)
            op, called = _OP_NAME.search(line), _CALLS.search(line)
            scoped[name] = op_leg(op.group(1)) if op else "unscoped"
            calls[name] = called and called.group(1)
            refs[name] = _REF.findall(line[m.end():])
            body.append(name)
    caller = {c: n for n, r in refs.items() for c in r if c in comps}

    def fused(name):
        called = calls.pop(name, None)      # each fusion is resolved once
        if scoped[name] == "unscoped" and called in comps:
            scoped[name] = next((lg for lg in map(fused,
                                                  reversed(comps[called]))
                                 if lg != "unscoped"), "unscoped")
        return scoped[name]

    for name in scoped:
        fused(name)
    operands, users = {}, {}
    for names in comps.values():
        inside = set(names)
        for n in names:
            operands[n] = [r for r in refs[n] if r in inside and r != n]
            for r in operands[n]:
                users.setdefault(r, []).append(n)

    def nearest(name, edges):
        seen, todo = {name}, collections.deque(edges.get(name, ()))
        while todo:
            n = todo.popleft()
            if n not in seen:
                seen.add(n)
                if scoped[n] != "unscoped":
                    return scoped[n]
                todo.extend(edges.get(n, ()))
        return "unscoped"

    comp_of = {n: c for c, names in comps.items() for n in names}

    def called_from(name):
        while scoped[name] == "unscoped" and comp_of[name] in caller:
            name = caller[comp_of[name]]
        return scoped[name]

    legs = {}
    for name, leg in scoped.items():
        if leg == "unscoped":
            leg = nearest(name, users)
        if leg == "unscoped":
            leg = nearest(name, operands)
        if leg == "unscoped":
            leg = called_from(name)
        legs[name] = leg
    return legs


def engine_leg_map(pg: PartitionedGraph, alg, cfg: EngineConfig,
                   mesh=None, axis: str = "x") -> dict[str, str]:
    """{HLO instruction name: leg} of the optimized engine program that
    :func:`_call` runs for ``pg``'s shapes: the names the profiler gives
    the device operations (``fusion.442``, ``sort.475``; on the CPU also
    ``wrapped_scatter``), each with the innermost of
    :data:`ENGINE_SCOPES` it was traced in (:func:`hlo_legs`), else
    "unscoped".  The program is lowered from shapes and compiled again:
    after a call, the executable that call ran.  A persistent
    compilation cache keyed without metadata (JAX's default) can hand a
    program the executable of one that differs only in its scopes, whose
    metadata then names no leg."""
    prog = as_program(alg)
    state = [jax.ShapeDtypeStruct((pg.T, pg.v_chunk), dt)
             for dt in (jnp.float32, jnp.bool_, jnp.float32)]
    arrays = (pg.ptr_start, pg.deg, pg.edge_dst, pg.edge_val)
    if mesh is None:
        shard = GraphShard(*(jax.ShapeDtypeStruct(a.shape, a.dtype)
                             for a in arrays))
        lowered = _local_call.lower(prog, cfg, pg.T, pg.e_chunk,
                                    pg.v_chunk, shard, *state)
    else:
        fn, sharding = _spmd_program(pg, prog, cfg, mesh, axis)
        lowered = fn.lower(*(jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=sharding)
                             for a in list(arrays) + state))
    return hlo_legs(lowered.compile().as_text())


# --------------------------------------------------------------------------
# Workload drivers.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Result:
    values: np.ndarray  # (V,) in original vertex order
    stats: Stats
    epochs: int = 1
    trace: object = None  # TraceBuf when cfg.trace, else None


# PageRank's epoch, while it runs one: an argument of _call's span
_EPOCH = contextvars.ContextVar("epoch", default=None)


def _call(pg, alg, cfg, value, frontier, mesh=None, axis="x", acc=None):
    """One engine run on either comm path: ``(value, acc, stats, trace)``.
    A run that ``max_rounds`` cut with work still pending, or that
    dropped messages, raises.  Its ``engine_call`` span names the program
    and PageRank's epoch, the identifier its device work shares."""
    name = as_program(alg).name
    span = {"program": name}
    if _EPOCH.get() is not None:
        span["epoch"] = _EPOCH.get()
    with TraceAnnotation("engine_call", **span):
        with TraceAnnotation("engine_dispatch"):
            if mesh is None:
                out = local_engine_call(pg, alg, cfg, value, frontier, acc)
            else:
                out = spmd_engine_call(pg, alg, cfg, value, frontier, mesh,
                                       axis, acc)
        *result, pending = out
        with TraceAnnotation("engine_wait"):
            check_finished(cfg, pending, f"program {name!r}",
                           drops=result[2].drops)
    return result


def bfs(pg: PartitionedGraph, root: int, cfg: EngineConfig = EngineConfig(),
        mesh=None) -> Result:
    value, frontier = init_min_state(pg, [root])
    v, _, stats, trace = _call(pg, BFS, cfg, value, frontier, mesh)
    out = to_original(pg, v).astype(np.float64)
    out[out >= np.float32(np.finfo(np.float32).max)] = np.inf
    return Result(out, stats, trace=trace)


def sssp(pg: PartitionedGraph, root: int, cfg: EngineConfig = EngineConfig(),
         mesh=None) -> Result:
    value, frontier = init_min_state(pg, [root])
    v, _, stats, trace = _call(pg, SSSP, cfg, value, frontier, mesh)
    out = to_original(pg, v).astype(np.float64)
    out[out >= np.float32(np.finfo(np.float32).max)] = np.inf
    return Result(out, stats, trace=trace)


def wcc(pg: PartitionedGraph, cfg: EngineConfig = EngineConfig(),
        mesh=None) -> Result:
    """Label propagation to the min original id (graph must be symmetric)."""
    value, frontier = init_wcc_state(pg)
    v, _, stats, trace = _call(pg, WCC, cfg, value, frontier, mesh)
    return Result(to_original(pg, v).astype(np.int64), stats, trace=trace)


def spmv(pg: PartitionedGraph, x: np.ndarray,
         cfg: EngineConfig = EngineConfig(), mesh=None) -> Result:
    """Push-mode y[dst] += val * x[src] — one engine epoch."""
    value, frontier = init_add_state(pg, x)
    _, acc, stats, trace = _call(pg, SPMV, cfg, value, frontier, mesh)
    return Result(to_original(pg, acc).astype(np.float64), stats,
                  trace=trace)


def pagerank(pg: PartitionedGraph, damping: float = 0.85, iters: int = 20,
             tol: float = 0.0, cfg: EngineConfig = EngineConfig(),
             mesh=None) -> Result:
    """Epoch-synchronized PageRank (the paper keeps the barrier for PR).

    Each epoch is one engine run (push contributions, accumulate); the rank
    update + dangling redistribution happen between epochs — the host-driven
    barrier the paper describes reusing the chip-idle signal for.
    """
    V = pg.num_vertices
    real = real_mask(pg)
    deg = np.asarray(pg.deg)
    rank = np.where(real, np.float32(1.0 / V), 0.0).astype(np.float32)
    # telemetry shapes depend on the NoC backend; a backend-shaped zero is
    # always safe to accumulate (also the iters == 0 result).
    total = zero_stats(cfg, pg.T, PAGERANK)
    epochs = 0
    trace = None  # the LAST epoch's ring (each epoch restarts the engine)
    for epoch in range(iters):
        with TraceAnnotation("init_state"):
            frontier = jnp.asarray(real & (deg > 0))
            value = jnp.asarray(rank)
        token = _EPOCH.set(epoch)
        try:
            _, acc, stats, trace = _call(pg, PAGERANK, cfg, value, frontier,
                                         mesh)
        finally:
            _EPOCH.reset(token)
        with TraceAnnotation("epoch_update"):
            acc = np.asarray(acc)
            dangling = rank[real & (deg == 0)].sum()
            new_rank = np.where(
                real, (1 - damping) / V + damping * (acc + dangling / V),
                0.0).astype(np.float32)
            diff = np.abs(new_rank - rank).sum()
            rank = new_rank
            total = _acc_stats(total, stats)
        epochs += 1
        if tol and diff < tol:
            break
    return Result(to_original(pg, rank).astype(np.float64), total, epochs,
                  trace=trace)


def kcore(pg: PartitionedGraph, k: int, cfg: EngineConfig = EngineConfig(),
          mesh=None) -> Result:
    """k-core membership by peeling (graph must be symmetric, deduped).

    values[v] = 1 if v survives in the k-core, else 0.  The engine peels
    asynchronously (or per BSP epoch): removed vertices emit one decrement
    per edge and the threshold fold re-arms the frontier — the same
    3-channel shape as BFS with a different T3.
    """
    value, frontier, acc = init_kcore_state(pg, k)
    _, a, stats, trace = _call(pg, kcore_program(int(k)), cfg, value,
                               frontier, mesh, acc=acc)
    member = (to_original(pg, a) == 0.0).astype(np.int64)
    return Result(member, stats, trace=trace)


def sort_adjacency(pg: PartitionedGraph) -> PartitionedGraph:
    """Sort every per-vertex edge segment by placed destination id.

    Factored out of :func:`prepare_triangles` so a migration pass
    (repro.place) can restore the ``sorted_adj`` layout after re-dealing
    segments: the sort key is the *placed* destination, so it must be
    re-applied whenever the owner map changes."""
    dst = np.asarray(pg.edge_dst).copy()
    val = np.asarray(pg.edge_val).copy()
    degs = np.asarray(pg.deg)
    for t in range(pg.T):
        total = int(degs[t].sum())
        seg = np.full(pg.e_chunk, np.iinfo(np.int64).max, np.int64)
        seg[:total] = np.repeat(np.arange(pg.v_chunk), degs[t])
        order = np.lexsort((dst[t], seg))
        dst[t] = dst[t][order]
        val[t] = val[t][order]
    return dataclasses.replace(pg, edge_dst=jnp.asarray(dst, jnp.int32),
                               edge_val=jnp.asarray(val, jnp.float32),
                               sorted_adj=True)


def prepare_triangles(g: CSRGraph, T: int,
                      scheme: str = "low_order") -> PartitionedGraph:
    """Partition for triangle counting: vertex-aligned edges (each tile
    owns its vertices' full adjacency) with every per-vertex segment sorted
    by placed destination, so the closing-edge check is a local binary
    search.  ``g`` must be symmetric and deduplicated (use
    :func:`symmetrize`)."""
    return sort_adjacency(partition_graph(g, T, scheme,
                                          edge_mode="vertex_aligned"))


def triangles(pg: PartitionedGraph, cfg: EngineConfig = EngineConfig(),
              mesh=None) -> Result:
    """2-hop triangle counting on a :func:`prepare_triangles` partition.

    values[v] = number of triangles whose placed-minimum vertex is v
    (each triangle counted exactly once; ``values.sum()`` is the total).
    A 4-channel program: range -> wedge at the neighbor's owner -> second
    range -> intersection-count fold.
    """
    # the close fold binary-searches each vertex's local sorted adjacency —
    # any other partition layout would silently miscount.
    assert pg.edge_mode == "vertex_aligned" and pg.sorted_adj, (
        "triangles() needs a prepare_triangles partition (vertex-aligned "
        f"edges, sorted segments); got edge_mode={pg.edge_mode!r}, "
        f"sorted_adj={pg.sorted_adj}")
    cfg = sized_cfg(cfg, TRIANGLES, pg.T)
    real = real_mask(pg)
    deg = np.asarray(pg.deg)
    value = jnp.zeros((pg.T, pg.v_chunk), jnp.float32)
    frontier = jnp.asarray(real & (deg > 0))
    _, a, stats, trace = _call(pg, TRIANGLES, cfg, value, frontier, mesh)
    return Result(to_original(pg, a).astype(np.int64), stats, trace=trace)


def _acc_stats(a: Stats, b: Stats) -> Stats:
    """Combine per-epoch Stats: counters add, peaks take the max.

    Shape-checked: telemetry arrays are shaped by the NoC backend and the
    channel counters by the program — accumulating mismatched runs (or a
    default ``Stats.zero()``) is a bug, not a broadcast.
    """
    for name, x, y in zip(Stats._fields, a, b):
        if jnp.shape(x) != jnp.shape(y):
            raise ValueError(
                f"Stats.{name} shape mismatch {jnp.shape(x)} vs "
                f"{jnp.shape(y)}: accumulating stats from different NoC "
                f"backends/programs? Use zero_stats(cfg, T, alg) instead "
                f"of Stats.zero().")
    merged = jax.tree.map(lambda x, y: x + y, a, b)
    return merged._replace(
        max_link_occupancy=jnp.maximum(a.max_link_occupancy,
                                       b.max_link_occupancy))


# --------------------------------------------------------------------------
# Convenience: build + partition + symmetrize.
# --------------------------------------------------------------------------

def symmetrize(g: CSRGraph) -> CSRGraph:
    src = np.repeat(np.arange(g.num_vertices), g.ptr[1:] - g.ptr[:-1])
    s2 = np.concatenate([src, g.dst])
    d2 = np.concatenate([g.dst, src])
    v2 = np.concatenate([g.val, g.val])
    return CSRGraph.from_edges(g.num_vertices, s2, d2, v2, dedup=True)


def prepare(g: CSRGraph, T: int, scheme: str = "low_order",
            edge_mode: str = "equal_edges",
            dies: tuple[int, int] | None = None) -> PartitionedGraph:
    """``dies=(ndies_y, ndies_x)`` is required by the ``*_dielocal``
    placement schemes and must match the hier NoC geometry
    (``EngineConfig.ndies_y/ndies_x``) for partitions to be die-resident
    on the fabric that runs them."""
    return partition_graph(g, T, scheme, edge_mode, dies=dies)
