"""Device legs and host spans: the engine's ``jax.named_scope``s cover the
round, ``engine_leg_map`` reads them from the compiled program, and the
drivers emit their ``HOST_SPANS`` nested as documented.

The round loop is compiled here on the CPU for a small BFS and PageRank
(and a BFS on the mesh fabric, whose routing counts its links in its
own code); every instruction of its body that does work must land in one
of ``ROUND_LEGS``.  The host spans are read from a profile recorded here.
"""
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import algorithms as alg
from repro.core.engine import (BFS, PAGERANK, ROUND_LEGS, EngineConfig,
                               GraphShard)
from repro.core.graph import CSRGraph, rmat_edges
from repro.core.program import as_program

pytestmark = pytest.mark.trace

TRIVIAL = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
COMPUTATION = re.compile(r"^(ENTRY )?%([^ ]+) .*\{$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^ ]+) = .*?\s([a-z][\w-]*)\(")


def small_cfg(**kw):
    base = dict(f_pop=8, r_pop=8, u_pop=16, max_t2=8, cap_route_range=8,
                cap_route_update=32, cap_rangeq=128, cap_updq=4096,
                max_rounds=5000)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module", autouse=True)
def own_executables():
    """Key the compilation cache on the programs' metadata, and forget the
    executables of this process: JAX's cache strips the metadata from its
    key, so an executable cached for the same program without its scopes
    (a parent commit's) would be loaded, and it names no leg."""
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.clear_caches()
    yield
    jax.config.update("jax_compilation_cache_include_metadata_in_key", was)
    jax.clear_caches()


@pytest.fixture(scope="module")
def pg():
    n, src, dst, val = rmat_edges(7, edge_factor=5, seed=3)
    return alg.prepare(alg.symmetrize(CSRGraph.from_edges(n, src, dst, val)),
                       T=4)


def computations(hlo: str) -> tuple[dict, str]:
    """({computation: [(instruction, opcode, line)]}, ENTRY's name)."""
    comps, entry, body = {}, None, None
    for line in hlo.splitlines():
        m = COMPUTATION.match(line)
        if m:
            body = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
            continue
        m = INSTRUCTION.match(line)
        if m and body is not None:
            body.append((m.group(1), m.group(2), line))
    return comps, entry


def compiled_text(pg, program, cfg) -> str:
    state = [jax.ShapeDtypeStruct((pg.T, pg.v_chunk), dt)
             for dt in (jnp.float32, jnp.bool_, jnp.float32)]
    shard = GraphShard(*(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in
                         (pg.ptr_start, pg.deg, pg.edge_dst, pg.edge_val)))
    return alg._local_call.lower(as_program(program), cfg, pg.T, pg.e_chunk,
                                 pg.v_chunk, shard, *state
                                 ).compile().as_text()


@pytest.mark.parametrize("program,cfg", [
    (BFS, small_cfg()),
    (PAGERANK, small_cfg()),
    (BFS, small_cfg(noc="mesh", link_cap=4)),
], ids=["bfs", "pagerank", "bfs-mesh"])
def test_every_instruction_of_the_round_has_a_leg(pg, program, cfg):
    hlo = compiled_text(pg, program, cfg)
    comps, entry = computations(hlo)
    loops = [re.search(r"\bbody=%([^ ,]+)", line).group(1)
             for _, op, line in comps[entry] if op == "while"]
    assert len(loops) == 1, "one round loop in the engine program"
    legs = alg.hlo_legs(hlo)
    missing = [line.strip()[:160] for name, op, line in comps[loops[0]]
               if op not in TRIVIAL and legs[name] not in ROUND_LEGS]
    assert not missing, missing
    # and every operation traced inside the round names its leg itself
    stacks = set(re.findall(r'op_name="([^"]*/while/body/[^"]*)"', hlo))
    assert stacks
    assert {alg.op_leg(s) for s in stacks} <= set(ROUND_LEGS)
    assert {legs[name] for name, _, _ in comps[loops[0]]} >= {
        "control", "source", "queue", "route", "link_count", "scan", "fold",
        "telemetry"}


def canonical(hlo: str) -> str:
    """An HLO module's text without metadata, source tables and names:
    each instruction and computation renamed by first appearance."""
    body = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    body = body[body.index("\n\n", body.index("StackFrames")):] \
        if "StackFrames" in body else body
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  body)


def test_scopes_change_no_instruction(pg, monkeypatch):
    """The engine compiled with its scopes and with every scope a no-op:
    the same program, instruction for instruction (the names of some
    instructions follow the name stack, so they are compared by place)."""
    cfg = small_cfg()
    scoped = compiled_text(pg, PAGERANK, cfg)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain = compiled_text(pg, PAGERANK, cfg)
    monkeypatch.undo()
    jax.clear_caches()
    assert "link_count" in scoped and "link_count" not in plain
    assert canonical(scoped) == canonical(plain)


@pytest.mark.parametrize("op_name,leg", [
    ("jit(_local_call)/while/body/route/vmap(link_count)/scatter-add",
     "link_count"),
    ("jit(_local_call)/while/body/vmap(route)/jit(sort)/sort", "route"),
    ("jit(_local_call)/while/cond/control/lt", "control"),
    ("jit(_local_call)/init/broadcast_in_dim", "init"),
    ("jit(_local_call)/while/body/recorder/dynamic_update_slice",
     "recorder"),
    ("jit(_local_call)/while", "unscoped"),
    ("reduce_window_sum", "unscoped"),
])
def test_op_leg_is_the_innermost_engine_scope(op_name, leg):
    assert alg.op_leg(op_name) == leg


def test_hlo_legs_follows_fusions_and_the_data_an_op_serves():
    hlo = "\n".join([
        "%fused (p: s32[4]) -> s32[4] {",
        "  %p = s32[4] parameter(0)",
        '  %t = s32[4] negate(%p), metadata={op_name="a/fold/negate"}',
        "  ROOT %s = s32[4] scatter(%t)",
        "}",
        "ENTRY %main (x: s32[4]) -> s32[4] {",
        "  %x = s32[4] parameter(0)",
        "  %copy.1 = s32[4] copy(%x)",
        '  %sort.2 = s32[4] sort(%copy.1), metadata={op_name="a/route/sort"}',
        "  %fusion.3 = s32[4] fusion(%sort.2), calls=%fused",
        "  ROOT %copy.4 = s32[4] copy(%fusion.3)",
        "}"])
    legs = alg.hlo_legs(hlo)
    assert legs["fusion.3"] == "fold"        # the fused computation's
    assert legs["copy.1"] == "route"         # the op it feeds
    assert legs["copy.4"] == "fold"          # feeds nothing: its operand
    assert legs["t"] == "fold" and legs["s"] == "fold"


def test_leg_map_names_the_operations_of_the_engine_call(pg):
    cfg = small_cfg()
    legs = alg.engine_leg_map(pg, BFS, cfg)
    assert set(legs.values()) <= set(alg.ENGINE_SCOPES) | {"unscoped"}
    assert set(ROUND_LEGS) <= set(legs.values())
    assert legs == alg.hlo_legs(compiled_text(pg, BFS, cfg))


def profile(tmp_path, fn):
    """The host events of a profile of ``fn()`` as (name, start, end,
    args)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in alg.HOST_SPANS:
                        s = int(e.start_ns)
                        events.append((e.name, s, s + int(e.duration_ns),
                                       dict(e.stats)))
    return events


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_bfs_emits_its_host_spans_nested(pg, tmp_path):
    cfg = small_cfg()
    alg.bfs(pg, 0, cfg)                        # compile outside the profile
    events = profile(tmp_path, lambda: alg.bfs(pg, 0, cfg))
    names = [e[0] for e in events]
    assert sorted(set(names)) == sorted({"engine_call", "engine_dispatch",
                                         "engine_wait", "init_state",
                                         "to_original"})
    call, = [e for e in events if e[0] == "engine_call"]
    assert call[3] == {"program": "bfs"}
    for name in ("engine_dispatch", "engine_wait"):
        span, = [e for e in events if e[0] == name]
        assert inside(span, call)
    for name in ("init_state", "to_original"):
        span, = [e for e in events if e[0] == name]
        assert not inside(span, call)


def test_pagerank_emits_one_epoch_update_per_epoch(pg, tmp_path):
    cfg = small_cfg()
    res = alg.pagerank(pg, iters=2, cfg=cfg)
    events = profile(tmp_path, lambda: alg.pagerank(pg, iters=2, cfg=cfg))
    assert {e[0] for e in events} == set(alg.HOST_SPANS)
    calls = sorted((e for e in events if e[0] == "engine_call"),
                   key=lambda e: e[1])
    updates = sorted((e for e in events if e[0] == "epoch_update"),
                     key=lambda e: e[1])
    assert res.epochs == 2 and len(calls) == 2 and len(updates) == 2
    assert [c[3] for c in calls] == [{"program": "pagerank", "epoch": 0},
                                     {"program": "pagerank", "epoch": 1}]
    for call, update in zip(calls, updates):
        assert call[2] <= update[1]            # each update follows its call
        assert sum(inside(e, call) for e in events
                   if e[0] in ("engine_dispatch", "engine_wait")) == 2
    assert calls[1][1] >= updates[0][2]

