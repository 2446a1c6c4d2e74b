"""Kernel-launch overhead microbench: what does one ``pallas_call`` cost?

PR7's fused round leg exists because per-launch dispatch overhead — not
tile work — dominates a round once the kernels themselves are small (the
ALPHA-PIM observation: on real silicon, per-operation launch/sync cost is
what separates modeled from measured GTEPS).  This bench prices that
overhead directly, so fig11's ``launches_per_round`` column converts to
time:

* ``bump_chain_*`` — a chain of N trivial (+1) kernels launched one
  ``pallas_call`` each, vs the same N adds inside ONE fused launch
  (``fused_leg_call``).  The wall-clock difference over N-1 saved
  launches is the marginal per-launch overhead (``us_per_launch_saved``).
* ``leg_*`` — a synthetic classic channel leg (frontier-pop -> FIFO turn
  -> segment-gather -> scatter-fold) on representative shapes, as PR4's
  four standalone kernel launches vs PR7's single fused launch
  (``leg_delta_us`` = the per-leg fusion win).

Launch counts per variant are *measured* (the ``repro.kernels.engine.
launches`` tally around an abstract trace), not hardcoded — the fused
variants must count exactly 1.  Wall-clock columns are machine-dependent
(and, on the CPU, where the kernels are interpreted, interpreter-taxed); the
deterministic ``launches`` column is what the smoke baseline keeps.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.engine import (edge_scan_gather, fifo_turn, fold_scatter,
                                  frontier_pop, frontier_take,
                                  fused_leg_call, interpret_mode,
                                  queue_push_pop, record, scatter_body,
                                  segment_gather, tally)

_K_MAX = 16     # frontier pop bound / queue pop budget
_MAX_T2 = 8     # edge-scan bound


def _bump_kernel(x_ref, y_ref):
    y_ref[...] = x_ref[...] + 1


def _bump(x):
    record()  # raw pallas_call: tally it like the library wrappers do
    return pl.pallas_call(
        _bump_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret_mode())(x)


def _chain_unfused(n):
    def fn(x):
        for _ in range(n):
            x = _bump(x)
        return x
    return fn


def _chain_fused(n):
    def body(x):
        for _ in range(n):
            x = x + 1
        return x

    def fn(x):
        return fused_leg_call(body, x)
    return fn


def _leg_inputs(v_chunk, e_chunk, cap, seed=0):
    """Representative per-tile leg operands (classic program shapes)."""
    rng = np.random.default_rng(seed)
    return dict(
        mask=jnp.asarray(rng.random(v_chunk) < 0.3),
        budget=jnp.int32(_K_MAX),
        qdata=jnp.asarray(rng.integers(0, e_chunk, (cap, 3)), jnp.int32),
        qcount=jnp.int32(cap // 2),
        rows=jnp.asarray(rng.integers(0, e_chunk, (_K_MAX, 3)), jnp.int32),
        rvalid=jnp.asarray(rng.random(_K_MAX) < 0.8),
        edge_dst=jnp.asarray(
            rng.integers(-1, v_chunk, e_chunk), jnp.int32),
        edge_val=jnp.asarray(rng.random(e_chunk), jnp.float32),
        target=jnp.asarray(rng.random(v_chunk), jnp.float32),
    )


def _leg(fused):
    """The classic leg chain on one tile: pop -> turn -> gather -> fold.
    ``fused=False`` launches PR4's four standalone kernels; ``fused=True``
    composes the pure bodies and will be run inside ONE fused_leg_call."""

    def chain(mask, budget, qdata, qcount, rows, rvalid, edge_dst,
              edge_val, target):
        if fused:
            vidx, vvalid, mask2 = frontier_take(mask, budget, _K_MAX)
            taken, tvalid, qdata2, qcount2, drops = fifo_turn(
                qdata, qcount, rows, rvalid, budget, _K_MAX)
            nb, w, jv = segment_gather(
                edge_dst, edge_val, taken[:, 0], taken[:, 1], tvalid,
                _MAX_T2)
            lidx = jnp.where(jv, nb % target.shape[0],
                             target.shape[0]).reshape(-1)
            out = scatter_body(target, lidx, w.reshape(-1), jv.reshape(-1),
                               "min")
        else:
            vidx, vvalid, mask2 = frontier_pop(mask, budget, _K_MAX)
            taken, tvalid, qdata2, qcount2, drops = queue_push_pop(
                qdata, qcount, rows, rvalid, budget, _K_MAX)
            nb, w, jv = edge_scan_gather(
                edge_dst, edge_val, taken[:, 0], taken[:, 1], tvalid,
                _MAX_T2)
            lidx = jnp.where(jv, nb % target.shape[0],
                             target.shape[0]).reshape(-1)
            out = fold_scatter(target, lidx, w.reshape(-1), jv.reshape(-1),
                               op="min")
        return vidx, vvalid, mask2, qdata2, qcount2, drops, out

    if not fused:
        return chain

    def one_launch(*args):
        return fused_leg_call(chain, *args)
    return one_launch


def _count_launches(fn, *args) -> int:
    """Measured launch count: records taken while tracing fn abstractly."""
    with tally() as t:
        jax.eval_shape(fn, *args)
    return t.n


def _best_wall(fn, args, repeat):
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))  # compile
    best = None
    for _ in range(max(repeat, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def run(n_chain: int = 32, size: int = 1024, repeat: int = 3,
        timing: bool = True) -> list[dict]:
    """``timing=False`` drops the machine-dependent wall-clock columns
    (what smoke.py commits to the baseline; the measured ``launches``
    column stays)."""
    rows = []

    # --- bump chain: N launches vs 1 ---------------------------------
    x = jnp.zeros((size,), jnp.int32)
    un = _chain_unfused(n_chain)
    fu = _chain_fused(n_chain)
    l_un = _count_launches(un, x)
    l_fu = _count_launches(fu, x)
    w_un = _best_wall(un, (x,), repeat) if timing else None
    w_fu = _best_wall(fu, (x,), repeat) if timing else None
    row_un = {"bench": "kern_micro", "kernel": "bump_chain_unfused",
              "launches": l_un, "ok": l_un == n_chain}
    row_fu = {"bench": "kern_micro", "kernel": "bump_chain_fused",
              "launches": l_fu, "ok": l_fu == 1}
    if timing:
        row_un["wall_s"] = round(w_un, 5)
        row_fu["wall_s"] = round(w_fu, 5)
        row_fu["us_per_launch_saved"] = round(
            1e6 * (w_un - w_fu) / max(l_un - l_fu, 1), 2)
    rows += [row_un, row_fu]

    # --- one classic channel leg: 4 launches vs 1 ---------------------
    ins = _leg_inputs(v_chunk=size, e_chunk=4 * size, cap=4 * _K_MAX)
    args = tuple(ins.values())
    leg4 = _leg(fused=False)
    leg1 = _leg(fused=True)
    l4 = _count_launches(leg4, *args)
    l1 = _count_launches(leg1, *args)
    w4 = _best_wall(leg4, args, repeat) if timing else None
    w1 = _best_wall(leg1, args, repeat) if timing else None
    # the fused leg must be bit-identical to the four-kernel chain
    o4 = jax.jit(leg4)(*args)
    o1 = jax.jit(leg1)(*args)
    same = all(bool(np.array_equal(np.asarray(a), np.asarray(b)))
               for a, b in zip(jax.tree.leaves(o4), jax.tree.leaves(o1)))
    row4 = {"bench": "kern_micro", "kernel": "leg_unfused",
            "launches": l4, "ok": l4 == 4 and same}
    row1 = {"bench": "kern_micro", "kernel": "leg_fused",
            "launches": l1, "ok": l1 == 1 and same}
    if timing:
        row4["wall_s"] = round(w4, 5)
        row1["wall_s"] = round(w1, 5)
        row1["leg_delta_us"] = round(1e6 * (w4 - w1), 2)
    rows += [row4, row1]
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(f"{k}={v}" for k, v in r.items()))
