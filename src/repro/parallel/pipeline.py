"""GPipe-style pipeline parallelism over a mesh axis (shard_map+ppermute).

Each device on the ``stage`` axis owns one stage's parameters (never moved —
the Dalorex discipline again: weights are the immovable data, activations
are the routed messages).  Microbatches flow through a static schedule of
n_micro + n_stages - 1 ticks; stage outputs hop one link per tick via
``ppermute``; the last stage accumulates results which are psum-broadcast at
the end.  Differentiable end to end (ppermute transposes to the reverse
permutation), so the same function trains.

Bubble fraction = (n_stages - 1) / (n_micro + n_stages - 1) — callers pick
n_micro >> n_stages; the roofline harness reports it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_apply(stage_fn, params, x, *, mesh, axis: str, n_micro: int):
    """params: pytree with leading (n_stages,) axis on every leaf.
    x: (n_micro, mb, ...) microbatched input.  Returns (n_micro, mb, ...).

    ``stage_fn(stage_params, x_mb) -> y_mb`` must be shape-preserving
    (classic homogeneous-stage pipelining; heterogeneous stages wrap their
    own padding).
    """
    n_stages = mesh.shape[axis]

    def body(prm, xs):
        prm = jax.tree.map(lambda a: a[0], prm)  # this device's stage
        idx = jax.lax.axis_index(axis)
        xs = xs  # (n_micro, mb, ...) replicated input
        mb_shape = xs.shape[1:]
        carry = jnp.zeros(mb_shape, xs.dtype)
        outs = jnp.zeros_like(xs)
        fwd = [(i, i + 1) for i in range(n_stages - 1)]
        for t in range(n_micro + n_stages - 1):
            inject = xs[jnp.minimum(t, n_micro - 1)]
            x_in = jnp.where(idx == 0, inject, carry)
            y = stage_fn(prm, x_in)
            active = (t >= idx) & (t - idx < n_micro)
            y = jnp.where(active, y, 0)
            # emit from the last stage
            out_slot = t - (n_stages - 1)
            is_out = (idx == n_stages - 1) & (out_slot >= 0)
            outs = jax.lax.cond(
                is_out,
                lambda o: o.at[jnp.maximum(out_slot, 0)].set(y),
                lambda o: o, outs)
            carry = jax.lax.ppermute(y, axis, fwd)
        # broadcast the last stage's outputs to every device
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, 0), axis)
        return outs

    pspecs = jax.tree.map(lambda _: P(axis), params)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, P()),
        out_specs=P(), check_vma=False)
    return fn(params, x)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
