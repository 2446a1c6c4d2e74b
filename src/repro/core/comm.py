"""Communication backends for the Dalorex engine.

The engine's per-round code is written as *per-device local stages* glued by
collectives. Two interchangeable backends run the identical stage code:

* :class:`AxisComm` — real SPMD execution inside ``jax.shard_map`` over a
  named mesh axis (this is what runs on pods and in the dry-run).
* :class:`LocalComm` — single-device emulation where "devices" are a leading
  array axis; local stages are ``vmap``-ed and the all-to-all is a transpose.
  This gives fast, exact unit/property tests of the full engine on one CPU
  device, with bit-identical semantics to the SPMD path.

The all-to-all convention follows the probe of ``jax.lax.all_to_all`` with
``tiled=True``: send buffers are ``(T*s, W)`` with rows ``[d*s:(d+1)*s]``
addressed to device ``d``; after exchange, rows ``[t*s:(t+1)*s]`` hold what
device ``t`` sent us. This is the vectorized form of the paper's headerless
NoC: the slot position encodes the route, no metadata flits are spent.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AxisComm:
    """Collectives over a named shard_map axis."""

    axis: str
    size: int

    def a2a(self, x: jax.Array) -> jax.Array:
        # x: (T*s, ...) -> (T*s, ...)
        return jax.lax.all_to_all(x, self.axis, split_axis=0, concat_axis=0, tiled=True)

    def psum(self, x):
        return jax.lax.psum(x, self.axis)

    def pmax(self, x):
        return jax.lax.pmax(x, self.axis)

    def all_gather(self, x):
        return jax.lax.all_gather(x, self.axis)  # adds leading T axis

    def my_id(self):
        return jax.lax.axis_index(self.axis)

    def run(self, fn, *args):
        """Run a per-device function (identity here; LocalComm vmaps)."""
        return fn(self.my_id(), *args)

    def to_global(self, x):
        """Collapse a replicated per-device value to one global copy.

        Under AxisComm a post-``psum``/``pmax`` value is already the global
        copy; under LocalComm it carries a broadcast leading T axis."""
        return x


@dataclasses.dataclass(frozen=True)
class LocalComm:
    """Single-device emulation: arrays carry a leading T axis."""

    size: int

    def a2a(self, x: jax.Array) -> jax.Array:
        # x: (T, T*s, ...) -> (T, T*s, ...)
        t = self.size
        s = x.shape[1] // t
        y = x.reshape((t, t, s) + x.shape[2:])
        y = jnp.swapaxes(y, 0, 1)
        return y.reshape((t, t * s) + x.shape[2:])

    def psum(self, x):
        # x: (T, ...) -> same value broadcast to all "devices"
        s = x.sum(axis=0, keepdims=True)
        return jnp.broadcast_to(s, x.shape)

    def pmax(self, x):
        s = x.max(axis=0, keepdims=True)
        return jnp.broadcast_to(s, x.shape)

    def all_gather(self, x):
        # x: (T, ...) -> (T, T, ...): every device sees the full stack
        return jnp.broadcast_to(x[None], (self.size,) + x.shape)

    def my_id(self):
        return jnp.arange(self.size, dtype=jnp.int32)

    def run(self, fn, *args):
        return jax.vmap(fn)(self.my_id(), *args)

    def to_global(self, x):
        """Collapse a broadcast (T, ...) per-device value to one copy."""
        return x[0]

