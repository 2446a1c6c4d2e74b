"""Pluggable NoC backends for the Dalorex engine.

A :class:`Network` turns the engine's "route these messages to their
owners" step into an explicit fabric model.  All backends share the
engine-facing contract:

    route(comm, msgs, valid, capacity, dest_fn) -> NetRouted

where ``dest_fn`` decodes the destination tile from the *head flit* of each
message — the paper's headerless routing: every router re-derives the route
from message content, no metadata flits exist (Section III-E/F).  The
returned spill buffer holds messages that could not make progress this
round; because routes are content-derived, a spilled message can be
re-injected from *any* tile that holds it, so stranded-at-a-waypoint and
stranded-at-source replay through the same local-queue path.

Backends:

* :class:`IdealAllToAll` — the seed's semantics, extracted: one perfect
  crossbar round, contention only at endpoint slots (``capacity`` per
  destination).  Its "links" are the T ingress ports.
* :class:`Mesh2D` / :class:`Torus2D` / :class:`Ruche` — a (rows, cols)
  tile grid with dimension-ordered (X-then-Y) routing composed from two
  per-axis exchanges.  Each axis hop set is charged against **per-link**
  capacity (``link_cap`` flits per directed link per routing leg — an
  engine round has one leg per task channel of the running program) with
  the same spill-and-replay backpressure the endpoint queues use;
  telemetry counts every link traversal and the hop distance of every
  injection.
* :class:`Hier2D` — the multi-die composition: an ``ndies_y x ndies_x``
  array of intra-die meshes (or tori) whose lines are joined by inter-die
  express links (PIUMA-style die-of-dies).  Routing stays dimension-
  ordered; along each axis a cross-die journey completes its die-level
  express hops before the intra-die final approach.  At ``ndies = 1x1``
  it *is* the mesh/torus backend, link for link.

Link index space of the grid backends (``num_links = 8 * T``): an X block
``(rows, N_CHANNELS, cols)`` — the links of each row line — followed by a
Y block ``(cols, N_CHANNELS, rows)`` — the links of each column line —
both flattened.  Per-round occupancy of link ``l`` is the number of flits
that traversed it that round, summed over all tiles (``psum``).

Link-class contract: every backend exposes ``link_classes`` — a static
(num_links,) int32 vector attributing each directed link to one cost
class of :mod:`repro.noc.topology`, priced by :mod:`repro.perf`:

  ``LOCAL``  neighbor hop on a line           (1-tile wire)
  ``RUCHE``  ruche express channel            (``ruche_factor``-tile wire)
  ``WRAP``   torus ring-closing link          (longest wire on the line)
  ``PORT``   ideal-crossbar ingress port      (switch only, no wire)
  ``DIE``    hier die-to-die express link     (off-die wire + serdes)

Classes are a wiring property (what kind of wire the flit rides), not a
traffic property: links of an unused class simply never see flits (a mesh
carries RUCHE-class channel slots, a one-die hierarchy carries no
DIE-class traffic), which is what keeps telemetry and energy totals
bit-comparable across backends of identical geometry.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.queues import histogram
from repro.core.routing import bin_by_owner, route_tasks
from repro.noc.topology import (CLASS_PORT, N_CHANNELS, admit, grid_shape,
                                line_link_classes, line_usage)


def _die_coord(pos, seg: int):
    """Die index of a 1-D position under segment length ``seg`` (0 = the
    axis is not segmented; everything is die 0)."""
    return pos // seg if seg > 0 else jnp.zeros_like(pos)


class NetRouted(NamedTuple):
    """One network round, plus this tile's telemetry contribution.

    recv / recv_valid / spill / spill_valid match ``core.routing.Routed``.

    sent:       () int32 — messages this tile *delivered to their owner*
                this round (for the grid backends, counted at the final
                leg, so a message spilled mid-route is counted once, on
                the round it completes — totals reconcile across backends).
    link_flits: (num_links,) int32 — flits this tile pushed onto each
                directed link this round (psum over tiles = occupancy).
    hop_hist:   (max_hops + 1,) int32 — histogram of the remaining hop
                distance of every fabric injection this round.  Exact per
                message while nothing spills mid-route; a message stranded
                at a waypoint is histogrammed again with its remaining
                distance when re-injected, so under heavy backpressure the
                histogram counts injection attempts, not unique messages.
    die_hist:   (max_die_crossings + 1,) int32 — histogram of the number
                of die boundaries each fabric injection still has to
                cross (X + Y).  Non-hierarchical backends put every
                injection in bin 0; same injection-attempt caveat as
                ``hop_hist`` (a replay from a waypoint re-buckets with
                its remaining crossings).
    """

    recv: jax.Array
    recv_valid: jax.Array
    spill: jax.Array
    spill_valid: jax.Array
    sent: jax.Array
    link_flits: jax.Array
    hop_hist: jax.Array
    die_hist: jax.Array


@dataclasses.dataclass(frozen=True)
class IdealAllToAll:
    """The seed's single-round perfect fabric (endpoint contention only)."""

    T: int
    name = "ideal"

    @property
    def num_links(self) -> int:
        return self.T  # ingress port of each tile

    @property
    def max_hops(self) -> int:
        return 1

    @property
    def max_die_crossings(self) -> int:
        return 0  # one die (one crossbar); die_hist is a single bin

    @property
    def link_classes(self) -> np.ndarray:
        """Crossbar ingress ports: switch energy per flit, no wire
        latency (endpoint serialization lives in the compute term)."""
        return np.full(self.num_links, CLASS_PORT, np.int32)

    def route(self, comm, msgs, valid, capacity: int, dest_fn) -> NetRouted:
        T = self.T
        dest = comm.run(lambda _me, m: jnp.clip(dest_fn(m), 0, T - 1), msgs)
        r = route_tasks(comm, msgs, valid, dest, capacity)

        def telemetry(_me, d, v, spill_v, n_sent):
            with jax.named_scope("link_count"):
                link = histogram(d, v & ~spill_v, T)  # per-ingress-port
                hop = jnp.stack([jnp.zeros((), jnp.int32), n_sent])
                return link, hop, n_sent[None]  # die_hist: all in bin 0

        link, hop, die = comm.run(telemetry, dest, valid, r.spill_valid,
                                  r.sent)
        return NetRouted(r.recv, r.recv_valid, r.spill, r.spill_valid,
                         r.sent, link, hop, die)

    def pressure(self, me, link_flits):
        """Occupancy of this tile's ingress port last round."""
        return link_flits[me]

    def pressure_limit(self, cfg, route_caps=None) -> int:
        """TSU "fabric hot" threshold: the ideal crossbar has no links, so
        pressure only means endpoint-slot saturation — ingress near the
        combined per-destination slot bound of all the program's routing
        legs (``route_caps``; defaults to the classic two channels)."""
        if route_caps is None:
            route_caps = (cfg.cap_route_range, cfg.cap_route_update)
        return (3 * self.T * sum(route_caps)) // 4


@dataclasses.dataclass(frozen=True)
class _Grid2D:
    """Shared machinery of the physical (rows, cols) backends."""

    T: int
    rows: int
    cols: int
    link_cap: int = 0  # flits per directed link per round; 0 = unlimited
    name = "grid"
    wrap = False

    def __post_init__(self):
        if self.rows * self.cols != self.T:
            raise ValueError(f"{self.rows}x{self.cols} grid != {self.T} tiles")

    @property
    def ruche(self) -> int:
        return 0

    @property
    def die_x(self) -> int:
        """Die segment length of the X (row) lines; 0 = unsegmented."""
        return 0

    @property
    def die_y(self) -> int:
        """Die segment length of the Y (column) lines; 0 = unsegmented."""
        return 0

    @property
    def num_links(self) -> int:
        return 2 * N_CHANNELS * self.T  # X block + Y block

    @property
    def max_hops(self) -> int:
        if self.wrap:
            return max(self.cols // 2 + self.rows // 2, 1)
        return max(self.cols - 1 + self.rows - 1, 1)

    @property
    def max_die_crossings(self) -> int:
        return 0  # single-die grids: die_hist is one bin

    @property
    def link_classes(self) -> np.ndarray:
        """Per-link cost class in the link index space (X block then Y
        block) — ruche express channels, torus wraparounds and hier
        die-to-die links are priced differently from local neighbor hops
        by the perf model (see the module docstring's link-class
        contract)."""
        x = np.broadcast_to(line_link_classes(self.cols, self.wrap,
                                              self.die_x),
                            (self.rows, N_CHANNELS, self.cols))
        y = np.broadcast_to(line_link_classes(self.rows, self.wrap,
                                              self.die_y),
                            (self.cols, N_CHANNELS, self.rows))
        return np.concatenate([x.reshape(-1), y.reshape(-1)])

    def route(self, comm, msgs, valid, capacity: int, dest_fn) -> NetRouted:
        T, rows, cols = self.T, self.rows, self.cols
        wrap, ruche, cap = self.wrap, self.ruche, self.link_cap
        die_x, die_y = self.die_x, self.die_y
        n_hop = self.max_hops + 1
        n_die = self.max_die_crossings + 1
        tid = jnp.arange(T, dtype=jnp.int32)

        # Link capacity is global: tiles sharing a line admit in tile-major
        # FIFO order, each counting the (conservative) claims of every
        # earlier tile on that line — shared via one all_gather per leg.

        def x_geom(me, m, v):
            r_me, c_me = me // cols, me % cols
            d = jnp.clip(dest_fn(m), 0, T - 1)
            dr, dc = d // cols, d % cols
            hx, use_x = line_usage(jnp.broadcast_to(c_me, dc.shape), dc,
                                   cols, wrap, ruche, die_x)
            with jax.named_scope("link_count"):
                hy, _ = line_usage(jnp.broadcast_to(r_me, dr.shape), dr,
                                   rows, wrap, ruche, die_y)
                cross = (jnp.abs(_die_coord(dc, die_x)
                                 - _die_coord(c_me, die_x))
                         + jnp.abs(_die_coord(dr, die_y)
                                   - _die_coord(r_me, die_y)))
                hops = hx + hy
            claims = (use_x & v[:, None, None]).sum(0, dtype=jnp.int32)
            return dc, hops, cross, use_x, claims

        def phase_x(me, m, v, dc, hops, cross, use_x, base):
            # X leg: ride the own-row line to the destination column; also
            # record the full X+Y hop distance and the remaining die
            # crossings of every admitted injection.
            r_me, c_me = me // cols, me % cols
            ok = admit(use_x, v, cap, base)
            buf, _, ep_spill, _ = bin_by_owner(m, v & ok, r_me * cols + dc,
                                               T, capacity)
            sent_mask = (v & ok) & ~ep_spill
            spill_v = v & ~sent_mask
            with jax.named_scope("link_count"):
                lx = jnp.zeros((rows, N_CHANNELS, cols), jnp.int32).at[
                    r_me].add((use_x & sent_mask[:, None, None]).sum(
                        0, dtype=jnp.int32))
                hop = histogram(hops, sent_mask, n_hop)
                die = histogram(cross, sent_mask, n_die)
            return buf, m, spill_v, lx.reshape(-1), hop, die

        def x_base(me, all_claims):
            # standing claims of tiles earlier on my row line (tile-major)
            r_me, c_me = me // cols, me % cols
            earlier = (tid // cols == r_me) & (tid % cols < c_me)
            return jnp.where(earlier[:, None, None], all_claims, 0).sum(0)

        dc, hops, cross, use_x, claims_x = comm.run(x_geom, msgs, valid)
        if cap > 0:
            base_x = comm.run(x_base, comm.all_gather(claims_x))
        else:  # uncapped: admit() ignores claims — skip the exchange
            base_x = claims_x * 0
        bufx, spill1, spill1_v, lx, hop, die = comm.run(
            phase_x, msgs, valid, dc, hops, cross, use_x, base_x)
        mid = comm.a2a(bufx)

        def y_geom(me, rec):
            r_me, c_me = me // cols, me % cols
            v = rec[:, 0] >= 0
            d = jnp.clip(dest_fn(rec), 0, T - 1)
            dr = d // cols
            _, use_y = line_usage(jnp.broadcast_to(r_me, dr.shape), dr,
                                  rows, wrap, ruche, die_y)
            claims = (use_y & v[:, None, None]).sum(0, dtype=jnp.int32)
            return dr, use_y, claims

        def phase_y(me, rec, dr, use_y, base):
            # Y leg from the waypoint (src_row, dst_col) — which is this
            # tile for every message that arrived via phase X.
            r_me, c_me = me // cols, me % cols
            v = rec[:, 0] >= 0
            ok = admit(use_y, v, cap, base)
            buf, _, ep_spill, _ = bin_by_owner(rec, v & ok,
                                               dr * cols + c_me, T, capacity)
            sent_mask = (v & ok) & ~ep_spill
            spill_v = v & ~sent_mask
            with jax.named_scope("link_count"):
                ly = jnp.zeros((cols, N_CHANNELS, rows), jnp.int32).at[
                    c_me].add((use_y & sent_mask[:, None, None]).sum(
                        0, dtype=jnp.int32))
            return (buf, rec, spill_v, sent_mask.sum(dtype=jnp.int32),
                    ly.reshape(-1))

        def y_base(me, all_claims):
            r_me, c_me = me // cols, me % cols
            earlier = (tid % cols == c_me) & (tid // cols < r_me)
            return jnp.where(earlier[:, None, None], all_claims, 0).sum(0)

        dr, use_y, claims_y = comm.run(y_geom, mid)
        if cap > 0:
            base_y = comm.run(y_base, comm.all_gather(claims_y))
        else:
            base_y = claims_y * 0
        # `sent` counts Y-leg completions, i.e. messages delivered to their
        # owner this round — so replays of mid-route spills are not
        # re-counted and grid totals reconcile with the ideal backend's.
        bufy, spill2, spill2_v, sent, ly = comm.run(
            phase_y, mid, dr, use_y, base_y)
        recv = comm.a2a(bufy)

        spill = jnp.concatenate([spill1, spill2], axis=-2)
        spill_v = jnp.concatenate([spill1_v, spill2_v], axis=-1)
        with jax.named_scope("link_count"):
            link = jnp.concatenate([lx, ly], axis=-1)
        return NetRouted(recv, recv[..., 0] >= 0, spill, spill_v, sent,
                         link, hop, die)

    def pressure_limit(self, cfg, route_caps=None) -> int:
        """TSU "fabric hot" threshold.  A link sees up to ``link_cap`` flits
        per leg and pressure sums every leg of the program's round (one per
        task channel), so hot = 3/4 of n_legs*link_cap; uncapped links fall
        back to the endpoint-saturation bound."""
        if route_caps is None:
            route_caps = (cfg.cap_route_range, cfg.cap_route_update)
        if self.link_cap > 0:
            return (3 * len(route_caps) * self.link_cap) // 4
        return (3 * self.T * sum(route_caps)) // 4

    def pressure(self, me, link_flits):
        """Max occupancy over the links this tile's traffic rides: its own
        row line (X block) and its own column line (Y block)."""
        r_me, c_me = me // self.cols, me % self.cols
        x = jax.lax.dynamic_slice(
            link_flits, (r_me * N_CHANNELS * self.cols,),
            (N_CHANNELS * self.cols,))
        y = jax.lax.dynamic_slice(
            link_flits,
            (N_CHANNELS * self.T + c_me * N_CHANNELS * self.rows,),
            (N_CHANNELS * self.rows,))
        return jnp.maximum(x.max(), y.max())


@dataclasses.dataclass(frozen=True)
class Mesh2D(_Grid2D):
    name = "mesh"
    wrap = False


@dataclasses.dataclass(frozen=True)
class Torus2D(_Grid2D):
    name = "torus"
    wrap = True


@dataclasses.dataclass(frozen=True)
class Ruche(_Grid2D):
    """Mesh plus long-range channels skipping ``ruche_factor`` tiles."""

    ruche_factor: int = 2
    name = "ruche"
    wrap = False

    @property
    def ruche(self) -> int:
        return max(self.ruche_factor, 2)


@dataclasses.dataclass(frozen=True)
class Hier2D(_Grid2D):
    """Multi-die hierarchical NoC: an ``ndies_y x ndies_x`` array of
    intra-die grids joined by DIE-class express links (module docstring).

    ``base`` selects the intra-die wiring: ``"mesh"`` (monotone lines) or
    ``"torus"`` (each die closes its own rings; the wrap shortcut applies
    to die-local traffic).  The global grid is still (rows, cols) with the
    same link index space as the flat backends, so ``ndies_x = ndies_y =
    1`` with a mesh base is **bit-identical** to :class:`Mesh2D` — same
    links, same routes, same telemetry — which is the equivalence anchor
    the tests pin down.  ``max_hops`` keeps the flat-mesh bound (a valid
    upper bound for every die shape, and the histogram shape that makes
    the ndies=1 Stats comparable).
    """

    ndies_x: int = 1
    ndies_y: int = 1
    base: str = "mesh"
    name = "hier"

    def __post_init__(self):
        super().__post_init__()
        if self.base not in ("mesh", "torus"):
            raise ValueError(f"hier base must be mesh|torus, got "
                             f"{self.base!r}")
        if (self.ndies_x <= 0 or self.ndies_y <= 0
                or self.cols % self.ndies_x or self.rows % self.ndies_y):
            raise ValueError(
                f"{self.rows}x{self.cols} grid not divisible into "
                f"{self.ndies_y}x{self.ndies_x} dies")

    @property
    def wrap(self) -> bool:
        return self.base == "torus"

    @property
    def die_x(self) -> int:
        return self.cols // self.ndies_x

    @property
    def die_y(self) -> int:
        return self.rows // self.ndies_y

    @property
    def max_hops(self) -> int:
        return max(self.cols - 1 + self.rows - 1, 1)

    @property
    def max_die_crossings(self) -> int:
        return self.ndies_x - 1 + self.ndies_y - 1


def make_network(cfg, T: int):
    """Build the backend selected by ``EngineConfig.noc`` for a T-tile run."""
    if cfg.noc == "ideal":
        return IdealAllToAll(T)
    rows, cols = grid_shape(T, cfg.noc_rows)
    if cfg.noc == "mesh":
        return Mesh2D(T, rows, cols, cfg.link_cap)
    if cfg.noc == "torus":
        return Torus2D(T, rows, cols, cfg.link_cap)
    if cfg.noc == "ruche":
        return Ruche(T, rows, cols, cfg.link_cap, cfg.ruche_factor)
    if cfg.noc == "hier":
        return Hier2D(T, rows, cols, cfg.link_cap,
                      ndies_x=cfg.ndies_x, ndies_y=cfg.ndies_y,
                      base=cfg.hier_base)
    raise ValueError(f"unknown noc backend {cfg.noc!r}")
