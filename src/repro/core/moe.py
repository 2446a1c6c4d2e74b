"""Dalorex-routed Mixture-of-Experts dispatch.

Tokens are task messages; experts are the immovable data.  Expert placement
uses the paper's uniform low-order scattering over the ``model`` axis:

  * E >= M ("moonshot": 64 experts / 16 shards) — expert ``e`` lives on shard
    ``e mod M`` at local slot ``e div M`` (Eps = E/M local experts).
  * E <  M ("mixtral": 8 experts / 16 shards) — each expert is
    *tensor-split*: shard ``m`` holds ff-slice ``m div E`` of expert
    ``m mod E`` (tp = M/E slices).  A token sends ``tp`` messages; the
    partial w_down outputs sum at the source — exact TP, no replica
    divergence, memory fully sharded.

Dispatch is the engine's slot-claiming (``occurrence_index``) + ONE
all_to_all each way; per-destination capacity is the paper's bounded channel
queue.  Overflowed tokens pass through on the residual (counted — the
telemetry the TSU would expose).  The same per-device code runs single-device
(M=1, a2a = identity) for smoke tests, and :func:`moe_dense_oracle` is the
drop-free reference the dispatch must match when nothing overflows.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.queues import occurrence_index
from repro.parallel.sharding import ParamSpec, current_mesh, current_rules


@dataclasses.dataclass(frozen=True)
class MoEDims:
    E: int          # experts
    k: int          # experts per token
    M: int          # model-axis shards
    d: int
    ff: int
    mlp: str        # swiglu | squared_relu | gelu

    @property
    def eps(self) -> int:  # local experts per shard
        return max(self.E // self.M, 1)

    @property
    def tp(self) -> int:   # ff slices per expert (E < M)
        return max(self.M // self.E, 1)

    @property
    def ff_local(self) -> int:
        return self.ff // self.tp

    @property
    def slots(self) -> int:  # global expert-slot axis (leading param axis)
        return self.M * self.eps

    def check(self):
        assert self.E % self.M == 0 or self.M % self.E == 0, (self.E, self.M)


def moe_param_specs(d: int, ff: int, E: int, M: int, mlp: str, dtype: str):
    # "expert_ff" resolves to None under training rules (d gets FSDP) and to
    # "data" under decode rules (weights-stationary 2D expert sharding)
    dims = MoEDims(E, 0, M, d, ff, mlp)
    g, ffl = dims.slots, dims.ff_local
    specs = {"router": ParamSpec((d, E), (None, None), "float32")}
    if mlp == "swiglu":
        specs["w_gate"] = ParamSpec((g, d, ffl),
                                    ("expert", "fsdp", "expert_ff"), dtype)
    specs["w_up"] = ParamSpec((g, d, ffl), ("expert", "fsdp", "expert_ff"),
                              dtype)
    specs["w_down"] = ParamSpec((g, ffl, d), ("expert", "expert_ff", "fsdp"),
                                dtype)
    return specs


def _expert_ffn(params, x, dims: MoEDims):
    """x: (..., d) -> (..., d) through ONE expert's (sliced) FFN.
    params leaves have a leading local-slot axis handled by the caller."""
    if dims.mlp == "swiglu":
        g = jnp.einsum("...d,df->...f", x, params["w_gate"],
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("...d,df->...f", x, params["w_up"],
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
    else:
        u = jnp.einsum("...d,df->...f", x, params["w_up"],
                       preferred_element_type=jnp.float32)
        h = (jnp.square(jax.nn.relu(u)) if dims.mlp == "squared_relu"
             else jax.nn.gelu(u)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, params["w_down"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _router(params, x, dims: MoEDims):
    """Returns (gates (n,k), experts (n,k) int32, aux_loss scalar)."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, dims.k)
    gates = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    f = jnp.zeros((dims.E,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (x.shape[0] * dims.k))
    p = probs.mean(0)
    aux = dims.E * jnp.sum(f * p)
    return gates.astype(x.dtype), top_e.astype(jnp.int32), aux


def _dispatch_local(params, x, dims: MoEDims, capacity: int,
                    a2a, psum):
    """Per-device MoE block.  x: (n, d) local tokens.

    Single-level slot claiming (§Perf moonshot iteration): entries claim a
    slot of their GLOBAL expert slot directly — per-destination rows are
    contiguous (dest d owns rows [d·eps·cap_e, (d+1)·eps·cap_e)), so ONE
    payload all_to_all delivers tokens already grouped by local expert.  No
    metadata flits at all (the slot position IS the expert id — the
    headerless-NoC idea one level deeper), no second binning pass, no 2x
    staging buffer.

    ``capacity`` is per destination; per-expert capacity = capacity // eps.
    Returns (y (n, d), aux_loss, overflow) — aux/overflow reduced by the
    caller-provided psum.
    """
    n, d = x.shape
    gates, experts, aux = _router(params, x, dims)
    k, tp, M, eps = dims.k, dims.tp, dims.M, dims.eps
    cap_e = max(1, capacity // eps)   # slots per expert
    n_slots = M * eps

    # entries: (n*k*tp,) — token i, choice c, slice j
    tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k * tp)
    e = jnp.repeat(experts.reshape(-1), tp)                # (n*k*tp,)
    j = jnp.tile(jnp.arange(tp, dtype=jnp.int32), n * k)
    gate = jnp.repeat(gates.reshape(-1), tp)
    if dims.E >= M:
        dest = e % M
        le = e // M
    else:
        dest = e + j * dims.E
        le = jnp.zeros_like(e)
    g = dest * eps + le                                    # global slot
    valid = jnp.ones_like(dest, dtype=bool)

    occ = occurrence_index(g, valid, n_slots)
    fits = occ < cap_e
    slot = jnp.where(fits, g * cap_e + occ, n_slots * cap_e)
    overflow = (~fits).sum(dtype=jnp.int32)

    payload = jnp.zeros((n_slots * cap_e + 1, d), x.dtype).at[slot].set(
        x[tok])
    recv = a2a(payload[:-1])                      # (M*eps*cap_e, d)

    local = {key: v for key, v in params.items() if key != "router"}
    if eps == 1:
        out = _expert_ffn(jax.tree.map(lambda a: a[0], local), recv, dims)
    else:
        # rows arrive grouped source-major: (M, eps, cap_e, d) — regroup
        # per local expert with one transpose, batch the expert FFNs
        grouped = recv.reshape(M, eps, cap_e, d).transpose(1, 0, 2, 3)
        grouped = grouped.reshape(eps, M * cap_e, d)
        out_e = jax.vmap(lambda p, xx: _expert_ffn(p, xx, dims))(
            local, grouped)
        out = out_e.reshape(eps, M, cap_e, d).transpose(1, 0, 2, 3)
        out = out.reshape(M * eps * cap_e, d)

    back = a2a(out)  # results return to their claim slots
    contrib = jnp.take(back, jnp.minimum(slot, n_slots * cap_e - 1), axis=0)
    contrib = jnp.where(fits[:, None], contrib, 0)
    y = jnp.zeros((n, d), jnp.float32).at[tok].add(
        contrib.astype(jnp.float32) * gate[:, None].astype(jnp.float32))
    return y.astype(x.dtype), psum(aux) / M, psum(overflow)


def _dispatch_resident(params, x, dims: MoEDims, capacity: int, my_idx,
                       model_axis: str = "model"):
    """No-network dispatch for replicated tokens (decode serving).

    x: (n, d) — the SAME tokens on every shard.  This shard computes only
    the entries owned by its expert slots / ff slice; the caller psums the
    per-shard partial y over (model, ff) axes.  aux/ovf are psum-free
    (identical math on every shard).
    """
    n, d = x.shape
    gates, experts, aux = _router(params, x, dims)
    k, tp, M, eps = dims.k, dims.tp, dims.M, dims.eps

    tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k * tp)
    e = jnp.repeat(experts.reshape(-1), tp)
    j = jnp.tile(jnp.arange(tp, dtype=jnp.int32), n * k)
    gate = jnp.repeat(gates.reshape(-1), tp)
    if dims.E >= M:
        dest = e % M
        le = e // M
    else:
        dest = e + j * dims.E
        le = jnp.zeros_like(e)
    mine = dest == my_idx

    # claim local slots: (eps, cap_e) buffer for this shard only
    cap_e = max(1, (2 * capacity * M) // (M * eps))
    occ = occurrence_index(jnp.where(mine, le, eps), mine, eps)
    fits = mine & (occ < cap_e)
    slot = jnp.where(fits, le * cap_e + occ, eps * cap_e)
    # overflow counted once per token-entry across the grid: only the owner
    # shard counts it, and the caller's replicated out_spec is satisfied
    # because every shard computes the same mine/fits masks for ITS index —
    # psum over model in the caller... aux is identical; ovf differs per
    # shard, so reduce it here.
    overflow = (mine & ~fits).sum(dtype=jnp.int32)
    overflow = jax.lax.psum(overflow, model_axis)

    payload = jnp.zeros((eps * cap_e + 1, d), x.dtype).at[slot].set(x[tok])
    buf = payload[:-1].reshape(eps, cap_e, d)
    local = {key: v for key, v in params.items() if key != "router"}
    if eps == 1:
        out_e = _expert_ffn(jax.tree.map(lambda a: a[0], local), buf[0],
                            dims)[None]
    else:
        out_e = jax.vmap(lambda p, xx: _expert_ffn(p, xx, dims))(local, buf)
    out_flat = out_e.reshape(eps * cap_e, d)
    contrib = jnp.take(out_flat, jnp.minimum(slot, eps * cap_e - 1), axis=0)
    contrib = jnp.where(fits[:, None], contrib, 0)
    y = jnp.zeros((n, d), jnp.float32).at[tok].add(
        contrib.astype(jnp.float32) * gate[:, None].astype(jnp.float32))
    return y.astype(x.dtype), aux, overflow


def moe_block(params, x, *, E: int, k: int, ff: int, mlp: str,
              model_axis: str = "model", batch_axes=("data",),
              seq_shard: bool = True, capacity_factor: float = 1.25):
    """x: (B, S, d).  Runs the Dalorex dispatch as a shard_map island under a
    mesh context, or single-device (M=1) otherwise.  Returns (y, aux, ovf).
    """
    B, S, d = x.shape
    mesh = current_mesh()
    if mesh is None:
        dims = MoEDims(E, k, 1, d, ff, mlp)
        dims.check()
        n = B * S
        cap = max(1, int(n * k * dims.tp * capacity_factor))
        y, aux, ovf = _dispatch_local(
            params, x.reshape(n, d), dims, cap,
            a2a=lambda a: a, psum=lambda a: a)
        return y.reshape(B, S, d), aux, ovf

    M = mesh.shape[model_axis]
    dims = MoEDims(E, k, M, d, ff, mlp)
    dims.check()
    # weights-stationary 2D expert sharding (decode rules): the expert ff
    # dimension is sharded over these axes; every such shard replicates the
    # dispatch and computes its ff-slice; partial outputs psum at the end.
    rules = current_rules()
    ff_axes = rules.get("expert_ff") if rules is not None else None
    if ff_axes is not None and not isinstance(ff_axes, tuple):
        ff_axes = (ff_axes,)
    ffd = 1
    if ff_axes:
        for a in ff_axes:
            ffd *= mesh.shape[a]
    if ffd > 1:
        # Decode weights-stationary path (§Perf iter 2): tokens are
        # replicated across the whole (model x ff) grid, so NO dispatch
        # network round is needed at all — each shard locally selects the
        # tokens owned by its expert slots (the Dalorex move in its purest
        # form: data never moves, the task shows up where the data is),
        # computes its ff-slice, and ONE psum over (model, ff) combines
        # expert-parallel partials and ff-slice partials together.
        n_local = B * S
        capacity = max(1, int(n_local * k * dims.tp * capacity_factor) // M)

        def body2(prm, xb):
            xl = xb.reshape(-1, d)
            y, aux, ovf = _dispatch_resident(
                prm, xl, dims, capacity,
                my_idx=jax.lax.axis_index(model_axis),
                model_axis=model_axis)
            y = jax.lax.psum(y, (model_axis,) + ff_axes)
            return y.reshape(xb.shape), aux, ovf

        ffspec = ff_axes if len(ff_axes) > 1 else ff_axes[0]
        pspec = {}
        for key in params:
            if key == "router":
                pspec[key] = P(None, None)
            elif key == "w_down":
                pspec[key] = P(model_axis, ffspec, None)
            else:
                pspec[key] = P(model_axis, None, ffspec)
        fn = jax.shard_map(
            body2, mesh=mesh,
            in_specs=(pspec, P(None, None, None)),
            out_specs=(P(None, None, None), P(), P()), check_vma=False)
        return fn(params, x)

    # drop non-divisible shardings (e.g. batch=1 long-context decode)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    if B % dp != 0 or B < dp:
        batch_axes, dp = (), 1
    if S % M != 0 or S < M:
        seq_shard = False
    n_local = (B // dp) * (S // (M if seq_shard else 1))
    capacity = max(1, int(n_local * k * dims.tp * capacity_factor) // M)

    bspec = (tuple(batch_axes) if len(batch_axes) > 1
             else batch_axes[0] if batch_axes else None)
    sspec = model_axis if seq_shard else None

    def body(prm, xb):
        xl = xb.reshape(-1, d)
        y, aux, ovf = _dispatch_local(
            prm, xl, dims, capacity,
            a2a=lambda a: jax.lax.all_to_all(a, model_axis, 0, 0, tiled=True),
            psum=lambda a: jax.lax.psum(a, model_axis))
        return y.reshape(xb.shape), aux, ovf

    pspec = {key: P(model_axis, None, None) for key in params
             if key != "router"}
    pspec["router"] = P(None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P(bspec, sspec, None)),
        out_specs=(P(bspec, sspec, None), P(), P()), check_vma=False)
    return fn(params, x)


def to_dispatch_layout(params, E: int, M: int):
    """Convert oracle layout (E, d, ff) to the placed dispatch layout
    (M*eps, d, ff_local) — the low-order expert scattering of Section III-A.

    E >= M: slot m*eps+le holds expert le*M + m.
    E <  M: slot m        holds ff-slice m//E of expert m%E.
    """
    import numpy as np
    out = {"router": params["router"]}
    eps, tp = max(E // M, 1), max(M // E, 1)
    for key, w in params.items():
        if key == "router":
            continue
        w = np.asarray(w)
        ff_axis = 2 if key != "w_down" else 1
        ffl = w.shape[ff_axis] // tp
        slots = []
        for m in range(M):
            for le in range(eps):
                if E >= M:
                    slots.append(w[le * M + m])
                else:
                    j = m // E
                    sl = [slice(None)] * 3
                    sl[ff_axis] = slice(j * ffl, (j + 1) * ffl)
                    slots.append(w[m % E][tuple(sl[1:])])
        out[key] = jnp.asarray(np.stack(slots))
    return out


def moe_dense_oracle(params, x, *, E: int, k: int, ff: int, mlp: str):
    """Drop-free reference: every token computes ALL experts densely and
    mixes with its top-k gates.  Used by tests to validate the dispatch
    (must match exactly when overflow == 0).  Single-device only (params in
    the M=1 layout, i.e. leading slot axis == E, full ff)."""
    B, S, d = x.shape
    dims = MoEDims(E, k, 1, d, ff, mlp)
    xt = x.reshape(-1, d)
    gates, experts, aux = _router(params, xt, dims)
    local = {key: v for key, v in params.items() if key != "router"}
    outs = jax.vmap(lambda p: _expert_ffn(p, xt, dims))(
        jax.tree.map(lambda a: a, local))  # (E, n, d)
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)  # (n, k, E)
    w = (onehot * gates[..., None].astype(jnp.float32)).sum(1)  # (n, E)
    y = jnp.einsum("ne,end->nd", w, outs.astype(jnp.float32))
    return y.reshape(B, S, d).astype(x.dtype), aux
