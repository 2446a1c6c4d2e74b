"""Ring attention (context parallelism) — §Perf train iteration B.

Sequence stays sharded; the (small, GQA-compact) kv blocks rotate around the
model axis with ``ppermute`` while every device accumulates online-softmax
partials for its own q block.  Per layer this moves M-1 kv blocks
(~kv_bytes), replacing the (B,S,d)-sized activation gathers of the
gather-style attention — for internlm2 train_4k: 252 MB vs ~4.5 GB.

Differentiable end to end (ppermute transposes to the reverse ring); remat
recomputes the ring in the backward pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def _block_scores(q, k, qpos, kpos, window):
    """q: (B, Sq, Hkv, G, hd); k: (B, Sk, Hkv, hd) -> (B, Hkv, G, Sq, Sk)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    mask = kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    return jnp.where(mask[None, None, None], s, NEG_INF)


def ring_attention(q, k, v, *, mesh, model_axis: str = "model",
                   batch_axes=("data",), window: int = 0):
    """q: (B, S, H, hd); k, v: (B, S, Hkv, hd), S sharded over model_axis.

    Returns (B, S, H, hd), same sharding as q.
    """
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    M = mesh.shape[model_axis]
    bspec = tuple(batch_axes) if len(batch_axes) > 1 else batch_axes[0]

    def body(qb, kb, vb):
        # qb: (B_l, S_loc, H, hd); kb/vb: (B_l, S_loc, Hkv, hd)
        idx = jax.lax.axis_index(model_axis)
        S_loc = qb.shape[1]
        qg = qb.reshape(qb.shape[0], S_loc, Hkv, G, hd)
        qpos = idx * S_loc + jnp.arange(S_loc, dtype=jnp.int32)
        perm = [(i, (i + 1) % M) for i in range(M)]

        def step(carry, t):
            m, l, acc, kc, vc = carry
            src = (idx - t) % M  # original owner of the block in hand
            kpos = src * S_loc + jnp.arange(S_loc, dtype=jnp.int32)
            # skip fully-masked blocks: future blocks (causal) and blocks
            # beyond the sliding window never touch the accumulators —
            # ~2x compute saved causal, ~M/(window/S_loc) for SWA
            kmin = src * S_loc
            kmax = kmin + S_loc - 1
            qmin, qmax = idx * S_loc, (idx + 1) * S_loc - 1
            relevant = kmin <= qmax  # some kv position is <= some q
            if window:
                relevant &= kmax > qmin - window

            def attend(args):
                m, l, acc = args
                s = _block_scores(qg, kc, qpos, kpos, window)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l2 = l * corr + p.sum(-1)
                pv = jnp.einsum("bhgqk,bkhd->bhgqd", p,
                                vc.astype(jnp.float32),
                                preferred_element_type=jnp.float32)
                return m_new, l2, acc * corr[..., None] + pv

            m, l, acc = jax.lax.cond(relevant, attend,
                                     lambda args: args, (m, l, acc))
            kc = jax.lax.ppermute(kc, model_axis, perm)
            vc = jax.lax.ppermute(vc, model_axis, perm)
            return (m, l, acc, kc, vc), None

        Bl = qb.shape[0]
        # sliding window: only ceil(window/S_loc)+1 source blocks can ever
        # be visible — the ring stops early (STATIC; device-independent).
        # causal-only skips stay dynamic (lax.cond) inside the step.
        n_steps = M
        if window:
            n_steps = min(M, -(-window // S_loc) + 1)
        m0 = jnp.full((Bl, Hkv, G, S_loc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((Bl, Hkv, G, S_loc), jnp.float32)
        a0 = jnp.zeros((Bl, Hkv, G, S_loc, hd), jnp.float32)
        (m, l, acc, _, _), _ = jax.lax.scan(
            step, (m0, l0, a0, kb, vb),
            jnp.arange(n_steps, dtype=jnp.int32))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, Hkv, G, S_loc, hd) -> (B, S_loc, H, hd)
        return out.transpose(0, 3, 1, 2, 4).reshape(
            Bl, S_loc, H, hd).astype(qb.dtype)

    spec_q = P(bspec, model_axis, None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_q, spec_q, spec_q),
        out_specs=spec_q, check_vma=False)
    return fn(q, k, v)
