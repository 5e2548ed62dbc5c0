"""Flash-decoding under shard_map: keep decode caches sharded, always.

The baseline (auto-GSPMD) decode step lets the partitioner handle the
per-batch cache scatter ``cache.at[bidx, :, slot].set(k)`` and the
attention einsums over the cache.  For several cache layouts the scatter's
per-batch dynamic indices defeat the partitioner and it materializes the
*whole* cache with an all-gather every layer, every token — the dominant
collective term of every decode cell in the baseline roofline table
(e.g. deepseek-v3 decode_32k: 35.8 s of ICI time per token).

This module replaces that path with an explicit ``shard_map``:

  * the cache never moves: each shard updates its own slice (a local
    scatter masked to the owning shard),
  * attention runs as partial softmax per shard (flash-decoding adapted
    to the TPU mesh: the "split-KV" axis is the model axis of the mesh),
  * shards combine with three tiny collectives: pmax(m), psum(l),
    psum(ctx) — O(B x H x D) bytes instead of O(cache).

Two cache layouts are supported, matching distributed/sharding.py:
  * head-sharded  (n_kv_heads % tp == 0): update + attention are fully
    local per shard; no collective at all inside the block.
  * seq-sharded   (cache length % tp == 0): flash-decoding combine.
Anything else falls back to the caller's auto-sharded path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _dp_axis(ctx, n: int):
    """Batch axis spec: data axes when they divide the batch, else None."""
    dp = ctx.data_axes
    if ctx.tp_off:
        dp = dp + (ctx.model_axis,)
    size = 1
    for a in dp:
        size *= ctx.mesh.shape[a]
    if n % size != 0:
        return None
    return dp if len(dp) > 1 else dp[0]


def _tp(ctx) -> tuple[Optional[str], int]:
    if ctx.tp_off or ctx.mesh is None:
        return None, 1
    ma = ctx.model_axis
    return ma, ctx.mesh.shape[ma]


# ===========================================================================
# GQA / MQA / MHA / SWA
# ===========================================================================
def gqa_decode(q, k_new, v_new, cache, pos, *, cfg, ctx, active=None):
    """q (B,Hq,1,D); k_new/v_new (B,Hkv,D); cache {"k","v","slot_pos"}.
    ``active`` is the serving batcher's per-slot mask (B, bool): inactive
    batch slots keep their cache bytes untouched (their request left, or
    the slot is waiting for a join), so a partially-full resident batch
    stays bitwise-correct.  Returns (out (B,Hq,1,D), new_cache) with the
    cache still sharded."""
    B, Hq, _, Dk = q.shape
    Hkv = k_new.shape[1]
    S = cache["k"].shape[2]
    ma, tp = _tp(ctx)
    b_ax = _dp_axis(ctx, B)
    head_ok = tp > 1 and Hkv % tp == 0 and Hq % tp == 0
    seq_ok = tp > 1 and S % tp == 0
    if ctx.mesh is None or tp == 1 or not (head_ok or seq_ok):
        return None  # caller falls back to the auto path

    window = cfg.window
    scale = Dk ** -0.5
    if active is None:
        active = jnp.ones((B,), bool)

    if head_ok:
        # fully local: each shard owns Hq/tp query heads + their kv heads
        def local(q, k_new, v_new, kc, vc, sp, pos, act):
            kc, vc, sp = _update_local_slot(kc, vc, sp, k_new, v_new, pos,
                                            active=act)
            out = _softmax_attend(q, kc, vc, sp, pos, window, scale)
            return out, kc, vc, sp

        specs = dict(
            q=P(b_ax, ma, None, None),
            k_new=P(b_ax, ma, None), v_new=P(b_ax, ma, None),
            kc=P(b_ax, ma, None, None), vc=P(b_ax, ma, None, None),
            sp=P(b_ax, None), pos=P(b_ax), act=P(b_ax),
        )
        out_specs = (P(b_ax, ma, None, None), specs["kc"], specs["vc"],
                     specs["sp"])
    else:
        # seq-sharded cache: local slice update + flash-decoding combine
        def local(q, k_new, v_new, kc, vc, sp, pos, act):
            S_l = kc.shape[2]
            lo = jax.lax.axis_index(ma) * S_l
            kc, vc, sp = _update_local_slot(
                kc, vc, sp, k_new, v_new, pos, lo=lo, tp=tp, active=act)
            ctx_l, m, l = _partial_attend(q, kc, vc, sp, pos, window, scale)
            m_g = jax.lax.pmax(m, ma)
            alpha = jnp.exp(m - m_g)
            l_g = jax.lax.psum(l * alpha, ma)
            ctx_g = jax.lax.psum(ctx_l * alpha[..., None], ma)
            out = (ctx_g / jnp.maximum(l_g, 1e-30)[..., None])
            B_l, G = q.shape[0], Hq // Hkv
            out = out.reshape(B_l, Hq, 1, vc.shape[-1]).astype(q.dtype)
            return out, kc, vc, sp

        specs = dict(
            q=P(b_ax, None, None, None),
            k_new=P(b_ax, None, None), v_new=P(b_ax, None, None),
            kc=P(b_ax, None, ma, None), vc=P(b_ax, None, ma, None),
            sp=P(b_ax, ma), pos=P(b_ax), act=P(b_ax),
        )
        out_specs = (P(b_ax, None, None, None), specs["kc"], specs["vc"],
                     specs["sp"])

    out, kc, vc, sp = shard_map(
        local, mesh=ctx.mesh,
        in_specs=(specs["q"], specs["k_new"], specs["v_new"], specs["kc"],
                  specs["vc"], specs["sp"], specs["pos"], specs["act"]),
        out_specs=out_specs, check_vma=False,
    )(q, k_new, v_new, cache["k"], cache["v"], cache["slot_pos"], pos,
      active)
    return out, {"k": kc, "v": vc, "slot_pos": sp}


def _update_local_slot(kc, vc, sp, k_new, v_new, pos, lo=None, tp=1,
                       active=None):
    """Write the new token into ring slot pos%S on the owning shard only.
    kc/vc (B,H,S_l,D); sp (B,S_l); k_new/v_new (B,H,D); pos (B,).
    head-sharded (lo=None): the local seq axis is the full ring.
    seq-sharded: the global ring has length S_l*tp; only the shard whose
    range [lo, lo+S_l) contains the slot actually writes.
    ``active`` (B, bool) additionally masks the write per batch slot —
    an inactive serving slot's ring is never touched."""
    B = kc.shape[0]
    S_l = kc.shape[2]
    if lo is None:
        slot = pos % S_l
        hit = jnp.ones((B,), bool)
        local_slot = slot
    else:
        slot = pos % (S_l * tp)
        hit = (slot >= lo) & (slot < lo + S_l)
        local_slot = jnp.clip(slot - lo, 0, S_l - 1)
    if active is not None:
        hit = hit & active
    bidx = jnp.arange(B)
    kw = jnp.where(hit[:, None, None], k_new.astype(kc.dtype),
                   kc[bidx, :, local_slot])
    vw = jnp.where(hit[:, None, None], v_new.astype(vc.dtype),
                   vc[bidx, :, local_slot])
    kc = kc.at[bidx, :, local_slot].set(kw)
    vc = vc.at[bidx, :, local_slot].set(vw)
    spw = jnp.where(hit, pos.astype(sp.dtype), sp[bidx, local_slot])
    sp = sp.at[bidx, local_slot].set(spw)
    return kc, vc, sp


def _valid_mask(sp, pos, window):
    valid = (sp >= 0) & (sp <= pos[:, None])
    if window is not None:
        valid &= sp > (pos[:, None] - window)
    return valid


def _softmax_attend(q, kc, vc, sp, pos, window, scale):
    """Full (local) softmax: q (B,Hq,1,D) x cache (B,Hkv,S,D)."""
    B, Hq, _, Dk = q.shape
    Hkv = kc.shape[1]
    G = Hq // Hkv
    qf = q.reshape(B, Hkv, G, Dk).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bhsd->bhgs", qf, kc.astype(jnp.float32))
    s = jnp.where(_valid_mask(sp, pos, window)[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, vc.astype(jnp.float32))
    return out.reshape(B, Hq, 1, vc.shape[-1]).astype(q.dtype)


def _partial_attend(q, kc, vc, sp, pos, window, scale):
    """Partial-softmax accumulators over the local KV slice.
    Returns (ctx (B,Hkv,G,Dv) f32, m (B,Hkv,G) f32, l (B,Hkv,G) f32)."""
    B, Hq, _, Dk = q.shape
    Hkv = kc.shape[1]
    G = Hq // Hkv
    qf = q.reshape(B, Hkv, G, Dk).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bhsd->bhgs", qf, kc.astype(jnp.float32))
    s = jnp.where(_valid_mask(sp, pos, window)[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    e = jnp.exp(s - m[..., None])
    l = jnp.sum(e, axis=-1)
    ctx = jnp.einsum("bhgs,bhsd->bhgd", e, vc.astype(jnp.float32))
    return ctx, m, l


# ===========================================================================
# MLA (latent cache)
# ===========================================================================
def mla_decode(q_lat, q_rope, ckv_new, krope_new, cache, pos, *, cfg, ctx,
               active=None):
    """Absorbed MLA decode over a sequence-sharded latent cache.

    q_lat (B,1,h,lora), q_rope (B,1,h,r); ckv_new (B,lora), krope_new (B,r);
    cache {"ckv" (B,S,lora), "krope" (B,S,r), "slot_pos" (B,S)}.
    ``active`` (B, bool): serving slot mask — inactive slots' cache is
    never written (see ``gqa_decode``).
    Returns (ctx_lat (B,1,h,lora) f32, new_cache) or None (fallback)."""
    B = q_lat.shape[0]
    S = cache["ckv"].shape[1]
    ma, tp = _tp(ctx)
    b_ax = _dp_axis(ctx, B)
    if ctx.mesh is None or tp == 1 or S % tp != 0:
        return None
    m_cfg = cfg.mla
    scale = (m_cfg.qk_nope_dim + m_cfg.qk_rope_dim) ** -0.5
    if active is None:
        active = jnp.ones((B,), bool)

    def local(q_lat, q_rope, ckv_new, krope_new, ckv, krope, sp, pos, act):
        B_l, S_l = sp.shape
        lo = jax.lax.axis_index(ma) * S_l
        slot = pos % (S_l * tp)
        hit = (slot >= lo) & (slot < lo + S_l) & act
        local_slot = jnp.clip(slot - lo, 0, S_l - 1)
        bidx = jnp.arange(B_l)
        ckv = ckv.at[bidx, local_slot].set(
            jnp.where(hit[:, None], ckv_new.astype(ckv.dtype),
                      ckv[bidx, local_slot]))
        krope = krope.at[bidx, local_slot].set(
            jnp.where(hit[:, None], krope_new.astype(krope.dtype),
                      krope[bidx, local_slot]))
        sp = sp.at[bidx, local_slot].set(
            jnp.where(hit, pos.astype(sp.dtype), sp[bidx, local_slot]))

        s = jnp.einsum("bshl,btl->bhst", q_lat.astype(jnp.float32),
                       ckv.astype(jnp.float32))
        s += jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                        krope.astype(jnp.float32))
        s *= scale                                          # (B,h,1,S_l)
        valid = (sp >= 0) & (sp <= pos[:, None])
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)                             # (B,h,1)
        e = jnp.exp(s - m[..., None])
        l = jnp.sum(e, axis=-1)
        ctx_l = jnp.einsum("bhst,btl->bshl", e, ckv.astype(jnp.float32))
        m_g = jax.lax.pmax(m, ma)
        alpha = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * alpha, ma)
        # (B,h,1) -> (B,1,h,1) to broadcast over the lora dim
        w = alpha.transpose(0, 2, 1)[..., None]
        ctx_g = jax.lax.psum(ctx_l * w, ma)
        lg = l_g.transpose(0, 2, 1)[..., None]
        out = ctx_g / jnp.maximum(lg, 1e-30)
        return out, ckv, krope, sp

    cspec = dict(ckv=P(b_ax, ma, None), krope=P(b_ax, ma, None),
                 sp=P(b_ax, ma))
    out, ckv, krope, sp = shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(b_ax, None, None, None), P(b_ax, None, None, None),
                  P(b_ax, None), P(b_ax, None),
                  cspec["ckv"], cspec["krope"], cspec["sp"], P(b_ax),
                  P(b_ax)),
        out_specs=(P(b_ax, None, None, None), cspec["ckv"], cspec["krope"],
                   cspec["sp"]),
        check_vma=False,
    )(q_lat, q_rope, ckv_new, krope_new,
      cache["ckv"], cache["krope"], cache["slot_pos"], pos, active)
    return out, {"ckv": ckv, "krope": krope, "slot_pos": sp}
