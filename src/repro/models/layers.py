"""Model building blocks: norms, RoPE/M-RoPE, attention variants, MLPs.

Pure functional JAX; parameters are plain dicts.  Attention has three
execution paths:

  * ``blockwise_attention`` — pure-JAX online-softmax attention (a lax.scan
    over KV blocks).  Never materializes the (Sq, Sk) score matrix, so 32k
    prefill fits in HBM; this is the XLA path the dry-run rooflines use.
  * ``kernels.ops.attention`` — the Pallas flash kernel (TPU target).
  * ``decode_attention`` — single-query attention over a cache (decode).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .config import MLAConfig, ModelConfig

Params = dict


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = (d_in ** -0.5) if scale is None else scale
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(
        dtype
    )


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (n * w.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE / M-RoPE
# --------------------------------------------------------------------------
def rope_cos_sin(positions: jax.Array, dim: int, theta: float,
                 sections: Optional[tuple[int, ...]] = None):
    """cos/sin tables.  positions: (..., S) for standard RoPE, or
    (3, ..., S) with ``sections`` for M-RoPE (t/h/w streams, qwen2-vl)."""
    half = dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if sections is None:
        freqs = positions[..., None].astype(jnp.float32) * inv  # (...,S,half)
    else:
        assert sum(sections) == half, (sections, half)
        stream = jnp.repeat(
            jnp.arange(len(sections)), jnp.array(sections),
            total_repeat_length=half,
        )                                                        # (half,)
        # positions: (3, ..., S) -> select stream per frequency
        pos_sel = jnp.take(positions, stream, axis=0)            # (half,...,S)
        pos_sel = jnp.moveaxis(pos_sel, 0, -1)                   # (...,S,half)
        freqs = pos_sel.astype(jnp.float32) * inv
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2)."""
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# blockwise (online-softmax) attention — pure JAX
# --------------------------------------------------------------------------
NEG_INF = -1e30


def blockwise_attention(
    q: jax.Array,   # (B, Hq, Sq, Dk)
    k: jax.Array,   # (B, Hkv, Sk, Dk)
    v: jax.Array,   # (B, Hkv, Sk, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_k: int = 1024,
) -> jax.Array:
    B, Hq, Sq, Dk = q.shape
    _, Hkv, Sk, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = (Dk ** -0.5) if scale is None else scale
    block_k = min(block_k, Sk)
    assert Sk % block_k == 0
    nk = Sk // block_k

    # GQA via repeat (a gather): keeps the q-head axis intact so tensor
    # parallelism on heads survives (reshaping Hq->(Hkv,G) would break the
    # sharding and force GSPMD to replicate the score tensor).
    if G > 1:
        k = jnp.repeat(k, G, axis=1)
        v = jnp.repeat(v, G, axis=1)
    qf = q.astype(jnp.float32) * scale
    kb = jnp.moveaxis(k.reshape(B, Hq, nk, block_k, Dk), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, Hq, nk, block_k, Dv), 2, 0)
    qpos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, j = inp
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        kpos = j * block_k + jnp.arange(block_k)
        mask = jnp.ones((Sq, block_k), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask[None, None],
                      jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hq, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nk))
    )
    out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l, 1e-30)[..., None],
                    0.0)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,        # (B, Hq, 1, Dk)
    k_cache: jax.Array,  # (B, Hkv, S, Dk)
    v_cache: jax.Array,  # (B, Hkv, S, Dv)
    slot_pos: jax.Array, # (B, S) absolute position stored in each slot, -1=empty
    pos: jax.Array,      # (B,) current absolute position of the query
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    B, Hq, _, Dk = q.shape
    Hkv = k_cache.shape[1]
    G = Hq // Hkv
    Dv = v_cache.shape[-1]
    scale = (Dk ** -0.5) if scale is None else scale
    # grouped einsum: reads each KV slot once regardless of G.  When
    # n_kv < |model| the cache is *sequence*-sharded over the model axis
    # (flash-decoding style) and GSPMD turns the softmax/v reductions into
    # partial-softmax all-reduces.
    qf = q.reshape(B, Hkv, G, Dk).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bhsd->bhgs", qf, k_cache.astype(jnp.float32))
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if window is not None:
        valid &= slot_pos > (pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, 1, Dv).astype(q.dtype)


def page_write(pool: jax.Array, layer, row: jax.Array, lane: jax.Array,
               ok: jax.Array, new: jax.Array) -> jax.Array:
    """``pool.at[layer, row, :, lane].set(new)`` for each slot where
    ``ok``, in a stack of page pools: (L, N, H, ps, d) with ``new`` (B, H,
    d), or (L, N, ps, d) with ``new`` (B, d).  Written as a scatter of
    whole ``d``-rows into the pool seen as (rows, d), a reshape that moves
    no byte: the pool keeps the layout the paged kernels read, so XLA
    updates it in place instead of copying it into a layout the scatter
    prefers and back.  Rows where not ``ok`` are dropped."""
    n, ps, d = pool.shape[1], pool.shape[-2], pool.shape[-1]
    heads = pool.shape[2] if pool.ndim == 5 else 1
    at = ((layer * n + row)[:, None] * heads + jnp.arange(heads)) * ps
    at = jnp.where(ok[:, None], at + lane[:, None], pool.size // d)
    rows = pool.reshape(-1, d).at[at.reshape(-1)].set(
        new.reshape(-1, d).astype(pool.dtype), mode="drop")
    return rows.reshape(pool.shape)


# --------------------------------------------------------------------------
# GQA attention block (covers MHA / GQA / MQA / SWA / M-RoPE)
# --------------------------------------------------------------------------
def gqa_init(key, cfg: ModelConfig) -> Params:
    d, dh = cfg.d_model, cfg.head_dim
    dt = cfg.compute_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * dh, dt),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * dh, dt),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * dh, dt),
        "wo": dense_init(ks[3], cfg.n_heads * dh, d, dt),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * dh,), dt)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * dh,), dt)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * dh,), dt)
    return p


def gqa_cache_init(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """KV cache for one layer.  SWA archs only keep `window` slots."""
    S = min(max_len, cfg.window) if cfg.window else max_len
    dh, dt = cfg.head_dim, cfg.compute_dtype
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, S, dh), dt),
        "v": jnp.zeros((batch, cfg.n_kv_heads, S, dh), dt),
        "slot_pos": jnp.full((batch, S), -1, jnp.int32),
    }


def gqa_paged_cache_init(cfg: ModelConfig, n_pages: int,
                         page_size: int) -> dict:
    """Paged KV pool for one layer: ``n_pages`` fixed-size pages shared by
    every slot; the per-slot page table (``serving/paging.py``) maps
    logical page index -> pool row.  No ``slot_pos`` — lane validity is
    derived from the page table and the query position."""
    dh, dt = cfg.head_dim, cfg.compute_dtype
    return {
        "k": jnp.zeros((n_pages, cfg.n_kv_heads, page_size, dh), dt),
        "v": jnp.zeros((n_pages, cfg.n_kv_heads, page_size, dh), dt),
    }


def gqa_attention(
    p: Params, x: jax.Array, cfg: ModelConfig, *,
    positions: jax.Array,                 # (B,S) or (3,B,S) for mrope
    cache: Optional[dict] = None,         # decode when present
    block_k: int = 1024,
    ctx=None,                             # ShardCtx for decode_shardmap
    active: Optional[jax.Array] = None,   # (B,) serving slot mask (decode)
    pages: Optional[jax.Array] = None,    # (B,P) page table -> paged decode
    layer: Optional[jax.Array] = None,    # this layer in the stacked pool
) -> tuple[jax.Array, Optional[dict]]:
    B, S, d = x.shape
    dh = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.use_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, dh)
    k = k.reshape(B, S, cfg.n_kv_heads, dh)
    v = v.reshape(B, S, cfg.n_kv_heads, dh)
    cos, sin = rope_cos_sin(positions, dh, cfg.rope_theta,
                            cfg.mrope_sections)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = q.transpose(0, 2, 1, 3)  # (B,H,S,D)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)

    if cache is None:
        out = blockwise_attention(
            q, k, v, causal=True, window=cfg.window, block_k=block_k
        )
        new_cache = None
    else:
        assert S == 1, "decode path handles one token at a time"
        pos = positions[0] if cfg.mrope_sections else positions  # (B,S)
        pos = pos[:, 0]                                          # (B,)
        if pages is not None:
            # paged decode: cache is every layer's shared page pool,
            # stacked (L,N,Hkv,ps,dh); the write lands at (layer, row,
            # lane) through the page table, in place, and attention
            # reads this layer's mapped pages out of the stack via the
            # fused kernel.
            assert not cfg.window, "paged decode excludes windowed archs"
            from repro.kernels.paged_decode import paged_gqa_attention

            psz = cache["k"].shape[3]
            lane = pos % psz
            row = jnp.take_along_axis(pages, (pos // psz)[:, None], 1)[:, 0]
            ok = row >= 0
            if active is not None:
                ok = ok & active
            # inactive slots and unmapped pages write nothing (page rows
            # are per-slot disjoint, so no cross-slot collisions either way)
            k_pool = page_write(cache["k"], layer, row, lane, ok, k[:, :, 0])
            v_pool = page_write(cache["v"], layer, row, lane, ok, v[:, :, 0])
            out = paged_gqa_attention(q[:, :, 0], k_pool, v_pool,
                                      pages, pos, layer=layer)
            out = out[:, None].reshape(B, S, cfg.n_heads * dh)
            return out @ p["wo"], {"k": k_pool, "v": v_pool}
        if (ctx is not None and getattr(ctx, "decode_shardmap", False)
                and ctx.mesh is not None):
            from repro.distributed import decode as DD

            res = DD.gqa_decode(q, k[:, :, 0], v[:, :, 0], cache, pos,
                                cfg=cfg, ctx=ctx, active=active)
            if res is not None:
                out, new_cache = res
                out = out.transpose(0, 2, 1, 3).reshape(
                    B, S, cfg.n_heads * dh)
                return out @ p["wo"], new_cache
        Sc = cache["k"].shape[2]
        slot = (pos % Sc)                                        # (B,)
        bidx = jnp.arange(B)
        # serving slot mask: an inactive slot's ring buffer keeps its old
        # bytes (the write re-writes the current slot value)
        def gate(new, old, ax):
            if active is None:
                return new
            a = active.reshape((B,) + (1,) * ax)
            return jnp.where(a, new, old)

        k_cache = cache["k"].at[bidx, :, slot].set(
            gate(k[:, :, 0].astype(cache["k"].dtype),
                 cache["k"][bidx, :, slot], 2))
        v_cache = cache["v"].at[bidx, :, slot].set(
            gate(v[:, :, 0].astype(cache["v"].dtype),
                 cache["v"][bidx, :, slot], 2))
        slot_pos = cache["slot_pos"].at[bidx, slot].set(
            gate(pos, cache["slot_pos"][bidx, slot], 0))
        out = decode_attention(
            q, k_cache, v_cache, slot_pos, pos, window=cfg.window
        )
        new_cache = {"k": k_cache, "v": v_cache, "slot_pos": slot_pos}
    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * dh)
    return out @ p["wo"], new_cache


# --------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V3)
# --------------------------------------------------------------------------
def mla_init(key, cfg: ModelConfig) -> Params:
    m = cfg.mla or MLAConfig()
    d, h = cfg.d_model, cfg.n_heads
    dt = cfg.compute_dtype
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], d, m.q_lora_rank, dt),
        "q_norm": jnp.ones((m.q_lora_rank,), dt),
        "wq_b": dense_init(ks[1], m.q_lora_rank,
                           h * (m.qk_nope_dim + m.qk_rope_dim), dt),
        "wkv_a": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_dim, dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "wkv_b": dense_init(ks[3], m.kv_lora_rank,
                            h * (m.qk_nope_dim + m.v_head_dim), dt),
        "wo": dense_init(ks[4], h * m.v_head_dim, d, dt),
    }


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    m = cfg.mla or MLAConfig()
    dt = cfg.compute_dtype
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
        "krope": jnp.zeros((batch, max_len, m.qk_rope_dim), dt),
        "slot_pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def mla_paged_cache_init(cfg: ModelConfig, n_pages: int,
                         page_size: int) -> dict:
    """Paged latent-KV pool for one layer (see ``gqa_paged_cache_init``)."""
    m = cfg.mla or MLAConfig()
    dt = cfg.compute_dtype
    return {
        "ckv": jnp.zeros((n_pages, page_size, m.kv_lora_rank), dt),
        "krope": jnp.zeros((n_pages, page_size, m.qk_rope_dim), dt),
    }


def mla_attention(
    p: Params, x: jax.Array, cfg: ModelConfig, *,
    positions: jax.Array,
    cache: Optional[dict] = None,
    block_k: int = 1024,
    ctx=None,                             # ShardCtx for decode_shardmap
    active: Optional[jax.Array] = None,   # (B,) serving slot mask (decode)
    pages: Optional[jax.Array] = None,    # (B,P) page table -> paged decode
    layer: Optional[jax.Array] = None,    # this layer in the stacked pool
) -> tuple[jax.Array, Optional[dict]]:
    m = cfg.mla or MLAConfig()
    B, S, d = x.shape
    h = cfg.n_heads
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5

    q = rmsnorm(x @ p["wq_a"], p["q_norm"], cfg.rms_eps) @ p["wq_b"]
    q = q.reshape(B, S, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    kv = x @ p["wkv_a"]
    ckv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    ckv = rmsnorm(ckv, p["kv_norm"], cfg.rms_eps)

    cos, sin = rope_cos_sin(positions, m.qk_rope_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]  # (B,S,r)

    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    w_uk = wkv_b[:, :, : m.qk_nope_dim]     # (lora, h, nope)
    w_uv = wkv_b[:, :, m.qk_nope_dim:]      # (lora, h, v)

    if cache is None:
        # expanded path (train / prefill): per-head k,v from the latent
        k_nope = jnp.einsum("bsl,lhn->bshn", ckv, w_uk)
        v = jnp.einsum("bsl,lhv->bshv", ckv, w_uv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, h, m.qk_rope_dim))], -1
        )
        qfull = jnp.concatenate([q_nope, q_rope], -1)
        out = blockwise_attention(
            qfull.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, scale=scale,
            block_k=block_k,
        )  # (B,h,S,v)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, h * m.v_head_dim)
        return out @ p["wo"], None

    # absorbed path (decode): attend in the latent space
    assert S == 1
    pos = positions[:, 0]                                   # (B,)
    if pages is not None:
        from repro.kernels.paged_decode import paged_mla_attention

        # cache: every layer's latent pool, stacked (L,N,ps,lora|rope)
        psz = cache["ckv"].shape[2]
        lane = pos % psz
        row = jnp.take_along_axis(pages, (pos // psz)[:, None], 1)[:, 0]
        ok = row >= 0
        if active is not None:
            ok = ok & active
        ckv_pool = page_write(cache["ckv"], layer, row, lane, ok, ckv[:, 0])
        krope_pool = page_write(cache["krope"], layer, row, lane, ok,
                                k_rope[:, 0])
        q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk)  # (B,1,h,lora)
        ctx_lat = paged_mla_attention(
            q_lat[:, 0], q_rope[:, 0], ckv_pool, krope_pool, pages, pos,
            scale=scale, layer=layer,
        )                                                   # (B,h,lora) f32
        out = jnp.einsum("bshl,lhv->bshv", ctx_lat[:, None].astype(x.dtype),
                         w_uv)
        out = out.reshape(B, S, h * m.v_head_dim)
        return out @ p["wo"], {"ckv": ckv_pool, "krope": krope_pool}
    if (ctx is not None and getattr(ctx, "decode_shardmap", False)
            and ctx.mesh is not None):
        from repro.distributed import decode as DD

        q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk)
        res = DD.mla_decode(q_lat, q_rope, ckv[:, 0], k_rope[:, 0],
                            cache, pos, cfg=cfg, ctx=ctx, active=active)
        if res is not None:
            ctx_lat, new_cache = res
            out = jnp.einsum("bshl,lhv->bshv", ctx_lat.astype(x.dtype),
                             w_uv)
            out = out.reshape(B, S, h * m.v_head_dim)
            return out @ p["wo"], new_cache
    Sc = cache["ckv"].shape[1]
    slot = pos % Sc
    bidx = jnp.arange(B)

    def gate(new, old, ax):
        # serving slot mask: inactive slots keep their old cache bytes
        if active is None:
            return new
        return jnp.where(active.reshape((B,) + (1,) * ax), new, old)

    ckv_c = cache["ckv"].at[bidx, slot].set(
        gate(ckv[:, 0].astype(cache["ckv"].dtype),
             cache["ckv"][bidx, slot], 1))
    krope_c = cache["krope"].at[bidx, slot].set(
        gate(k_rope[:, 0].astype(cache["krope"].dtype),
             cache["krope"][bidx, slot], 1))
    slot_pos = cache["slot_pos"].at[bidx, slot].set(
        gate(pos, cache["slot_pos"][bidx, slot], 0))

    q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk)      # (B,1,h,lora)
    s_lat = jnp.einsum("bshl,btl->bhst", q_lat.astype(jnp.float32),
                       ckv_c.astype(jnp.float32))
    s_rope = jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                        krope_c.astype(jnp.float32))
    s = (s_lat + s_rope) * scale                            # (B,h,1,S)
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,btl->bshl", pattn,
                     ckv_c.astype(jnp.float32))             # (B,1,h,lora)
    out = jnp.einsum("bshl,lhv->bshv", ctx.astype(x.dtype), w_uv)
    out = out.reshape(B, S, h * m.v_head_dim)
    return out @ p["wo"], {"ckv": ckv_c, "krope": krope_c,
                           "slot_pos": slot_pos}


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def mlp_init(key, d_model: int, d_ff: int, act: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = {
        "w1": dense_init(ks[0], d_model, d_ff, dtype),
        "w2": dense_init(ks[1], d_ff, d_model, dtype),
    }
    if act == "swiglu":
        p["w3"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp(p: Params, x: jax.Array, act: str) -> jax.Array:
    h = x @ p["w1"]
    if act == "swiglu":
        h = jax.nn.silu(h) * (x @ p["w3"])
    else:
        h = jax.nn.gelu(h)
    return h @ p["w2"]
