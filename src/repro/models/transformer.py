"""Configurable decoder-only LM covering all assigned architectures.

The model is organized in *segments*: maximal runs of identical layer
structure, each executed as a ``lax.scan`` over stacked parameters (keeps the
HLO small for 61-layer models and composes with remat).  Segment kinds:

  attn_mlp   -- [norm->attention->residual] [norm->MLP->residual]
  attn_moe   -- same with MoE mixer (+ optional shared experts)
  mamba      -- [norm->mamba2 block->residual]
  zamba_unit -- ``shared_attn_every`` mamba layers followed by one invocation
                of a weight-shared attention+MLP block over concat(h, e0)
                (Zamba2; the shared block's weights live outside the scan)

Three entry points:
  forward(...)           logits (train / prefill; optional cache fill)
  loss_fn(...)           next-token cross-entropy (+ MoE aux, + optional MTP)
  decode_step(...)       one-token serve step over KV/SSM caches
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import LOCAL, ShardCtx
from .config import ModelConfig
from . import layers as L
from .moe import moe_block, moe_init
from .ssm import mamba_block, mamba_cache_init, mamba_init

Params = dict


# --------------------------------------------------------------------------
# segment plan
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str    # attn_mlp | attn_moe | mamba | zamba_unit
    count: int   # scan length
    sub: int = 1 # layers folded inside one scan step (zamba_unit)


def segment_plan(cfg: ModelConfig) -> list[Segment]:
    if cfg.mixer_type == "mamba2":
        if cfg.shared_attn_every:
            k = cfg.shared_attn_every
            assert cfg.n_layers % k == 0, (cfg.n_layers, k)
            return [Segment("zamba_unit", cfg.n_layers // k, sub=k)]
        return [Segment("mamba", cfg.n_layers)]
    if cfg.mixer_type == "moe":
        nd = cfg.moe.n_dense_layers if cfg.moe else 0
        segs = []
        if nd:
            segs.append(Segment("attn_mlp", nd))
        segs.append(Segment("attn_moe", cfg.n_layers - nd))
        return segs
    return [Segment("attn_mlp", cfg.n_layers)]


# --------------------------------------------------------------------------
# per-layer init
# --------------------------------------------------------------------------
def _attn_init(key, cfg: ModelConfig) -> Params:
    if cfg.attn_type == "mla":
        return L.mla_init(key, cfg)
    return L.gqa_init(key, cfg)


def _layer_init(key, cfg: ModelConfig, kind: str) -> Params:
    d, dt = cfg.d_model, cfg.compute_dtype
    ks = jax.random.split(key, 4)
    if kind == "mamba":
        return {
            "norm": jnp.ones((d,), dt),
            "mamba": mamba_init(ks[0], cfg),
        }
    if kind == "zamba_unit":
        sub = cfg.shared_attn_every
        mk = jax.random.split(ks[0], sub)
        return {
            "norms": jnp.ones((sub, d), dt),
            "mamba": jax.vmap(lambda k: mamba_init(k, cfg))(mk),
            "in_proj": L.dense_init(ks[1], 2 * d, d, dt),
            "attn_norm": jnp.ones((d,), dt),
        }
    p = {
        "ln1": jnp.ones((d,), dt),
        "ln2": jnp.ones((d,), dt),
        "attn": _attn_init(ks[0], cfg),
    }
    if kind == "attn_moe":
        p["moe"] = moe_init(ks[1], cfg)
    else:
        p["mlp"] = L.mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_act, dt)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    dt = cfg.compute_dtype
    d, V = cfg.d_model, cfg.vocab_size
    keys = jax.random.split(key, 8)
    if cfg.n_codebooks > 1:
        embed = (jax.random.normal(keys[0], (cfg.n_codebooks, V, d),
                                   jnp.float32) * 0.02).astype(dt)
    else:
        embed = (jax.random.normal(keys[0], (V, d), jnp.float32)
                 * 0.02).astype(dt)
    params: Params = {"embed": embed, "final_norm": jnp.ones((d,), dt)}
    segs = segment_plan(cfg)
    seg_params = []
    for i, seg in enumerate(segs):
        sk = jax.random.split(jax.random.fold_in(keys[1], i), seg.count)
        seg_params.append(
            jax.vmap(lambda k, seg=seg: _layer_init(k, cfg, seg.kind))(sk)
        )
    params["segments"] = seg_params
    if cfg.shared_attn_every and cfg.mixer_type == "mamba2":
        params["shared_attn"] = {
            "attn": _attn_init(keys[2], cfg),
            "mlp": L.mlp_init(keys[3], d, cfg.d_ff, cfg.mlp_act, dt),
            "ln1": jnp.ones((d,), dt),
            "ln2": jnp.ones((d,), dt),
        }
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            params["lm_head"] = (jax.random.normal(
                keys[4], (cfg.n_codebooks, d, V), jnp.float32,
            ) * (d ** -0.5)).astype(dt)
        else:
            params["lm_head"] = L.dense_init(keys[4], d, V, dt)
    if cfg.mtp:
        params["mtp_proj"] = L.dense_init(keys[5], 2 * d, d, dt)
        params["mtp_norm"] = jnp.ones((d,), dt)
    return params


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------
def embed_tokens(params: Params, tokens: jax.Array, cfg: ModelConfig,
                 ctx: ShardCtx) -> jax.Array:
    table = params["embed"]
    if cfg.n_codebooks > 1:       # (K,V,d); tokens (B,S,K)
        if ctx.embed_strategy == "onehot":
            oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=table.dtype)
            return jnp.einsum("bskv,kvd->bsd", oh, table)
        return sum(
            jnp.take(table[k], tokens[..., k], axis=0)
            for k in range(cfg.n_codebooks)
        )
    if ctx.embed_strategy == "onehot":
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=table.dtype)
        return oh @ table
    return jnp.take(table, tokens, axis=0)


def unembed(params: Params, h: jax.Array, cfg: ModelConfig,
            ctx: ShardCtx) -> jax.Array:
    if cfg.n_codebooks > 1:
        if cfg.tie_embeddings:
            return jnp.einsum("bsd,kvd->bskv", h, params["embed"])
        return jnp.einsum("bsd,kdv->bskv", h, params["lm_head"])
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


# --------------------------------------------------------------------------
# layer bodies
# --------------------------------------------------------------------------
def _shared_attn_apply(shared: Params, xin: jax.Array, cfg: ModelConfig,
                       ctx: ShardCtx, positions, cache, fill_cache,
                       active=None):
    """The Zamba2 weight-shared transformer block (attention + MLP)."""
    h = xin
    a, kv = _attention(shared["attn"], L.rmsnorm(h, shared["ln1"],
                                                 cfg.rms_eps),
                       cfg, ctx, positions, cache, fill_cache, active)
    h = h + a
    h = h + L.mlp(shared["mlp"], L.rmsnorm(h, shared["ln2"], cfg.rms_eps),
                  cfg.mlp_act)
    return h, kv


def _attention(p, x, cfg: ModelConfig, ctx: ShardCtx, positions, cache,
               fill_cache, active=None, prompt_len=None, pages=None,
               layer=None):
    """Returns (out, cache_out).  cache_out is the updated cache (decode),
    the filled cache (fill_cache), or None.  ``active`` is the serving
    batcher's per-slot mask, threaded into the decode cache update.
    With ``pages`` the cache is every layer's page pool, stacked, and
    ``layer`` this layer's index into it.
    ``prompt_len`` (scalar, may be traced) masks the *fill* path for
    bucket-padded prefill: cache entries at positions >= prompt_len are
    scrubbed (slot_pos=-1, zero K/V) so the filled cache is
    indistinguishable from an exact-length prefill — causality already
    keeps trailing padding out of every real position's logits."""
    fn = L.mla_attention if cfg.attn_type == "mla" else L.gqa_attention
    if cache is not None:
        return fn(p, x, cfg, positions=positions, cache=cache, ctx=ctx,
                  active=active, pages=pages, layer=layer)
    out, _ = fn(p, x, cfg, positions=positions, cache=None,
                block_k=ctx.block_k)
    if not fill_cache:
        return out, None
    # re-derive the kv projections to populate a decode cache
    B, S, _ = x.shape
    if cfg.attn_type == "mla":
        m = cfg.mla
        kv = x @ p["wkv_a"]
        ckv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
        ckv = L.rmsnorm(ckv, p["kv_norm"], cfg.rms_eps)
        cos, sin = L.rope_cos_sin(positions, m.qk_rope_dim, cfg.rope_theta)
        k_rope = L.apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
        sp = jnp.broadcast_to(positions.astype(jnp.int32), (B, S))
        if prompt_len is not None:
            keep = (sp >= 0) & (sp < prompt_len)
            ckv = jnp.where(keep[..., None], ckv, jnp.zeros_like(ckv))
            k_rope = jnp.where(keep[..., None], k_rope,
                               jnp.zeros_like(k_rope))
            sp = jnp.where(keep, sp, -1)
        filled = {"ckv": ckv, "krope": k_rope, "slot_pos": sp}
        return out, filled
    dh = cfg.head_dim
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, dh)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, dh)
    if cfg.use_bias:
        k = k + p["bk"].reshape(cfg.n_kv_heads, dh)
        v = v + p["bv"].reshape(cfg.n_kv_heads, dh)
    pos2d = positions[0] if cfg.mrope_sections else positions
    cos, sin = L.rope_cos_sin(positions, dh, cfg.rope_theta,
                              cfg.mrope_sections)
    k = L.apply_rope(k, cos, sin).transpose(0, 2, 1, 3)   # (B,H,S,D)
    v = v.transpose(0, 2, 1, 3)
    W = min(cfg.window, S) if cfg.window else S
    if cfg.window and S >= cfg.window:
        # keep the trailing window, at slot = pos % W
        tail = jnp.arange(S - W, S)
        slots = tail % W
        kc = jnp.zeros_like(k[:, :, :W]).at[:, :, slots].set(
            k[:, :, S - W:])
        vc = jnp.zeros_like(v[:, :, :W]).at[:, :, slots].set(
            v[:, :, S - W:])
        sp = jnp.full((B, W), -1, jnp.int32).at[:, slots].set(
            jnp.broadcast_to(pos2d[..., S - W:], (B, W)).astype(jnp.int32))
    else:
        kc, vc = k, v
        sp = jnp.broadcast_to(pos2d, (B, S)).astype(jnp.int32)
    if prompt_len is not None:
        keep = (sp >= 0) & (sp < prompt_len)
        kc = jnp.where(keep[:, None, :, None], kc, jnp.zeros_like(kc))
        vc = jnp.where(keep[:, None, :, None], vc, jnp.zeros_like(vc))
        sp = jnp.where(keep, sp, -1)
    return out, {"k": kc, "v": vc, "slot_pos": sp}


def _layer_apply(p: Params, h: jax.Array, cfg: ModelConfig, kind: str,
                 ctx: ShardCtx, positions, cache, fill_cache,
                 shared: Optional[Params], e0: Optional[jax.Array],
                 active=None, prompt_len=None, pages=None, layer=None):
    """One scan step.  Returns (h, cache_out, aux)."""
    aux = jnp.float32(0)
    if kind == "mamba":
        y, c = mamba_block(
            p["mamba"], L.rmsnorm(h, p["norm"], cfg.rms_eps), cfg,
            cache=cache, fill_cache=fill_cache, pallas=ctx.pallas,
        )
        return h + y, c, aux
    if kind == "zamba_unit":
        sub = cfg.shared_attn_every
        mcaches = []
        for i in range(sub):
            pi = jax.tree.map(lambda x, i=i: x[i], p["mamba"])
            ci = (jax.tree.map(lambda x, i=i: x[i], cache["mamba"])
                  if cache is not None else None)
            y, c = mamba_block(
                pi, L.rmsnorm(h, p["norms"][i], cfg.rms_eps), cfg,
                cache=ci, fill_cache=fill_cache, pallas=ctx.pallas,
            )
            h = h + y
            mcaches.append(c)
        xin = jnp.concatenate([h, e0], axis=-1) @ p["in_proj"]
        xin = L.rmsnorm(xin, p["attn_norm"], cfg.rms_eps)
        acache = cache["attn"] if cache is not None else None
        u, kv = _shared_attn_apply(shared, xin, cfg, ctx, positions,
                                   acache, fill_cache, active)
        h = h + u
        cout = None
        if mcaches[0] is not None or kv is not None:
            cout = {
                "mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *mcaches),
                "attn": kv,
            }
        return h, cout, aux
    # attn_mlp / attn_moe
    a, cout = _attention(p["attn"], L.rmsnorm(h, p["ln1"], cfg.rms_eps),
                         cfg, ctx, positions, cache, fill_cache, active,
                         prompt_len, pages, layer)
    # pin the TP boundary on the bf16 block output: without the constraint
    # the partitioner is free to place the model-axis all-reduce after the
    # f32 upcast of the next rmsnorm, doubling its wire bytes (§Perf)
    a = ctx.constrain(a, "dp", None, None)
    h = h + a
    x2 = L.rmsnorm(h, p["ln2"], cfg.rms_eps)
    if kind == "attn_moe":
        y, aux = moe_block(p["moe"], x2, cfg, ctx)
    else:
        y = L.mlp(p["mlp"], x2, cfg.mlp_act)
    y = ctx.constrain(y, "dp", None, None)
    h = h + y
    h = ctx.constrain(h, "dp", "tp" if ctx.seq_shard_acts else None, None)
    return h, cout, aux


# --------------------------------------------------------------------------
# forward / loss / decode
# --------------------------------------------------------------------------
def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                    # (B,S) or (B,S,K)
    *,
    ctx: ShardCtx = LOCAL,
    positions: Optional[jax.Array] = None,
    vision_embeds: Optional[jax.Array] = None,
    fill_cache: bool = False,
    prompt_len=None,
):
    """Returns (logits, filled_cache|None, aux).

    ``prompt_len`` (scalar, traceable; serving's bucketed prefill): the
    true prompt length when ``tokens`` is right-padded to a compile
    bucket.  The filled attention caches are scrubbed past it and logits
    at real positions are untouched (causal masking).  Attention-only
    paths: recurrent (mamba) segments fold padding into their final
    state, so bucket padding cannot be masked after the fact — callers
    gate on the segment plan."""
    B, S = tokens.shape[:2]
    if prompt_len is not None and (
            cfg.window or cfg.n_vision_tokens or any(
                seg.kind in ("mamba", "zamba_unit")
                for seg in segment_plan(cfg))):
        raise ValueError(
            "prompt_len (bucket-padded prefill) requires full-attention "
            "text models: recurrent mamba state folds padding in, a "
            "sliding-window fill keeps trailing PADDED positions (evicting "
            "real prompt KV), and the vision splice depends on the "
            "physical prompt length")
    if positions is None:
        positions = jnp.arange(S)[None, :]
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[None], (3, 1, S))
    h = embed_tokens(params, tokens, cfg, ctx)
    if vision_embeds is not None and cfg.n_vision_tokens:
        nv = cfg.n_vision_tokens
        h = jnp.concatenate(
            [vision_embeds.astype(h.dtype), h[:, nv:]], axis=1
        )
    h = ctx.constrain(h, "dp", None, None)
    e0 = h if cfg.shared_attn_every else None
    shared = params.get("shared_attn")
    aux_total = jnp.float32(0)
    caches = []

    for seg, sp in zip(segment_plan(cfg), params["segments"]):
        def body(carry, xs):
            h, aux = carry
            lp = xs
            h, cout, a = _layer_apply(
                lp, h, cfg, seg.kind, ctx, positions, None, fill_cache,
                shared, e0, None, prompt_len,
            )
            return (h, aux + a), cout

        if ctx.remat == "full":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable
            )
        elif ctx.remat == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable,
            )
        if ctx.unroll:
            couts = []
            for i in range(seg.count):
                lp = jax.tree.map(lambda x, i=i: x[i], sp)
                (h, aux_total), cout = body((h, aux_total), lp)
                couts.append(cout)
            cout = (jax.tree.map(lambda *xs: jnp.stack(xs), *couts)
                    if couts[0] is not None else None)
        else:
            (h, aux_total), cout = jax.lax.scan(body, (h, aux_total), sp)
        caches.append(cout)

    h = L.rmsnorm(h, params["final_norm"], cfg.rms_eps)
    logits = unembed(params, h, cfg, ctx)
    logits = ctx.constrain(
        logits, "dp", None, "tp") if cfg.n_codebooks == 1 else logits
    cache_out = None
    if fill_cache:
        cache_out = {
            "segments": caches,
            "pos": jnp.full((B,), S, jnp.int32),
        }
    return logits, cache_out, (aux_total, h)


def _xent(logits: jax.Array, labels: jax.Array, mask: jax.Array,
          use_onehot: bool = False):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    if use_onehot:
        # vocab-sharded logits: one-hot multiply keeps the reduction local
        # per shard + a scalar all-reduce, instead of a gather across shards.
        # The einsum reads the f32 view `lf` (not `logits`): its transpose
        # then routes the cotangent through the astype, keeping the entire
        # backward activation chain in bf16 — einsum-ing the bf16 logits
        # directly emits an f32 cotangent that add_any-promotes every
        # residual/attention/MoE cotangent to f32, doubling backward wire
        # bytes at every sharding boundary (§Perf iteration 4).
        oh = jax.nn.one_hot(labels, logits.shape[-1], dtype=lf.dtype)
        ll = jnp.einsum("bsv,bsv->bs", lf, oh,
                        preferred_element_type=jnp.float32)
    else:
        ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(
    cfg: ModelConfig, params: Params, batch: dict, *, ctx: ShardCtx = LOCAL
):
    """batch: tokens (B,S[,K]) int32, optional loss_mask (B,S),
    optional vision_embeds / positions.  Returns (loss, metrics)."""
    tokens = batch["tokens"]
    logits, _, (aux, h) = forward(
        cfg, params, tokens, ctx=ctx,
        positions=batch.get("positions"),
        vision_embeds=batch.get("vision_embeds"),
    )
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(tokens.shape[:2], jnp.float32)
    onehot = ctx.mesh is not None
    if cfg.n_codebooks > 1:
        loss = jnp.float32(0)
        for k in range(cfg.n_codebooks):
            loss = loss + _xent(
                logits[:, :-1, k], tokens[:, 1:, k], mask[:, 1:], onehot
            )
        loss = loss / cfg.n_codebooks
    else:
        loss = _xent(logits[:, :-1], tokens[:, 1:], mask[:, 1:], onehot)
    metrics = {"xent": loss, "aux": aux}
    if cfg.mtp:
        # predict t+2 from (h_t, embed(tok_{t+1})) — simplified MTP head
        emb_next = embed_tokens(params, tokens[:, 1:], cfg, ctx)
        h_mtp = (jnp.concatenate([h[:, :-1], emb_next], axis=-1)
                 @ params["mtp_proj"])
        h_mtp = L.rmsnorm(h_mtp, params["mtp_norm"], cfg.rms_eps)
        logits2 = unembed(params, h_mtp, cfg, ctx)
        mtp_loss = _xent(logits2[:, :-1], tokens[:, 2:], mask[:, 2:], onehot)
        metrics["mtp"] = mtp_loss
        loss = loss + 0.1 * mtp_loss
    loss = loss + aux
    metrics["loss"] = loss
    return loss, metrics


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    segs = segment_plan(cfg)
    out = []
    for seg in segs:
        def one(kind=seg.kind):
            if kind == "mamba":
                return mamba_cache_init(cfg, batch)
            if kind == "zamba_unit":
                return {
                    "mamba": jax.tree.map(
                        lambda x: jnp.stack([x] * cfg.shared_attn_every),
                        mamba_cache_init(cfg, batch),
                    ),
                    "attn": (L.mla_cache_init(cfg, batch, max_len)
                             if cfg.attn_type == "mla"
                             else L.gqa_cache_init(cfg, batch, max_len)),
                }
            return (L.mla_cache_init(cfg, batch, max_len)
                    if cfg.attn_type == "mla"
                    else L.gqa_cache_init(cfg, batch, max_len))

        out.append(jax.tree.map(
            lambda x: jnp.stack([x] * seg.count), one()
        ))
    return {"segments": out, "pos": jnp.zeros((batch,), jnp.int32)}


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int) -> dict:
    """Paged serving cache: per-layer page POOLS shared by every slot
    (page axis replaces the batch axis of the dense cache), plus the
    usual per-slot ``pos``.  Attention-only segment plans — recurrent
    (mamba/zamba) state is not pageable and callers fall back to
    ``init_cache``."""
    segs = segment_plan(cfg)
    if any(seg.kind in ("mamba", "zamba_unit") for seg in segs):
        raise ValueError("paged cache requires attention-only models")
    if cfg.window:
        raise ValueError("paged cache excludes sliding-window archs")
    one = (L.mla_paged_cache_init(cfg, n_pages, page_size)
           if cfg.attn_type == "mla"
           else L.gqa_paged_cache_init(cfg, n_pages, page_size))
    out = [jax.tree.map(lambda x: jnp.stack([x] * seg.count), one)
           for seg in segs]
    return {"segments": out, "pos": jnp.zeros((batch,), jnp.int32)}


def _is_page_pool(seg_cache) -> bool:
    """A segment cache that is a stack of per-layer page pools (GQA
    ``k``/``v`` or MLA ``ckv``/``krope``, no per-slot ``slot_pos``)."""
    return isinstance(seg_cache, dict) and set(seg_cache) in (
        {"k", "v"}, {"ckv", "krope"})


def decode_step(
    cfg: ModelConfig, params: Params, cache: dict, tokens: jax.Array,
    *, ctx: ShardCtx = LOCAL, active: Optional[jax.Array] = None,
    pages: Optional[jax.Array] = None,
):
    """One serve step: tokens (B,1[,K]) -> (logits (B,1[,K],V), new cache).

    ``active`` (B, bool) is the continuous batcher's slot mask: inactive
    batch slots (free, or a request that just left) keep their cache bytes
    and position untouched, so a partially-full resident batch decodes
    bitwise-identically to a full one.  The mask is threaded through the
    attention cache-update paths (local scatter and the shard_map decode
    of ``distributed/decode.py``); callers that hold whole-state slots
    (the serving decoder cell) additionally gate their state writeback.

    A paged segment's cache (a page pool per layer, stacked) rides in the
    layer loop's carry: each layer writes its token into the stack in
    place and its attention kernel reads the stack by layer index, so the
    step never copies a layer's pool out of the stack or back into it.
    Dense caches and recurrent state are scanned over, layer by layer.
    """
    B = tokens.shape[0]
    pos = cache["pos"]                       # (B,)
    positions = pos[:, None]
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[None], (3, B, 1))
    h = embed_tokens(params, tokens, cfg, ctx)
    h = ctx.constrain(h, "dp", None, None)
    e0 = h if cfg.shared_attn_every else None
    shared = params.get("shared_attn")
    new_segs = []
    for seg, sp, sc in zip(segment_plan(cfg), params["segments"],
                           cache["segments"]):
        if _is_page_pool(sc):
            def body(carry, xs):
                h, pool = carry
                lp, i = xs
                h, pool, _ = _layer_apply(
                    lp, h, cfg, seg.kind, ctx, positions, pool, False,
                    shared, e0, active, None, pages, i,
                )
                return (h, pool), None

            layers = jnp.arange(seg.count, dtype=jnp.int32)
            if ctx.unroll:
                carry = (h, sc)
                for i in range(seg.count):
                    carry, _ = body(carry, jax.tree.map(
                        lambda x, i=i: x[i], (sp, layers)))
                h, new_c = carry
            else:
                (h, new_c), _ = jax.lax.scan(body, (h, sc), (sp, layers))
            new_segs.append(new_c)
            continue

        def body(h, xs):
            lp, lc = xs
            h, cout, _ = _layer_apply(
                lp, h, cfg, seg.kind, ctx, positions, lc, False, shared, e0,
                active,
            )
            return h, cout

        if ctx.unroll:
            outs = []
            for i in range(seg.count):
                h, c = body(h, jax.tree.map(lambda x, i=i: x[i], (sp, sc)))
                outs.append(c)
            new_c = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        else:
            h, new_c = jax.lax.scan(body, h, (sp, sc))
        new_segs.append(new_c)
    h = L.rmsnorm(h, params["final_norm"], cfg.rms_eps)
    logits = unembed(params, h, cfg, ctx)
    new_pos = pos + 1 if active is None else pos + active.astype(pos.dtype)
    return logits, {"segments": new_segs, "pos": new_pos}
