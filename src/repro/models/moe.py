"""Mixture-of-Experts: top-k token-choice routing with capacity-based
dispatch and expert parallelism.

Two implementations of identical math:

  * ``_moe_local`` — single-shard dispatch (scatter into (E, C, d) capacity
    buffers, grouped expert GEMM, gather+combine).  Used on one device and
    as the oracle for the distributed path.
  * ``_moe_spmd``  — expert-parallel path under ``jax.shard_map``: tokens are
    sharded over (data x model) (batch over data, sequence over model), each
    shard routes its own tokens, builds per-destination capacity buffers and
    exchanges them with an ``all_to_all`` over the model axis, where each
    shard owns E/|model| experts.  This is the TPU-native analogue of the
    DeepSeek/GShard a2a dispatch.

Routing: softmax top-k (granite) or sigmoid with normalized top-k gates
(deepseek-v3), plus the standard load-balance auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from .config import ModelConfig, MoEConfig
from .layers import dense_init

Params = dict


def moe_init(key, cfg: ModelConfig) -> Params:
    moe = cfg.moe
    d, dt = cfg.d_model, cfg.compute_dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, moe.n_experts, jnp.float32, scale=0.02),
        "w1": _experts_init(ks[1], moe.n_experts, d, moe.d_ff_expert, dt),
        "w2": _experts_init(ks[2], moe.n_experts, moe.d_ff_expert, d, dt),
    }
    if cfg.mlp_act == "swiglu":
        p["w3"] = _experts_init(ks[3], moe.n_experts, d, moe.d_ff_expert, dt)
    if moe.n_shared_experts:
        from .layers import mlp_init

        p["shared"] = mlp_init(
            ks[4], d, moe.d_ff_expert * moe.n_shared_experts, cfg.mlp_act, dt
        )
    return p


def _experts_init(key, e, d_in, d_out, dtype):
    return (
        jax.random.normal(key, (e, d_in, d_out), jnp.float32) * (d_in ** -0.5)
    ).astype(dtype)


# --------------------------------------------------------------------------
# routing
# --------------------------------------------------------------------------
def _route(logits: jax.Array, moe: MoEConfig):
    """logits (T, E) fp32 -> (gates (T,k), idx (T,k), aux loss scalar)."""
    k = moe.top_k
    if moe.router_act == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        gates, idx = jax.lax.top_k(scores, k)
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
        probs = scores / (jnp.sum(scores, -1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    # load-balance aux (local view; callers psum/mean across shards)
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0
    )
    aux = E * jnp.sum(me * ce) * moe.aux_coef
    return gates, idx, aux


def _capacity(n_tokens: int, moe: MoEConfig) -> int:
    c = int(n_tokens * moe.top_k * moe.capacity_factor / moe.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


# --------------------------------------------------------------------------
# dispatch/combine via scatter into capacity buffers
# --------------------------------------------------------------------------
def _dispatch(xf, gates, idx, E: int, C: int):
    """xf (T,d); returns (buffers (E*C, d), slots (T*k,), keep (T*k,))."""
    T, d = xf.shape
    k = idx.shape[1]
    flat_e = idx.reshape(T * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                          # running count
    my_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = my_pos < C
    slot = jnp.where(keep, flat_e * C + my_pos, E * C)            # drop slot
    xrep = jnp.repeat(xf, k, axis=0)                              # (T*k, d)
    buf = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].add(
        xrep * keep[:, None].astype(xf.dtype)
    )[: E * C]
    return buf, slot, keep


def _combine(h_flat, slot, keep, gates, T: int, k: int):
    """h_flat (E*C, d) -> (T, d) weighted by gates."""
    d = h_flat.shape[-1]
    padded = jnp.concatenate([h_flat, jnp.zeros((1, d), h_flat.dtype)])
    y = padded[jnp.where(keep, slot, h_flat.shape[0])]            # (T*k, d)
    y = y * gates.reshape(T * k, 1).astype(y.dtype)
    return jnp.sum(y.reshape(T, k, d), axis=1)


def _expert_ffn(p: Params, buf_e: jax.Array, act: str) -> jax.Array:
    """buf_e (E, C, d) -> (E, C, d) through each expert's FFN."""
    h = jnp.einsum("ecd,edf->ecf", buf_e, p["w1"])
    if act == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf_e, p["w3"])
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, p["w2"])


# --------------------------------------------------------------------------
# single-shard path (oracle + small-scale)
# --------------------------------------------------------------------------
def _router_logits(xf: jax.Array, wr: jax.Array) -> jax.Array:
    """Router logits with f32 accumulation but WITHOUT upcasting the token
    activations: an ``astype(f32)`` on xf lets XLA hoist the convert above
    the sharding boundary, turning every boundary all-gather of the tokens
    into an f32 transfer (2x wire; §Perf).  bf16 x bf16 -> f32-accumulate
    is the MXU-native form."""
    return jnp.einsum("td,de->te", xf, wr.astype(xf.dtype),
                      preferred_element_type=jnp.float32)


def _moe_local(p: Params, x: jax.Array, cfg: ModelConfig):
    moe = cfg.moe
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    logits = (xf.astype(jnp.float32)) @ p["router"]
    gates, idx, aux = _route(logits, moe)
    C = _capacity(T, moe)
    buf, slot, keep = _dispatch(xf, gates, idx, moe.n_experts, C)
    h = _expert_ffn(p, buf.reshape(moe.n_experts, C, d), cfg.mlp_act)
    y = _combine(h.reshape(-1, d), slot, keep, gates, T, moe.top_k)
    return y.reshape(B, S, d), aux


# --------------------------------------------------------------------------
# expert-parallel path (shard_map + all_to_all over the model axis)
# --------------------------------------------------------------------------
def _moe_spmd(p: Params, x: jax.Array, cfg: ModelConfig, ctx):
    moe = cfg.moe
    mesh = ctx.mesh
    ma = ctx.model_axis
    dp = tuple(ctx.data_axes)
    nm = mesh.shape[ma]
    E = moe.n_experts
    assert E % nm == 0, (E, nm)
    E_l = E // nm

    def local_fn(xl, wr, w1, w2, w3):
        B_l, S_l, d = xl.shape
        T_l = B_l * S_l
        xf = xl.reshape(T_l, d)
        logits = _router_logits(xf, wr)
        gates, idx, aux = _route(logits, moe)
        aux = jax.lax.pmean(aux, dp + (ma,))
        C = _capacity(T_l, moe)
        buf, slot, keep = _dispatch(xf, gates, idx, E, C)     # (E*C, d)
        # exchange: shard e-axis over model -> each shard gets its experts'
        # buffers from every source shard
        sendbuf = buf.reshape(nm, E_l * C, d)
        recv = jax.lax.all_to_all(sendbuf, ma, split_axis=0, concat_axis=0,
                                  tiled=False)
        if recv.ndim == 4:  # (nm, 1, E_l*C, d) depending on tiling semantics
            recv = recv.reshape(nm, E_l * C, d)
        # (nm src, E_l, C, d) -> (E_l, nm*C, d)
        tok = recv.reshape(nm, E_l, C, d).transpose(1, 0, 2, 3)
        tok = tok.reshape(E_l, nm * C, d)
        pl = {"w1": w1, "w2": w2}
        if w3 is not None:
            pl["w3"] = w3
        h = _expert_ffn(pl, tok, cfg.mlp_act)                 # (E_l, nm*C, d)
        back = h.reshape(E_l, nm, C, d).transpose(1, 0, 2, 3)
        back = back.reshape(nm, E_l * C, d)
        ret = jax.lax.all_to_all(back, ma, split_axis=0, concat_axis=0,
                                 tiled=False)
        if ret.ndim == 4:
            ret = ret.reshape(nm, E_l * C, d)
        y = _combine(ret.reshape(E * C, d), slot, keep, gates, T_l, moe.top_k)
        return y.reshape(B_l, S_l, d), aux

    def local_fn_ar(xl, wr, w1, w2, w3):
        """Decode-path EP: tokens replicated over the model axis (S==1 is not
        shardable), each shard runs only its own E_l experts and the combine
        is completed with a psum — all_to_all dispatch degenerates to an
        all-reduce of the (tiny) per-step activations."""
        B_l, S_l, d = xl.shape
        T_l = B_l * S_l
        xf = xl.reshape(T_l, d)
        logits = _router_logits(xf, wr)
        gates, idx, aux = _route(logits, moe)
        aux = jax.lax.pmean(aux, dp)
        C = _capacity(T_l, moe)
        buf, slot, keep = _dispatch(xf, gates, idx, E, C)     # (E*C, d)
        rank = jax.lax.axis_index(ma)
        loc = jax.lax.dynamic_slice_in_dim(
            buf.reshape(E, C, d), rank * E_l, E_l, axis=0)    # (E_l, C, d)
        pl = {"w1": w1, "w2": w2}
        if w3 is not None:
            pl["w3"] = w3
        h_loc = _expert_ffn(pl, loc, cfg.mlp_act)             # (E_l, C, d)
        h_full = jax.lax.dynamic_update_slice(
            jnp.zeros((E, C, d), h_loc.dtype), h_loc, (rank * E_l, 0, 0))
        y = _combine(h_full.reshape(E * C, d), slot, keep, gates,
                     T_l, moe.top_k)
        y = jax.lax.psum(y, ma)
        return y.reshape(B_l, S_l, d), aux

    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    ep_axes = dp + (ma,)
    n_ep = dp_size * nm
    E_l2 = E // n_ep if E % n_ep == 0 else 0

    def local_fn_ep2d(xl, wr, w1, w2, w3):
        """Serve-mode EP2D: one expert (slice) per chip, weights stationary.
        The *tokens* move instead (tiny at decode): all-gather them over the
        data axes, every chip computes its own expert's contribution for the
        full batch, and a psum over (data x model) completes the combine."""
        B_l, S_l, d = xl.shape
        xf = xl.reshape(B_l * S_l, d)
        xf = jax.lax.all_gather(xf, dp, axis=0, tiled=True)   # (T, d)
        T = xf.shape[0]
        logits = _router_logits(xf, wr)
        gates, idx, aux = _route(logits, moe)
        C = _capacity(T, moe)
        buf, slot, keep = _dispatch(xf, gates, idx, E, C)     # (E*C, d)
        # expert-shard rank in the P(dp + (ma,)) layout (first axis major)
        rank = jax.lax.axis_index(ma)
        stride = nm
        for a in reversed(dp):
            rank = rank + jax.lax.axis_index(a) * stride
            stride *= mesh.shape[a]
        loc = jax.lax.dynamic_slice_in_dim(
            buf.reshape(E, C, d), rank * E_l2, E_l2, axis=0)
        pl = {"w1": w1, "w2": w2}
        if w3 is not None:
            pl["w3"] = w3
        h_loc = _expert_ffn(pl, loc, cfg.mlp_act)             # (E_l2, C, d)
        h_full = jax.lax.dynamic_update_slice(
            jnp.zeros((E, C, d), h_loc.dtype), h_loc, (rank * E_l2, 0, 0))
        y = _combine(h_full.reshape(E * C, d), slot, keep, gates,
                     T, moe.top_k)
        y = jax.lax.psum(y, ep_axes)                          # (T, d)
        # slice back this shard's batch rows
        drank = jnp.int32(0)
        dstride = 1
        for a in reversed(dp):
            drank = drank + jax.lax.axis_index(a) * dstride
            dstride *= mesh.shape[a]
        y = jax.lax.dynamic_slice_in_dim(
            y, drank * (B_l * S_l), B_l * S_l, axis=0)
        return y.reshape(B_l, S_l, d), aux

    w3 = p.get("w3")
    seq_shardable = x.shape[1] % nm == 0
    use_ep2d = (not seq_shardable and getattr(ctx, "serve_ep2d", False)
                and E_l2 > 0)
    if use_ep2d:
        fn, e_spec = local_fn_ep2d, P(ep_axes, None, None)
        x_spec = P(dp, None, None)
    elif seq_shardable:
        fn, e_spec = local_fn, P(ma, None, None)
        x_spec = P(dp, ma, None)    # batch over data, seq over model
    else:
        fn, e_spec = local_fn_ar, P(ma, None, None)
        x_spec = P(dp, None, None)  # seq=1: replicated over model
    in_specs = (
        x_spec,
        P(),                        # router replicated
        e_spec,                     # experts sharded over model (or 2D)
        e_spec,
        e_spec if w3 is not None else P(),
    )
    out_specs = (P(dp, ma, None) if seq_shardable else P(dp, None, None),
                 P())
    y, aux = shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x, p["router"], p["w1"], p["w2"],
      w3 if w3 is not None else jnp.zeros((), x.dtype))
    return y, aux


def moe_block(p: Params, x: jax.Array, cfg: ModelConfig, ctx=None):
    """Returns (y, aux_loss).  Adds the shared-expert path if configured."""
    if ctx is not None and getattr(ctx, "mesh", None) is not None:
        y, aux = _moe_spmd(p, x, cfg, ctx)
    else:
        y, aux = _moe_local(p, x, cfg)
    if cfg.moe.n_shared_experts:
        from .layers import mlp

        y = y + mlp(p["shared"], x, cfg.mlp_act)
    return y, aux
