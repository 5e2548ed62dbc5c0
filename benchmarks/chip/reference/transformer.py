"""Plain float32 reference of the decoder-only transformer family, and its
operation and byte counts.

The forward follows the layer equations: token embedding; per layer
x += Wo·attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)), Wv·n1(x)) with causal
softmax over grouped key/value heads, then x += MLP(n2(x)) with SwiGLU
(w2·(silu(w1·x) ⊙ w3·x)) or tanh-GELU (w2·gelu(w1·x)); a final norm;
logits against the output matrix (the embedding when tied).  Norms are
RMSNorm.  It reads the weights the benchmark made, by the names it gave
them, and imports nothing of the program.

Every matrix product runs at ``Precision.HIGHEST`` in float32.  The
weights are bfloat16 values, exactly representable in float32, and are
widened one layer at a time so the reference fits beside its inputs.

``quant="fp8"`` is the control: the same forward with every matrix
product's operands rounded to float8 (e4m3, one scale per tensor for the
weights and one per row for the activations), the lower precision a
later change might be tempted to serve in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# --------------------------------------------------------------------------
# operation and byte counts (used by the per-layer readers)
# --------------------------------------------------------------------------
def head_dim(m: dict) -> int:
    return m.get("d_head") or m["d_model"] // m["n_heads"]


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies by in one layer (attention + MLP)."""
    d, dh = m["d_model"], head_dim(m)
    attn = d * dh * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    mlp = d * m["d_ff"] * (3 if m.get("mlp_act", "swiglu") == "swiglu" else 2)
    return attn + mlp


def token_flops(m: dict, keys: int) -> float:
    """Model operations of one token through every layer, attending over
    ``keys`` cached positions (itself included), without the unembedding."""
    dh = head_dim(m)
    per_layer = 2 * layer_matmul_params(m) + 4 * m["n_heads"] * dh * keys
    return float(m["n_layers"] * per_layer)


def prefill_flops(m: dict, n: int) -> float:
    """Model operations of an ``n``-token prompt (causal attention), plus
    the one unembedding that yields its first token."""
    dh = head_dim(m)
    mat = 2 * layer_matmul_params(m) * n
    attn = 4 * m["n_heads"] * dh * n * (n + 1) / 2
    return float(m["n_layers"] * (mat + attn) + unembed_flops(m))


def decode_flops(m: dict, keys: int) -> float:
    """One decoded token: the layers over ``keys`` positions plus the
    unembedding."""
    return token_flops(m, keys) + unembed_flops(m)


def unembed_flops(m: dict) -> float:
    return float(2 * m["d_model"] * m["vocab_size"])


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Cached key and value bytes of one position over all layers."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * head_dim(m) * itemsize


def paged_attention_bytes(m: dict, keys: int, itemsize: int = 2) -> int:
    """Least HBM traffic of one slot's decode attention over all layers:
    the live keys and values once, the query read and the output written."""
    qo = 2 * m["n_heads"] * head_dim(m) * itemsize
    return kv_bytes_per_token(m, itemsize) * keys + m["n_layers"] * qo


def paged_attention_flops(m: dict, keys: int) -> float:
    return float(m["n_layers"] * 4 * m["n_heads"] * head_dim(m) * keys)


# --------------------------------------------------------------------------
# the forward
# --------------------------------------------------------------------------
def _q8(x, axis):
    """Round to float8 with one scale per slice along ``axis`` (None: one
    scale for the tensor), and widen back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(
        jnp.float32
    )


def _rope(x, theta):
    """x: (S, H, D); rotate-half RoPE at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, block):
    """Causal softmax attention, ``block`` query rows at a time.
    q: (S, H, D); k, v: (S, Hkv, D); query head h reads kv head h // G."""
    s, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = d**-0.5

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        qpos = i * block + jnp.arange(block)
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(one, jnp.arange(s // block))
    return out.reshape(s, h, d)


def _layer(m, quant, block, h, lp):
    """One decoder layer on the residual stream ``h`` (S, d), float32."""
    s = h.shape[0]
    dh, nh, nkv = head_dim(m), m["n_heads"], m["n_kv_heads"]
    a = lp["attn"]
    x = _rms(h, lp["ln1"], m["rms_eps"])
    q, k, v = _mm(x, a["wq"], quant), _mm(x, a["wk"], quant), _mm(x, a["wv"], quant)
    if m.get("use_bias"):
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q = _rope(q.reshape(s, nh, dh), m["rope_theta"])
    k = _rope(k.reshape(s, nkv, dh), m["rope_theta"])
    o = _attention(q, k, v.reshape(s, nkv, dh), block).reshape(s, nh * dh)
    h = h + _mm(o, a["wo"], quant)
    x = _rms(h, lp["ln2"], m["rms_eps"])
    mp = lp["mlp"]
    u = _mm(x, mp["w1"], quant)
    if m.get("mlp_act", "swiglu") == "swiglu":
        u = jax.nn.silu(u) * _mm(x, mp["w3"], quant)
    else:
        u = jax.nn.gelu(u, approximate=True)
    return h + _mm(u, mp["w2"], quant)


@functools.lru_cache(maxsize=None)
def _compiled(key, quant, block):
    m = dict(key)

    @jax.jit
    def embed(params, tokens):
        return params["embed"][tokens].astype(jnp.float32)

    @jax.jit
    def layer(params, i, h):
        lp = jax.tree.map(lambda x: x[i], params["segments"][0])
        return _layer(m, quant, block, h, lp)

    @jax.jit
    def head(params, h, rows):
        x = _rms(h[rows], params["final_norm"], m["rms_eps"])
        w = params["lm_head"] if "lm_head" in params else params["embed"].T
        return _mm(x, w, quant)

    return embed, layer, head


def logits_at(m: dict, params, tokens: np.ndarray, rows: np.ndarray, *,
              quant: str = "", pad_to: int = 512, block: int = 512) -> np.ndarray:
    """Reference logits (len(rows), vocab) float32 at positions ``rows`` of
    the sequence ``tokens``.  The sequence is right-padded to a multiple of
    ``pad_to`` (causality keeps padding out of every real position), so one
    compile serves every length in a bucket."""
    s = len(tokens)
    padded = -(-s // pad_to) * pad_to
    tok = np.zeros(padded, np.int32)
    tok[:s] = tokens
    embed, layer, head = _compiled(tuple(sorted(m.items())), quant, min(block, padded))
    h = embed(params, jnp.asarray(tok))
    for i in range(m["n_layers"]):
        h = layer(params, jnp.int32(i), h)
    r = np.zeros(-(-len(rows) // 64) * 64, np.int32)
    r[: len(rows)] = rows
    out = head(params, h, jnp.asarray(r))
    return np.asarray(out)[: len(rows)]
