"""Blocked (flash) attention Pallas TPU kernel.

Online-softmax attention with GQA head mapping, causal and sliding-window
masking.  TPU adaptation notes (vs the CUDA flash-attention formulation):

  * tiling is chosen for VMEM and the 128x128 MXU: Q/K blocks are multiples
    of 128 lanes on the head dim, f32 accumulators live in VMEM scratch;
  * the KV loop is the innermost *sequential* grid dimension; scratch
    persists across it (the TPU analogue of a CUDA thread-block loop);
  * blocks that cannot contribute under the causal/window mask are skipped
    with ``pl.when`` (no MXU work issued), the structural equivalent of
    warp-level early exit;
  * GQA is expressed in the BlockSpec index map (kv head = q head // G) so
    KV tiles are fetched once per group, not repeated in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i,
    *, scale: float, causal: bool, window: int | None,
    q_offset: int, block_q: int, block_k: int, n_k: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    qi = pl.program_id(2)
    q_start = qi * block_q + q_offset
    k_start = ki * block_k

    # Can this KV block contribute to this Q block at all?
    contribute = True
    if causal:
        contribute = k_start <= q_start + block_q - 1
    if window is not None:
        # newest key in block must be inside the window of the oldest query
        contribute = jnp.logical_and(
            contribute, k_start + block_k - 1 > q_start - window
        )

    @pl.when(contribute)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # (BQ, BK)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i[...], jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_i[...] - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_i[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_i[...]
        out = jnp.where(l > 0, acc[...] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,   # (B, Hq, Sq, D)
    k: jax.Array,   # (B, Hkv, Sk, D)
    v: jax.Array,   # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = (D ** -0.5) if scale is None else scale

    kernel = functools.partial(
        _attn_kernel,
        scale=scale, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, n_k=n_k,
    )
    grid = (B, Hq, n_q, n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, h, i, j, G=G: (b, h // G, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, h, i, j, G=G: (b, h // G, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
