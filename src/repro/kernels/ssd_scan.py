"""Mamba2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

Computes y_t = C_t . h_t,  h_t = exp(dt_t a) h_{t-1} + dt_t B_t x_t^T
in chunks of Q timesteps: the intra-chunk part is a masked, decay-weighted
(C B^T) @ X matmul (MXU work), and the inter-chunk recurrence is carried in a
VMEM scratch state across the *sequential* chunk grid dimension — the TPU
analogue of the SSD paper's chunkwise algorithm, with the recurrent carry
living in scratch rather than shared memory.

Cumulative sums inside the chunk are computed with a lower-triangular ones
matmul (MXU-friendly and deterministic) instead of a serial scan.

The kernel works head-major: the wrapper lays x/B/C out as (B, heads, L, .)
so each block's last two dims are (chunk, width) — the TPU's (8, 128)
tiling rule holds for any chunk that is a multiple of 8 — and the per-head
decay ``a`` is read from SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, ht_ref, s_ref,
    *, chunk: int, n_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = h0_ref[...].reshape(s_ref.shape).astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)                        # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                      # (Q, 1)
    a = a_ref[pl.program_id(1)]                                # scalar f32
    bm = b_ref[0, 0].astype(jnp.float32)                       # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)                       # (Q, N)

    da = dt * a                                        # (Q, 1)
    # inclusive cumsum via lower-triangular ones matmul
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (jj <= ii).astype(jnp.float32)
    cum = jax.lax.dot(tril, da, preferred_element_type=jnp.float32)  # (Q,1)

    # intra-chunk: w[i,j] = (C_i.B_j) exp(cum_i - cum_j) dt_j  (j <= i)
    decay = jnp.where(jj <= ii, jnp.exp(cum - cum.T), 0.0)     # (Q, Q)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    w = cb * decay * dt.T
    y = jax.lax.dot(w, x, preferred_element_type=jnp.float32)  # (Q, P)

    # inter-chunk: y_i += exp(cum_i) C_i . S_prev
    s_prev = s_ref[...]
    y = y + jnp.exp(cum) * jax.lax.dot(
        cm, s_prev, preferred_element_type=jnp.float32
    )

    # state update: S = exp(cum_last) S_prev + sum_j exp(cum_last-cum_j) dt_j B_j x_j^T
    cum_last = jnp.sum(da)                                      # scalar
    wlast = jnp.exp(cum_last - cum) * dt                        # (Q, 1)
    s_new = jnp.exp(cum_last) * s_prev + jax.lax.dot_general(
        bm * wlast, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                           # (N, P)
    s_ref[...] = s_new
    y_ref[...] = y.astype(y_ref.dtype).reshape(y_ref.shape)

    @pl.when(ci == n_chunks - 1)
    def _final():
        ht_ref[...] = s_new.reshape(ht_ref.shape)


def ssd_scan(
    x: jax.Array,    # (B, L, H, P)
    dt: jax.Array,   # (B, L, H)
    a: jax.Array,    # (H,)
    b: jax.Array,    # (B, L, G, N)
    c: jax.Array,    # (B, L, G, N)
    *,
    h0: jax.Array | None = None,  # (B, H, N, P)
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    assert H % G == 0
    rep = H // G
    chunk = min(chunk, L)
    assert L % chunk == 0, (L, chunk)
    n_chunks = L // chunk
    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)
    # head-major layouts: (B, H|G, L, width)
    xt = x.transpose(0, 2, 1, 3)
    dtt = dt.transpose(0, 2, 1)[..., None]
    bt = b.transpose(0, 2, 1, 3)
    ct = c.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    grid = (B, H, n_chunks)
    head = lambda w: pl.BlockSpec(  # noqa: E731
        (1, 1, chunk, w), lambda bi, h, ci: (bi, h, ci, 0))
    group = pl.BlockSpec(
        (1, 1, chunk, N), lambda bi, h, ci, rep=rep: (bi, h // rep, ci, 0))
    state = pl.BlockSpec((1, 1, N, P), lambda bi, h, ci: (bi, h, 0, 0))
    y, ht = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            head(P),
            head(1),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            group,
            group,
            state,
        ],
        out_specs=[head(P), state],
        out_shape=[
            jax.ShapeDtypeStruct(xt.shape, x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(xt, dtt, a.astype(jnp.float32), bt, ct, h0)
    return y.transpose(0, 2, 1, 3), ht
