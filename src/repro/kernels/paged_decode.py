"""Fused paged-decode attention Pallas kernels (gqa + mla).

Single-query attention over a PAGED KV cache: each sequence's KV bytes
live in fixed-size pages of one shared pool (``serving/paging.py``), and
the per-slot page table maps logical page index -> pool row.  The kernel
fuses the gather-from-pages with the attention math in one
``pallas_call``: the page loop is the innermost sequential grid
dimension, each step DMA-ing one page of K/V into VMEM scratch via a
scalar-prefetched page-table lookup (``PrefetchScalarGridSpec`` — the
index map reads the page id, so unmapped pages are never fetched twice),
and the final step runs exactly the dense ``decode_attention`` /
absorbed-MLA math over the gathered scratch.

The pool may hold every layer's pages, stacked on a leading layer axis;
a second scalar-prefetch operand, the layer index, then picks the
layer's pages in the index map, so the decode step hands the kernel the
stack it updates in place and no per-layer slice of it is ever made.

Bitwise parity with the dense path is load-bearing (the serving engine's
paged-vs-dense token parity gate): the finalize step performs the SAME
ops in the SAME f32 shapes and lane order as ``layers.decode_attention``
(gqa) / the absorbed-MLA decode (mla) — full softmax, no online
rescaling — so a paged decode emits bit-identical logits to a dense one.

``interpret=None`` auto-resolves to interpret mode off-TPU (like
``fused_step.py``), so CPU CI exercises the real kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _resolve_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        from . import ops

        return not ops.on_tpu()
    return bool(interpret)


# --------------------------------------------------------------------------
# GQA paged decode
# --------------------------------------------------------------------------
def _gqa_kernel(
    pm_ref,  # (B, P) int32 scalar-prefetch: page table (-1 = unmapped)
    layer_ref,  # (1,) int32 scalar-prefetch: layer of the stacked pool
    q_ref,  # (1, Hq, Dk) block
    k_ref,  # (1, Hkv, ps, Dk) block: the page selected by the index map
    v_ref,  # (1, Hkv, ps, Dk) block
    valid_ref,  # (1, 1, S) int32 block: lane is mapped and <= pos
    o_ref,  # (1, Hq, Dk) block
    k_scr,  # (Hkv, S, Dk) VMEM scratch, S = P * ps
    v_scr,  # (Hkv, S, Dk) VMEM scratch
    *,
    scale: float,
    ps: int,
    n_pages_per_slot: int,
    hkv: int,
    group: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    ok = pm_ref[b, i] >= 0
    at = pl.multiple_of(i * ps, ps)
    # unmapped pages gather as zeros — exactly the dense empty-cache bytes
    k_scr[:, pl.ds(at, ps), :] = jnp.where(ok, k_ref[0], 0)
    v_scr[:, pl.ds(at, ps), :] = jnp.where(ok, v_ref[0], 0)

    @pl.when(i == n_pages_per_slot - 1)
    def _finalize():
        q = q_ref[0]  # (Hq, Dk)
        dk = q.shape[-1]
        qf = q.reshape(hkv, group, dk).astype(jnp.float32) * scale
        kf = k_scr[...].astype(jnp.float32)  # (Hkv, S, Dk)
        # same contraction as the dense einsum "bhgd,bhsd->bhgs" per b
        s = jax.lax.dot_general(
            qf, kf, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # (Hkv, G, S)
        s = jnp.where(valid_ref[0][None] > 0, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        vf = v_scr[...].astype(jnp.float32)
        o = jax.lax.dot_general(
            p, vf, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # (Hkv, G, Dk)
        o_ref[0] = o.reshape(hkv * group, dk).astype(o_ref.dtype)


def _valid_lanes(pages: jax.Array, pos: jax.Array, ps: int) -> jax.Array:
    """(B, 1, S) int32: lane s is on a mapped page and at or before the
    query position.  Built in XLA, so the kernel reads it as one lane-dense
    block instead of storing per-page flags at unaligned lane offsets."""
    mapped = jnp.repeat(pages >= 0, ps, axis=1)  # (B, S)
    lane = jnp.arange(mapped.shape[1], dtype=jnp.int32)
    valid = mapped & (lane[None] <= pos.astype(jnp.int32)[:, None])
    return valid.astype(jnp.int32)[:, None]


def _stacked(pools: tuple, layer, ndim: int) -> tuple:
    """The pools with a leading layer axis, and the layer index as the
    (1,) int32 scalar-prefetch operand.  A single layer's pool (``layer``
    None) gains a size-one axis, a free reshape."""
    if layer is None:
        pools, layer = tuple(p[None] for p in pools), 0
    assert all(p.ndim == ndim for p in pools), [p.shape for p in pools]
    return pools, jnp.asarray(layer, jnp.int32).reshape(1)


def _vmem_limit(scratch_bytes: int) -> int:
    """Scoped-VMEM budget: the gathered scratch plus the f32 copies the
    finalize makes of it, with headroom, within the v5e's 128 MiB."""
    return min(3 * scratch_bytes + (16 << 20), 120 << 20)


def paged_gqa_attention(
    q: jax.Array,  # (B, Hq, Dk)
    k_pool: jax.Array,  # (N, Hkv, ps, Dk) shared page pool, or (L, N, ...)
    v_pool: jax.Array,  # (N, Hkv, ps, Dk), or (L, N, Hkv, ps, Dk)
    pages: jax.Array,  # (B, P) int32 per-slot page table, -1 = unmapped
    pos: jax.Array,  # (B,) int32 current query position
    *,
    layer: jax.Array | int | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-query GQA attention reading K/V through a page table.

    Bit-identical to ``layers.decode_attention`` over the equivalent
    dense cache (pages gathered in logical order, unmapped pages = zero
    lanes masked invalid).  With ``layer`` the pools are the stack of
    every layer's pool and the kernel reads layer ``layer``'s pages.
    Returns (B, Hq, Dk) in q.dtype."""
    (k_pool, v_pool), layer = _stacked((k_pool, v_pool), layer, 5)
    B, Hq, Dk = q.shape
    _, _, Hkv, ps, _ = k_pool.shape
    P = pages.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = (Dk**-0.5) if scale is None else scale
    seq = P * ps

    kernel = functools.partial(
        _gqa_kernel, scale=scale, ps=ps, n_pages_per_slot=P, hkv=Hkv, group=G
    )
    page = pl.BlockSpec(
        (pl.squeezed, 1, Hkv, ps, Dk),
        lambda b, i, pm, li: (li[0], jnp.maximum(pm[b, i], 0), 0, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, Hq, Dk), lambda b, i, pm, li: (b, 0, 0)),
            page,
            page,
            pl.BlockSpec((1, 1, seq), lambda b, i, pm, li: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, Dk), lambda b, i, pm, li: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, seq, Dk), k_pool.dtype),
            pltpu.VMEM((Hkv, seq, Dk), v_pool.dtype),
        ],
    )
    scratch = 2 * Hkv * seq * Dk * k_pool.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dk), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(scratch),
        ),
        interpret=_resolve_interpret(interpret),
        name="paged_gqa_attention",
    )(
        pages.astype(jnp.int32),
        layer,
        q,
        k_pool,
        v_pool,
        _valid_lanes(pages, pos, ps),
    )


# --------------------------------------------------------------------------
# MLA paged decode (absorbed latent attention)
# --------------------------------------------------------------------------
def _mla_kernel(
    pm_ref,  # (B, P) int32
    layer_ref,  # (1,) int32: layer of the stacked pool
    ql_ref,  # (1, h, lora) block: latent-absorbed query
    qr_ref,  # (1, h, rope) block: rope query
    ckv_ref,  # (1, ps, lora) block: selected latent page
    kr_ref,  # (1, ps, rope) block: selected rope page
    valid_ref,  # (1, 1, S) int32 block: lane is mapped and <= pos
    o_ref,  # (1, h, lora) f32 block: latent context
    ckv_scr,  # (S, lora) VMEM scratch
    kr_scr,  # (S, rope) VMEM scratch
    *,
    scale: float,
    ps: int,
    n_pages_per_slot: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    ok = pm_ref[b, i] >= 0
    at = pl.multiple_of(i * ps, ps)
    ckv_scr[pl.ds(at, ps), :] = jnp.where(ok, ckv_ref[0], 0)
    kr_scr[pl.ds(at, ps), :] = jnp.where(ok, kr_ref[0], 0)

    @pl.when(i == n_pages_per_slot - 1)
    def _finalize():
        qlf = ql_ref[0].astype(jnp.float32)  # (h, lora)
        qrf = qr_ref[0].astype(jnp.float32)  # (h, rope)
        ckv = ckv_scr[...].astype(jnp.float32)  # (S, lora)
        kr = kr_scr[...].astype(jnp.float32)  # (S, rope)
        # dense: s = (s_lat + s_rope) * scale — scale applied AFTER sum
        s_lat = jax.lax.dot_general(
            qlf, ckv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (h, S)
        s_rope = jax.lax.dot_general(
            qrf, kr, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = (s_lat + s_rope) * scale
        s = jnp.where(valid_ref[0] > 0, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_ref[0] = jax.lax.dot_general(
            p, ckv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (h, lora) f32 — caller casts at the w_uv einsum like dense


def paged_mla_attention(
    q_lat: jax.Array,  # (B, h, lora) latent-absorbed query
    q_rope: jax.Array,  # (B, h, rope)
    ckv_pool: jax.Array,  # (N, ps, lora), or (L, N, ps, lora)
    krope_pool: jax.Array,  # (N, ps, rope), or (L, N, ps, rope)
    pages: jax.Array,  # (B, P) int32
    pos: jax.Array,  # (B,) int32
    *,
    scale: float,
    layer: jax.Array | int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Absorbed-MLA single-query attention through a page table.  Returns
    the f32 latent context (B, h, lora) — bit-identical to the dense
    absorbed decode's ``einsum("bhst,btl->bshl", softmax(s), ckv)``.
    ``layer`` as in ``paged_gqa_attention``."""
    (ckv_pool, krope_pool), layer = _stacked((ckv_pool, krope_pool), layer, 4)
    B, h, lora = q_lat.shape
    _, _, ps, _ = ckv_pool.shape
    P = pages.shape[1]
    rope = q_rope.shape[-1]
    seq = P * ps

    kernel = functools.partial(_mla_kernel, scale=scale, ps=ps, n_pages_per_slot=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, h, lora), lambda b, i, pm, li: (b, 0, 0)),
            pl.BlockSpec((1, h, rope), lambda b, i, pm, li: (b, 0, 0)),
            pl.BlockSpec(
                (pl.squeezed, 1, ps, lora),
                lambda b, i, pm, li: (li[0], jnp.maximum(pm[b, i], 0), 0, 0),
            ),
            pl.BlockSpec(
                (pl.squeezed, 1, ps, rope),
                lambda b, i, pm, li: (li[0], jnp.maximum(pm[b, i], 0), 0, 0),
            ),
            pl.BlockSpec((1, 1, seq), lambda b, i, pm, li: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, lora), lambda b, i, pm, li: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((seq, lora), ckv_pool.dtype),
            pltpu.VMEM((seq, rope), krope_pool.dtype),
        ],
    )
    scratch = seq * (lora + rope) * ckv_pool.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, lora), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(scratch),
        ),
        interpret=_resolve_interpret(interpret),
        name="paged_mla_attention",
    )(
        pages.astype(jnp.int32),
        layer,
        q_lat,
        q_rope,
        ckv_pool,
        krope_pool,
        _valid_lanes(pages, pos, ps),
    )
