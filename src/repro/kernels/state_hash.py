"""Fused state fingerprint Pallas TPU kernel (beyond-paper optimization),
plus the word-stream driver shared by every redundancy kernel.

Under spatial (cross-pod) DMR the paper's full-state bitwise compare moves
O(state) bytes over ICI.  The optimized compare hashes each pod's local
shard into 4 uint32 accumulators and compares 16 bytes instead.  A naive
jnp implementation makes four passes over the state (one per accumulator);
this kernel computes all four in a single HBM pass.

Accumulators (position-weighted, wraparound uint32 arithmetic — must match
``ref.state_hash_ref`` bit-for-bit):

    w_i = i * 2654435761 + 0x9E3779B9           (global position weight)
    h1  = sum v_i * w_i          h2 = sum (v_i ^ w_i) * 2654435761
    h3  = xor v_i ^ (w_i * PHI)  h4 = sum (v_i + w_i) ^ (v_i >> 7)

Word-stream layout (``stream_call``): a flat stream of ``g * block`` words
is viewed as ``(g * rows, cols)`` with ``cols = 128`` lanes whenever the
block is lane-aligned, so a grid block ``(rows, cols)`` obeys the TPU's
(8, 128) tiling rule for the real block sizes (multiples of 1024 words).
Each grid step walks its block in slabs of 8 rows and folds every
per-word term into a resident ``(slab, cols)`` accumulator with elementwise
adds / xors only — Mosaic has no unsigned reductions, and none is needed:
the wrapper folds the accumulators to scalars in XLA.  Sums and xors are
exact in any order, so results are independent of the block size.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_PHI = 0x9E3779B9
_MIX = 2654435761
LANES = 128
SUBLANES = 8

#: accumulator fold ops: sums wrap (uint32) or count (int32); xor folds bits
SUM, XOR = "sum", "xor"
#: the four fingerprint accumulators' fold ops, in (h1, h2, h3, h4) order
FINGERPRINT_OPS = (SUM, SUM, XOR, SUM)


def fingerprint_terms(v: jax.Array, i: jax.Array):
    """Per-word (h1, h2, h3, h4) terms of words ``v`` at global indices ``i``.

    Single source of truth for the fingerprint math — shared by
    ``state_hash`` and the fused DMR/TMR kernels in ``fused_step.py``, whose
    cross-backend parity depends on the accumulators staying bit-for-bit
    identical."""
    phi = jnp.uint32(_PHI)
    mix = jnp.uint32(_MIX)
    w = i * mix + phi
    return v * w, (v ^ w) * mix, v ^ (w * phi), (v + w) ^ (v >> 7)


def _fold(op: str, acc: jax.Array, term: jax.Array) -> jax.Array:
    return acc ^ term if op == XOR else acc + term


def _finish(op: str, acc: jax.Array) -> jax.Array:
    """(slab, cols) accumulator -> scalar total (in XLA, outside the kernel)."""
    if op == XOR:
        return jax.lax.reduce(acc, jnp.zeros((), acc.dtype),
                              jax.lax.bitwise_xor, (0, 1))
    return jnp.sum(acc, dtype=acc.dtype)


def _stream_kernel(*refs, slab_fn, n_in, with_out, ops, rows, slab, cols):
    ins = refs[:n_in]
    outs = refs[n_in:]
    out_ref = outs[0] if with_out else None
    acc_refs = outs[1:] if with_out else outs
    block = rows * cols

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for r in acc_refs:
            r[...] = jnp.zeros(r.shape, r.dtype)

    local = (jax.lax.broadcasted_iota(jnp.uint32, (slab, cols), 0)
             * jnp.uint32(cols)
             + jax.lax.broadcasted_iota(jnp.uint32, (slab, cols), 1))
    base = pl.program_id(0).astype(jnp.uint32) * jnp.uint32(block)

    def body(j, carry):
        r0 = pl.multiple_of(j * slab, slab)
        words = [ref[pl.ds(r0, slab), :] for ref in ins]
        idx = base + r0.astype(jnp.uint32) * jnp.uint32(cols) + local
        out, terms = slab_fn(words, idx)
        if with_out:
            out_ref[pl.ds(r0, slab), :] = out
        return tuple(_fold(op, c, t) for op, c, t in zip(ops, carry, terms))

    init = tuple(jnp.zeros((slab, cols), r.dtype) for r in acc_refs)
    carry = jax.lax.fori_loop(0, rows // slab, body, init)
    for op, r, c in zip(ops, acc_refs, carry):
        r[...] = _fold(op, r[...], c)


def stream_call(
    slab_fn: Callable,
    streams: Sequence[jax.Array],
    *,
    block: int,
    acc: Sequence[tuple[str, jnp.dtype]],
    with_out: bool = False,
    interpret: bool = False,
):
    """Run ``slab_fn`` over equal-length flat uint32 ``streams`` in one pass.

    ``slab_fn(words, idx) -> (out_words | None, terms)`` sees one slab of
    each stream plus the slab's global word indices, and returns an output
    slab (when ``with_out``) and one term per accumulator in ``acc``
    (``(fold op, dtype)`` pairs).  Returns ``(out stream | None, totals)``
    with one scalar total per accumulator."""
    n = streams[0].shape[0]
    block = min(block, n)
    assert n % block == 0, (n, block)
    g = n // block
    cols = LANES if block % LANES == 0 else block
    rows = block // cols
    slab = SUBLANES if rows % SUBLANES == 0 else rows
    blk = pl.BlockSpec((rows, cols), lambda i: (i, 0))
    res = pl.BlockSpec((slab, cols), lambda i: (0, 0))  # resident accumulator
    out_specs = [blk] * with_out + [res] * len(acc)
    out_shape = ([jax.ShapeDtypeStruct((g * rows, cols), jnp.uint32)]
                 * with_out
                 + [jax.ShapeDtypeStruct((slab, cols), dt) for _, dt in acc])
    kernel = functools.partial(
        _stream_kernel, slab_fn=slab_fn, n_in=len(streams),
        with_out=with_out, ops=tuple(op for op, _ in acc),
        rows=rows, slab=slab, cols=cols,
    )
    outs = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[blk] * len(streams),
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*(s.reshape(g * rows, cols) for s in streams))
    out = outs[0].reshape(n) if with_out else None
    accs = outs[1:] if with_out else outs
    return out, [_finish(op, a) for (op, _), a in zip(acc, accs)]


def _hash_slab(words, idx):
    return None, fingerprint_terms(words[0], idx)


def state_hash(
    v: jax.Array, *, block: int = 128 * 1024, interpret: bool = False
) -> jax.Array:
    """4 x uint32 fingerprint of a flat uint32 array, single fused pass."""
    assert v.ndim == 1 and v.dtype == jnp.uint32
    _, h = stream_call(
        _hash_slab, [v], block=block,
        acc=[(op, jnp.uint32) for op in FINGERPRINT_OPS],
        interpret=interpret,
    )
    return jnp.stack(h)
