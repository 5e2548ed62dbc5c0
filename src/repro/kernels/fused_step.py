"""Fused per-step redundancy kernels for the ``lockstep_pallas`` back-end.

The XLA lockstep back-end lowers a replicated cell's compare/vote to a
chain of separate elementwise + reduce ops (and the generic ``ops.py``
wrappers dispatch ``tmr_vote`` and ``state_hash`` as *separate* kernels, so
the replica states cross HBM twice).  These kernels collapse the whole
per-step dependability epilogue into ONE ``pallas_call`` per cell:

  * ``dmr_compare`` — word-level bitwise compare of the two replica
    streams AND both replicas' 4 x uint32 fingerprints, in a single pass
    (2 reads per word, no extra hash dispatches).  The fingerprint is what
    a spatial-DMR deployment ships cross-pod (16 bytes instead of the
    state), and it is bit-identical to ``state_hash`` over the same
    padded stream.
  * ``tmr_step``    — bitwise 2-of-3 majority vote, per-replica mismatch
    word counts (the permanent-fault localization signal), and the voted
    stream's fingerprint, in a single pass (3 reads + 1 write per word).

Both kernels accumulate lane-wise partials that the wrappers fold exactly
(wraparound uint32 sums / xors and integer sums; ``state_hash.stream_call``),
so results are independent of the block size and bit-identical to the
separate ``tmr_vote``/``state_hash`` kernels they fuse.  On CPU CI they run
with ``interpret=True``; on TPU they are the fast path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the fingerprint math and the word-stream driver live in ONE place
# (state_hash.py) so the bit-for-bit equality the parity gates rely on
# cannot drift
from .state_hash import (
    FINGERPRINT_OPS,
    LANES,
    SUBLANES,
    SUM,
    fingerprint_terms,
    stream_call,
)

#: VMEM-friendly default: 64Ki words = 256 KiB per replica stream.
DEFAULT_BLOCK = 64 * 1024
#: one (8, 128) uint32 tile: the granule a multi-block stream is cut into
TILE = SUBLANES * LANES


def pick_block(total_words: int, cap: int = DEFAULT_BLOCK) -> int:
    """Words per grid step for a state of ``total_words`` u32 words: one
    tile-aligned block for small states, the VMEM cap for large ones (the
    flat stream is zero-padded to a multiple of the block)."""
    if total_words >= cap:
        return cap
    return max(TILE, -(-total_words // TILE) * TILE)


_FP_ACC = [(op, jnp.uint32) for op in FINGERPRINT_OPS]


# --------------------------------------------------------------------------
# DMR: compare + both fingerprints, one pass
# --------------------------------------------------------------------------
def _dmr_slab(words, idx):
    a, b = words
    return None, [(a != b).astype(jnp.int32),
                  *fingerprint_terms(a, idx), *fingerprint_terms(b, idx)]


def dmr_compare(
    a: jax.Array, b: jax.Array,
    *, block: int = DEFAULT_BLOCK, interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(mismatching word count: int32, fingerprints: (2, 4) uint32) over two
    flat uint32 replica streams of equal length, in one fused pass."""
    assert a.ndim == 1 and a.shape == b.shape
    assert a.dtype == jnp.uint32 and b.dtype == jnp.uint32
    _, t = stream_call(_dmr_slab, [a, b], block=block,
                       acc=[(SUM, jnp.int32)] + _FP_ACC * 2,
                       interpret=interpret)
    return t[0], jnp.stack(t[1:]).reshape(2, 4)


# --------------------------------------------------------------------------
# TMR: vote + counts + voted fingerprint, one pass
# --------------------------------------------------------------------------
def _tmr_slab(words, idx):
    a, b, c = words
    v = (a & b) | (a & c) | (b & c)
    return v, [*((r != v).astype(jnp.int32) for r in (a, b, c)),
               *fingerprint_terms(v, idx)]


def tmr_step(
    a: jax.Array, b: jax.Array, c: jax.Array,
    *, block: int = DEFAULT_BLOCK, interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(voted stream, per-replica mismatch word counts[3], voted
    fingerprint[4]) over three flat uint32 replica streams, one pass."""
    assert a.ndim == 1 and a.shape == b.shape == c.shape
    assert a.dtype == jnp.uint32
    voted, t = stream_call(_tmr_slab, [a, b, c], block=block,
                           acc=[(SUM, jnp.int32)] * 3 + _FP_ACC,
                           with_out=True, interpret=interpret)
    return voted, jnp.stack(t[:3]), jnp.stack(t[3:])
