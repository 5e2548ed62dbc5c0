"""TMR bitwise majority vote + per-replica mismatch counts (paper §IV).

The dependability hot path: after a triple-replicated transition the runtime
must vote the three states word-by-word and count, per replica, how many
words disagreed with the vote (the permanent-fault localization signal).
This is pure memory bandwidth — a naive composition reads each replica
twice (once to vote, once to compare).  The kernel fuses vote + three
compares + count into a single pass: 3 reads + 1 write per word.

Operates on uint32 words; ``ops.py`` flattens/bitcasts arbitrary state
pytrees.  Counts accumulate lane-wise across the grid and the wrapper
folds them (deterministic integer sums; see ``state_hash.stream_call``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .state_hash import SUM, stream_call


def _vote_slab(words, _idx):
    a, b, c = words
    v = (a & b) | (a & c) | (b & c)
    return v, [(r != v).astype(jnp.int32) for r in (a, b, c)]


def tmr_vote(
    a: jax.Array, b: jax.Array, c: jax.Array,
    *, block: int = 64 * 1024, interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(voted, counts[3]) over flat uint32 arrays of equal length.

    block: words per grid step; 64Ki words = 256 KiB per operand, so the
    working set (3 in + 1 out) is 1 MiB — comfortably inside VMEM while long
    enough to amortize the HBM->VMEM pipeline.
    """
    assert a.ndim == 1 and a.shape == b.shape == c.shape
    assert a.dtype == jnp.uint32
    voted, counts = stream_call(
        _vote_slab, [a, b, c], block=block, acc=[(SUM, jnp.int32)] * 3,
        with_out=True, interpret=interpret,
    )
    return voted, jnp.stack(counts)
