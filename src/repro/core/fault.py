"""Soft-error injection (to test paper §IV's detection/correction claims).

Transitions are pure, so two replica executions are bit-identical unless the
hardware misbehaves.  To *test* the dependability machinery we emulate a
particle strike: flip one bit of one replica's freshly-computed state.  The
fault is described by a ``FaultSpec`` of plain int32 scalars and threaded
through the (jitted) step function, so re-arming a fault never recompiles.
The cells a spec can strike are static when known on the host: a disarmed
step traces no injection at all, so cells it leaves unchanged stay the
very same buffers (``core/jit.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def bitcast_uint(x: jax.Array) -> jax.Array:
    """Reinterpret any array as an unsigned integer array of equal width."""
    nbits = x.dtype.itemsize * 8
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint8)
    return jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{nbits}"))


def bitcast_back(u: jax.Array, dtype) -> jax.Array:
    if jnp.dtype(dtype) == jnp.bool_:
        return u.astype(jnp.bool_)
    return jax.lax.bitcast_convert_type(u, dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FaultSpec:
    """One armed bit-flip.  ``step == -1`` disarms (the common case)."""

    step: jax.Array      # int32: transition step at which to strike
    cell_id: jax.Array   # int32: index of the target cell in program order
    replica: jax.Array   # int32: which replica's output to corrupt
    leaf: jax.Array      # int32: which state leaf (flatten order)
    index: jax.Array     # int32: flat element index within the leaf
    bit: jax.Array       # int32: bit position (mod leaf bit-width)
    #: static: ids of the cells this spec can strike, or None for any cell
    #: (a spec whose ``cell_id`` is only known traced, or a stacked batch)
    cells: Optional[tuple[int, ...]] = dataclasses.field(
        default=None, metadata=dict(static=True))

    def may_strike(self, cell_id: int) -> bool:
        return self.cells is None or cell_id in self.cells

    @staticmethod
    def none() -> "FaultSpec":
        z = jnp.int32(-1)
        return FaultSpec(step=z, cell_id=z, replica=z, leaf=z, index=z, bit=z,
                         cells=())

    @staticmethod
    def at(step, cell_id, replica=0, leaf=0, index=0, bit=0) -> "FaultSpec":
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        known = isinstance(cell_id, (int, np.integer))
        return FaultSpec(
            step=i32(step), cell_id=i32(cell_id), replica=i32(replica),
            leaf=i32(leaf), index=i32(index), bit=i32(bit),
            cells=(int(cell_id),) if known else None,
        )


def inject(
    spec: FaultSpec, *, cell_id: int, step: jax.Array, replicated_state
):
    """Flip ``spec``'s bit in the replica outputs when (step, cell) match.

    ``replicated_state``: pytree whose leaves have a leading replica axis R.

    Fully ELEMENTWISE: the flat element index is decomposed into per-dim
    coordinates (host-side strides; traced scalar div/mod) and the strike is
    an ``xor`` masked by per-dim ``iota == coord`` comparisons.  No reshape,
    no scatter — the op fuses into the transition's output write and, under
    GSPMD, never moves a sharded leaf (an earlier flatten-and-scatter
    version forced a full all-gather of every state leaf per step, which
    dominated the roofline collective term — see EXPERIMENTS.md §Perf).
    """
    if not spec.may_strike(cell_id):
        return replicated_state
    leaves, treedef = jax.tree.flatten(replicated_state)
    hit_cell = (spec.cell_id == jnp.int32(cell_id)) & (spec.step == step)

    new_leaves = []
    for i, leaf in enumerate(leaves):
        u = bitcast_uint(leaf)
        R = u.shape[0]
        nbits = u.dtype.itemsize * 8
        hit = hit_cell & (spec.leaf == jnp.int32(i))
        rep = jnp.clip(spec.replica, 0, R - 1)
        # flat index -> per-dim coordinates (row-major, int32-safe per dim)
        rest = u.shape[1:]
        idx = spec.index
        coords = []
        for d in reversed(rest):
            coords.append(jax.lax.rem(idx, jnp.int32(d)))
            idx = jax.lax.div(idx, jnp.int32(d))
        coords = list(reversed(coords))
        # elementwise hit mask over the whole leaf
        mask = jnp.broadcast_to(hit, u.shape)
        mask &= jax.lax.broadcasted_iota(jnp.int32, u.shape, 0) == rep
        for ax, c in enumerate(coords):
            mask &= (jax.lax.broadcasted_iota(jnp.int32, u.shape, ax + 1)
                     == c)
        bitmask = (
            jnp.uint32(1) << (spec.bit % nbits).astype(jnp.uint32)
        ).astype(u.dtype)
        flipped = jnp.where(mask, u ^ bitmask, u)
        new_leaves.append(bitcast_back(flipped, leaf.dtype))
    return jax.tree.unflatten(treedef, new_leaves)


def random_fault_campaign(
    rng: np.random.Generator, *, n: int, steps: int, cell_id: int,
    replicas: int, leaf_sizes: list[int], bits: int = 32,
) -> list[FaultSpec]:
    """Sample a campaign of n single-bit faults (host-side, for tests/benches)."""
    out = []
    for _ in range(n):
        leaf = int(rng.integers(len(leaf_sizes)))
        out.append(
            FaultSpec.at(
                step=int(rng.integers(steps)),
                cell_id=cell_id,
                replica=int(rng.integers(replicas)),
                leaf=leaf,
                index=int(rng.integers(max(1, leaf_sizes[leaf]))),
                bit=int(rng.integers(bits)),
            )
        )
    return out
