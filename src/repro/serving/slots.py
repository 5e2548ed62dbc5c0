"""Slot bookkeeping + pure-array slot surgery for the continuous batcher.

The resident decoder cell has a fixed batch dimension of ``n_slots``; the
engine multiplexes many requests onto it by scattering prompt caches into
free slots between stream ticks and evicting finished ones.  This module
has the two halves of that:

  * ``SlotManager`` — host-side ownership (which request holds which
    slots; per-request *replica* slots for DMR/TMR policies).
  * pure jittable array helpers — ``join_slot`` / ``read_slot`` /
    ``copy_slot`` / ``slot_fingerprints`` / ``mask_slots``, all driven by
    a per-leaf *slot-axis* pytree (``infer_slot_axes``), because the
    decoder state's batch axis is not in the same position on every leaf
    (KV caches stack a layer axis in front; positions are rank-1).

Everything here is model-agnostic: the LM adapter and the toy test
programs use the same helpers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.jit import forwarding_jit, named
from repro.core.redundancy import bit_mismatch_elems, fingerprint

Pytree = Any


# --------------------------------------------------------------------------
# slot-axis inference
# --------------------------------------------------------------------------
def infer_slot_axes(
    make_state: Callable[[int], Pytree], w1: int = 2, w2: int = 3
) -> Pytree:
    """Per-leaf slot (batch) axis of a slotted cell state, found
    structurally: evaluate the state's shape at two widths and locate the
    single axis that scales with the width.  Shape-only (``eval_shape``),
    so no arrays are allocated.  Raises if any leaf has zero or several
    width-dependent axes — every leaf of a slotted state must be
    per-slot, otherwise join/leave could not be expressed."""
    s1 = jax.eval_shape(lambda: make_state(w1))
    s2 = jax.eval_shape(lambda: make_state(w2))

    def ax(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        if len(diffs) != 1:
            raise ValueError(
                f"leaf {a.shape}/{b.shape} has {len(diffs)} width-dependent "
                "axes; a slotted cell state needs exactly one slot axis "
                "per leaf"
            )
        return diffs[0]

    return jax.tree.map(ax, s1, s2)


def _bcast(mask: jax.Array, ndim: int, ax: int) -> jax.Array:
    """Reshape a (B,) mask to broadcast against a rank-``ndim`` leaf whose
    slot axis is ``ax``."""
    return mask.reshape((1,) * ax + (-1,) + (1,) * (ndim - ax - 1))


# --------------------------------------------------------------------------
# pure slot surgery (jit these with ``axes`` closed over)
# --------------------------------------------------------------------------
def mask_slots(active: jax.Array, new: Pytree, old: Pytree, axes: Pytree) -> Pytree:
    """Per-slot select: active slots take ``new``, inactive keep ``old``
    bit-for-bit.  The writeback gate of the slot-masked decoder."""
    return jax.tree.map(
        lambda n, o, ax: jnp.where(_bcast(active, n.ndim, ax), n, o), new, old, axes
    )


def join_slot(
    state: Pytree, slot_state: Pytree, slot: jax.Array, axes: Pytree
) -> Pytree:
    """Scatter a width-1 slot state into batch slot ``slot`` (traced index
    is fine — one compile covers every slot)."""

    def put(dst, src, ax):
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=ax
        )

    return jax.tree.map(put, state, slot_state, axes)


def read_slot(state: Pytree, slot: jax.Array, axes: Pytree) -> Pytree:
    """The width-1 view of batch slot ``slot`` (inverse of ``join_slot``)."""
    return jax.tree.map(
        lambda x, ax: jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=ax), state, axes
    )


def copy_slot(state: Pytree, src: jax.Array, dst: jax.Array, axes: Pytree) -> Pytree:
    """Copy slot ``src`` over slot ``dst`` — TMR repair: re-synchronize a
    minority replica slot from a majority one (exact, bitwise)."""
    return join_slot(state, read_slot(state, src, axes), dst, axes)


def slot_fingerprints(
    state: Pytree, axes: Pytree, *, n: Optional[int] = None,
    read: Callable[[Pytree, jax.Array, Pytree], Pytree] = read_slot,
) -> jax.Array:
    """(B, 4) uint32: the 128-bit state fingerprint of every slot's view
    of the state.  Replica slots of one request are bitwise-equal by
    construction, so equal fingerprints <=> healthy; the engine compares
    these between ticks to detect (DMR) and localize (TMR) strikes at
    request granularity, at O(B * 16 bytes) host traffic.

    Slots are hashed one at a time, each from its width-1 view
    ``read(state, slot, axes)``, so the working set is one slot's view,
    not a widened copy of the whole batch.  ``n`` is the slot count
    (default: read off the first leaf)."""
    if n is None:
        leaf, ax = jax.tree.leaves(state)[0], jax.tree.leaves(axes)[0]
        n = leaf.shape[ax]
    return jax.lax.map(lambda s: fingerprint(read(state, s, axes)),
                       jnp.arange(n, dtype=jnp.int32))


# --------------------------------------------------------------------------
# the surgery protocol: how the engine cuts state in and out of slots
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SlotSurgery:
    """The engine's slot-state operations, bundled so a state layout can
    swap in its own implementations (``serving/paging.py`` routes these
    through a page table; ``default_surgery`` is the dense whole-leaf
    layout the helpers above implement directly).

    All slot arguments are host ints; ``damage``/``damage_vs`` return
    host floats (mismatched elements, temporal-lockstep units).

      join(states, slot_state, slot, req=None)  scatter a width-1 state in
      scrub(states, slot)                       evict: slot back to empty
      copy(states, src, dst)                    bitwise slot copy (repair)
      adopt(states, other, slot)                take ``other``'s slot view
      fingerprints(cell_state) -> (B, 4) u32    per-slot 128-bit fps
      damage(states, a, b) -> float             mismatch between two slots
      damage_vs(states, other, slot) -> float   mismatch vs another state
    """

    join: Callable[..., dict]
    scrub: Callable[[dict, int], dict]
    copy: Callable[[dict, int, int], dict]
    adopt: Callable[[dict, dict, int], dict]
    fingerprints: Callable[[Pytree], jax.Array]
    damage: Callable[[dict, int, int], float]
    damage_vs: Callable[[dict, dict, int], float]


def default_surgery(
    cell: str, axes: Pytree, make_empty: Callable[[], Pytree]
) -> SlotSurgery:
    """Dense-layout surgery: every leaf is whole-per-slot, so join/copy/
    adopt are the pure helpers above, jitted once with ``axes`` closed
    over (traced slot indices — one compile covers every slot)."""
    # forwarding: the cells a slot op leaves alone (the weights) come back
    # as the same buffers, not copies
    _join = forwarding_jit(
        lambda st, ss, slot: {**st, cell: join_slot(st[cell], ss, slot, axes)},
        name="slot_join",
    )
    _copy = forwarding_jit(
        lambda st, src, dst: {**st, cell: copy_slot(st[cell], src, dst, axes)},
        name="slot_copy",
    )

    def _adopt_impl(st, other, slot):
        taken = read_slot(other[cell], slot, axes)
        return {**st, cell: join_slot(st[cell], taken, slot, axes)}

    _adopt = forwarding_jit(_adopt_impl, name="slot_adopt")
    _fps = jax.jit(named(lambda dec: slot_fingerprints(dec, axes), "slot_fingerprints"))

    # real damage accounting: mismatched ELEMENTS between two replica
    # slots (same semantics as temporal lockstep's bitwise compare), not
    # fingerprint words
    def _damage_impl(st, a, b):
        return bit_mismatch_elems(
            read_slot(st[cell], a, axes), read_slot(st[cell], b, axes)
        )

    def _damage_vs_impl(st, other, slot):
        return bit_mismatch_elems(
            read_slot(st[cell], slot, axes), read_slot(other[cell], slot, axes)
        )

    _damage = jax.jit(named(_damage_impl, "slot_damage"))
    _damage_vs = jax.jit(named(_damage_vs_impl, "slot_damage_vs"))

    def _damage_host(st, a, b):
        return float(jax.device_get(_damage(st, jnp.int32(a), jnp.int32(b))))

    def _damage_vs_host(st, other, slot):
        return float(jax.device_get(_damage_vs(st, other, jnp.int32(slot))))

    empty = make_empty()
    return SlotSurgery(
        join=lambda st, ss, slot, req=None: _join(st, ss, jnp.int32(slot)),
        scrub=lambda st, slot: _join(st, empty, jnp.int32(slot)),
        copy=lambda st, src, dst: _copy(st, jnp.int32(src), jnp.int32(dst)),
        adopt=lambda st, other, slot: _adopt(st, other, jnp.int32(slot)),
        fingerprints=_fps,
        damage=_damage_host,
        damage_vs=_damage_vs_host,
    )


# --------------------------------------------------------------------------
# host-side ownership
# --------------------------------------------------------------------------
@dataclasses.dataclass
class SlotManager:
    """Ownership of the resident batch's slots.

    A request occupies ``policy.level`` slots (1 = none, 2 = DMR, 3 =
    TMR): replication maps onto *extra batch rows* of the decoder — the
    same observation that makes cell replication "mechanically identical
    to data parallelism" (core/redundancy.py), applied per request, so
    unprotected requests pay nothing for their neighbors' protection.

    Replica slots are allocated CONTIGUOUS (``alloc(..., contiguous=
    True)``) so a replicated request occupies one aligned run of batch
    rows.  Churn fragments the free list; rather than rejecting a
    replicated admission that fits by count but not by adjacency,
    ``defrag_plan``/``relocate`` let the engine compact: a running
    request's slot is moved with the existing ``copy_slot`` + scrub
    machinery (bitwise-transparent to its owner — the slot-position
    invariance tested in tests/test_serving.py), so fragmentation never
    blocks an admission the batch has capacity for.

    SPATIAL placement (``pods > 1``): the global slot space is the
    concatenation of ``pods`` per-pod row blocks — pod ``p`` owns global
    slots ``[p*spp, (p+1)*spp)`` where ``spp = n_slots // pods`` (the
    mesh shards the decoder's slot axis over the pod axis in exactly
    this blocked layout).  ``alloc(..., spatial=True)`` reserves the
    SAME column on pods ``0..n-1`` — one replica slot per pod, so a
    hardware strike on one pod hits exactly one replica — and there is
    no adjacency requirement at all: spatial admissions never
    defragment, and spatial tenants are pinned (``defrag_plan`` never
    relocates them, which would tear a replica off its pod).  Temporal
    runs and defrag windows are confined to a single pod's block, and
    unreplicated requests fill from the HIGHEST pod down so low-pod
    columns stay clear for spatial groups (level-1 traffic uses pods as
    plain data parallelism).
    """

    n_slots: int
    pods: int = 1

    def __post_init__(self):
        if self.pods < 1 or self.n_slots % self.pods:
            raise ValueError(
                f"n_slots={self.n_slots} must be a positive multiple of "
                f"pods={self.pods} (the mesh splits the slot axis evenly)"
            )
        self.per_pod = self.n_slots // self.pods
        self._free: list[int] = list(range(self.n_slots))
        self._slots_of: dict[str, list[int]] = {}
        self._owner: dict[int, str] = {}
        self._pinned: set[int] = set()  # spatial tenants: never relocated

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def active(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def replicated(self) -> bool:
        """Some resident request holds replica slots (more than one)."""
        return any(len(got) > 1 for got in self._slots_of.values())

    def slots_of(self, rid: str) -> list[int]:
        return list(self._slots_of.get(rid, ()))

    def owner(self, slot: int) -> Optional[str]:
        return self._owner.get(slot)

    def alloc(
        self,
        rid: str,
        n: int,
        contiguous: bool = False,
        spatial: bool = False,
    ) -> Optional[list[int]]:
        """n free slots for request ``rid``; None if the batch can't fit
        it right now.  ``contiguous=True`` (replicated requests) requires
        one adjacent run of n slots — run ``defrag_plan``/``relocate``
        first if ``find_run`` comes up empty.  ``spatial=True`` instead
        reserves one slot PER POD at a shared column (``find_column``) —
        no adjacency, no defrag; the returned list is ordered by pod, so
        replica index i lives on pod i."""
        if rid in self._slots_of:
            raise ValueError(f"request {rid!r} already holds slots")
        if n > len(self._free):
            return None
        if spatial and n > 1:
            if n > self.pods:
                return None
            col = self.find_column(n)
            if col is None:
                return None
            got = [p * self.per_pod + col for p in range(n)]
            for s in got:
                self._free.remove(s)
            self._pinned.update(got)
        elif contiguous and n > 1:
            start = self.find_run(n)
            if start is None:
                return None
            got = list(range(start, start + n))
            for s in got:
                self._free.remove(s)
        elif self.pods > 1:
            # unreplicated / unconstrained: fill from the highest pod
            # down, keeping low-pod columns open for spatial groups
            got = [self._free.pop() for _ in range(n)]
        else:
            got = [self._free.pop(0) for _ in range(n)]
        self._slots_of[rid] = got
        for s in got:
            self._owner[s] = rid
        return list(got)  # caller-owned copy: relocate() mutates ours

    def find_run(self, n: int) -> Optional[int]:
        """Start index of the leftmost run of ``n`` adjacent free slots
        (confined to one pod's block when ``pods > 1`` — a run crossing
        a pod boundary is not adjacent on any device)."""
        free = set(self._free)
        for start in range(self.n_slots - n + 1):
            if start // self.per_pod != (start + n - 1) // self.per_pod:
                continue
            if all(start + i in free for i in range(n)):
                return start
        return None

    def find_column(self, n: int) -> Optional[int]:
        """Lowest column ``c`` whose slot is free on pods ``0..n-1`` —
        the spatial-placement allocation unit (one replica per pod at a
        shared column index)."""
        free = set(self._free)
        for c in range(self.per_pod):
            if all(p * self.per_pod + c in free for p in range(n)):
                return c
        return None

    def defrag_plan(self, n: int) -> Optional[list[tuple[int, int]]]:
        """Relocations ``[(src, dst), ...]`` that open an n-slot adjacent
        free run: pick the window holding the fewest REPLICA slots, then
        the fewest tenants overall (single-slot tenants are the preferred
        eviction victims — moving a replicated tenant's slot would
        scatter the adjacent run it was just given), and evacuate them
        into free slots outside the window.  None if total free capacity
        < n; [] if a run already exists.  Always satisfiable when ``free
        >= n``: a window of n slots has at most ``n - free_inside``
        tenants and there are exactly ``free_total - free_inside >=
        n - free_inside`` free slots outside it.  (When every window
        overlaps a replicated tenant, one is evacuated and loses
        adjacency — correctness is unaffected, the run layout degrades.)

        Windows never cross a pod boundary (a cross-pod run is not
        adjacent on any device) and never overlap a PINNED (spatial)
        tenant — relocating one would tear a replica off its pod — so
        with spatial tenants resident the plan can come back None even
        when free capacity exists; the admission then simply waits.
        """
        if n > len(self._free):
            return None
        free = set(self._free)

        def cost(start):
            occ = [s for s in range(start, start + n) if s not in free]
            repl = sum(1 for s in occ if len(self._slots_of[self._owner[s]]) > 1)
            return (repl, len(occ)), occ

        best_cost, best_start, best_occ = None, None, None
        for start in range(self.n_slots - n + 1):
            if start // self.per_pod != (start + n - 1) // self.per_pod:
                continue
            if any(s in self._pinned for s in range(start, start + n)):
                continue
            c, occ = cost(start)
            if best_cost is None or c < best_cost:
                best_cost, best_start, best_occ = c, start, occ
        if best_start is None:
            return None
        dsts = [
            s
            for s in sorted(free)
            if (s < best_start or s >= best_start + n) and s not in self._pinned
        ]
        return list(zip(best_occ, dsts))

    def relocate(self, src: int, dst: int) -> str:
        """Move the tenant of slot ``src`` to free slot ``dst`` (ownership
        only — the engine performs the matching state copy + scrub).
        Returns the owning request id."""
        rid = self._owner.pop(src)
        self._free.remove(dst)
        self._free.append(src)
        self._free.sort()
        self._owner[dst] = rid
        sl = self._slots_of[rid]
        sl[sl.index(src)] = dst
        return rid

    def release(self, rid: str) -> list[int]:
        got = self._slots_of.pop(rid, [])
        for s in got:
            del self._owner[s]
            self._pinned.discard(s)
            self._free.append(s)
        self._free.sort()  # deterministic reuse order
        return got
