"""Paged KV-cache subsystem for the continuous batcher.

The dense serving cache gives every slot a contiguous ``max_len`` KV
allocation, so memory — not compute — caps the resident batch, and
replicated admissions need the contiguous-run/defrag machinery of
``slots.py``.  Here the cache is instead ONE shared pool of fixed-size
pages per layer; each slot owns a *page table* ((P,) int32 pool rows, -1
= unmapped) and its KV bytes live wherever the table points:

  * ``PageTable`` — the host-side manager: free list, per-slot page
    rows, admission *reservations* (a slot reserves its worst-case page
    count up front, so demand growth mid-decode can never find the pool
    empty), and alloc/free/evict as pure page-table ops.  Defragmentation
    disappears: pages need no adjacency, so a paged admission that fits
    by count always fits.
  * pure transforms between the dense slot layout and the pooled one
    (``dense_to_pool`` install scatter, ``pool_slot_view`` gather), used
    by the paged ``SlotSurgery``: fingerprints/damage/repair operate on
    the GATHERED dense-layout view, so per-request DMR/TMR works
    unchanged even though replica slots share one pool — replicas hold
    different pool rows but bitwise-identical page *contents*.
  * ``paged_surgery`` / ``make_pre_tick`` — the engine-facing half:
    join installs a dense prefill into freshly-mapped pages, scrub
    releases them, the pre-tick hook demand-maps pages ahead of the
    positions the next transition will write (counted as
    ``page_faults``), zeroing newly-mapped rows so page reuse between
    requests is invisible (clean-on-map: a mapped page's bytes are a
    pure function of the owning request's trajectory).

Layout conventions (the LM decoder state of ``models/lm_cells.py``):
pool leaves are (L, N, ..., ps, d) — layer axis 0, page axis 1, page
lane at ndim-2; the matching dense stacked leaves are (L, B, ..., S, d)
with the slot axis at 1 and S = P * ps.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jit import forwarding_jit, named
from repro.core.redundancy import bit_mismatch_elems

from .slots import SlotSurgery, _bcast, read_slot, slot_fingerprints

Pytree = Any

#: slot-axis sentinel for pool leaves: no slot axis — the leaf is shared
#: by every slot through the page table
POOL = "pool"


# --------------------------------------------------------------------------
# slot-axis inference with pool leaves
# --------------------------------------------------------------------------
def infer_paged_axes(
    make_state: Callable[[int], Pytree], w1: int = 2, w2: int = 3
) -> Pytree:
    """Like ``slots.infer_slot_axes`` but pool leaves (zero
    width-dependent axes) map to the ``POOL`` sentinel instead of
    raising."""
    s1 = jax.eval_shape(lambda: make_state(w1))
    s2 = jax.eval_shape(lambda: make_state(w2))

    def ax(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        if not diffs:
            return POOL
        if len(diffs) != 1:
            raise ValueError(
                f"leaf {a.shape}/{b.shape} has {len(diffs)} width-dependent "
                "axes; a paged slot state needs at most one slot axis per "
                "leaf"
            )
        return diffs[0]

    return jax.tree.map(ax, s1, s2)


def mask_slots_paged(
    active: jax.Array, new: Pytree, old: Pytree, axes: Pytree
) -> Pytree:
    """``slots.mask_slots`` for a paged state: pool leaves pass through —
    their writes are already per-slot gated at the scatter (inactive and
    unmapped rows are dropped), and a whole-pool where() would let one
    slot's mask clobber another's pages."""

    def sel(n, o, ax):
        if ax == POOL:
            return n
        return jnp.where(_bcast(active, n.ndim, ax), n, o)

    return jax.tree.map(sel, new, old, axes)


# --------------------------------------------------------------------------
# the host-side page-table manager
# --------------------------------------------------------------------------
class PageTable:
    """Fixed-size KV pages in one shared pool; per-slot page rows.

    Reservation discipline: ``assign(slot, reserve)`` at admission claims
    the slot's worst-case page count against ``available`` (free pages
    minus everyone's outstanding reservations); every page the slot later
    maps (``grow_to``) is drawn from its own reservation.  Admission that
    passes ``can_admit`` therefore guarantees the request can reach its
    full token budget without ever exhausting the pool mid-decode — the
    paged analogue of the dense cache's capacity-by-construction.
    """

    def __init__(self, n_pages: int, page_size: int, pages_per_slot: int):
        if n_pages < 1 or page_size < 1:
            raise ValueError((n_pages, page_size))
        self.n_pages = n_pages
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self._free: list[int] = list(range(n_pages))
        self._rows: dict[int, list[int]] = {}
        self._reserved: dict[int, int] = {}
        #: pages demand-mapped by the pre-tick hook (decode/walk growth,
        #: as opposed to the admission install)
        self.page_faults = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def available(self) -> int:
        """Free pages not spoken for by outstanding reservations."""
        return len(self._free) - sum(self._reserved.values())

    def can_admit(self, n: int) -> bool:
        return n <= self.available

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 0) // self.page_size)

    def assign(self, slot: int, reserve: int) -> None:
        """Open a slot's (empty) page row and reserve its worst-case page
        count.  ``can_admit(reserve)`` must have been checked."""
        if slot in self._rows:
            raise ValueError(f"slot {slot} already assigned")
        if reserve > self.available:
            raise RuntimeError(
                f"reservation of {reserve} pages exceeds available "
                f"{self.available} (admission must check can_admit)"
            )
        self._rows[slot] = []
        self._reserved[slot] = reserve

    def grow_to(self, slot: int, n_tokens: int, demand: bool = False) -> list[int]:
        """Map pages until the slot covers positions [0, n_tokens); each
        mapped page consumes one unit of the slot's reservation.  Returns
        the newly mapped pool rows (callers zero them: clean-on-map).
        ``demand=True`` counts the growth as page faults."""
        rows = self._rows[slot]
        need = self.pages_for(n_tokens)
        if need > self.pages_per_slot:
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens needs {need} pages > "
                f"pages_per_slot {self.pages_per_slot}"
            )
        new = []
        while len(rows) < need:
            if not self._free:
                raise RuntimeError(
                    "page pool exhausted despite reservations — "
                    "reservation accounting is broken"
                )
            rows.append(self._free.pop(0))
            new.append(rows[-1])
            self._reserved[slot] = max(0, self._reserved[slot] - 1)
        if demand and new:
            self.page_faults += len(new)
        return new

    def rows_of(self, slot: int) -> list[int]:
        return list(self._rows.get(slot, ()))

    def row_array(self, slot: int) -> np.ndarray:
        """(pages_per_slot,) int32 page row of a slot, -1-padded."""
        out = np.full((self.pages_per_slot,), -1, np.int32)
        rows = self._rows.get(slot, ())
        out[: len(rows)] = rows
        return out

    def release(self, slot: int) -> list[int]:
        """Evict: the slot's pages go back to the free list (sorted, for
        deterministic reuse) and its reservation is dropped."""
        rows = self._rows.pop(slot, [])
        self._reserved.pop(slot, None)
        self._free.extend(rows)
        self._free.sort()
        return rows


# --------------------------------------------------------------------------
# pure layout transforms: dense slot leaves <-> page pools
# --------------------------------------------------------------------------
def dense_to_pool(pool: jax.Array, dense: jax.Array, rows: jax.Array) -> jax.Array:
    """Scatter a width-1 dense cache leaf (L, 1, ..., S, d) into the pool
    (L, N, ..., ps, d) at page rows ``rows`` ((P,) int32, -1 = skip).
    Whole pages are written — the dense zero tail past the filled prefix
    lands too, so freshly-mapped install pages come out clean."""
    n, ps = pool.shape[1], pool.shape[-2]
    x = jnp.squeeze(dense, axis=1)  # (L, ..., S, d)
    p = x.shape[-2] // ps
    x = x.reshape(x.shape[:-2] + (p, ps) + x.shape[-1:])
    x = jnp.moveaxis(x, -3, 1)  # (L, P, ..., ps, d)
    safe = jnp.where(rows >= 0, rows, n)  # OOB -> dropped
    return pool.at[:, safe].set(x.astype(pool.dtype))


def pool_slot_view(pool: jax.Array, pages: jax.Array) -> jax.Array:
    """Gather the dense-layout view (L, B, ..., S, d) of every slot from
    the pool through the page tables ``pages`` ((B, P) int32); unmapped
    pages read as zeros.  Bit-identical leaf layout to the dense stacked
    cache — fingerprints, damage accounting, and repair reads all run on
    this view, which is why replica slots holding *different* pool rows
    still fingerprint equal."""
    n = pool.shape[1]
    safe = jnp.clip(pages, 0, n - 1)
    g = pool[:, safe]  # (L, B, P, ..., ps, d)
    mapped = (pages >= 0).reshape((1,) + pages.shape + (1,) * (g.ndim - 3))
    g = jnp.where(mapped, g, 0)
    g = jnp.moveaxis(g, 2, -3)  # (L, B, ..., P, ps, d)
    return g.reshape(g.shape[:-3] + (-1,) + g.shape[-1:])


def paged_view(dec: dict, pages: Optional[jax.Array] = None) -> dict:
    """The dense-layout view of a paged decoder state: pool leaves
    gathered per slot, the raw ``pages`` leaf dropped (replica slots hold
    different rows by construction — comparing them would flag healthy
    replicas).  A strike on the pages leaf still surfaces: the gather
    then reads the wrong (or no) page, and the view diverges."""
    pages = dec["pages"] if pages is None else pages
    view = {k: v for k, v in dec.items() if k not in ("cache", "pages")}
    view["cache"] = {
        "segments": [
            {k: pool_slot_view(v, pages) for k, v in seg.items()}
            for seg in dec["cache"]["segments"]
        ],
        "pos": dec["cache"]["pos"],
    }
    return view


def paged_read_slot(dec: dict, slot: jax.Array, vaxes: Pytree) -> dict:
    """``read_slot(paged_view(dec), slot, vaxes)``, gathering only that
    slot's pages instead of every slot's."""
    rest = {k: v for k, v in dec.items() if k not in ("cache", "pages")}
    one = read_slot(rest, slot, {k: vaxes[k] for k in rest})
    one["cache"] = {
        "segments": dec["cache"]["segments"],
        "pos": read_slot(dec["cache"]["pos"], slot, vaxes["cache"]["pos"]),
    }
    one["pages"] = jax.lax.dynamic_slice_in_dim(dec["pages"], slot, 1, axis=0)
    return paged_view(one)


def view_axes_of(axes: Pytree) -> Pytree:
    """Slot axes of ``paged_view``'s output: gathered cache leaves carry
    the slot axis at 1 (dense stacked layout); everything else keeps its
    inferred axis."""
    va = {k: v for k, v in axes.items() if k not in ("cache", "pages")}
    va["cache"] = {
        "segments": [
            jax.tree.map(lambda a: 1, seg) for seg in axes["cache"]["segments"]
        ],
        "pos": axes["cache"]["pos"],
    }
    return va


# --------------------------------------------------------------------------
# paged SlotSurgery
# --------------------------------------------------------------------------
def paged_surgery(
    table: PageTable,
    cell: str,
    axes: Pytree,
    empty: Pytree,
    *,
    reserve_fn: Callable[[Any], int],
) -> SlotSurgery:
    """The engine's slot operations routed through ``table``.

    ``axes`` is the paged state's axis tree (``infer_paged_axes``);
    ``empty`` a width-1 paged slot state (its non-pool leaves scrub
    evicted slots; pool bytes are left in place and cleaned on next map);
    ``reserve_fn(request)`` the worst-case page count of one replica
    slot.  Join receives the DENSE width-1 prefill state and installs it
    into freshly-mapped pages."""
    vaxes = view_axes_of(axes)

    # non-pool state entries may be NESTED (the speculative draft cache
    # is a whole dense cache dict living beside the pool leaves), so the
    # per-slot update/slice run leaf-wise over the subtree
    def _put(dst, src, slot, ax):
        return jax.tree.map(
            lambda d, s, a: jax.lax.dynamic_update_slice_in_dim(
                d, s.astype(d.dtype), slot, axis=a
            ),
            dst,
            src,
            ax,
        )

    def _take(src, slot, ax):
        return jax.tree.map(
            lambda s, a: jax.lax.dynamic_slice_in_dim(s, slot, 1, axis=a),
            src,
            ax,
        )

    def _install(st, ss, slot, rows):
        dec = st[cell]
        new = {}
        for k, v in dec.items():
            if k == "cache":
                segs = [
                    {kk: dense_to_pool(pseg[kk], dseg[kk], rows) for kk in pseg}
                    for pseg, dseg in zip(v["segments"], ss["cache"]["segments"])
                ]
                pv = ss["cache"]["pos"].astype(v["pos"].dtype)
                pos = jax.lax.dynamic_update_slice_in_dim(v["pos"], pv, slot, axis=0)
                new[k] = {"segments": segs, "pos": pos}
            elif k == "pages":
                new[k] = jax.lax.dynamic_update_slice_in_dim(
                    v, rows[None].astype(v.dtype), slot, axis=0
                )
            else:
                new[k] = _put(v, ss[k], slot, axes[k])
        return {**st, cell: new}

    def _scrub(st, slot):
        dec = st[cell]
        blank = jnp.full((1, table.pages_per_slot), -1, jnp.int32)
        new = {}
        for k, v in dec.items():
            if k == "cache":
                pv = empty["cache"]["pos"].astype(v["pos"].dtype)
                pos = jax.lax.dynamic_update_slice_in_dim(v["pos"], pv, slot, axis=0)
                new[k] = {"segments": v["segments"], "pos": pos}
            elif k == "pages":
                new[k] = jax.lax.dynamic_update_slice_in_dim(
                    v, blank.astype(v.dtype), slot, axis=0
                )
            else:
                new[k] = _put(v, empty[k], slot, axes[k])
        return {**st, cell: new}

    def _copy_pool(pool, src_rows, dst_rows):
        n = pool.shape[1]
        vals = pool[:, jnp.clip(src_rows, 0, n - 1)]
        dst = jnp.where(dst_rows >= 0, dst_rows, n)  # OOB -> dropped
        return pool.at[:, dst].set(vals)

    def _copy(st, src, dst, src_rows, dst_rows):
        """Replica repair src -> dst: per-slot leaves copied; page
        CONTENTS copied row-by-row (replicas hold the same page count —
        same request, same position); the dst pages leaf is restored from
        the host-authoritative rows, so a strike on the pages leaf itself
        is repaired too."""
        dec = st[cell]
        new = {}
        for k, v in dec.items():
            if k == "cache":
                segs = [
                    {kk: _copy_pool(pseg[kk], src_rows, dst_rows) for kk in pseg}
                    for pseg in v["segments"]
                ]
                pv = jax.lax.dynamic_slice_in_dim(v["pos"], src, 1, axis=0)
                pos = jax.lax.dynamic_update_slice_in_dim(v["pos"], pv, dst, axis=0)
                new[k] = {"segments": segs, "pos": pos}
            elif k == "pages":
                new[k] = jax.lax.dynamic_update_slice_in_dim(
                    v, dst_rows[None].astype(v.dtype), dst, axis=0
                )
            else:
                new[k] = _put(v, _take(v, src, axes[k]), dst, axes[k])
        return {**st, cell: new}

    def _copy_pool_from(pool, other_pool, rows):
        n = pool.shape[1]
        vals = other_pool[:, jnp.clip(rows, 0, n - 1)].astype(pool.dtype)
        dst = jnp.where(rows >= 0, rows, n)
        return pool.at[:, dst].set(vals)

    def _adopt(st, other, slot, rows):
        """DMR §IV adoption: per-slot leaves and the slot's page CONTENTS
        (at the same host rows — a replay never remaps pages) come from
        ``other``; the pages leaf is restored host-authoritatively."""
        dec, odec = st[cell], other[cell]
        new = {}
        for k, v in dec.items():
            if k == "cache":
                segs = [
                    {kk: _copy_pool_from(pseg[kk], oseg[kk], rows) for kk in pseg}
                    for pseg, oseg in zip(v["segments"], odec["cache"]["segments"])
                ]
                opos = odec["cache"]["pos"]
                pv = jax.lax.dynamic_slice_in_dim(opos, slot, 1, axis=0)
                pos = jax.lax.dynamic_update_slice_in_dim(
                    v["pos"], pv.astype(v["pos"].dtype), slot, axis=0
                )
                new[k] = {"segments": segs, "pos": pos}
            elif k == "pages":
                new[k] = jax.lax.dynamic_update_slice_in_dim(
                    v, rows[None].astype(v.dtype), slot, axis=0
                )
            else:
                new[k] = _put(v, _take(odec[k], slot, axes[k]), slot, axes[k])
        return {**st, cell: new}

    # forwarding: the cells a slot op leaves alone (the weights) come back
    # as the same buffers, not copies
    jit_install = forwarding_jit(_install, name="paged_install")
    jit_scrub = forwarding_jit(_scrub, name="paged_scrub")
    jit_copy = forwarding_jit(_copy, name="paged_copy")
    jit_adopt = forwarding_jit(_adopt, name="paged_adopt")

    def _fps_impl(dec):
        return slot_fingerprints(
            dec, vaxes, n=dec["pages"].shape[0], read=paged_read_slot
        )

    jit_fps = jax.jit(named(_fps_impl, "paged_fingerprints"))

    def _damage_impl(st, a, b):
        return bit_mismatch_elems(
            paged_read_slot(st[cell], a, vaxes),
            paged_read_slot(st[cell], b, vaxes),
        )

    def _damage_vs_impl(st, other, slot):
        return bit_mismatch_elems(
            paged_read_slot(st[cell], slot, vaxes),
            paged_read_slot(other[cell], slot, vaxes),
        )

    jit_damage = jax.jit(named(_damage_impl, "paged_damage"))
    jit_damage_vs = jax.jit(named(_damage_vs_impl, "paged_damage_vs"))

    def join(st, ss, slot, req=None):
        if req is None:
            raise ValueError(
                "paged join needs the admitting request "
                "(page reservation sizing)"
            )
        table.assign(slot, reserve_fn(req))
        if join.tracer is not None:
            with join.tracer.span("sync.join_pos", "engine"):
                pos0 = int(jax.device_get(ss["cache"]["pos"][0]))
        else:
            pos0 = int(jax.device_get(ss["cache"]["pos"][0]))
        table.grow_to(slot, pos0)  # install pages: admission, not faults
        rows = jnp.asarray(table.row_array(slot))
        return jit_install(st, ss, jnp.int32(slot), rows)

    def scrub(st, slot):
        table.release(slot)
        return jit_scrub(st, jnp.int32(slot))

    def copy(st, src, dst):
        src_rows = table.row_array(src)
        dst_rows = table.row_array(dst)
        if (src_rows >= 0).sum() != (dst_rows >= 0).sum():
            raise RuntimeError(f"replica slots {src}/{dst} page counts differ")
        sr, dr = jnp.asarray(src_rows), jnp.asarray(dst_rows)
        return jit_copy(st, jnp.int32(src), jnp.int32(dst), sr, dr)

    def adopt(st, other, slot):
        rows = jnp.asarray(table.row_array(slot))
        return jit_adopt(st, other, jnp.int32(slot), rows)

    def _damage_host(st, a, b):
        return float(jax.device_get(jit_damage(st, jnp.int32(a), jnp.int32(b))))

    def _damage_vs_host(st, other, slot):
        return float(jax.device_get(jit_damage_vs(st, other, jnp.int32(slot))))

    #: set by SlotAdapter.attach_tracer, as ``pre_tick.tracer`` is
    join.tracer = None
    return SlotSurgery(
        join=join,
        scrub=scrub,
        copy=copy,
        adopt=adopt,
        fingerprints=jit_fps,
        damage=_damage_host,
        damage_vs=_damage_vs_host,
    )


# --------------------------------------------------------------------------
# pre-tick demand growth
# --------------------------------------------------------------------------
def make_pre_tick(
    table: PageTable, cell: str, batch: int, walk_chunk: int = 1,
    draft_len: int = 0
) -> Callable[[dict], dict]:
    """The engine's pre-tick hook for a paged program: before each
    resident transition, map pages covering every position the tick will
    write (the decode append, up to ``walk_chunk`` prefill-walk tokens,
    or a ``k_eff + 1``-position speculative verify walk), charge them as
    page faults, and ZERO the newly-mapped pool rows (clean-on-map —
    page reuse between requests leaves no stale bytes, so replica
    fingerprints and paged-vs-dense parity hold).

    ``draft_len`` > 0 (speculative engines) makes the hook read the
    per-slot ``spec_k``/``budget`` leaves and apply the SAME effective-
    draft-length clamp as the in-graph walk
    (``models/lm_cells.py:spec_k_eff``) — host and device must agree on
    how far the tick writes, or a verify sub-step would land on an
    unmapped page.  A rejected speculation rolls ``pos`` back but never
    unmaps: the pages stay with the slot (they are inside its
    reservation) and are simply re-written when decode reaches them.

    Runs BEFORE the engine snapshots the tick's input buffer, so a §IV
    replay sees the same page tables the live tick did."""
    # newly-mapped rows per tick is bounded: each active slot crosses at
    # most ceil(max_step/ps)+1 page boundaries
    max_step = max(walk_chunk, draft_len + 1)
    cap = batch * (-(-max_step // table.page_size) + 1)
    max_len = table.pages_per_slot * table.page_size

    def grow(st, rows, grew, clean):
        dec = st[cell]
        new = dict(dec)
        new["pages"] = jnp.where(grew[:, None], rows, dec["pages"])
        # clean rows scatter through an OOB-padded index list: pad
        # entries (row == n_pages) land out of bounds and are dropped
        new["cache"] = {
            "segments": [
                {k: v.at[:, clean].set(0) for k, v in seg.items()}
                for seg in dec["cache"]["segments"]
            ],
            "pos": dec["cache"]["pos"],
        }
        return {**st, cell: new}

    jit_grow = forwarding_jit(grow, name="paged_grow")

    def pre_tick(states):
        if pre_tick.tracer is not None:
            with pre_tick.tracer.span("page_grow", "engine"):
                return grow_pages(states, pre_tick.tracer)
        return grow_pages(states, None)

    def grow_pages(states, tracer):
        dec = states[cell]
        leaves = [dec["active"], dec["cache"]["pos"], dec["p_head"], dec["p_len"]]
        if draft_len > 0:
            leaves += [dec["spec_k"], dec["budget"], dec["n_decoded"]]
        if tracer is not None:
            with tracer.span("sync.page_state", "engine"):
                host = [np.asarray(x) for x in jax.device_get(leaves)]
        else:
            host = [np.asarray(x) for x in jax.device_get(leaves)]
        act, pos, p_head, p_len = host[:4]
        rows = np.full((batch, table.pages_per_slot), -1, np.int32)
        grew = np.zeros((batch,), bool)
        clean: list[int] = []
        for s in range(batch):
            if not act[s]:
                continue
            r = int(p_len[s] - p_head[s])
            if r > 0:
                step = min(walk_chunk, r)
            elif draft_len > 0:
                # host mirror of models/lm_cells.py:spec_k_eff — the two
                # clamps must stay in lock-step, or the device verify
                # walk writes a position this hook never mapped
                spec_k, budget, n_dec = host[4], host[5], host[6]
                room = min(
                    int(budget[s]) - int(n_dec[s]) - 2,
                    max_len - 1 - int(pos[s]),
                )
                k_eff = max(0, min(int(spec_k[s]), room, draft_len))
                step = 1 + k_eff
            else:
                step = 1
            new = table.grow_to(s, int(pos[s]) + step, demand=True)
            if new:
                clean.extend(new)
                rows[s] = table.row_array(s)
                grew[s] = True
                if tracer is not None:
                    # one instant per faulting slot: which pool pages
                    # the demand-map just pulled in and for what position
                    tracer.instant(
                        "page_fault",
                        "engine",
                        slot=s,
                        pages=[int(p) for p in new],
                        pos=int(pos[s]) + step,
                    )
        if not grew.any():
            return states
        carr = np.full((cap,), table.n_pages, np.int32)
        carr[: len(clean)] = clean
        rows_d, grew_d, carr_d = map(jnp.asarray, (rows, grew, carr))
        return jit_grow(states, rows_d, grew_d, carr_d)

    #: set by SlotAdapter.attach_tracer when the engine has a tracer —
    #: a function attribute, so the closure stays picklable/simple and
    #: the untraced path is one ``is not None`` check
    pre_tick.tracer = None
    return pre_tick
