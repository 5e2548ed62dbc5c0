"""Spatial serving support: cross-pod strike detection and sharding pins.

With ``placement="spatial"`` a DMR/TMR request's replica slots sit at the
same slot COLUMN on different mesh pods (pod ``p`` owns global slots
``[p*spp, (p+1)*spp)``), so replica ``r`` of the group anchored at column
``c`` is global slot ``r*spp + c`` — the replica index IS the pod index.
Detection then stops being a host-side fingerprint walk over every slot
and becomes one O(1)-wire collective per tick (``distributed/
collectives.py``):

  DMR  — each pod fingerprints its local slots (128 bits each) and the
         member pods exchange them through ``psum_delta``: the delta is
         nonzero exactly where the two members disagree, 16 bytes per
         active column on the wire, no all_gather.
  TMR  — one ``all_gather`` of the (spp, 4) fingerprint block; every pod
         then runs the same majority pick locally, so the struck-pod
         verdict is replicated for free.

Both variants compute the *identical* per-slot fingerprints the temporal
engine compares on the host (``slots.slot_fingerprints``), which is what
makes spatial and temporal detection agree event-for-event — the parity
gate in tests/test_serving_spatial.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core.jit import named
from repro.distributed.collectives import psum_delta

from .slots import SlotSurgery, slot_fingerprints


def make_detect(mesh, axes, *, pod_axis: str = "pod", tmr: bool):
    """-> jitted ``detect(dec_state, lvl) -> (events, struck)``.

    ``lvl`` is a replicated (spp,) int32 array: the redundancy level of
    the spatial group anchored at each column (0 = no group there this
    tick).  ``events[c]`` is 1 where the group at column ``c`` diverged;
    ``struck[c]`` is the struck pod for a TMR majority verdict, -1 when
    healthy or not localizable (DMR), -2 on TMR triple divergence (all
    three disagree — fall back to replay, same as DMR).  Outputs are
    computed identically on every pod, so they come back replicated.

    Two statically-compiled variants: the DMR-only one (``tmr=False``)
    never gathers; the ``tmr=True`` one serves mixed DMR+TMR ticks from
    the one all_gather.  The engine picks per tick.
    """

    def leaf_spec(ax):
        return P(*((None,) * ax + (pod_axis,)))

    dec_specs = jax.tree.map(leaf_spec, axes)

    def local(dec, lvl):
        h = slot_fingerprints(dec, axes)  # (spp, 4) u32, pod-local slots
        if tmr:
            hs = jax.lax.all_gather(h, pod_axis)  # (n_pods, spp, 4)
            eq01 = jnp.all(hs[0] == hs[1], axis=-1)
            eq02 = jnp.all(hs[0] == hs[2], axis=-1)
            eq12 = jnp.all(hs[1] == hs[2], axis=-1)
            healthy3 = eq01 & eq02
            # first agreeing pair wins, same precedence as the temporal
            # engine's [(0,1), (0,2), (1,2)] walk; no pair -> -2 (replay)
            struck3 = jnp.where(eq12, jnp.int32(0), jnp.int32(-2))
            struck3 = jnp.where(eq02, jnp.int32(1), struck3)
            struck3 = jnp.where(eq01, jnp.int32(2), struck3)
            struck3 = jnp.where(healthy3, jnp.int32(-1), struck3)
            ev3 = (lvl == 3) & ~healthy3
            ev2 = (lvl == 2) & ~eq01
            events = (ev2 | ev3).astype(jnp.int32)
            struck = jnp.where(ev3, struck3, jnp.int32(-1))
        else:
            me = jax.lax.axis_index(pod_axis)
            m2 = (lvl == 2) & (me < 2)
            hm = jnp.where(m2[:, None], h, jnp.uint32(0))
            # psum over members 0,1 minus twice the local value: zero
            # words exactly where the two members agree (u32 wraparound)
            delta = psum_delta(hm, pod_axis)
            mism = m2 & jnp.any(delta != 0, axis=-1)
            events = jax.lax.psum((mism & (me == 0)).astype(jnp.int32), pod_axis)
            struck = jnp.full(lvl.shape, -1, jnp.int32)
        return events, struck

    mapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(dec_specs, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(named(mapped, "spatial_detect"))


def detect_wire_bytes(n_pods: int, spp: int, tmr: bool) -> int:
    """Per-pod per-tick cross-pod payload of one detect call (analytic;
    the bench reports it next to tokens/s).  DMR: the 16-byte-per-column
    fingerprint psum plus the 4-byte event-count psum.  TMR: the
    all_gather delivers every pod's (spp, 4) u32 block."""
    if tmr:
        return n_pods * spp * 16
    return spp * 16 + spp * 4


def pin_surgery(base: SlotSurgery, canon) -> SlotSurgery:
    """Wrap a surgery so every state-returning op lands back on the
    canonical shardings captured at ``engine.start()``.

    Host-side joins/copies otherwise come back with whatever sharding
    ``jit`` inferred, and feeding that into the shard_map'd step would
    either reshard on the wire every tick or recompile per layout.
    ``device_put`` onto an already-matching sharding is a no-copy no-op,
    so the temporal path could use this too — it just has nothing to pin.
    """

    def pin(st):
        return jax.device_put(st, canon)

    return dataclasses.replace(
        base,
        join=lambda *a, **k: pin(base.join(*a, **k)),
        scrub=lambda *a, **k: pin(base.scrub(*a, **k)),
        copy=lambda *a, **k: pin(base.copy(*a, **k)),
        adopt=lambda *a, **k: pin(base.adopt(*a, **k)),
    )
