"""Dependability executors (paper §IV).

Because a cell's state is written by exactly one transition and read states
are immutable (double buffering), replication is mechanically identical to
data parallelism: give the state a leading *replica axis* R and ``vmap`` the
transition over it.  The replica axis is then either

  * kept on the same devices ("temporal" placement — R x compute), or
  * sharded over a mesh axis, conventionally ``pod`` ("spatial" placement —
    replicas live on different boards/HBM, the paper's "different processors
    and memories"; compare becomes a cross-pod collective).

Detection/correction, per the paper:

  DMR (level 2): compare the two new states; on mismatch a *third equal
      transition* decides between the two outcomes (host-side
      ``tiebreak``, re-run from the immutable previous buffer).
  TMR (level 3): in-graph bitwise majority vote; mismatching replicas are
      re-synchronized to the voted value, and per-replica mismatch counters
      feed permanent-fault localization.

Compare modes: "bitwise" (paper-faithful, O(state) traffic under spatial
placement) and "hash" (beyond-paper 128-bit fingerprints, O(1) traffic).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from .cell import CellType, restrict_reads, undeclared_read_error
from .fault import FaultSpec, bitcast_back, bitcast_uint, inject

Pytree = Any

MAX_REPLICAS = 3


# --------------------------------------------------------------------------
# comparison primitives
# --------------------------------------------------------------------------
def bit_mismatch_elems(a: Pytree, b: Pytree) -> jax.Array:
    """Number of elements whose bit patterns differ (float32 accumulator)."""
    leaves_a = jax.tree.leaves(a)
    leaves_b = jax.tree.leaves(b)
    total = jnp.float32(0)
    for la, lb in zip(leaves_a, leaves_b):
        total += jnp.sum(
            (bitcast_uint(la) != bitcast_uint(lb)).astype(jnp.float32)
        )
    return total


def majority_vote(a: Pytree, b: Pytree, c: Pytree) -> Pytree:
    """Elementwise bitwise 2-of-3 majority (exact for replicated transitions)."""

    def vote(x, y, z):
        ux, uy, uz = bitcast_uint(x), bitcast_uint(y), bitcast_uint(z)
        return bitcast_back((ux & uy) | (ux & uz) | (uy & uz), x.dtype)

    return jax.tree.map(vote, a, b, c)


_PHI = jnp.uint32(0x9E3779B9)
_MIX = jnp.uint32(2654435761)
_FNV = jnp.uint32(16777619)


def fingerprint(state: Pytree) -> jax.Array:
    """128-bit (4 x uint32) order-sensitive fingerprint of a state pytree.

    Four independent modular accumulators over position-weighted words; any
    single bit flip changes all four with overwhelming probability.  All
    reductions are commutative wraparound sums/xors -> one cheap pass, and
    under spatial replication each pod hashes locally so the cross-pod
    compare moves 16 bytes instead of the full state.
    """
    h = jnp.zeros((4,), jnp.uint32)
    for k, leaf in enumerate(jax.tree.leaves(state)):
        v = bitcast_uint(leaf).astype(jnp.uint32)
        if v.ndim == 0:
            v = v[None]
        # position weights from per-dim iotas — NO reshape(-1): flattening a
        # sharded leaf to rank-1 is an all-gather under GSPMD, whereas
        # elementwise iotas + full reductions stay shard-local and combine
        # with scalar psums (same lesson as inject(); §Perf iteration 0)
        idx = jnp.zeros(v.shape, jnp.uint32)
        stride = 1
        for ax in reversed(range(v.ndim)):
            idx = idx + (jax.lax.broadcasted_iota(jnp.uint32, v.shape, ax)
                         * jnp.uint32(stride & 0xFFFFFFFF))
            stride *= v.shape[ax]
        w = idx * _MIX + _PHI
        h1 = jnp.sum(v * w, dtype=jnp.uint32)
        h2 = jnp.sum((v ^ w) * _MIX, dtype=jnp.uint32)
        # all four accumulators are wraparound SUMS: a cross-replica xor
        # reduce lowers to an all-reduce with a bitwise computation, which
        # backends need not support — sums always psum
        h3 = jnp.sum((v ^ (w * _PHI)) * _FNV, dtype=jnp.uint32)
        h4 = jnp.sum((v + w) ^ (v >> 7), dtype=jnp.uint32)
        leaf_h = jnp.stack([h1, h2, h3, h4])
        h = (h * _FNV) ^ (leaf_h + jnp.uint32(k + 1))
    return h


# --------------------------------------------------------------------------
# reports
# --------------------------------------------------------------------------
def fingerprint_majority(hs: jax.Array):
    """Majority relation over a (3, 4) stack of replica fingerprints.

    Returns ``((eq01, eq02, eq12), idx, per)``: the pairwise equality
    flags, the index of a replica belonging to the majority (hash-mode TMR
    adopts that replica's state wholesale), and the per-replica mismatch
    indicators (float32).  Single source of truth shared by the temporal
    hash-TMR epilogue below and the spatial back-end's cross-pod vote
    (``core/backend_spatial.py``) — bitwise parity between the two
    placements depends on this logic staying identical."""
    eq01 = jnp.all(hs[0] == hs[1])
    eq02 = jnp.all(hs[0] == hs[2])
    eq12 = jnp.all(hs[1] == hs[2])
    idx = jnp.where(eq01 | eq02, 0, jnp.where(eq12, 1, 0))
    per = jnp.stack([
        (~(eq01 | eq02)).astype(jnp.float32),
        (~(eq01 | eq12)).astype(jnp.float32),
        (~(eq02 | eq12)).astype(jnp.float32),
    ])
    return (eq01, eq02, eq12), idx, per


def zero_report() -> dict:
    return {
        "mismatch_elems": jnp.float32(0),   # elements (or hash words) differing
        "events": jnp.float32(0),           # 1.0 if this transition mismatched
        "per_replica": jnp.zeros((MAX_REPLICAS,), jnp.float32),
    }


# --------------------------------------------------------------------------
# replication helpers
# --------------------------------------------------------------------------
def replicate_state(state: Pytree, level: int) -> Pytree:
    """Duplicate the memory contents (paper: 'the memory contents may be
    duplicated') -> leading replica axis of size `level`."""
    if level == 1:
        return state
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (level,) + jnp.shape(x)), state
    )


def canonical_state(state: Pytree, level: int) -> Pytree:
    """The agreed single view of a replicated state (replica 0)."""
    if level == 1:
        return state
    return jax.tree.map(lambda x: x[0], state)


def _replica_in_axes(cell: CellType, levels: Mapping[str, int]) -> dict:
    """vmap in_axes for the read dict: pairwise replica reads where the read
    cell is replicated at the same level, broadcast otherwise."""
    R = cell.redundancy.level
    axes = {}
    for name in {cell.name, *cell.reads}:
        lr = levels.get(name, 1)
        axes[name] = 0 if lr == R else None
    return axes


# --------------------------------------------------------------------------
# the executor
# --------------------------------------------------------------------------
def _canonical_reads(
    cell: CellType, prevs: Mapping[str, Pytree], levels: Mapping[str, int]
) -> dict:
    """Reads with cells replicated at a *different* level canonicalized."""
    R = cell.redundancy.level
    reads = restrict_reads(cell, prevs)
    canon = {}
    for name, val in reads.items():
        lr = levels.get(name, 1)
        if lr != 1 and lr != R:
            canon[name] = canonical_state(val, lr)
        else:
            canon[name] = val
    return canon


def replicated_transition(
    cell: CellType,
    prevs: Mapping[str, Pytree],
    levels: Mapping[str, int],
    *,
    cell_id: int,
    step: jax.Array,
    fault: Optional[FaultSpec] = None,
) -> Pytree:
    """The replicated front half of ``run_transition`` (R > 1): canonicalize
    reads, vmap the transition over the replica axis, inject the armed
    fault.  Shared with the Pallas-fused back-end, which swaps only the
    compare/vote epilogue — so both paths are bitwise-identical up to it."""
    canon = _canonical_reads(cell, prevs, levels)
    axes = _replica_in_axes(cell, {k: levels.get(k, 1) for k in canon})
    try:
        new = jax.vmap(cell.transition, in_axes=(axes,))(canon)
    except KeyError as e:  # read of an undeclared cell, mid-trace
        raise undeclared_read_error(
            cell, e.args[0] if e.args else e, tuple(canon)
        ) from None
    if fault is not None:
        new = inject(fault, cell_id=cell_id, step=step, replicated_state=new)
    return new


def run_transition(
    cell: CellType,
    prevs: Mapping[str, Pytree],
    levels: Mapping[str, int],
    *,
    cell_id: int,
    step: jax.Array,
    fault: Optional[FaultSpec] = None,
    compare_now: bool | jax.Array = True,
) -> tuple[Pytree, dict]:
    """Execute one cell transition under its redundancy policy.

    prevs: full program state (replicated cells carry their replica axis).
    Returns (new state for this cell — with replica axis if level>1, report).
    """
    policy = cell.redundancy
    R = policy.level

    if R == 1:
        canon = _canonical_reads(cell, prevs, levels)
        try:
            new = cell.transition(canon)
        except KeyError as e:  # read of an undeclared cell, mid-trace
            raise undeclared_read_error(
                cell, e.args[0] if e.args else e, tuple(canon)
            ) from None
        if fault is not None and fault.may_strike(cell_id):
            # unprotected cells are still physically strikeable — the flip
            # simply goes undetected (the paper's motivating failure mode)
            exp = jax.tree.map(lambda x: x[None], new)
            exp = inject(fault, cell_id=cell_id, step=step,
                         replicated_state=exp)
            new = jax.tree.map(lambda x: x[0], exp)
        return new, zero_report()

    new = replicated_transition(cell, prevs, levels, cell_id=cell_id,
                                step=step, fault=fault)

    report = zero_report()
    reps = [jax.tree.map(lambda x, i=i: x[i], new) for i in range(R)]

    if R == 2:
        if policy.compare == "hash":
            h = jnp.stack([fingerprint(r) for r in reps])  # (2, 4)
            diff = jnp.sum((h[0] != h[1]).astype(jnp.float32))
        else:
            diff = bit_mismatch_elems(reps[0], reps[1])
        diff = jnp.where(jnp.asarray(compare_now), diff, 0.0)
        report["mismatch_elems"] = diff
        report["events"] = (diff > 0).astype(jnp.float32)
        return new, report

    # R == 3: in-graph correction
    if policy.compare == "hash":
        h = jnp.stack([fingerprint(r) for r in reps])  # (3, 4)
        _, idx, per = fingerprint_majority(h)
        voted = jax.tree.map(
            lambda x: jnp.take(x, idx, axis=0), new
        )
    else:
        voted = majority_vote(*reps)
        per = jnp.stack(
            [bit_mismatch_elems(r, voted) for r in reps]
        )
    per = jnp.where(jnp.asarray(compare_now), per, jnp.zeros_like(per))
    report["per_replica"] = (per > 0).astype(jnp.float32) * jnp.maximum(per, 1.0)
    report["mismatch_elems"] = jnp.sum(per)
    report["events"] = (jnp.sum(per) > 0).astype(jnp.float32)
    # re-synchronize replicas to the voted value (prevents divergence)
    new = replicate_state(voted, R)
    return new, report


def make_tiebreak(cell: CellType, levels: Mapping[str, int]):
    """Paper §IV DMR recovery: 'a third equal transition should be executed
    to decide between the two possible outcomes.'  Host calls this with the
    immutable previous program state (possible because of double buffering)
    and the two disagreeing replicas; returns the repaired replicated state.
    """

    def tiebreak(prevs: Mapping[str, Pytree], disagreeing: Pytree) -> Pytree:
        reads = restrict_reads(cell, prevs)
        canon = {
            name: canonical_state(val, levels.get(name, 1))
            for name, val in reads.items()
        }
        third = cell.transition(canon)
        r0 = jax.tree.map(lambda x: x[0], disagreeing)
        r1 = jax.tree.map(lambda x: x[1], disagreeing)
        voted = majority_vote(r0, r1, third)
        return replicate_state(voted, cell.redundancy.level)

    return tiebreak


# --------------------------------------------------------------------------
# permanent-fault localization (paper: "By identifying MISO cells that are
# frequently erroneous, it is possible to detect permanent failures")
# --------------------------------------------------------------------------
@dataclasses.dataclass
class FaultLedger:
    """Host-side accumulator of per-cell mismatch reports."""

    window: int = 100
    threshold: int = 3
    totals: dict = dataclasses.field(default_factory=dict)
    recent: dict = dataclasses.field(default_factory=dict)
    flagged: set = dataclasses.field(default_factory=set)

    def update(self, step: int, reports: Mapping[str, dict]) -> None:
        for name, rep in reports.items():
            ev = float(rep["events"])
            t = self.totals.setdefault(
                name, {"events": 0.0, "elems": 0.0, "per_replica": [0.0] * 3}
            )
            t["events"] += ev
            t["elems"] += float(rep["mismatch_elems"])
            # per_replica may be shorter than MAX_REPLICAS: the serving
            # engine sizes it to the request's actual level (DMR -> 2)
            pr = [float(x) for x in rep["per_replica"]]
            for i, x in enumerate(pr[:MAX_REPLICAS]):
                t["per_replica"][i] += 1.0 if x > 0 else 0.0
            if ev > 0:
                self.recent.setdefault(name, []).append(step)
                self.recent[name] = [
                    s for s in self.recent[name] if s > step - self.window
                ]
                if len(self.recent[name]) >= self.threshold:
                    self.flagged.add(name)

    def permanent_fault_suspects(self) -> dict:
        """cells (and, under TMR, which replica slot) needing maintenance."""
        out = {}
        for name in self.flagged:
            pr = self.totals[name]["per_replica"]
            # DMR cannot attribute the faulty replica (two-way disagreement
            # is symmetric — the paper's motivation for the third run); TMR
            # majority voting can.  None = "cell pair flagged, run tie-break
            # diagnostics" rather than a misleading slot 0.
            worst = (max(range(3), key=lambda i: pr[i])
                     if any(p > 0 for p in pr) else None)
            out[name] = {"replica": worst, "events": self.totals[name]["events"]}
        return out
