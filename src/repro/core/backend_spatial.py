"""Sharded spatial-DMR back-end: ``compile(prog, backend="spatial_lockstep")``.

The paper's §IV dependability story names two placements for a replicated
cell: *temporal* (replicas recomputed on the same cores — what the
``lockstep``/``lockstep_pallas``/``host`` back-ends realize) and *spatial*
("the calculations may be performed on different processor cores and the
memory contents may be duplicated").  This back-end makes the spatial
placement real on a device mesh: the replica axis of every cell whose
policy says ``placement="spatial"`` is laid on the mesh's ``pod`` axis, one
replica per pod, and the per-step transition runs under ``shard_map`` with
detect/vote as cross-pod collectives (``distributed/collectives.py``):

  DMR, ``compare="hash"``    — each pod fingerprints its own replica
      (``redundancy.fingerprint``, 128 bits) and the compare is one 16-byte
      ``psum``: ``psum(h) - 2h`` is nonzero exactly where the two pods
      disagree, so no all_gather and no O(state) wire traffic.
  DMR, ``compare="bitwise"`` — the paper-faithful full compare: one
      ppermute of the u32 word stream, elementwise compare locally.
  TMR, ``compare="hash"``    — all_gather of the three 16-byte
      fingerprints picks the majority replica; only on an actual mismatch
      does the minority pod adopt the majority state (a ``lax.cond``-gated
      masked-psum broadcast), so the steady-state wire cost is 48 bytes.
  TMR, ``compare="bitwise"`` — all_gather of the word streams, then the
      *identical* majority-vote/per-replica-count code the temporal
      back-ends run (``redundancy.majority_vote``/``bit_mismatch_elems``).

Everything else — scan ``run``/``stream``, ``compare_every`` amortization,
fault threading, checkpoint segmentation, ledger attribution,
``pure_step``, ``run_campaign`` — is inherited from ``LockstepExecutor``
through the ``_compile_step`` hook, exactly how ``lockstep_pallas`` plugs
in.  Trajectories and fault reports are bitwise-identical to temporal
``lockstep`` for the parity programs in ``tests/test_spatial.py`` (states
AND FaultLedger attribution); the injected-fault plumbing maps the global
replica index onto the pod index, so the same ``FaultSpec`` strikes the
same bit of the same replica under either placement.

Caveat: the spatial transition runs unbatched per pod while the temporal
path ``vmap``s it over the replica axis.  For elementwise/IEEE-exact
transitions (every parity program, and any transition whose per-element
result is independent of batching) the two lower to bit-identical math;
reduction-heavy transitions may reassociate differently under vmap, in
which case parity holds to numerical, not bitwise, equality.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.distributed.collectives import (
    bcast_pytree,
    exchange_pytree,
    gather_replicas,
    psum_delta,
)
from repro.kernels import ops

from .executor import LockstepExecutor, compile_step, register_backend
from .fault import FaultSpec, inject
from .program import MisoProgram
from .redundancy import (
    bit_mismatch_elems,
    canonical_state,
    fingerprint,
    fingerprint_majority,
    majority_vote,
    run_transition,
    zero_report,
)


def spatial_cells(program: MisoProgram) -> dict:
    """{name: cell} for every cell placed spatially (level > 1)."""
    return {
        name: cell
        for name, cell in program.cells.items()
        if cell.redundancy.level > 1
        and cell.redundancy.placement == "spatial"
    }


def _pod_local_fault(fault: FaultSpec, my_pod: jax.Array) -> FaultSpec:
    """The fault as seen by one pod: a strike on global replica r belongs
    to pod r, where the local replica index is 0; every other pod disarms
    it (by pushing the armed step out of range, so arming never recompiles
    — same trick as ``FaultSpec.none``)."""
    mine = fault.replica == my_pod
    return dataclasses.replace(
        fault,
        replica=jnp.int32(0),
        step=jnp.where(mine, fault.step, jnp.int32(-(2**30))),
    )


def _spatial_transition(
    cell, states, levels, spatial, *, cell_id, step, fault, my_pod,
    pod_axis, compare_now,
):
    """One spatially-replicated cell transition, per pod.

    Mirrors ``redundancy.run_transition`` (R > 1) with the replica axis
    manual over ``pod_axis``: reads pair replica-to-replica where levels
    match (spatial reads are pod-local; temporal same-level reads take
    this pod's slot) and canonicalize otherwise, the transition runs on
    the local replica, the armed fault strikes this pod iff the global
    replica index is this pod, and compare/vote are pod collectives.
    Returns the (1, ...)-leading local state and the (replicated) report.
    """
    policy = cell.redundancy
    R = policy.level
    reads = {}
    for name in {cell.name, *cell.reads}:
        lr = levels.get(name, 1)
        if name in spatial:
            # same level by construction: pairwise replica read, pod-local
            reads[name] = jax.tree.map(lambda x: x[0], states[name])
        elif lr == R:
            # temporal cell replicated at the same level: the temporal
            # semantics pair replica axes, so this pod reads its own slot
            reads[name] = jax.tree.map(
                lambda x: jnp.take(x, my_pod, axis=0), states[name])
        elif lr != 1:
            reads[name] = canonical_state(states[name], lr)
        else:
            reads[name] = states[name]
    new = cell.transition(reads)

    # the strike is physical: it hits ONE pod's freshly-computed replica
    local = jax.tree.map(lambda x: x[None], new)
    local = inject(_pod_local_fault(fault, my_pod), cell_id=cell_id,
                   step=step, replicated_state=local)
    mine = jax.tree.map(lambda x: x[0], local)

    report = zero_report()
    if R == 2:
        if not compare_now:
            return local, report
        if policy.compare == "hash":
            # 16 bytes on the wire: nonzero delta words == differing words
            delta = psum_delta(fingerprint(mine), pod_axis)
            diff = jnp.sum((delta != 0).astype(jnp.float32))
        else:
            theirs = exchange_pytree(mine, pod_axis)
            diff = bit_mismatch_elems(mine, theirs)
        report["mismatch_elems"] = diff
        report["events"] = (diff > 0).astype(jnp.float32)
        return local, report

    # R == 3: in-graph correction (the vote runs every sub-step so
    # replicas re-synchronize; counters report only on compare steps —
    # exactly the temporal lockstep semantics)
    if policy.compare == "hash":
        hs = jax.lax.all_gather(fingerprint(mine), pod_axis)   # (3, 4)
        (eq01, eq02, _), idx, per = fingerprint_majority(hs)
        # every pod agrees on (eq*, idx), so the cond is taken uniformly:
        # no wire traffic at all unless a replica actually diverged
        voted = jax.lax.cond(
            eq01 & eq02,
            lambda m: m,
            lambda m: bcast_pytree(m, pod_axis, idx),
            mine,
        )
    else:
        reps_stacked = gather_replicas(mine, pod_axis)
        reps = [jax.tree.map(lambda x, i=i: x[i], reps_stacked)
                for i in range(3)]
        voted = majority_vote(*reps)
        per = jnp.stack([bit_mismatch_elems(r, voted) for r in reps])
    if not compare_now:
        per = jnp.zeros_like(per)
    report["per_replica"] = ((per > 0).astype(jnp.float32)
                             * jnp.maximum(per, 1.0))
    report["mismatch_elems"] = jnp.sum(per)
    report["events"] = (jnp.sum(per) > 0).astype(jnp.float32)
    # re-synchronize this pod's replica to the voted value
    return jax.tree.map(lambda x: x[None], voted), report


def _serve_local_fault(
    fault: FaultSpec, my_pod: jax.Array, *, dec_cid: int,
    leaf_shapes: list, leaf_axes: list, spp: int,
) -> FaultSpec:
    """The serve-mode fault as seen by one pod.

    In serve mode the slot (batch) axis of the decoder cell is sharded
    over pods, so a ``FaultSpec`` whose flat ``index`` addresses the
    GLOBAL decoder leaf must be rebased: decompose the index against the
    global leaf shape, pull out the slot coordinate at that leaf's slot
    axis, and recompose against the pod-local shape (slot coordinate
    mod ``spp``).  Only the owning pod (slot // spp) keeps the fault
    armed — every other pod pushes the step out of range, same trick as
    ``_pod_local_fault``.  ``fault.leaf`` is traced, so the candidate
    (owner, local index) is computed for every leaf and selected with
    ``where``.  Faults on other cells (replicated states) pass through
    untouched and stay armed on all pods, keeping replication coherent.
    """
    owner = jnp.int32(0)
    local = fault.index
    for i, (shape, ax) in enumerate(zip(leaf_shapes, leaf_axes)):
        rem = fault.index
        coords = [None] * len(shape)
        for d in reversed(range(len(shape))):
            coords[d] = rem % shape[d]
            rem = rem // shape[d]
        slot = coords[ax]
        own_i = slot // spp
        coords[ax] = slot % spp
        lshape = list(shape)
        lshape[ax] = spp
        flat = jnp.int32(0)
        for d in range(len(shape)):
            flat = flat * lshape[d] + coords[d]
        sel = fault.leaf == i
        owner = jnp.where(sel, own_i, owner)
        local = jnp.where(sel, flat, local)
    is_dec = fault.cell_id == dec_cid
    keep = jnp.logical_or(~is_dec, owner == my_pod)
    return dataclasses.replace(
        fault,
        index=jnp.where(is_dec, local, fault.index),
        step=jnp.where(keep, fault.step, jnp.int32(-(2**30))),
    )


def compile_step_spatial_serve(
    program: MisoProgram, mesh, *, pod_axis: str = "pod",
    with_compare: bool = True,
):
    """Serve-mode step: the UNMODIFIED temporal ``compile_step`` wrapped
    in one ``shard_map`` that splits the decoder cell's slot axis over
    ``pod_axis``.

    The serving engine's spatial placement puts a request's replica
    slots at the same slot COLUMN on different pods (pod p owns global
    slots ``[p*spp, (p+1)*spp)``), so the per-pod computation is just
    the ordinary slot-masked decode over the local ``spp`` rows — no
    collectives in the step at all; cross-pod detect/vote live in
    ``repro.serving.spatial`` and run as a separate post-tick call,
    matching the temporal engine's post-tick host compare timing.  The
    program itself is byte-identical to temporal serving (the
    ``spatial_serve`` marker carries only placement metadata), which is
    what makes bitwise token parity a meaningful gate.
    """
    serve = program.spatial_serve
    dec = serve["cell"]
    axes = serve["axes"]
    n_pods = mesh.shape[pod_axis]
    spp = serve["n_slots"] // n_pods
    names = list(program.cells)
    dec_cid = names.index(dec)

    g_state = jax.eval_shape(
        lambda: program.cells[dec].init(jax.random.PRNGKey(0)))
    g_leaves, tdef = jax.tree.flatten(g_state)
    leaf_shapes = [l.shape for l in g_leaves]
    leaf_axes = jax.tree.leaves(axes)

    base = compile_step(program, with_compare=with_compare)

    def local_step(states: dict, step_idx, fault):
        my_pod = jax.lax.axis_index(pod_axis)
        fault = _serve_local_fault(
            fault, my_pod, dec_cid=dec_cid, leaf_shapes=leaf_shapes,
            leaf_axes=leaf_axes, spp=spp)
        return base(states, step_idx, fault)

    def leaf_spec(ax):
        return P(*((None,) * ax + (pod_axis,)))

    state_specs = {
        name: jax.tree.map(leaf_spec, axes) if name == dec else P()
        for name in names
    }
    report_specs = {name: P() for name in names}
    mapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_specs, P(), P()),
        out_specs=(state_specs, report_specs),
        check_vma=False,
    )

    def step(states: dict, step_idx, fault):
        return mapped(states, step_idx, fault)

    return step


def compile_step_spatial(
    program: MisoProgram, mesh, *, pod_axis: str = "pod",
    with_compare: bool = True,
):
    """program -> step(states, step_idx, fault) running under one
    ``shard_map`` over ``mesh`` with the spatial replica axes manual on
    ``pod_axis``.

    Non-spatial cells compute redundantly on every pod (their states and
    reports stay replicated); their reads of spatial cells resolve to the
    canonical replica-0 state (one cross-pod broadcast per read cell per
    step) — or, for temporal cells replicated at the same level, to the
    full gathered replica axis so the temporal pairing semantics hold.
    """
    levels = program.levels()
    names = list(program.cells)
    spatial = spatial_cells(program)

    def local_step(states: dict, step_idx, fault):
        my_pod = jax.lax.axis_index(pod_axis)
        canon_cache: dict = {}

        def canonical_spatial(name):
            # replica 0 lives on pod 0; bit-exact broadcast, shared by
            # every reader of `name` this step
            if name not in canon_cache:
                local = jax.tree.map(lambda x: x[0], states[name])
                canon_cache[name] = bcast_pytree(local, pod_axis, 0)
            return canon_cache[name]

        new_states, reports = {}, {}
        for cid, name in enumerate(names):
            cell = program.cells[name]
            if name in spatial:
                new, rep = _spatial_transition(
                    cell, states, levels, spatial,
                    cell_id=cid, step=step_idx, fault=fault,
                    my_pod=my_pod, pod_axis=pod_axis,
                    compare_now=with_compare,
                )
            else:
                prevs, lvl = {}, {}
                for r in {name, *cell.reads}:
                    if r in spatial:
                        if cell.redundancy.level == levels[r]:
                            # replica-paired read of a spatial cell: the
                            # reader's vmap wants the full replica axis
                            local = jax.tree.map(
                                lambda x: x[0], states[r])
                            prevs[r] = gather_replicas(local, pod_axis)
                            lvl[r] = levels[r]
                        else:
                            prevs[r] = canonical_spatial(r)
                            lvl[r] = 1
                    else:
                        prevs[r] = states[r]
                        lvl[r] = levels[r]
                new, rep = run_transition(
                    cell, prevs, lvl,
                    cell_id=cid, step=step_idx, fault=fault,
                    compare_now=with_compare,
                )
            new_states[name] = new
            reports[name] = rep
        return new_states, reports

    state_specs = {
        name: P(pod_axis) if name in spatial else P()
        for name in names
    }
    report_specs = {name: P() for name in names}
    mapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_specs, P(), P()),
        out_specs=(state_specs, report_specs),
        check_vma=False,
    )

    def step(states: dict, step_idx, fault):
        return mapped(states, step_idx, fault)

    return step


@register_backend("spatial_lockstep")
class SpatialLockstepExecutor(LockstepExecutor):
    """Lock-step schedule with spatially-placed replicas (one per pod).

    Requires ``compile(..., mesh=...)`` where the mesh has a ``pod`` axis
    (configurable via ``pod_axis``) whose size equals the replication
    level of every ``placement="spatial"`` cell.  ``init`` places the
    replica axis of spatial cells over the pod axis and replicates
    everything else, unless an explicit ``sharding`` was given.

    The scan ``run``/``stream``, ``compare_every``, fault-window plumbing,
    checkpoint segmentation, ledger attribution, ``pure_step``, and
    ``run_campaign`` are inherited from the lockstep back-end — only the
    per-cell step compiler differs (the ``_compile_step`` hook).
    """

    def __init__(self, program, *, pod_axis: str = "pod", **kw):
        mesh = kw.get("mesh")
        if mesh is None:
            raise ValueError(
                "backend='spatial_lockstep' places replicas across pods: "
                "compile(..., mesh=...) is required")
        if pod_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has no {pod_axis!r} axis (axes: {mesh.axis_names}); "
                "spatial replicas need the pod axis to live on")
        spatial = spatial_cells(program)
        serve = getattr(program, "spatial_serve", None)
        if not spatial and serve is None:
            raise ValueError(
                "program has no placement='spatial' replicated cells; "
                "use backend='lockstep' for temporal redundancy")
        n_pods = mesh.shape[pod_axis]
        if serve is not None:
            # serve mode (repro.serving): the slot axis is sharded over
            # pods and replication lives at the SLOT level in the engine,
            # so there are no per-cell level checks — only an even split.
            if serve["n_slots"] % n_pods:
                raise ValueError(
                    f"spatial serving needs n_slots={serve['n_slots']} "
                    f"divisible by the {pod_axis!r} mesh axis "
                    f"({n_pods} pods)")
        for name, cell in spatial.items():
            if cell.redundancy.level != n_pods:
                raise ValueError(
                    f"cell {name!r} wants {cell.redundancy.level} spatial "
                    f"replicas but the {pod_axis!r} mesh axis has {n_pods} "
                    "pods; they must match (one replica per pod)")
            if ops.word_layout(
                    jax.eval_shape(lambda c=cell: c.init(
                        jax.random.PRNGKey(0)))).total == 0:
                raise ValueError(
                    f"cell {name!r} has an empty state; spatial replication "
                    "has nothing to place across pods")
        self.pod_axis = pod_axis
        self._spatial = spatial
        self._serve = serve
        super().__init__(program, **kw)

    def _compile_step(self, *, with_compare: bool):
        if self._serve is not None:
            return compile_step_spatial_serve(
                self.program, self.mesh, pod_axis=self.pod_axis,
                with_compare=with_compare,
            )
        return compile_step_spatial(
            self.program, self.mesh, pod_axis=self.pod_axis,
            with_compare=with_compare,
        )

    def init(self, key: jax.Array) -> dict:
        """Initialize and *place*: spatial cells' replica axes shard over
        the pod axis, everything else is replicated across the mesh.  In
        serve mode the decoder cell's SLOT axis shards instead (per-leaf
        axis from the ``spatial_serve`` marker)."""
        states = self.program.init_states(key)
        sharding = self.sharding
        if sharding is None:
            rep = NamedSharding(self.mesh, P())
            if self._serve is not None:
                dec, axes = self._serve["cell"], self._serve["axes"]
                mesh, pod_axis = self.mesh, self.pod_axis
                sharding = {
                    name: jax.tree.map(
                        lambda ax: NamedSharding(
                            mesh, P(*((None,) * ax + (pod_axis,)))),
                        axes)
                    if name == dec
                    else jax.tree.map(lambda _: rep, states[name])
                    for name in states
                }
            else:
                pod = NamedSharding(self.mesh, P(self.pod_axis))
                sharding = {
                    name: jax.tree.map(
                        lambda _: pod if name in self._spatial else rep,
                        states[name])
                    for name in states
                }
        states = jax.device_put(states, sharding)
        self._t = 0
        return states

    def metrics(self) -> dict:
        m = super().metrics()
        m["placement"] = "spatial"
        m["pod_axis"] = self.pod_axis
        m["n_pods"] = int(self.mesh.shape[self.pod_axis])
        if self._serve is not None:
            m["slots_per_pod"] = self._serve["n_slots"] // m["n_pods"]
        return m
