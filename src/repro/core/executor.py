"""The unified executor layer: one ``compile()`` over every back-end.

The paper's core claim is that one IR (cells = state + transition) can be
retargeted — sequential, SIMD, MIMD, or replicated for dependability —
*without changing the source program* (MISO §III–§IV).  This module makes
that claim true at the API layer: every scheduler is a registered back-end
behind a single front door,

    exe = miso.compile(program, backend="lockstep" | "lockstep_pallas"
                                        | "host" | "wavefront" | "auto")
    states = exe.init(jax.random.PRNGKey(0))
    result = exe.run(states, n_steps)          # -> RunResult

and all executors speak the same ``Executor`` protocol:

    init(key)                    -> states        (replica axes included)
    step(states, ...)            -> (states', reports)
    run(states, n_steps, ...)    -> RunResult(states, reports, collected)
    stream(states[, n_steps])    -> generator of (states', reports)
    metrics()                    -> dict (FaultLedger / compare / backend
                                    statistics)

Back-ends (see the ``@register_backend`` registry; new back-ends plug in
without touching any call site):

  * ``lockstep``  — one fused, jit-able step computing every cell's
    transition from the previous program state (double-buffered); ``run``
    is an in-graph ``lax.scan``.  Independent cells have no data edges in
    the emitted HLO, so XLA overlaps them (MIMD) and the mesh shards
    instance axes (SIMD).  Production path for training and decoding.
  * ``lockstep_pallas`` — the same schedule with the per-cell redundancy
    epilogue (DMR compare / TMR vote + counts + fingerprint) fused into
    one Pallas kernel per replicated cell per step (see
    ``core/backend_pallas.py``); TPU fast path, ``interpret=True`` off-TPU.
  * ``spatial_lockstep`` — the same schedule with ``placement="spatial"``
    replicas laid one-per-pod across the mesh's ``pod`` axis; detect/vote
    are cross-pod collectives (16-byte fingerprint psum for DMR-hash; see
    ``core/backend_spatial.py``).  Requires ``compile(..., mesh=...)``.
  * ``host``      — lock-step with the paper's §IV recovery protocol in the
    loop: DMR mismatches trigger a third tie-breaking execution from the
    immutable previous buffer; a FaultLedger accumulates per-cell counters
    for permanent-fault localization; checkpoint callbacks snapshot the
    previous buffer while the next step runs.
  * ``wavefront`` — the §III "no global barrier" schedule: the SCC
    condensation of the read graph gives units that advance independently,
    each free-running up to a bounded buffer window ahead of its consumers.
  * ``auto``      — resolves at compile time: wavefront when the dependency
    graph has more than one independent unit (weakly-connected component of
    the SCC condensation — cells with no direct or indirect dependency in
    either direction), lock-step otherwise (``lockstep_pallas`` on TPU,
    ``lockstep`` elsewhere).  "The back-end observes the parallel nature of
    the program" made automatic.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any, Callable, Collection, Iterator, Mapping, Optional

import jax
import jax.numpy as jnp

from .fault import FaultSpec
from .jit import forwarding_jit
from .program import MisoProgram
from .redundancy import (
    FaultLedger,
    make_tiebreak,
    run_transition,
)

Pytree = Any


# --------------------------------------------------------------------------
# lock-step step compilation (shared by the lockstep and host back-ends)
# --------------------------------------------------------------------------
def compile_step(program: MisoProgram, *, with_compare: bool = True):
    """program -> step(states, step_idx, fault) -> (states', reports).

    Reads always come from the *input* ``states`` (never from the dict being
    built), which is exactly the paper's read-prev/write-next semantics.
    ``with_compare=False`` statically elides replica comparison (used by the
    compare-every-k path so skipped steps pay zero compare cost).
    """
    levels = program.levels()
    names = list(program.cells)

    def step(states: dict, step_idx: jax.Array, fault: Optional[FaultSpec]):
        new_states = {}
        reports = {}
        for cid, name in enumerate(names):
            cell = program.cells[name]
            new, rep = run_transition(
                cell, states, levels,
                cell_id=cid, step=step_idx, fault=fault,
                compare_now=with_compare,
            )
            new_states[name] = new
            reports[name] = rep
        return new_states, reports

    return step


# --------------------------------------------------------------------------
# fault-argument plumbing
# --------------------------------------------------------------------------
def _as_fault_list(faults) -> list[FaultSpec]:
    if faults is None:
        return []
    if isinstance(faults, FaultSpec):
        return [faults]
    return list(faults)


def _single_fault(faults) -> FaultSpec:
    fs = _as_fault_list(faults)
    if len(fs) > 1:
        raise ValueError(
            "this backend threads a single FaultSpec through the compiled "
            f"step (step-gated in-graph); got {len(fs)}.  Use "
            "backend='host' for multi-fault campaigns."
        )
    return fs[0] if fs else FaultSpec.none()


def _fault_in_window(faults: list, t: int, stride: int):
    """The armed fault whose step falls in [t, t + stride) — the in-graph
    step gate fires it on the exact sub-step.  A step() call threads one
    FaultSpec, so two strikes in the same window cannot both fire."""
    hits = [f for f in faults if t <= int(f.step) < t + stride]
    if len(hits) > 1:
        raise ValueError(
            f"{len(hits)} faults fall in the step window [{t}, {t + stride})"
            " but one step() threads a single FaultSpec; split the campaign"
            " across runs or steps")
    return hits[0] if hits else None


def _is_traced(tree) -> bool:
    return any(isinstance(l, jax.core.Tracer) for l in jax.tree.leaves(tree))


# --------------------------------------------------------------------------
# result type + protocol base
# --------------------------------------------------------------------------
@dataclasses.dataclass
class RunResult:
    """Uniform return of ``Executor.run`` across every back-end.

    states    -- final program state (replica axes included).
    reports   -- per-cell redundancy reports summed over the run.
    collected -- per-step stack of ``collect(states)`` (None if no collect).
    """

    states: dict
    reports: dict
    collected: Any = None


class Executor:
    """Uniform execution protocol over a compiled MISO program.

    Back-ends subclass this and register under a name; construct through
    ``compile(program, backend=...)``, not directly.  The base class
    provides the generic host-side ``run``/``stream`` loops on top of
    ``step``; back-ends override what they can do better (the lockstep
    back-end's ``run`` is one in-graph ``lax.scan``).
    """

    name: str = "base"
    #: ``step(donate=...)`` really hands the named cells' buffers to the
    #: compiled step, which writes its result over them; back-ends
    #: without it ignore ``donate`` and keep their input
    step_donates: bool = False

    def __init__(
        self,
        program: MisoProgram,
        *,
        mesh=None,
        sharding: Optional[Pytree] = None,
        compare_every: Optional[int] = None,
        donate: bool = True,
        checkpoint_cb: Optional[Callable[[int, dict], None]] = None,
        checkpoint_every: int = 0,
        on_event: Optional[Callable[[str, dict], None]] = None,
    ):
        self.program = program
        self.mesh = mesh
        self.sharding = sharding
        self.compare_every = compare_every or 1
        self.donate = donate
        #: observability hook, sibling of swap/checkpoint_cb in the base
        #: protocol: ``on_event(name, attrs)`` fires for executor-level
        #: events — timed steps and scan segments (``dur_us`` in attrs),
        #: checkpoints, replica-compare mismatches, §IV recoveries.  None
        #: (the default) is genuinely free: every emission site is guarded,
        #: so no event dicts are allocated and no clocks are read.
        #: ``Tracer.executor_hook()`` adapts this into trace events.
        self.on_event = on_event
        #: ``(name) -> context manager`` wrapping the serving stream's
        #: step and its blocking reports read in named host spans; the
        #: serving engine sets it when it has a tracer.  None = no span.
        self.span_hook: Optional[Callable[[str], Any]] = None
        #: checkpointing is part of the base protocol: ``run``/``stream``
        #: hand the cb the consistent pre-step buffer every
        #: ``checkpoint_every`` steps (MISO's double buffering makes the
        #: previous state a snapshot for free).  The lockstep back-end
        #: splits its in-graph scan into segments at the same boundaries;
        #: the serving engine uses this to snapshot resident decoder state.
        self.checkpoint_cb = checkpoint_cb
        self.checkpoint_every = checkpoint_every
        if checkpoint_every and checkpoint_every % self.compare_every != 0:
            raise ValueError(
                "checkpoint_every must be a multiple of compare_every "
                f"(got {checkpoint_every} vs {self.compare_every})")
        self.ledger = FaultLedger()
        self.recoveries: list[tuple[int, str]] = []
        self._t = 0  # next step index when start_step is not given

    # -- state ----------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        """Initialize all cell states (replicated cells get their replica
        axis); places leaves under ``sharding`` when one was given."""
        states = self.program.init_states(key)
        if self.sharding is not None:
            states = jax.device_put(states, self.sharding)
        self._t = 0
        return states

    # -- single transition ----------------------------------------------
    @property
    def step_stride(self) -> int:
        """Transitions one ``step()`` call advances — ``compare_every`` on
        the lockstep back-end (its compiled step fuses k sub-steps), 1
        elsewhere."""
        return self.compare_every

    def step(
        self,
        states: dict,
        *,
        step_idx: Optional[int] = None,
        fault: Optional[FaultSpec] = None,
        donate: Collection[str] = (),
    ) -> tuple[dict, dict]:
        """One step window.  ``donate`` names the cells whose input
        buffers the step may consume (see ``step_donates``): the caller
        holds no other use for them, and they are deleted."""
        raise NotImplementedError

    def pure_step(
        self,
        states: dict,
        step_idx: int,
        fault: Optional[FaultSpec] = None,
        *,
        compare: bool = True,
    ) -> tuple[dict, dict]:
        """Side-effect-free re-execution of one step window: no ledger
        update, no counter advance, no recovery protocol.  This is the
        paper's §IV "third equal transition" surfaced on the executor —
        the serving engine replays a tick from the immutable previous
        buffer to tie-break a DMR mismatch.  ``compare=False``
        additionally elides the replica compare statically (reports stay
        zero; on the spatial back-end the cross-pod compare collectives
        disappear from the dispatch — the straggler policy's adopt path
        really does not wait for the slow pod).  TMR still votes and
        re-synchronizes every sub-step, so the trajectory is unchanged.
        Back-ends with a compiled step implement it; schedules without
        one (wavefront) raise."""
        raise NotImplementedError(
            f"backend {self.name!r} has no side-effect-free replay")

    # -- n-step execution ------------------------------------------------
    def run(
        self,
        states: dict,
        n_steps: int,
        *,
        start_step: Optional[int] = None,
        faults=None,
        collect: Optional[Callable[[dict], Pytree]] = None,
    ) -> RunResult:
        stride = self.step_stride
        if n_steps % stride != 0:
            raise ValueError("n_steps must be a multiple of compare_every")
        start = self._t if start_step is None else int(start_step)
        flist = _as_fault_list(faults)
        totals = None
        collected = [] if collect is not None else None
        for t in range(start, start + n_steps, stride):
            self._maybe_checkpoint(t, states)
            if self.on_event is not None:
                # bracket the dispatch AND the device work: the split
                # tells host-bound from device-bound steps apart
                t0 = time.perf_counter()
                states, rep = self.step(
                    states, step_idx=t,
                    fault=_fault_in_window(flist, t, stride))
                t1 = time.perf_counter()
                jax.block_until_ready(states)
                t2 = time.perf_counter()
                self.on_event("step", {
                    "step": t, "dur_us": (t2 - t0) * 1e6,
                    "dispatch_us": (t1 - t0) * 1e6,
                    "device_us": (t2 - t1) * 1e6,
                })
            else:
                states, rep = self.step(
                    states, step_idx=t,
                    fault=_fault_in_window(flist, t, stride))
            totals = rep if totals is None else jax.tree.map(
                lambda a, b: a + b, totals, rep)
            if collect is not None:
                collected.append(collect(states))
        if collected:
            collected = jax.tree.map(lambda *xs: jnp.stack(xs), *collected)
        return RunResult(states=states,
                         reports=totals if totals is not None else {},
                         collected=collected)

    # -- multi-fault campaigns --------------------------------------------
    def run_campaign(
        self,
        states: dict,
        n_steps: int,
        faults,
        *,
        start_step: Optional[int] = None,
        collect: Optional[Callable[[dict], Pytree]] = None,
    ) -> RunResult:
        """Run the SAME trajectory once per armed ``FaultSpec`` — a fault
        campaign.  Returns a ``RunResult`` whose states/reports/collected
        carry a leading campaign axis of size ``len(faults)``.

        Campaigns are analysis, not production runs: no FaultLedger
        entries, no step-counter advance (the §IV ``pure_step`` contract,
        batched).  This base implementation loops ``pure_step`` on the
        host; the lock-step back-ends override it with a single vmap'd
        in-graph dispatch over a stacked FaultSpec batch.
        """
        flist = _as_fault_list(faults)
        if not flist:
            raise ValueError("run_campaign needs at least one FaultSpec")
        stride = self.step_stride
        if n_steps % stride != 0:
            raise ValueError("n_steps must be a multiple of compare_every")
        start = self._t if start_step is None else int(start_step)
        finals, totals_all, coll_all = [], [], []
        for fault in flist:
            st, totals = states, None
            coll = [] if collect is not None else None
            for t in range(start, start + n_steps, stride):
                st, rep = self.pure_step(
                    st, t, _fault_in_window([fault], t, stride))
                totals = rep if totals is None else jax.tree.map(
                    lambda a, b: a + b, totals, rep)
                if collect is not None:
                    coll.append(collect(st))
            finals.append(st)
            totals_all.append(totals)
            if collect is not None:
                coll_all.append(jax.tree.map(
                    lambda *xs: jnp.stack(xs), *coll))
        stack = lambda trees: jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
        return RunResult(
            states=stack(finals),
            reports=stack(totals_all),
            collected=stack(coll_all) if collect is not None else None,
        )

    # -- serving stream ---------------------------------------------------
    def stream(
        self,
        states: dict,
        n_steps: Optional[int] = None,
        *,
        start_step: Optional[int] = None,
        faults=None,
        swap: Optional[Callable[[int, dict], Optional[dict]]] = None,
        donate: Optional[Callable[[], Collection[str]]] = None,
    ) -> Iterator[tuple[dict, dict]]:
        """Generator of per-step ``(states, reports)`` — the serving loop.
        Each tick advances ``step_stride`` transitions (1 unless the
        lockstep back-end was compiled with ``compare_every``).
        ``n_steps=None`` streams forever (caller breaks).

        ``swap`` is the state swap-in/swap-out hook: called *before* every
        tick with ``(step_idx, states)``; a non-None return value replaces
        the resident states for that tick and onward.  This is how the
        continuous batcher joins/leaves requests in the decoder cell's
        batch between ticks without tearing the stream down.  Checkpoints
        (``checkpoint_cb``) snapshot the post-swap pre-step buffer.

        ``donate``, called after ``swap`` on every tick, names the cells
        that tick's step may consume (``step(donate=...)``)."""
        stride = self.step_stride
        if n_steps is not None and n_steps % stride != 0:
            raise ValueError("n_steps must be a multiple of compare_every")
        start = self._t if start_step is None else int(start_step)
        flist = _as_fault_list(faults)
        t = start
        while n_steps is None or t < start + n_steps:
            if swap is not None:
                swapped = swap(t, states)
                if swapped is not None:
                    states = swapped
            self._maybe_checkpoint(t, states)
            fault = _fault_in_window(flist, t, stride)
            names = donate() if donate is not None else ()
            if self.span_hook is not None:
                with self.span_hook("step"):
                    states, rep = self.step(
                        states, step_idx=t, fault=fault, donate=names)
            else:
                states, rep = self.step(
                    states, step_idx=t, fault=fault, donate=names)
            yield states, rep
            t += stride

    # -- statistics -------------------------------------------------------
    def metrics(self) -> dict:
        """FaultLedger / compare statistics accumulated so far."""
        return {
            "backend": self.name,
            "steps": self._t,
            "fault_totals": self.ledger.totals,
            "flagged": sorted(self.ledger.flagged),
            "suspects": self.ledger.permanent_fault_suspects(),
            "recoveries": list(self.recoveries),
        }

    def export_metrics(self, registry) -> None:
        """Publish this executor's statistics into a ``MetricsRegistry``
        (obs/metrics.py) — typed instruments instead of the ad-hoc dict:
        counters for steps/recoveries and per-cell fault totals, gauges
        for flagged/suspect cells.  Idempotent per call (set, not inc)."""
        registry.gauge(
            "executor_steps",
            "transitions executed by the resident executor").set(self._t)
        registry.gauge(
            "executor_recoveries_total",
            "§IV tie-break recoveries performed").set(len(self.recoveries))
        registry.gauge(
            "executor_flagged_cells",
            "cells currently flagged by the fault ledger").set(
                len(self.ledger.flagged))
        registry.gauge(
            "executor_suspect_cells",
            "cells suspected of a permanent fault").set(
                len(self.ledger.permanent_fault_suspects()))
        for cell, tot in self.ledger.totals.items():
            safe = "".join(c if c.isalnum() else "_" for c in cell)
            registry.gauge(
                f"executor_fault_events_{safe}",
                f"replica-compare mismatch events attributed to cell "
                f"{cell}").set(float(tot["events"]))

    # -- shared internals -------------------------------------------------
    def _maybe_checkpoint(self, t: int, states: dict) -> None:
        if (self.checkpoint_cb is not None and self.checkpoint_every
                and t % self.checkpoint_every == 0):
            # the pre-step buffer is immutable for the duration of the next
            # dispatch (double buffering) — a consistent snapshot for free
            if self.on_event is not None:
                t0 = time.perf_counter()
                self.checkpoint_cb(t, states)
                self.on_event("checkpoint", {
                    "step": t,
                    "dur_us": (time.perf_counter() - t0) * 1e6,
                })
            else:
                self.checkpoint_cb(t, states)

    def _ledger_update(self, step: int, reports: dict) -> None:
        if _is_traced(reports):
            return  # inside an outer trace: no host-side accounting
        host = self._fetch_reports(reports)
        self.ledger.update(step, host)
        if self.on_event is not None:
            self._emit_mismatches(step, host)

    def _fetch_reports(self, reports: dict) -> dict:
        """The step's reports on the host: waits for the step to finish."""
        if self.span_hook is not None:
            with self.span_hook("sync.step_reports"):
                return jax.tree.map(jax.device_get, reports)
        return jax.tree.map(jax.device_get, reports)

    def _emit_mismatches(self, step: int, host_reports: dict) -> None:
        """Surface replica-compare disagreements (caller guards on
        ``on_event``) — one event per cell that detected any this step."""
        for name, rep in host_reports.items():
            ev = rep.get("events") if isinstance(rep, dict) else None
            if ev is not None and int(ev) > 0:
                self.on_event("compare_mismatch", {
                    "step": int(step), "cell": name, "events": int(ev)})

    def _mesh_ctx(self):
        import contextlib

        return self.mesh if self.mesh is not None else contextlib.nullcontext()


# --------------------------------------------------------------------------
# back-end registry
# --------------------------------------------------------------------------
BACKENDS: dict[str, type] = {}


def register_backend(name: str):
    """Class decorator: make an Executor subclass reachable through
    ``compile(program, backend=name)``.  Future back-ends (a Pallas-fused
    lock-step, a sharded spatial-DMR executor, ...) plug in here without
    touching any call site."""

    def deco(cls):
        cls.name = name
        BACKENDS[name] = cls
        return cls

    return deco


def available_backends() -> list[str]:
    return sorted(BACKENDS)


# --------------------------------------------------------------------------
# lock-step back-end
# --------------------------------------------------------------------------
@register_backend("lockstep")
class LockstepExecutor(Executor):
    """Fused single-dispatch schedule; ``run`` is one in-graph scan.

    With ``compare_every=k`` the compiled step advances k transitions with
    replica comparison only on the last one (statically elided on the
    others), so ``step``/``run`` granularity is k transitions.
    """

    step_donates = True

    def _compile_step(self, *, with_compare: bool):
        """Step-function factory hook.  Subclasses (the Pallas-fused
        ``lockstep_pallas`` back-end) swap the per-cell transition/compare
        implementation here; the scan ``run``, ``stream``, fault-window
        plumbing, and per-step ledger attribution above are shared."""
        return compile_step(self.program, with_compare=with_compare)

    def __init__(self, program, **kw):
        super().__init__(program, **kw)
        k = self.compare_every
        self._step_cmp = self._compile_step(with_compare=True)
        self._step_plain = (self._compile_step(with_compare=False)
                            if k > 1 else None)

        def step_fn(states, step_idx, fault):
            for j in range(k - 1):
                states, _ = self._step_plain(states, step_idx + j, fault)
            return self._step_cmp(states, step_idx + k - 1, fault)

        #: raw (unjitted) fused step — (states, step_idx, fault) ->
        #: (states', reports).  Exposed for lowering/cost analysis (the
        #: dry-run driver) and for embedding in larger jit programs.
        self.step_fn = step_fn
        # pass-through cells (static weights) come back as the same
        # buffers instead of a fresh copy per step
        self._jit_step = forwarding_jit(step_fn, name="lockstep_step")
        self._jit_plain_window = None   # lazy: pure_step(compare=False)
        self._run_cache: dict = {}

    def step(self, states, *, step_idx=None, fault=None, donate=()):
        t = self._t if step_idx is None else int(step_idx)
        fault = fault if fault is not None else FaultSpec.none()
        mask = (({name: name in donate for name in states}, False, False)
                if donate else None)
        with self._mesh_ctx():
            states, reports = self._jit_step(
                states, jnp.int32(t), fault, donate=mask)
        # the replica compare runs on the window's last sub-step — attribute
        # events there, matching run()'s per-step ledger entries
        self._ledger_update(t + self.compare_every - 1, reports)
        self._t = t + self.compare_every
        return states, reports

    def pure_step(self, states, step_idx, fault=None, *, compare=True):
        """The §IV third execution: replay one compiled step window with no
        ledger/counter side effects (see ``Executor.pure_step``).
        ``compare=False`` dispatches an all-plain window (every sub-step
        compiled ``with_compare=False``), so the compare — and, spatially,
        its collectives — is statically gone, not merely discarded."""
        fault = fault if fault is not None else FaultSpec.none()
        if not compare:
            if self._jit_plain_window is None:
                plain = (self._step_plain if self._step_plain is not None
                         else self._compile_step(with_compare=False))
                k = self.compare_every

                def window(states, step_idx, fault):
                    reports = None
                    for j in range(k):
                        states, reports = plain(states, step_idx + j, fault)
                    return states, reports

                self._jit_plain_window = forwarding_jit(
                    window, name="lockstep_plain_window")
            with self._mesh_ctx():
                return self._jit_plain_window(
                    states, jnp.int32(int(step_idx)), fault)
        with self._mesh_ctx():
            return self._jit_step(states, jnp.int32(int(step_idx)), fault)

    def _scan_segment(self, states, n_steps, start, fault, collect, donate):
        """One in-graph scan of ``n_steps`` transitions.  Returns
        ``(final, summed_reports, stacked_reports, collected)``."""
        k = self.compare_every
        iters = n_steps // k
        # keyed on the collect callable's identity: pass a *stable* collect
        # to reuse the compiled scan across calls (a fresh lambda per call
        # re-traces).  Bounded so per-call lambdas can't grow it forever.
        key = (n_steps, None if collect is None else id(collect), donate)
        fn = self._run_cache.get(key)
        if fn is None:
            while len(self._run_cache) >= 16:
                self._run_cache.pop(next(iter(self._run_cache)))
            def scan_run(states, start, fault):
                idxs = start + jnp.arange(iters, dtype=jnp.int32) * k

                def body(st, idx):
                    st, rep = self.step_fn(st, idx, fault)
                    out = (rep, collect(st) if collect is not None else None)
                    return st, out

                # per-compare-step reports come back stacked so the host can
                # attribute events to their true step (the FaultLedger's
                # windowed permanent-fault flagging needs per-step entries)
                final, (stacked, collected) = jax.lax.scan(body, states, idxs)
                summed = jax.tree.map(lambda x: jnp.sum(x, axis=0), stacked)
                return final, summed, stacked, collected

            fn = jax.jit(scan_run,
                         donate_argnums=(0,) if donate else ())
            self._run_cache[key] = fn
        with self._mesh_ctx():
            return fn(states, jnp.int32(start), fault)

    def run(self, states, n_steps, *, start_step=None, faults=None,
            collect=None):
        k = self.compare_every
        if n_steps % k != 0:
            raise ValueError("n_steps must be a multiple of compare_every")
        start = self._t if start_step is None else int(start_step)
        fault = _single_fault(faults)
        every = self.checkpoint_every
        # with checkpointing enabled the scan splits into segments whose
        # boundaries land exactly on the checkpoint grid (t % every == 0,
        # reachable from `start` in strides of k — same steps the per-step
        # back-ends fire on), snapshotting between segments.  The cb keeps
        # a live reference to the pre-segment buffer, so checkpointed
        # segments must NOT donate it.  Without checkpointing the whole
        # run is a single donating scan (unchanged).
        cp = (self.checkpoint_cb is not None and every
              and start % k == 0)
        totals = None
        collected_segs = []
        traced = False
        t = start
        while t < start + n_steps:
            if cp:
                n = min((t // every + 1) * every, start + n_steps) - t
            else:
                n = start + n_steps - t
            self._maybe_checkpoint(t, states)
            seg_t0 = time.perf_counter() if self.on_event is not None else 0.0
            states, summed, stacked, collected = self._scan_segment(
                states, n, t, fault, collect,
                self.donate and not cp)
            if self.on_event is not None:
                jax.block_until_ready(states)
                self.on_event("scan_segment", {
                    "start": t, "n_steps": n,
                    "dur_us": (time.perf_counter() - seg_t0) * 1e6,
                })
            totals = summed if totals is None else jax.tree.map(
                lambda a, b: a + b, totals, summed)
            if collect is not None:
                collected_segs.append(collected)
            if _is_traced(stacked):
                traced = True
            else:
                host = jax.tree.map(jax.device_get, stacked)
                for i in range(n // k):
                    step_host = jax.tree.map(lambda x, i=i: x[i], host)
                    self.ledger.update(t + i * k + k - 1, step_host)
                    if self.on_event is not None:
                        self._emit_mismatches(t + i * k + k - 1, step_host)
            t += n
        if not traced:
            self._t = start + n_steps
        collected = None
        if collect is not None:
            collected = (collected_segs[0] if len(collected_segs) == 1
                         else jax.tree.map(
                             lambda *xs: jnp.concatenate(xs, axis=0),
                             *collected_segs))
        return RunResult(states=states,
                         reports=totals if totals is not None else {},
                         collected=collected)

    def run_campaign(self, states, n_steps, faults, *, start_step=None,
                     collect=None):
        """The vmap'd campaign: N FaultSpecs stack into one batched spec
        and the whole N-trajectory sweep is ONE dispatch (scan inside
        vmap), instead of the base class's host loop.  The initial states
        are closed over, so they broadcast across the batch without
        copying.  Same contract as the base: a leading campaign axis on
        every output, no ledger/counter side effects."""
        flist = _as_fault_list(faults)
        if not flist:
            raise ValueError("run_campaign needs at least one FaultSpec")
        k = self.compare_every
        if n_steps % k != 0:
            raise ValueError("n_steps must be a multiple of compare_every")
        start = self._t if start_step is None else int(start_step)
        # one batched spec strikes any cell: the stack's static part agrees
        flist = [dataclasses.replace(f, cells=None) for f in flist]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *flist)
        iters = n_steps // k
        # compiled-campaign cache, sibling of the run() scan cache: states
        # and start are traced arguments (not closed-over constants), so
        # repeated campaigns — a sensitivity sweep loop — retrace nothing
        key = ("campaign", n_steps,
               None if collect is None else id(collect))
        fn = self._run_cache.get(key)
        if fn is None:
            while len(self._run_cache) >= 16:
                self._run_cache.pop(next(iter(self._run_cache)))

            def campaign_run(states, start, stacked):
                def one(fault):
                    idxs = start + jnp.arange(iters, dtype=jnp.int32) * k

                    def body(st, idx):
                        st, rep = self.step_fn(st, idx, fault)
                        out = (rep,
                               collect(st) if collect is not None else None)
                        return st, out

                    final, (reps, coll) = jax.lax.scan(body, states, idxs)
                    summed = jax.tree.map(
                        lambda x: jnp.sum(x, axis=0), reps)
                    return final, summed, coll

                # `one` maps over the fault batch only; states/start are
                # broadcast through the closure (vmap in_axes=None)
                return jax.vmap(one)(stacked)

            fn = jax.jit(campaign_run)
            self._run_cache[key] = fn
        with self._mesh_ctx():
            finals, reports, coll = fn(states, jnp.int32(start), stacked)
        return RunResult(states=finals, reports=reports,
                         collected=coll if collect is not None else None)


# --------------------------------------------------------------------------
# host back-end: §IV recovery protocol in the loop
# --------------------------------------------------------------------------
@register_backend("host")
class HostExecutor(Executor):
    """Lock-step with the paper's §IV recovery in the host loop.

    Extra options: ``ledger`` (a FaultLedger), ``jit`` (default True).
    Checkpointing (``checkpoint_cb``/``checkpoint_every``) is part of the
    base protocol now — the run/stream loops snapshot the immutable
    previous buffer.  Accepts a *list* of FaultSpecs in ``run`` — one
    armed strike per step.
    """

    def __init__(self, program, *, ledger: Optional[FaultLedger] = None,
                 jit: bool = True, **kw):
        super().__init__(program, **kw)
        if self.compare_every != 1:
            raise ValueError(
                "backend='host' compares every step (the §IV protocol needs "
                "per-step reports); use backend='lockstep' for "
                "compare_every amortization")
        if ledger is not None:
            self.ledger = ledger
        self._jit = jit
        self._step = compile_step(program)
        self._step_nocmp = None        # lazy: pure_step(compare=False)
        if jit:
            self._step = jax.jit(self._step)
        levels = program.levels()
        self._tiebreakers = {
            name: (jax.jit(make_tiebreak(cell, levels)) if jit
                   else make_tiebreak(cell, levels))
            for name, cell in program.cells.items()
            if cell.redundancy.level == 2
        }

    def pure_step(self, states, step_idx, fault=None, *, compare=True):
        """Replay one transition with no ledger/recovery side effects (the
        §IV third execution; see ``Executor.pure_step``)."""
        fault = fault if fault is not None else FaultSpec.none()
        if not compare:
            if self._step_nocmp is None:
                fn = compile_step(self.program, with_compare=False)
                self._step_nocmp = jax.jit(fn) if self._jit else fn
            with self._mesh_ctx():
                return self._step_nocmp(
                    states, jnp.int32(int(step_idx)), fault)
        with self._mesh_ctx():
            return self._step(states, jnp.int32(int(step_idx)), fault)

    def step(self, states, *, step_idx=None, fault=None, donate=()):
        t = self._t if step_idx is None else int(step_idx)
        prev = states  # immutable previous buffer (double buffering)
        fault = fault if fault is not None else FaultSpec.none()
        with self._mesh_ctx():
            states, reports = self._step(prev, jnp.int32(t), fault)
        host_reports = self._fetch_reports(reports)
        self.ledger.update(t, host_reports)
        if self.on_event is not None:
            self._emit_mismatches(t, host_reports)
        # paper §IV: DMR mismatch -> third equal transition decides
        for name, rep in host_reports.items():
            cell = self.program.cells[name]
            if cell.redundancy.level == 2 and rep["events"] > 0:
                if self.on_event is not None:
                    t0 = time.perf_counter()
                    states = dict(states)
                    states[name] = self._tiebreakers[name](
                        prev, states[name])
                    jax.block_until_ready(states[name])
                    self.on_event("dmr_recovery", {
                        "step": t, "cell": name,
                        "dur_us": (time.perf_counter() - t0) * 1e6,
                    })
                else:
                    states = dict(states)
                    states[name] = self._tiebreakers[name](
                        prev, states[name])
                self.recoveries.append((t, name))
        self._t = t + 1
        return states, host_reports


# --------------------------------------------------------------------------
# wavefront back-end (paper §III: no global barrier)
# --------------------------------------------------------------------------
@register_backend("wavefront")
class WavefrontExecutor(Executor):
    """Dependency-aware asynchronous execution.

    Units = SCCs of the read graph.  Unit u may compute its step t+1 as soon
    as every unit it reads has produced step t (it does NOT wait for the rest
    of the program), bounded by ``window`` so producers never run more than
    ``window`` steps ahead of their slowest consumer (bounded buffers).
    Dispatches are independent jit calls, so JAX's async dispatch overlaps
    them on real hardware.
    """

    def __init__(self, program, *, window: int = 4, jit: bool = True, **kw):
        super().__init__(program, **kw)
        if self.compare_every != 1:
            raise ValueError("backend='wavefront' does not amortize "
                             "compares; compare_every must be 1")
        self.window = window
        g = program.graph()
        self.units, self._edges = g.condensation()
        self._unit_of = {}
        for i, comp in enumerate(self.units):
            for n in comp:
                self._unit_of[n] = i
        self._levels = program.levels()
        # external reads per unit
        self._ext_reads: list[set[str]] = []
        for comp in self.units:
            ext = set()
            for n in comp:
                for r in program.cells[n].reads:
                    if self._unit_of[r] != self._unit_of[n]:
                        ext.add(r)
            self._ext_reads.append(ext)
        self._consumers: dict[int, set[int]] = {
            i: set() for i in range(len(self.units))
        }
        for i, deps in self._edges.items():
            for d in deps:
                self._consumers[d].add(i)
        self._unit_step = [self._make_unit_step(i, jit)
                           for i in range(len(self.units))]
        self.trace: list[tuple[int, int]] = []  # (unit, step) order

    def _make_unit_step(self, ui: int, jit: bool):
        comp = self.units[ui]
        cells = [self.program.cells[n] for n in comp]
        ids = {n: self.program.cell_id(n) for n in comp}

        def ustep(own: dict, ext: dict, step_idx, fault):
            env = {**own, **ext}
            new, reports = {}, {}
            for cell in cells:
                new[cell.name], reports[cell.name] = run_transition(
                    cell, env, self._levels,
                    cell_id=ids[cell.name], step=step_idx, fault=fault,
                )
            return new, reports

        return jax.jit(ustep) if jit else ustep

    def step(self, states, *, step_idx=None, fault=None, donate=()):
        """One globally synchronized transition (all units advance once).
        Read-prev semantics make unit order irrelevant within a step."""
        t = self._t if step_idx is None else int(step_idx)
        fault = fault if fault is not None else FaultSpec.none()
        new, reports = {}, {}
        for ui in range(len(self.units)):
            own = {n: states[n] for n in self.units[ui]}
            ext = {r: states[r] for r in self._ext_reads[ui]}
            nstates, reps = self._unit_step[ui](own, ext, jnp.int32(t), fault)
            new.update(nstates)
            reports.update(reps)
        self._ledger_update(t, reports)
        self._t = t + 1
        return new, reports

    def run(self, states, n_steps, *, start_step=None, faults=None,
            collect=None):
        if collect is not None:
            raise ValueError(
                "backend='wavefront' advances units out of global step "
                "order, so a per-step collect of the full program state "
                "does not exist; use .stream() for per-step observation")
        if self.checkpoint_cb is not None and self.checkpoint_every:
            raise ValueError(
                "backend='wavefront' has no globally consistent cut "
                "mid-run (units free-run); use .stream(), whose ticks are "
                "globally synchronized, for checkpointing")
        start = self._t if start_step is None else int(start_step)
        fault = _single_fault(faults)
        nU = len(self.units)
        clock = [0] * nU
        # history[name] = deque of (step, state) for produced states
        hist: dict[str, collections.deque] = {
            n: collections.deque([(0, states[n])], maxlen=self.window + 1)
            for n in self.program.cells
        }
        self.trace.clear()
        step_reports: dict[int, dict] = {}  # step -> per-cell reports

        def ready(ui: int) -> bool:
            t = clock[ui]
            if t >= n_steps:
                return False
            for r in self._ext_reads[ui]:
                if not any(s == t for s, _ in hist[r]):
                    return False  # dependency hasn't produced step t yet
            for k in self._consumers[ui]:
                if t - clock[k] >= self.window:
                    return False  # bounded buffer: don't outrun consumers
            return True

        progressed = True
        while progressed:
            progressed = False
            for ui in range(nU):
                while ready(ui):
                    t = clock[ui]
                    own = {
                        n: next(st for s, st in hist[n] if s == t)
                        for n in self.units[ui]
                    }
                    ext = {
                        r: next(st for s, st in hist[r] if s == t)
                        for r in self._ext_reads[ui]
                    }
                    new, reps = self._unit_step[ui](
                        own, ext, jnp.int32(start + t), fault)
                    for n, st in new.items():
                        hist[n].append((t + 1, st))
                    step_reports.setdefault(t, {}).update(reps)
                    clock[ui] = t + 1
                    self.trace.append((ui, t))
                    if self.on_event is not None:
                        # the barrier-free schedule is the observable:
                        # emission order IS the wavefront execution order
                        self.on_event("unit_step", {
                            "unit": ui, "step": t,
                            "lead": max(clock) - min(clock)})
                    progressed = True
        if any(c != n_steps for c in clock):
            raise RuntimeError(f"wavefront deadlock: clocks={clock}")
        # single host sync at the end: attribute events to their true step
        # so the ledger's windowed permanent-fault flagging works here too
        totals = None
        for t in sorted(step_reports):
            self._ledger_update(start + t, step_reports[t])
            totals = step_reports[t] if totals is None else jax.tree.map(
                lambda a, b: a + b, totals, step_reports[t])
        self._t = start + n_steps
        final = {n: hist[n][-1][1] for n in self.program.cells}
        return RunResult(states=final, reports=totals or {})

    def max_lead(self) -> int:
        """Largest step-gap between units observed during execution — >0
        proves barrier-free overlap (paper §III)."""
        lead, clocks = 0, [0] * len(self.units)
        for ui, t in self.trace:
            clocks[ui] = t + 1
            lead = max(lead, max(clocks) - min(clocks))
        return lead

    def metrics(self) -> dict:
        m = super().metrics()
        m["units"] = len(self.units)
        m["max_lead"] = self.max_lead()
        m["window"] = self.window
        return m


# --------------------------------------------------------------------------
# the front door
# --------------------------------------------------------------------------
def _lockstep_flavor() -> str:
    """The lock-step back-end ``auto`` resolves to: on TPU the Pallas-fused
    ``lockstep_pallas`` (one fused kernel per replicated cell per step) is
    the fast path; elsewhere the XLA-fused ``lockstep``.  (Named explicitly,
    ``lockstep_pallas`` still runs off-TPU via ``interpret=True``.)"""
    from repro.kernels import ops

    if ops.on_tpu() and "lockstep_pallas" in BACKENDS:
        return "lockstep_pallas"
    return "lockstep"


def _auto_backend(program: MisoProgram) -> str:
    """Wavefront when the SCC condensation of the read graph has >1
    independent unit (weakly-connected component — no direct or indirect
    dependency in either direction), lock-step otherwise."""
    return ("wavefront"
            if len(program.graph().independent_groups()) > 1
            else _lockstep_flavor())


def _wants_spatial(program: MisoProgram, mesh, pod_axis: str) -> bool:
    """True when the program asks for spatial replica placement AND the
    mesh can realize it for EVERY spatial cell — auto then resolves to the
    spatial back-end (the only schedule that puts replicas on distinct
    pods).  A spatial cell the pod axis cannot hold keeps the whole
    program on the temporal fallback instead of a compile-time error
    (auto must always produce a runnable executor)."""
    from repro.kernels import ops

    if mesh is None or pod_axis not in getattr(mesh, "axis_names", ()):
        return False
    spatial = [
        c for c in program.cells.values()
        if c.redundancy.level > 1 and c.redundancy.placement == "spatial"
    ]
    return bool(spatial) and all(
        c.redundancy.level == mesh.shape[pod_axis]
        # mirror every constructor validation: an empty state has nothing
        # to place across pods, so it too falls back to temporal
        and ops.word_layout(jax.eval_shape(
            lambda c=c: c.init(jax.random.PRNGKey(0)))).total > 0
        for c in spatial
    )


def compile(
    program: MisoProgram,
    *,
    backend: str = "lockstep",
    mesh=None,
    sharding: Optional[Pytree] = None,
    policies: Optional[Mapping[str, Any]] = None,
    compare_every: Optional[int] = None,
    donate: bool = True,
    checkpoint_cb: Optional[Callable[[int, dict], None]] = None,
    checkpoint_every: int = 0,
    on_event: Optional[Callable[[str, dict], None]] = None,
    **backend_opts,
) -> Executor:
    """Compile a MisoProgram into an Executor — the single front door.

    backend       -- "lockstep" | "lockstep_pallas" | "spatial_lockstep"
                     | "host" | "wavefront" | "auto" (or any name added
                     through ``register_backend``).
    mesh          -- optional jax Mesh; compilation/execution happen under
                     this mesh context.  Required by the spatial back-end
                     (the replica axis lives on the mesh's ``pod`` axis).
    sharding      -- optional pytree of shardings applied to the states at
                     ``init``.
    policies      -- optional {cell_name: RedundancyPolicy}: selective
                     replication (§IV) applied before compilation, so the
                     *same* program runs under different dependability
                     decisions.
    compare_every -- compare replicas every k-th transition (lockstep-only
                     beyond-paper amortization).
    donate        -- donate the input state buffers of the in-graph run
                     (double-buffer in place; lockstep back-end).
    checkpoint_cb -- ``(step, states) -> None``, part of the base Executor
                     protocol: run/stream snapshot the consistent pre-step
                     buffer every ``checkpoint_every`` steps.  The lockstep
                     back-end splits its in-graph scan into segments at the
                     checkpoint boundaries; the wavefront back-end supports
                     it on ``stream`` only (its ``run`` has no globally
                     consistent mid-run cut).
    on_event      -- ``(name, attrs) -> None`` observability hook, part of
                     the base protocol alongside swap/checkpoint_cb: fires
                     for timed steps, scan segments, checkpoints, compare
                     mismatches, and §IV recoveries on every back-end.
                     ``Tracer.executor_hook()`` (obs/trace.py) adapts it
                     into Perfetto-loadable trace events.  None (default)
                     allocates nothing and reads no clocks.
    backend_opts  -- forwarded to the back-end (host: ledger, jit;
                     wavefront: window, jit; lockstep_pallas: interpret,
                     block; spatial_lockstep: pod_axis).
    """
    if policies:
        program = program.with_policies(policies)
    auto = backend == "auto"
    if auto:
        backend = _auto_backend(program)
        if compare_every and compare_every > 1 and backend == "wavefront":
            # only the lock-step back-ends amortize compares; honor the
            # option rather than letting the graph shape pick a back-end
            # that would reject it
            backend = _lockstep_flavor()
        if ("spatial_lockstep" in BACKENDS
                and _wants_spatial(program, mesh,
                                   backend_opts.get("pod_axis", "pod"))):
            # spatial placement is a *policy request*: only the spatial
            # back-end honors it (replicas on distinct pods), so it wins
            # over the graph-shape choice
            backend = "spatial_lockstep"
        else:
            spatial = sorted(n for n, c in program.cells.items()
                             if c.redundancy.level > 1
                             and c.redundancy.placement == "spatial")
            if spatial:
                warnings.warn(
                    f"cells {spatial} ask for spatial placement, but the "
                    f"mesh cannot hold them; their replicas run temporally "
                    f"on {backend!r}", stacklevel=2)
    try:
        cls = BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown backend {backend!r}; registered backends: "
            f"{available_backends()}") from None
    if auto and backend_opts:
        # auto may resolve to any back-end, so hints for the others
        # (e.g. window= when lockstep wins) are dropped, not fatal
        import inspect

        accepted = set(inspect.signature(cls.__init__).parameters)
        backend_opts = {k: v for k, v in backend_opts.items()
                        if k in accepted}
    return cls(program, mesh=mesh, sharding=sharding,
               compare_every=compare_every, donate=donate,
               checkpoint_cb=checkpoint_cb, checkpoint_every=checkpoint_every,
               on_event=on_event, **backend_opts)
