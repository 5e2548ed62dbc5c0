"""The continuous-batching serving engine.

One resident decoder program (a weights cell + a slot-masked decoder
cell) is compiled ONCE and driven through ``Executor.stream``; the engine
multiplexes many independent decode requests onto its fixed batch:

  * between ticks, the stream's ``swap`` hook scatters freshly prefilled
    prompt caches into free slots (join) and scrubs finished ones
    (leave/compact) — the resident states never leave the device;
  * per tick, the engine harvests each running request's new token,
    checks stop/budget/deadline, and evicts finished requests;
  * per-request dependability: a request's ``RedundancyPolicy`` maps onto
    *replica slots* of the same batch — replication is mechanically
    identical to data parallelism (core/redundancy.py), so DMR = the same
    prompt joined into 2 slots, TMR = 3.  Replica slots compute bitwise-
    identical trajectories unless hardware misbehaves; the engine
    compares their 128-bit per-slot fingerprints between ticks,
    attributes any mismatch to the *owning request* in the engine's
    FaultLedger, repairs (TMR: copy a majority slot over the minority;
    DMR: the paper's §IV third execution — ``Executor.pure_step`` replays
    the tick from the immutable previous buffer — decides, and both
    replicas adopt the replay), and only then emits the token.

The isolation invariant that makes all of this sound: an active slot's
trajectory is bitwise-identical no matter which other slots are occupied
(row-independent batch math + slot-masked writeback), so requests join
and leave mid-stream without perturbing anyone — tested in
tests/test_serving.py against static-batch decodes.
"""

from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import numpy as np

from repro.core import executor as _ex
from repro.core.redundancy import FaultLedger
from repro.obs import MetricsRegistry, Tracer

from .request import (
    CANCELLED,
    DONE,
    EXPIRED,
    QUEUED,
    REJECTED,
    RUNNING,
    Request,
    RequestQueue,
)
from .slots import SlotManager, SlotSurgery, default_surgery

Pytree = Any


def _fence(x: Pytree) -> None:
    """Block until ONE leaf of ``x`` is ready.

    The traced paths bracket device work this way.  One leaf is a
    sufficient fence for the outputs of a single compiled executable —
    they become ready together — and descending to it is O(depth),
    where ``jax.block_until_ready`` on the whole pytree walks (and
    blocks) every leaf, which costs measurable per-tick time on
    sub-millisecond ticks.
    """
    while isinstance(x, (dict, list, tuple)):
        x = next(iter(x.values())) if isinstance(x, dict) else x[0]
    jax.block_until_ready(x)


# --------------------------------------------------------------------------
# the typed engine configuration (replaces the old kwargs pass-through)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything ``ServingEngine`` needs beyond the program + adapter,
    as one typed value instead of the historical ``**engine_opts`` /
    ``**compile_opts`` double pass-through (which silently swallowed
    typos and made the executor surface invisible at the call site).

    backend          -- executor backend name (``miso.serve`` compiles
                        the program onto it).  With
                        ``placement="spatial"`` a plain ``"lockstep"``
                        auto-upgrades to ``"spatial_lockstep"``.
    placement        -- where a DMR/TMR request's replica slots live:
                        ``"temporal"`` = batch rows of one device group
                        (host fingerprint compare), ``"spatial"`` = the
                        same slot column on different mesh pods under
                        ``shard_map`` (O(1)-wire cross-pod detect).
    mesh / pod_axis  -- the device mesh (required for spatial placement)
                        and the axis replica slots are placed along.
    max_queue        -- bounded admission queue depth (back-pressure).
    retain_results   -- finished records kept for ``result()`` pickup.
    compare_every    -- executor compare cadence (None = backend default).
    checkpoint_cb/checkpoint_every -- executor checkpoint segmentation.
    tracer / registry -- the observability pair (obs/).
    compile_opts     -- escape hatch: extra kwargs for the executor
                        (``donate``, ``sharding``, ``policies``, ...).
    """

    backend: str = "lockstep"
    placement: str = "temporal"
    mesh: Any = None
    pod_axis: str = "pod"
    max_queue: int = 64
    retain_results: int = 1024
    compare_every: Optional[int] = None
    checkpoint_cb: Optional[Callable] = None
    checkpoint_every: int = 0
    tracer: Optional[Tracer] = None
    registry: Optional[MetricsRegistry] = None
    compile_opts: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.placement not in ("temporal", "spatial"):
            raise ValueError(
                f"placement={self.placement!r}: must be 'temporal' or 'spatial'"
            )
        if self.placement == "spatial":
            if self.mesh is None:
                raise ValueError(
                    "placement='spatial' places replica slots across mesh "
                    "pods: EngineConfig(mesh=...) is required"
                )
            if self.backend == "lockstep":
                object.__setattr__(self, "backend", "spatial_lockstep")


class EngineParts(NamedTuple):
    """Named return of ``lm_engine_parts``: the compiled-against program
    and its slot adapter.  Tuple-unpackable, so the historical
    ``prog, adapter = lm_engine_parts(...)`` keeps working."""

    program: Any
    adapter: "SlotAdapter"


# --------------------------------------------------------------------------
# the model adapter: everything request-format-specific in one place
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SlotAdapter:
    """What the engine needs to know about the slotted program.

    cell        -- name of the slot-masked decoder cell.
    n_slots     -- its batch width.
    slot_axes   -- per-leaf slot-axis pytree of the cell state
                   (``slots.infer_slot_axes``).
    prefill     -- ``(request, states) -> (slot_state, first_token)`` or
                   ``-> (slot_state, first_token | None, n_pending)``:
                   run the prompt (or its first chunk), return a width-1
                   decoder slot state ready to join, plus the first
                   emitted token.  The 3-tuple form supports chunked
                   prefill: ``n_pending`` > 0 means the slot still holds
                   that many prompt-tail tokens which the resident
                   transition consumes one per tick — no token is
                   emitted (first_token is None) until the walk drains.
    read_tokens -- ``(cell_state) -> (B, ...)`` device array of each
                   slot's last emitted token.
    make_empty  -- ``() -> slot_state``: a width-1 *inactive* slot state
                   (scrubbed cache); scattered over evicted slots.
    validate    -- optional ``(request) -> str | None`` admission check
                   (e.g. prompt longer than the cache); a string rejects.
    stats       -- optional ``() -> dict`` of adapter-side counters
                   merged into ``engine.metrics()`` (the LM adapter
                   reports ``prefill_compiles`` / ``prefill_buckets``).
    surgery     -- optional ``slots.SlotSurgery`` overriding how slot
                   state is joined/scrubbed/compared (the paged-KV
                   adapter routes these through its page table); None =
                   ``slots.default_surgery`` over the dense layout.
    has_capacity-- optional ``(request) -> bool`` extra admission gate
                   beyond free slots (paged: free PAGES for the
                   request's worst case); False holds the FIFO head.
    pre_tick    -- optional ``(states) -> states`` hook run after
                   admission, before the tick's input buffer is
                   snapshotted (paged: demand-map + zero the pages the
                   transition is about to write — running it pre-snapshot
                   keeps §IV replays bitwise-faithful).
    walk_chunk  -- prompt-tail tokens the resident transition consumes
                   per tick (``ServeConfig.prefill_chunk`` k-token walk);
                   the engine's host-side ``prefill_remaining`` ledger
                   drains at this rate.
    read_spec   -- optional ``(cell_state) -> (spec_out, spec_n)``:
                   speculative decoding's multi-token harvest.
                   ``spec_out`` is (B, K+1) committed tokens in emission
                   order, ``spec_n`` (B,) the committed count — > 0 for
                   a slot that ran a verify pass this tick (1 means the
                   first draft token was rejected), 0 for a slot that
                   plain-decoded (harvest falls back to one
                   ``read_tokens`` token).  Emission is per-token, so
                   stop/budget/deadline fire mid-commit exactly where
                   non-speculative decode would have stopped.
    contiguous_replicas -- replica slots need one adjacent run (dense
                   layout: the spatial-placement notch).  The paged
                   layout clears it — pages have no adjacency, so
                   replicated admissions never defragment.
    attach_tracer -- optional ``(tracer) -> None``: hand the engine's
                   tracer to adapter-side closures that emit their own
                   events and spans (the paged ``pre_tick`` and join).
                   Called once by the engine when a tracer is attached;
                   never called when tracing is off.
    """

    cell: str
    n_slots: int
    slot_axes: Pytree
    prefill: Callable[[Request, dict], tuple]
    read_tokens: Callable[[Pytree], jax.Array]
    make_empty: Callable[[], Pytree]
    validate: Optional[Callable[[Request], Optional[str]]] = None
    stats: Optional[Callable[[], dict]] = None
    surgery: Optional[SlotSurgery] = None
    has_capacity: Optional[Callable[[Request], bool]] = None
    pre_tick: Optional[Callable[[dict], dict]] = None
    walk_chunk: int = 1
    contiguous_replicas: bool = True
    read_spec: Optional[Callable[[Pytree], tuple]] = None
    attach_tracer: Optional[Callable[[Tracer], None]] = None


@dataclasses.dataclass
class RequestRecord:
    """Engine-side lifecycle record of one request (the report ledger's
    unit of attribution)."""

    req: Request
    status: str
    submitted_at: float
    slots: list[int] = dataclasses.field(default_factory=list)
    tokens: list[np.ndarray] = dataclasses.field(default_factory=list)
    ttft: Optional[float] = None
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    faults: int = 0
    cancel_requested: bool = False
    #: replica slots placed spatially (same column on different pods):
    #: checked by the cross-pod collective instead of the host compare
    spatial: bool = False
    #: chunked prefill: prompt-tail tokens the resident transition still
    #: has to consume before this request emits its first token (advances
    #: in lock-step with the device-side ``p_head`` cursor)
    prefill_remaining: int = 0
    #: tracing only: a "prefill_walk" span is open on this request's
    #: track (must be closed before the lifecycle span can end — B/E
    #: events nest as a stack per track)
    trace_walk_open: bool = False
    #: tracing only: the "queue_wait" span, open from submission until
    #: admission (or until the request leaves the queue unserved)
    trace_queue: Any = None

    @property
    def id(self) -> str:
        return self.req.id

    def token_ids(self) -> list[int]:
        return [int(t.reshape(-1)[0]) for t in self.tokens]


class ServingEngine:
    """Continuous batcher over one compiled ``Executor``.

    Construct through ``miso.serve(program, adapter, ...)``; then::

        engine.start(jax.random.PRNGKey(0))
        engine.submit(Request(prompt, max_new_tokens=32))
        engine.submit(Request(prompt2, policy=RedundancyPolicy(level=2)))
        engine.pump()                  # tick until drained
        engine.result("r0")            # tokens, status, ttft, faults
        engine.metrics()               # tokens/s, TTFT p50/p99, ledger
    """

    #: legacy kwargs the deprecation shim lifts into EngineConfig fields
    #: (anything else lands in ``compile_opts``, exactly as before)
    _LEGACY_FIELDS = (
        "backend",
        "placement",
        "mesh",
        "pod_axis",
        "max_queue",
        "retain_results",
        "compare_every",
        "checkpoint_cb",
        "checkpoint_every",
        "tracer",
        "registry",
    )

    def __init__(
        self,
        program,
        adapter: SlotAdapter,
        config: Optional[EngineConfig] = None,
        *,
        time_fn: Callable[[], float] = time.monotonic,
        **legacy,
    ):
        if legacy:
            # one-release shim: old kwargs keep working, loudly
            if config is not None:
                raise TypeError(
                    "pass EngineConfig OR the legacy keyword options, not both"
                )
            warnings.warn(
                "ServingEngine(program, adapter, backend=..., "
                "**compile_opts) is deprecated; pass "
                "config=EngineConfig(...) instead (legacy kwargs are "
                "honored for one release)",
                DeprecationWarning,
                stacklevel=2,
            )
            fields = {
                k: legacy.pop(k) for k in list(legacy) if k in self._LEGACY_FIELDS
            }
            config = EngineConfig(**fields, compile_opts=legacy)
        self.config = cfg = config if config is not None else EngineConfig()
        self.adapter = adapter
        #: spatial placement: replica slots live at one column across
        #: ``pods`` mesh pods; 1 = the temporal engine, bit for bit
        self.pods = 1
        if cfg.placement == "spatial":
            self.pods = int(cfg.mesh.shape[cfg.pod_axis])
            if adapter.n_slots % self.pods:
                raise ValueError(
                    f"spatial serving needs n_slots={adapter.n_slots} "
                    f"divisible by the {cfg.pod_axis!r} mesh axis "
                    f"({self.pods} pods)"
                )
        #: the observability pair.  ``tracer=None`` (default) is genuinely
        #: free: every emission site is guarded, the harvest path never
        #: allocates event objects, and tokens are bitwise-identical with
        #: and without it (gated in tests/test_obs.py).  The registry is
        #: always present — it IS the engine's counter storage.
        self.tracer = tracer = cfg.tracer
        self.registry = cfg.registry if cfg.registry is not None else MetricsRegistry()
        compile_opts = dict(cfg.compile_opts)
        if cfg.mesh is not None:
            compile_opts.setdefault("mesh", cfg.mesh)
        if cfg.compare_every is not None:
            compile_opts.setdefault("compare_every", cfg.compare_every)
        if cfg.checkpoint_cb is not None:
            compile_opts.setdefault("checkpoint_cb", cfg.checkpoint_cb)
        if cfg.checkpoint_every:
            compile_opts.setdefault("checkpoint_every", cfg.checkpoint_every)
        if cfg.placement == "spatial":
            compile_opts.setdefault("pod_axis", cfg.pod_axis)
        if tracer is not None and "on_event" not in compile_opts:
            # executor-level events (checkpoints, scan segments, compare
            # mismatches) land on the tracer's "executor" track
            compile_opts["on_event"] = tracer.executor_hook()
        if tracer is not None and adapter.attach_tracer is not None:
            # adapter closures (paged pre_tick page faults) emit too
            adapter.attach_tracer(tracer)
        self.exe = _ex.compile(program, backend=cfg.backend, **compile_opts)
        if tracer is not None:
            # the step and its blocking reports read, as engine spans
            self.exe.span_hook = lambda name: tracer.span(name, "engine")
        if type(self.exe).pure_step is _ex.Executor.pure_step:
            with_replay = sorted(
                name
                for name, klass in _ex.BACKENDS.items()
                if klass.pure_step is not _ex.Executor.pure_step
            )
            raise ValueError(
                f"backend {self.exe.name!r} has no pure_step replay; the "
                "engine needs it for DMR tie-breaks (backends with "
                f"replay: {', '.join(with_replay)})"
            )
        self.queue = RequestQueue(
            max_depth=cfg.max_queue, time_fn=time_fn, on_expire=self._on_queue_expire
        )
        self.slots = SlotManager(adapter.n_slots, pods=self.pods)
        self.ledger = FaultLedger()  # keyed by REQUEST id, not cell name
        self.time_fn = time_fn
        retain_results = cfg.retain_results
        self.requests: dict[str, RequestRecord] = {}
        #: finished records are retained for result() pickup, bounded so a
        #: long-running server's host memory stays flat: beyond
        #: `retain_results` finished requests, the oldest record (and its
        #: queue-status + non-flagged ledger entries) is dropped FIFO.
        #: Callers that want immediate reclamation call drop(rid).
        self.retain_results = retain_results
        self._finished: collections.deque[str] = collections.deque()
        self._states: Optional[dict] = None
        self._override: Optional[dict] = None
        self._tick_input: Optional[dict] = None
        self._tick_step: int = 0
        #: the step may write the decoder cell's new state over its input
        #: buffers on a tick whose pre-step state nothing reads again: no
        #: checkpoint callback, and (decided per tick in ``_swap``) no
        #: replicated request resident, so no §IV replay
        self._may_donate = self.exe.step_donates and self.exe.checkpoint_cb is None
        #: the cells the current tick's step consumes
        self._donate: tuple[str, ...] = ()
        #: counters live in the registry (typed instruments with
        #: Prometheus/JSON exposition replace the old ad-hoc ints);
        #: ``metrics()`` reads them back under the historical key names
        R = self.registry
        self._m_ticks = R.counter("serving_ticks_total", "engine ticks executed")
        self._m_in_place = R.counter(
            "serving_pool_in_place_ticks_total",
            "ticks whose step wrote the decoder state over its input buffers",
        )
        self._m_tokens = R.counter(
            "serving_tokens_emitted_total", "tokens emitted to requests"
        )
        self._m_submitted = R.counter(
            "serving_requests_submitted_total", "requests submitted"
        )
        self._m_rejected_invalid = R.counter(
            "serving_requests_rejected_invalid_total",
            "requests rejected by admission validation",
        )
        self._m_defrag = R.counter(
            "serving_defrag_moves_total", "slot relocations by defrag"
        )
        self._m_strikes = R.counter(
            "serving_strikes_detected_total",
            "replica mismatches detected, attributed, and repaired",
        )
        self._m_terminal = {
            DONE: R.counter("serving_requests_done_total", "requests completed"),
            CANCELLED: R.counter(
                "serving_requests_cancelled_total", "requests cancelled"
            ),
            EXPIRED: R.counter(
                "serving_requests_expired_total", "requests past deadline"
            ),
        }
        #: speculative decoding: verify passes seen / tokens they
        #: committed / smallest single-pass commit (1 = some tick
        #: rejected the very first draft token)
        self._m_spec_ticks = R.counter(
            "serving_spec_verify_ticks_total", "speculative verify passes"
        )
        self._m_spec_tokens = R.counter(
            "serving_spec_tokens_committed_total",
            "tokens committed by speculative verify passes",
        )
        self._spec_min_commit: Optional[int] = None
        #: streaming TTFT/latency/tick-time distributions: observed at
        #: emission/finish time over EVERY request ever served, so the
        #: percentiles in ``metrics()`` are unbiased by the FIFO-bounded
        #: record retention (the retain_results percentile-bias fix)
        self._h_ttft = R.histogram(
            "serving_ttft_seconds", "submit-to-first-token latency"
        )
        self._h_latency = R.histogram(
            "serving_request_latency_seconds", "submit-to-terminal-status latency"
        )
        self._h_tick = R.histogram(
            "serving_tick_seconds",
            "wall time per engine tick (swap + dispatch + harvest); sum = busy_s",
        )
        self._trace_tick_ts0 = 0.0  # tracer-clock start of current tick
        self._t0: Optional[float] = None

        # the surgery bundle: dense whole-leaf ops by default, or the
        # adapter's own (paged: page-table-routed)
        self._base_ops = adapter.surgery or default_surgery(
            adapter.cell, adapter.slot_axes, adapter.make_empty
        )
        self._ops = self._base_ops
        #: spatial detect collectives, compiled lazily per variant
        #: (DMR-only vs mixed-TMR) and cached for the engine's lifetime
        self._detect: dict[bool, Callable] = {}

    # -- lifecycle ---------------------------------------------------------
    def start(self, key: jax.Array) -> None:
        """Initialize the resident states (weights + empty slots).  Under
        spatial placement, also capture the canonical shardings and pin
        every surgery result back onto them — a host-side join that came
        back differently laid out would otherwise reshard on the wire
        (or recompile) at the shard_map boundary every tick."""
        self._states = self.exe.init(key)
        if self.pods > 1:
            from .spatial import pin_surgery

            canon = jax.tree.map(lambda x: x.sharding, self._states)
            self._ops = pin_surgery(self._base_ops, canon)
        self._t0 = self.time_fn()

    def _on_queue_expire(self, req: Request) -> None:
        """Queue expiry-sweep hook: make queued-deadline drops visible in
        the trace (the lifecycle span itself closes at ``_reconcile``)."""
        if self.tracer is not None:
            self.tracer.instant("request_expired", req.id)

    def submit(self, req: Request) -> bool:
        """Admission control + enqueue.  False = rejected (queue full,
        too many replica slots, or adapter validation).  Validation
        failures count as ``rejected_invalid`` — the queue never saw the
        request, so charging ``queue.rejected`` would conflate bad input
        with back-pressure in ``metrics()``."""
        reason = None
        if req.n_slots > self.adapter.n_slots:
            reason = (
                f"policy needs {req.n_slots} slots, engine has "
                f"{self.adapter.n_slots}"
            )
        elif (
            self.pods > 1
            and req.policy.placement == "spatial"
            and req.n_slots > self.pods
        ):
            reason = f"spatial policy needs {req.n_slots} pods, mesh has {self.pods}"
        elif self.adapter.validate is not None:
            reason = self.adapter.validate(req)
        rec = RequestRecord(req=req, status=QUEUED, submitted_at=self.time_fn())
        self.requests[req.id] = rec
        self._m_submitted.inc()
        if self.tracer is not None:
            # the request's lifecycle span: one track per request id,
            # open from submission to terminal status (_finish_record)
            self.tracer.begin(
                "request",
                req.id,
                prompt_len=req.prompt_len,
                level=req.policy.level,
                max_new_tokens=req.max_new_tokens,
            )
            self.tracer.instant("queued", req.id)
        if reason is not None:
            self._m_rejected_invalid.inc()
            self._finish_record(rec, REJECTED)
            return False
        ok = self.queue.submit(req)
        rec.status = self.queue.status[req.id]
        if not ok:
            self._finish_record(rec, REJECTED)
        elif self.tracer is not None:
            rec.trace_queue = self.tracer.span("queue_wait", req.id).start()
        return ok

    def cancel(self, rid: str) -> bool:
        """Cancel a queued request now, or a running one at the next tick
        boundary."""
        rec = self.requests.get(rid)
        if rec is None:
            return False
        if rec.status == QUEUED and self.queue.cancel(rid):
            self._finish_record(rec, CANCELLED)
            return True
        if rec.status == RUNNING:
            rec.cancel_requested = True
            return True
        return False

    def _reconcile(self) -> None:
        """Pull lazily-updated queue statuses (deadline expiry happens at
        queue-head inspection) into the engine records."""
        self.queue.peek()  # prune deadline-expired heads
        for rec in list(self.requests.values()):
            if rec.status == QUEUED:
                status = self.queue.status.get(rec.id, rec.status)
                if status != QUEUED:
                    self._finish_record(rec, status)

    def result(self, rid: str) -> dict:
        self._reconcile()
        rec = self.requests[rid]
        tokens: Any = list(rec.tokens)
        if rec.tokens and rec.tokens[0].size == 1:
            tokens = rec.token_ids()
        return {
            "status": rec.status,
            "tokens": tokens,
            "n_tokens": len(rec.tokens),
            "ttft_s": rec.ttft,
            "faults": rec.faults,
            "slots": list(rec.slots),
        }

    # -- the serving loop --------------------------------------------------
    def has_work(self) -> bool:
        """Anything queued or resident?  (pump() returns when this turns
        false; arrival loops poll it.)"""
        return self.queue.peek() is not None or self.slots.active > 0

    def pump(self, max_ticks: Optional[int] = None, *, faults=None) -> int:
        """Drive the stream until drained (or ``max_ticks``).  Returns the
        number of ticks executed.  ``faults`` (FaultSpecs keyed on global
        step index) thread into the compiled step — the fault-injection
        hook the dependability tests use."""
        if self._states is None:
            raise RuntimeError("call start(key) before pump()")
        if not self.has_work():
            return 0
        ticks = 0
        tr = self.tracer
        stream = self.exe.stream(
            self._states, swap=self._swap, faults=faults, donate=lambda: self._donate
        )
        try:
            while True:
                tick_t0 = self.time_fn()
                if tr is not None:
                    states = self._traced_tick(tr, stream)
                else:
                    # one tick = swap (admit/join) + compiled step
                    # dispatch (the stream never ends: n_steps is None)
                    states, _reports = next(stream)
                    states = self._postprocess(self._tick_step, states)
                self._states = states
                self._override = states
                self._m_ticks.inc()
                if self._donate:
                    self._m_in_place.inc()
                self._h_tick.observe(self.time_fn() - tick_t0)
                ticks += 1
                if max_ticks is not None and ticks >= max_ticks:
                    break
                if not self.has_work():
                    break
        finally:
            stream.close()
        return ticks

    def _traced_tick(self, tr: Tracer, stream) -> dict:
        """One tick inside a ``tick`` span, split into the swap and step
        (``dispatch_us``, which ends with the executor's blocking reports
        read), the rest of the device work (``device_us``) and the
        harvest (``harvest_us``); ``pool_in_place`` is 1 when the step
        wrote the decoder state over its input buffers."""
        with tr.span("tick", "engine") as tick:
            states, _reports = next(stream)
            ts1 = tr.now_us()
            _fence(states[self.adapter.cell])
            ts2 = tr.now_us()
            self._trace_tick_ts0 = tick.ts
            with tr.span("postprocess", "engine"):
                states = self._postprocess(self._tick_step, states)
            tick.args.update(
                step=self._tick_step,
                dispatch_us=ts1 - tick.ts,
                device_us=ts2 - ts1,
                harvest_us=tr.now_us() - ts2,
                pool_in_place=int(bool(self._donate)),
            )
        return states

    def _sync(self, name: str, x):
        """``jax.device_get(x)``: the host waits for the device to finish
        what ``x`` depends on, inside a ``name`` span when traced."""
        if self.tracer is None:
            return jax.device_get(x)
        with self.tracer.span(name, "engine"):
            return jax.device_get(x)

    def _swap(self, t: int, states: dict) -> dict:
        """The stream's state swap-in hook (pre-tick boundary): apply the
        previous tick's repairs/evictions, then join newly admitted
        requests into free slots."""
        if self._override is not None:
            states = self._override
            self._override = None
        states = self._admit(t, states)
        if self.adapter.pre_tick is not None:
            # paged demand growth runs BEFORE the replay snapshot, so a
            # §IV replay of this tick sees the same page tables
            states = self.adapter.pre_tick(states)
        if self._may_donate and not self.slots.replicated:
            self._donate = (self.adapter.cell,)
            self._tick_input = None
        else:
            self._donate = ()
            self._tick_input = states  # immutable prev buffer (§IV replays)
        self._tick_step = t
        return states

    # -- admission: queue -> slots ----------------------------------------
    def _admit(self, t: int, states: dict) -> dict:
        while True:
            req = self.queue.peek()
            if req is None or self.slots.free < req.n_slots:
                break  # FIFO: no overtaking of a head that doesn't fit
            cap = self.adapter.has_capacity
            if cap is not None and not cap(req):
                break  # paged: not enough free pages for its worst case
            spatial_req = (
                self.pods > 1 and req.n_slots > 1 and req.policy.placement == "spatial"
            )
            contig = (
                not spatial_req and self.adapter.contiguous_replicas and req.n_slots > 1
            )
            if spatial_req:
                # spatial groups take one slot COLUMN across pods; there
                # is nothing to defragment (pinned tenants never move),
                # so a missing column just holds the FIFO head
                if self.slots.find_column(req.n_slots) is None:
                    break
            elif contig and self.slots.find_run(req.n_slots) is None:
                # capacity exists but no adjacent run: defragment instead
                # of rejecting/stalling the replicated admission
                states = self._defrag(states, req.n_slots)
                if self.slots.find_run(req.n_slots) is None:
                    break  # pinned spatial tenants block every window
            if not self.queue.take(req):
                continue  # head expired underneath us: re-validate
            rec = self.requests[req.id]
            if self.tracer is not None:
                rec.trace_queue.stop()
                rec.trace_queue = None
                with self.tracer.span("admit", "engine", rid=req.id):
                    states = self._join_request(t, states, rec, contig, spatial_req)
            else:
                states = self._join_request(t, states, rec, contig, spatial_req)
        return states

    def _join_request(
        self, t: int, states: dict, rec: RequestRecord, contig: bool, spatial_req: bool
    ) -> dict:
        """Prefill an admitted request and join it into its slots."""
        req = rec.req
        if self.tracer is not None:
            with self.tracer.span("prefill", req.id, prompt_len=req.prompt_len):
                out = self.adapter.prefill(req, states)
                _fence(out[0])
        else:
            out = self.adapter.prefill(req, states)
        slot_state, first = out[0], out[1]
        pending = out[2] if len(out) > 2 else 0
        slots = self.slots.alloc(
            req.id, req.n_slots, contiguous=contig, spatial=spatial_req
        )
        for s in slots:
            states = self._ops.join(states, slot_state, s, req=req)
        now = self.time_fn()
        rec.slots = slots
        rec.spatial = spatial_req
        rec.status = RUNNING
        rec.started_at = now
        rec.prefill_remaining = int(pending)
        if self.tracer is not None:
            self.tracer.instant("admitted", req.id, step=t, slots=list(slots))
            if pending:
                # chunked prefill: the in-transition walk consumes
                # the prompt tail over the next ticks; the span ends
                # when prefill_remaining drains (_postprocess)
                self.tracer.begin("prefill_walk", req.id, pending=int(pending))
                rec.trace_walk_open = True
        if pending == 0:
            # the prefill's greedy continuation IS the first emitted
            # token; with a pending tail the first token arrives when
            # the in-slot walk drains (_postprocess)
            first = np.asarray(self._sync("sync.first_token", first))
            self._emit(rec, first.reshape(-1), now)
        status = self._should_finish(rec, now)
        if status is not None:  # e.g. max_new_tokens == 1
            states = self._evict(states, rec, status)
        return states

    def _defrag(self, states: dict, n: int) -> dict:
        """Relocate running requests' slots (bitwise copy + scrub) until
        an ``n``-slot adjacent free run exists (or no movable window is
        left — pinned spatial tenants are never relocated)."""
        plan = self.slots.defrag_plan(n)
        for src, dst in plan or ():
            states = self._ops.copy(states, src, dst)
            states = self._ops.scrub(states, src)
            rid = self.slots.relocate(src, dst)  # manager's bookkeeping
            rec = self.requests.get(rid)
            if rec is not None:  # engine's record copy
                rec.slots[rec.slots.index(src)] = dst
            self._m_defrag.inc()
            if self.tracer is not None:
                self.tracer.instant("defrag_move", "engine", src=src, dst=dst, rid=rid)
        return states

    # -- per-tick postprocessing: repair -> harvest -> evict ---------------
    def _postprocess(self, t: int, states: dict) -> dict:
        running = [r for r in self.requests.values() if r.status == RUNNING]
        replicated = [r for r in running if r.req.policy.level > 1]
        temporal = [r for r in replicated if not r.spatial]
        spatial = [r for r in replicated if r.spatial]
        if replicated:
            if self.tracer is not None:
                with self.tracer.span("check_replicas", "engine"):
                    states = self._check(t, states, temporal, spatial)
            else:
                states = self._check(t, states, temporal, spatial)
        if running:
            cell = states[self.adapter.cell]
            toks = np.asarray(self._sync("sync.tokens", self.adapter.read_tokens(cell)))
            sout = sn = None
            if self.adapter.read_spec is not None:
                sout, sn = (
                    np.asarray(x)
                    for x in self._sync("sync.spec", self.adapter.read_spec(cell))
                )
            now = self.time_fn()
            for rec in running:
                if rec.status != RUNNING:
                    continue  # evicted during repair (should not happen)
                if rec.prefill_remaining > 0:
                    # this tick consumed up to walk_chunk pending prompt
                    # tokens (the in-transition k-token walk)
                    rec.prefill_remaining -= min(
                        self.adapter.walk_chunk, rec.prefill_remaining
                    )
                    if rec.prefill_remaining > 0:
                        # still walking: nothing to emit, but a deadline
                        # can expire mid-walk
                        status = self._should_finish(rec, now)
                        if status is not None:
                            states = self._evict(states, rec, status)
                        continue
                    if self.tracer is not None and rec.trace_walk_open:
                        self.tracer.end(rec.id, "prefill_walk")
                        rec.trace_walk_open = False
                    # the tick consuming the LAST prompt token produced
                    # the first real continuation token -> harvest it
                slot = rec.slots[0]
                n_commit = int(sn[slot]) if sn is not None else 0
                if n_commit > 0:
                    # speculative commit: the tick verified a draft and
                    # committed n tokens; emit them ONE AT A TIME so
                    # stop/budget/deadline trip on exactly the token
                    # they would have under plain decode (eviction
                    # mid-commit just truncates the surplus — the extra
                    # cache entries leave with the slot)
                    self._m_spec_ticks.inc()
                    self._m_spec_tokens.inc(n_commit)
                    self._spec_min_commit = (
                        n_commit
                        if self._spec_min_commit is None
                        else min(self._spec_min_commit, n_commit)
                    )
                    if self.tracer is not None:
                        # the verify walk ran inside this tick's compiled
                        # step: span it over the tick so far, carrying
                        # the accept count (committed = accepted drafts
                        # + the verifier's own continuation token)
                        ts0 = self._trace_tick_ts0
                        self.tracer.complete(
                            "verify_walk",
                            rec.id,
                            ts0,
                            self.tracer.now_us() - ts0,
                            step=t,
                            committed=n_commit,
                            accepted=n_commit - 1,
                        )
                    status = None
                    for i in range(n_commit):
                        self._emit(rec, sout[slot, i : i + 1], now)
                        status = self._should_finish(rec, now)
                        if status is not None:
                            break
                else:
                    self._emit(rec, toks[slot].reshape(-1), now)
                    status = self._should_finish(rec, now)
                if status is not None:
                    states = self._evict(states, rec, status)
        return states

    def _check(self, t: int, states: dict, temporal: list, spatial: list) -> dict:
        if temporal:
            states = self._check_replicas(t, states, temporal)
        if spatial:
            states = self._check_spatial(t, states, spatial)
        return states

    def _damage(self, fn: Callable, *args) -> float:
        """Mismatching elements, by ``fn`` (a surgery ``damage`` or
        ``damage_vs``), which reads them to the host."""
        if self.tracer is None:
            return fn(*args)
        with self.tracer.span("sync.damage", "engine"):
            return fn(*args)

    def _adopt(self, states: dict, replay: dict, slots: list[int]) -> dict:
        """§IV adoption: every replica slot takes the replay's state."""
        if self.tracer is None:
            for sl in slots:
                states = self._ops.adopt(states, replay, sl)
            return states
        with self.tracer.span("adopt", "engine"):
            for sl in slots:
                states = self._ops.adopt(states, replay, sl)
        return states

    def _check_replicas(self, t: int, states: dict, recs: list[RequestRecord]) -> dict:
        """Compare each replicated request's replica-slot fingerprints;
        attribute mismatches to the owning request and repair."""
        fps = np.asarray(
            self._sync(
                "sync.fingerprints", self._ops.fingerprints(states[self.adapter.cell])
            )
        )
        replay = None  # lazy: one §IV replay serves every event this tick
        for rec in recs:
            s = rec.slots
            eq = [np.array_equal(fps[s[0]], fps[s[i]]) for i in range(1, len(s))]
            if all(eq) and (len(s) < 3 or np.array_equal(fps[s[1]], fps[s[2]])):
                continue
            level = rec.req.policy.level
            tr = self.tracer
            fid = None
            if tr is not None:
                # the dependability timeline: detect -> attribute ->
                # repair as ordered instants on the struck request's
                # track, with a flow arrow from detection into repair
                fid = tr.flow_id()
                tr.instant("strike_detected", rec.id, step=t, level=level)
                tr.flow_start(fid, rec.id, "strike")
            if level == 3:
                pairs = [
                    (0, 1, np.array_equal(fps[s[0]], fps[s[1]])),
                    (0, 2, np.array_equal(fps[s[0]], fps[s[2]])),
                    (1, 2, np.array_equal(fps[s[1]], fps[s[2]])),
                ]
                agree = [(i, j) for i, j, ok in pairs if ok]
                if agree:
                    i, j = agree[0]
                    bad = ({0, 1, 2} - {i, j}).pop()
                    # real damage: elements of the struck replica slot
                    # differing from a majority slot (pre-repair)
                    dmg = self._damage(self._ops.damage, states, s[i], s[bad])
                    if tr is not None:
                        tr.instant(
                            "strike_attributed",
                            rec.id,
                            step=t,
                            replicas=[bad],
                            damage_elems=float(dmg),
                        )
                    states = self._ops.copy(states, s[i], s[bad])
                    self._attribute(rec, t, [bad], level, dmg)
                    if tr is not None:
                        tr.instant("strike_repaired", rec.id, step=t, repair="tmr_vote")
                        tr.flow_end(fid, rec.id, "strike")
                    continue
                bad = [0, 1, 2]  # triple divergence: fall through to replay
            else:
                bad = None  # DMR: symmetric — the replay decides
            if replay is None:
                # paper §IV: "a third equal transition should be executed
                # to decide between the two possible outcomes" — replay
                # the tick (no armed fault) from the immutable pre-tick
                # buffer; pure_step has no ledger/counter side effects
                if tr is not None:
                    with tr.span("dmr_replay", "engine", step=t):
                        replay, _ = self.exe.pure_step(self._tick_input, t)
                        _fence(replay[self.adapter.cell])
                else:
                    replay, _ = self.exe.pure_step(self._tick_input, t)
                rfps = np.asarray(
                    self._sync(
                        "sync.fingerprints",
                        self._ops.fingerprints(replay[self.adapter.cell]),
                    )
                )
            if bad is None:
                bad = [
                    i for i, sl in enumerate(s) if not np.array_equal(fps[sl], rfps[sl])
                ]
            dmg = sum(
                self._damage(self._ops.damage_vs, states, replay, s[b]) for b in bad
            )
            if tr is not None:
                tr.instant(
                    "strike_attributed",
                    rec.id,
                    step=t,
                    replicas=list(bad),
                    damage_elems=float(dmg),
                )
            states = self._adopt(states, replay, s)
            self._attribute(rec, t, bad, level, dmg)
            if tr is not None:
                tr.instant("strike_repaired", rec.id, step=t, repair="dmr_replay")
                tr.flow_end(fid, rec.id, "strike")
        return states

    def _get_detect(self, tmr: bool) -> Callable:
        key = bool(tmr)
        if key not in self._detect:
            from .spatial import make_detect

            self._detect[key] = make_detect(
                self.config.mesh,
                self.adapter.slot_axes,
                pod_axis=self.config.pod_axis,
                tmr=key,
            )
        return self._detect[key]

    def _check_spatial(self, t: int, states: dict, recs: list[RequestRecord]) -> dict:
        """Cross-pod detect for spatially-placed replica groups.

        One O(1)-wire collective over the resident decoder state replaces
        the host fingerprint walk: ``lvl`` carries the level of the group
        anchored at each slot column, the collective compares the SAME
        128-bit per-slot fingerprints the temporal engine fetches to the
        host, and a TMR majority verdict comes back as the struck pod
        (replica index == pod index, so attribution names the pod).
        Repair reuses the temporal paths verbatim — TMR: copy a majority
        slot over the minority; DMR/triple-divergence: §IV replay and
        adopt — so the ledger entries are bitwise-identical to temporal
        replica-slot serving.
        """
        lvl = np.zeros(self.slots.per_pod, np.int32)
        for rec in recs:
            lvl[rec.slots[0]] = rec.req.policy.level  # slots[0] == column
        tmr = any(r.req.policy.level >= 3 for r in recs)
        events, struck = (
            np.asarray(x)
            for x in self._sync(
                "sync.fingerprints",
                self._get_detect(tmr)(states[self.adapter.cell], lvl),
            )
        )
        fps = rfps = replay = None  # lazy: one replay serves every event
        for rec in recs:
            col = rec.slots[0]
            if not events[col]:
                continue
            s = rec.slots
            level = rec.req.policy.level
            tr = self.tracer
            fid = None
            if tr is not None:
                fid = tr.flow_id()
                tr.instant("strike_detected", rec.id, step=t, level=level)
                tr.flow_start(fid, rec.id, "strike")
            if level == 3 and struck[col] >= 0:
                # majority verdict already replicated from the collective;
                # same pair precedence as the temporal [(0,1),(0,2),(1,2)]
                bad = int(struck[col])
                good = 0 if bad != 0 else 1
                dmg = self._damage(self._ops.damage, states, s[good], s[bad])
                if tr is not None:
                    tr.instant(
                        "strike_attributed",
                        rec.id,
                        step=t,
                        replicas=[bad],
                        pod=bad,
                        damage_elems=float(dmg),
                    )
                states = self._ops.copy(states, s[good], s[bad])
                self._attribute(rec, t, [bad], level, dmg)
                if tr is not None:
                    tr.instant("strike_repaired", rec.id, step=t, repair="tmr_vote")
                    tr.flow_end(fid, rec.id, "strike")
                continue
            # DMR (symmetric) or TMR triple divergence: the §IV replay
            # decides, exactly as in _check_replicas
            if replay is None:
                if tr is not None:
                    with tr.span("dmr_replay", "engine", step=t):
                        replay, _ = self.exe.pure_step(self._tick_input, t)
                        _fence(replay[self.adapter.cell])
                else:
                    replay, _ = self.exe.pure_step(self._tick_input, t)
                fps = np.asarray(
                    self._sync(
                        "sync.fingerprints",
                        self._ops.fingerprints(states[self.adapter.cell]),
                    )
                )
                rfps = np.asarray(
                    self._sync(
                        "sync.fingerprints",
                        self._ops.fingerprints(replay[self.adapter.cell]),
                    )
                )
            bad = [
                i for i, sl in enumerate(s) if not np.array_equal(fps[sl], rfps[sl])
            ]
            dmg = sum(
                self._damage(self._ops.damage_vs, states, replay, s[b]) for b in bad
            )
            if tr is not None:
                tr.instant(
                    "strike_attributed",
                    rec.id,
                    step=t,
                    replicas=list(bad),
                    pods=list(bad),
                    damage_elems=float(dmg),
                )
            states = self._adopt(states, replay, s)
            self._attribute(rec, t, bad, level, dmg)
            if tr is not None:
                tr.instant("strike_repaired", rec.id, step=t, repair="dmr_replay")
                tr.flow_end(fid, rec.id, "strike")
        return states

    def _attribute(
        self, rec: RequestRecord, t: int, bad: list[int], level: int, damage: float
    ) -> None:
        """One detected strike, charged to the owning request in the
        engine ledger (per-request fault accounting; repeated offenders
        surface in ``permanent_fault_suspects`` keyed by request).

        ``damage`` is the REAL corruption size — state elements of the
        struck replica slot(s) differing from the repaired value, the
        same unit temporal lockstep's bitwise compare reports — not the
        (<=4) differing 128-bit fingerprint words.  ``per_replica`` is
        sized to the request's actual level (DMR -> 2 entries)."""
        rec.faults += 1
        self._m_strikes.inc()
        per = [0.0] * level
        for b in bad:
            per[b] = 1.0
        entry = {
            "events": 1.0,
            "mismatch_elems": max(damage, 1.0),
            "per_replica": per,
        }
        self.ledger.update(t, {rec.id: entry})

    # -- emit / finish / evict --------------------------------------------
    def _emit(self, rec: RequestRecord, token: np.ndarray, now: float) -> None:
        rec.tokens.append(token)
        self._m_tokens.inc()
        if rec.ttft is None:
            rec.ttft = now - rec.submitted_at
            # streamed at observation time: the TTFT percentiles survive
            # record retention limits (every request ever served counts)
            self._h_ttft.observe(rec.ttft)
            if self.tracer is not None:
                self.tracer.instant("first_token", rec.id, ttft_s=rec.ttft)

    def _should_finish(self, rec: RequestRecord, now: float) -> Optional[str]:
        if rec.cancel_requested:
            return CANCELLED
        # DONE checks come BEFORE the deadline: a request whose final
        # budgeted (or stop) token was just emitted has delivered its
        # full output and must not be reported EXPIRED merely because
        # the deadline passed within the same tick
        if len(rec.tokens) >= rec.req.max_new_tokens:
            return DONE
        if rec.req.stop_token is not None and rec.tokens:
            if int(rec.tokens[-1].reshape(-1)[0]) == rec.req.stop_token:
                return DONE
        if rec.req.deadline is not None and now >= rec.req.deadline:
            return EXPIRED
        return None

    def _evict(self, states: dict, rec: RequestRecord, status: str) -> dict:
        """Leave: scrub the request's slots back to empty (inactive mask,
        zeroed cache) and return them to the free pool."""
        for s in self.slots.release(rec.id):
            states = self._ops.scrub(states, s)
        self._finish_record(rec, status)
        return states

    def _finish_record(self, rec: RequestRecord, status: str) -> None:
        rec.status = status
        rec.finished_at = self.time_fn()
        self.queue.status[rec.id] = status
        if status in self._m_terminal:
            self._m_terminal[status].inc()
        self._h_latency.observe(rec.finished_at - rec.submitted_at)
        if self.tracer is not None:
            if rec.trace_queue is not None:  # left the queue unserved
                rec.trace_queue.stop()
                rec.trace_queue = None
            if rec.trace_walk_open:  # evicted mid-walk: close inner span
                self.tracer.end(rec.id, "prefill_walk")
                rec.trace_walk_open = False
            self.tracer.instant(status, rec.id)
            self.tracer.end(
                rec.id,
                "request",
                status=status,
                n_tokens=len(rec.tokens),
                faults=rec.faults,
            )
        self._finished.append(rec.id)
        while len(self._finished) > self.retain_results:
            self.drop(self._finished[0])

    def drop(self, rid: str) -> bool:
        """Release a finished request's record and status (result() no
        longer answers for it); flagged-suspect ledger entries survive.
        Called automatically FIFO beyond ``retain_results``."""
        rec = self.requests.get(rid)
        if rec is None or rec.status in (QUEUED, RUNNING):
            return False
        try:
            self._finished.remove(rid)
        except ValueError:
            pass
        del self.requests[rid]
        self.queue.status.pop(rid, None)
        if rid not in self.ledger.flagged:
            self.ledger.totals.pop(rid, None)
            self.ledger.recent.pop(rid, None)
        return True

    # -- the metrics / SLO surface ----------------------------------------
    def metrics(self) -> dict:
        """The engine's SLO surface.  The historical keys are back-compat
        views over the registry instruments; ``engine.registry`` holds
        the same numbers as typed Counter/Gauge/Histogram instruments
        with Prometheus/JSON exposition.

        TTFT percentiles come from the streaming histogram (observed at
        first-token time for EVERY request ever served) — unbiased by
        the FIFO ``retain_results`` record retention, unlike the old
        exact-over-retained-records computation.

        ``busy_s`` is the tick-loop occupancy (sum of per-tick wall
        times); ``tokens_per_s_busy`` divides by it, so engine
        throughput under light load is not understated by idle gaps
        between arrivals the way wall-clock ``tokens_per_s`` is.
        """
        self._reconcile()
        recs = list(self.requests.values())
        wall = (self.time_fn() - self._t0) if self._t0 is not None else 0.0
        busy = self._h_tick.sum
        running = sum(1 for r in recs if r.status == RUNNING)
        tokens_out = int(self._m_tokens.value)
        R = self.registry
        R.gauge("serving_queue_depth", "requests waiting").set(self.queue.depth)
        R.gauge("serving_active_requests", "requests resident").set(running)
        R.gauge("serving_free_slots", "unoccupied batch slots").set(self.slots.free)
        R.counter(
            "serving_requests_rejected_queue_full_total",
            "requests shed by queue back-pressure",
        ).value = float(self.queue.rejected)
        self.exe.export_metrics(R)
        m = {
            "backend": self.exe.name,
            "placement": self.config.placement,
            "pods": self.pods,
            "n_slots": self.adapter.n_slots,
            "ticks": int(self._m_ticks.value),
            "pool_in_place_ticks": int(self._m_in_place.value),
            "queue_depth": self.queue.depth,
            "active_requests": running,
            "free_slots": self.slots.free,
            # cumulative over the engine's lifetime (records themselves are
            # retained only up to retain_results)
            "submitted": int(self._m_submitted.value),
            "done": int(self._m_terminal[DONE].value),
            "cancelled": int(self._m_terminal[CANCELLED].value),
            "expired": int(self._m_terminal[EXPIRED].value),
            # back-pressure and bad input are different signals: a full
            # queue calls for shedding load, a validation failure for
            # fixing the client
            "rejected_queue_full": self.queue.rejected,
            "rejected_invalid": int(self._m_rejected_invalid.value),
            "rejected": self.queue.rejected + int(self._m_rejected_invalid.value),
            "defrag_moves": int(self._m_defrag.value),
            "tokens_out": tokens_out,
            "wall_s": wall,
            "busy_s": busy,
            "utilization": busy / wall if wall > 0 else 0.0,
            "tokens_per_s": tokens_out / wall if wall > 0 else 0.0,
            "tokens_per_s_busy": tokens_out / busy if busy > 0 else 0.0,
            "request_faults": {r.id: r.faults for r in recs if r.faults},
            "fault_totals": self.ledger.totals,
            "suspects": self.ledger.permanent_fault_suspects(),
        }
        if self.adapter.read_spec is not None:
            spec_ticks = int(self._m_spec_ticks.value)
            spec_tokens = int(self._m_spec_tokens.value)
            m["spec_ticks"] = spec_ticks
            m["spec_tokens"] = spec_tokens
            m["spec_min_commit"] = self._spec_min_commit
            m["spec_tokens_per_tick"] = (
                spec_tokens / spec_ticks if spec_ticks else 0.0
            )
        if self._h_ttft.count:
            m["ttft_p50_s"] = self._h_ttft.quantile(0.5)
            m["ttft_p99_s"] = self._h_ttft.quantile(0.99)
        if self.adapter.stats is not None:
            m.update(self.adapter.stats())
        return m
