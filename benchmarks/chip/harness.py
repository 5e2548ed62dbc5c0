"""Drive one cell: build the engine from a configuration file, make its
weights from the seed, warm up every shape the mix reaches, run the open
loop, and reduce what happened to the cell's metrics.

Everything that belongs to one configuration, one mix or one per-layer
metric lives in files of its own (``configs/``, ``traffic/``,
``metrics/``, ``reference/``), found by the names in ``BENCHMARK.json``.
The program under test is driven through its public serving entry
points (``lm_engine_parts``, ``miso.serve``, ``submit``, ``pump``,
``result``); the benchmark keeps its own clock and its own per-request
record of every token and when it arrived.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import correct as C
from . import traffic as T

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: the traced sub-window: it opens this far into the window (a share of
#: it) and lasts at most ``TRACE_S`` seconds
TRACE_AT = 0.25
TRACE_S = 8.0
#: tracer ring: far above the events of one window, so none is dropped
TRACER_CAPACITY = 1 << 21


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by path (metric readers and
    references are named by their file, which may hold any name
    characters)."""
    spec = importlib.util.spec_from_file_location(
        f"_chipbench_{path.parent.name}_{path.stem}".replace(".", "_").replace(
            "-", "_"
        ),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# the cell as data
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    e2e: list
    per_layer: list


def load_cell(bench: dict, workload: str, root: pathlib.Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        mix=mix,
        e2e=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
    )


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------
def _leaf_init(name: str, key, sd):
    """One weight leaf, made in its served dtype: norm gains near 1,
    biases and the tied embedding small, matrices ~ N(0, 1/fan_in)."""
    z = jax.random.normal(key, sd.shape, sd.dtype)
    if name in ("ln1", "ln2", "final_norm") or name.endswith("norm"):
        return (1.0 + 0.1 * z).astype(sd.dtype)
    if name.startswith("b") and len(name) == 2:
        return (0.02 * z).astype(sd.dtype)
    if name == "embed":
        return (0.02 * z).astype(sd.dtype)
    return (z * (sd.shape[-2] ** -0.5)).astype(sd.dtype)


def make_weights(shapes, seed: int):
    """Every weight leaf in one jitted call on the device, from ``seed``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    names = [getattr(p[-1], "key", str(p[-1])) for p, _ in flat]
    sds = [s for _, s in flat]

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(sds))
        return [_leaf_init(n, k, s) for n, k, s in zip(names, keys, sds)]

    return jax.tree_util.tree_unflatten(tree, gen(seed_key(seed)))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------
@dataclasses.dataclass
class System:
    engine: Any
    cfg: Any
    scfg: Any
    params: Any
    ladder: tuple


def build(config: dict, seed: int, *, tracer=None) -> System:
    from repro import api as miso
    from repro.models.config import ModelConfig
    from repro.models.lm_cells import ServeConfig, prefill_bucket_ladder
    from repro.serving.lm import lm_engine_parts

    cfg = ModelConfig(**config["model"])
    serve = dict(config["serve"])
    engine_opts = {
        k: serve.pop(k) for k in ("max_queue", "retain_results") if k in serve
    }
    scfg = ServeConfig(**serve)
    prog, adapter = lm_engine_parts(cfg, scfg)
    engine = miso.serve(prog, adapter, miso.EngineConfig(tracer=tracer, **engine_opts))
    key = seed_key(seed)
    shapes = jax.eval_shape(prog.cells["weights"].init, key)
    if set(shapes) != {"params"}:
        raise ValueError(f"weights cell holds {sorted(shapes)}, expected params")
    params = make_weights(shapes["params"], seed)
    # the engine's resident state: the benchmark's weights and the
    # program's own empty decoder cell, placed by the executor's init.
    # ``ServingEngine.start`` takes no ready-made state, so the program's
    # ``init_states`` is replaced for this one call; a ``start(states=...)``
    # in the program would let this go (PERF.md, open questions).
    run_prog = engine.exe.program
    run_prog.init_states = lambda k: {
        "weights": {"params": params},
        "decoder": run_prog.cells["decoder"].init(k),
    }
    try:
        engine.start(key)
    finally:
        del run_prog.init_states
    return System(
        engine=engine,
        cfg=cfg,
        scfg=scfg,
        params=params,
        ladder=tuple(prefill_bucket_ladder(scfg)),
    )


def make_request(p: T.Planned, prefix: str):
    from repro import api as miso
    from repro.serving import Request

    return Request(
        prompt=p.prompt,
        max_new_tokens=p.max_new,
        policy=miso.RedundancyPolicy(level=p.level),
        id=f"{prefix}{p.idx}",
    )


def arm(sys_: System, req):
    """The program's own strike arming (``launch/serve.py::arm_strike``):
    a bit flip in the second replica slot of the resident ``req``, on the
    step after the next."""
    from repro.launch.serve import arm_strike

    return arm_strike(sys_.engine, sys_.cfg, sys_.scfg, req)


# --------------------------------------------------------------------------
# the open loop
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Track:
    plan: T.Planned
    req: Any
    sent_s: float = math.nan
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    status: str = "queued"
    struck: bool = False

    @property
    def rid(self) -> str:
        return self.req.id


@dataclasses.dataclass
class TickBook:
    """What one traced tick did, as the benchmark saw it."""

    t0: float
    t1: float
    decode: list  # (keys attended, replica slots) per request decoded
    prefill: list  # prompt tokens of each request admitted


@dataclasses.dataclass
class Loop:
    tracks: list
    window: tuple
    strikes: list  # (armed_s, tick_s, ok, victim)
    unarmed: int
    ticks: int
    compiles: int
    lowerings: int
    book: list
    trace_span: Optional[tuple]
    queue_at: dict


class CompileCount:
    """Counts backend compiles and lowerings (JAX monitoring events)."""

    def __init__(self):
        self.compiles = 0
        self.lowerings = 0

    def __call__(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1


_COUNTER: list = []


def compile_counter() -> CompileCount:
    """The process's one compile counter, registered with JAX on first use."""
    if not _COUNTER:
        _COUNTER.append(CompileCount())
        jax.monitoring.register_event_duration_secs_listener(_COUNTER[0])
    return _COUNTER[0]


def _harvest(engine, inflight: dict, now: float):
    """Copy each in-flight request's new tokens into its track, stamped
    with ``now``; drop finished ones from ``inflight``.  Returns what the
    tick did: (keys attended, replica slots) of each request it decoded,
    and the prompt length of each request it admitted."""
    from repro.serving import QUEUED, RUNNING

    dec, pre = [], []
    for rid in list(inflight):
        tr = inflight[rid]
        rec = engine.requests.get(rid)
        if rec is None:
            continue
        if rec.status == QUEUED:
            continue
        had, n = len(tr.tokens), len(rec.tokens)
        for k in range(had, n):
            tr.tokens.append(int(rec.tokens[k].reshape(-1)[0]))
            tr.times.append(now)
        if n > had:
            plen = len(tr.plan.prompt)
            if had == 0:
                pre.append(plen)
            if n - had - (had == 0) > 0:
                dec.append((plen + n - 1, tr.plan.level))
        if rec.status != RUNNING:
            tr.status = rec.status
            del inflight[rid]
    return dec, pre


def _quiet(name: str):
    return contextlib.nullcontext()


def run_loop(
    sys_: System,
    mix: dict,
    plan: list,
    *,
    seed: int,
    window_s: float,
    trace_dir: Optional[pathlib.Path] = None,
    prefix: str = "q",
    hard_s: float = 90.0,
) -> Loop:
    """Offer ``plan`` open-loop; return every request's track.  Arrivals go
    on past the window until every request due in it has finished (or
    ``hard_s`` after the window, when the rest count as failed)."""
    engine = sys_.engine
    lead = mix["lead_s"]
    w0, w1 = lead, lead + window_s
    strikes = T.strike_times(mix, window_s=window_s)
    srng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    tracks = [Track(plan=p, req=make_request(p, prefix)) for p in plan]
    in_window = [t for t in tracks if w0 <= t.plan.due_s < w1]
    inflight: dict = {}
    struck_log, unarmed = [], 0
    armed = None  # (fault, victim) until the fault's step has run
    ann = _quiet
    if trace_dir is not None:
        ann = jax.profiler.TraceAnnotation
    t_trace0 = w0 + TRACE_AT * window_s
    t_trace1 = t_trace0 + min(TRACE_S, window_s * (1 - TRACE_AT))
    tracing, trace_span = False, None
    book: Optional[list] = None
    books: list = []
    queue_at = {}
    i, ticks = 0, 0
    counter = compile_counter()
    c0, l0 = counter.compiles, counter.lowerings
    start = time.perf_counter()

    def clock():
        return time.perf_counter() - start

    while True:
        now = clock()
        if trace_dir is not None and not tracing and not trace_span and now >= t_trace0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tracing, book, t_on = True, [], clock()
        if tracing and now >= t_trace1:
            t_off = clock()
            jax.profiler.stop_trace()
            tracing, trace_span = False, (t_on + start, t_off + start)
            books, book = book, None
        with ann("bench_submit"):
            while i < len(tracks) and tracks[i].plan.due_s <= now:
                tr = tracks[i]
                tr.sent_s = clock()
                if engine.submit(tr.req):
                    inflight[tr.rid] = tr
                else:
                    tr.status = "rejected"
                i += 1
        for frac in (0.25, 1.0):
            if frac not in queue_at and now >= w0 + frac * window_s:
                queue_at[frac] = engine.queue.depth
        if now >= w1 and not any(t.rid in inflight for t in in_window):
            break
        if now >= w1 + hard_s:
            break
        if armed is None and strikes and strikes[0] <= now and w0 <= now < w1:
            with ann("bench_strike"):
                victim = _pick_victim(engine, inflight, srng)
                if victim is not None:
                    armed = (arm(sys_, victim.req), victim)
                    strikes.pop(0)
        elif strikes and strikes[0] < w0:
            strikes.pop(0)
        if strikes and now >= w1:
            unarmed += len(strikes)
            strikes = []
        if engine.has_work():
            fault = armed[0] if armed else None
            # the tick that runs the armed step: the executor's step count
            # is the index of the step it runs next
            struck = fault is not None and engine.exe.metrics()["steps"] >= int(fault.step)
            if struck:
                faults_before = sum(r.faults for r in engine.requests.values())
            with ann("bench_pump"):
                t0 = time.perf_counter()
                engine.pump(max_ticks=1, faults=fault)
                t1 = time.perf_counter()
            ticks += 1
            dec, pre = _harvest(engine, inflight, t1 - start)
            if book is not None:
                book.append(TickBook(t0, t1, dec, pre))
            if struck:
                victim = armed[1]
                rec = engine.requests[victim.rid]
                total = sum(r.faults for r in engine.requests.values())
                ok = rec.faults >= 1 and total == faults_before + 1
                victim.struck = True
                struck_log.append((now, t1 - t0, ok, victim.rid))
                armed = None
        else:
            nxt = tracks[i].plan.due_s if i < len(tracks) else now + 0.01
            with ann("bench_wait"):
                time.sleep(max(0.0, min(nxt - clock(), 0.01)))
    if tracing:
        t_off = clock()
        jax.profiler.stop_trace()
        trace_span = (t_on + start, t_off + start)
        books = book
    unarmed += armed is not None
    for tr in tracks:
        if tr.rid in inflight:
            tr.status = "unfinished"
    return Loop(
        tracks=tracks,
        window=(w0, w1),
        strikes=struck_log,
        unarmed=unarmed,
        ticks=ticks,
        compiles=counter.compiles - c0,
        lowerings=counter.lowerings - l0,
        book=books or [],
        trace_span=trace_span,
        queue_at=queue_at,
    )


def _pick_victim(engine, inflight: dict, rng) -> Optional[Track]:
    """A resident replicated request with decode budget left for the flip,
    armed for the step after the next, to land and be repaired before it
    finishes."""
    from repro.serving import RUNNING

    ok = []
    for rid in sorted(inflight):
        tr = inflight[rid]
        rec = engine.requests.get(rid)
        if (
            rec is not None
            and rec.status == RUNNING
            and tr.plan.level > 1
            and rec.prefill_remaining == 0
            and len(rec.tokens) + 3 <= tr.plan.max_new
        ):
            ok.append(tr)
    if not ok:
        return None
    return ok[int(rng.integers(0, len(ok)))]


def drain(engine) -> None:
    """Cancel whatever is still queued or resident and tick it away."""
    for rid, rec in list(engine.requests.items()):
        if rec.status in ("queued", "running"):
            engine.cancel(rid)
    while engine.has_work():
        engine.pump(max_ticks=1)


# --------------------------------------------------------------------------
# warm-up: every shape the mix reaches, nothing else
# --------------------------------------------------------------------------
def warm_buckets(ladder: tuple, lo: int, hi: int) -> list[int]:
    """Prompt lengths that reach each prefill bucket the mix can reach."""
    out, prev = [], 0
    for b in ladder:
        if b >= lo and prev < hi:
            out.append(min(b, hi))
        prev = b
    return out


def warm_up(sys_: System, mix: dict, seed: int) -> dict:
    engine = sys_.engine
    lo, hi = T.prompt_range(mix)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 5]))
    vocab = sys_.cfg.vocab_size
    levels = sorted(
        {T.LEVELS[k] for k, v in mix.get("policies", {"none": 1}).items() if v > 0}
    )
    plans = []
    for n, plen in enumerate(warm_buckets(sys_.ladder, lo, hi)):
        toks = rng.integers(0, vocab, plen).astype(np.int32)
        plans.append(T.Planned(idx=n, due_s=0.0, prompt=toks, max_new=3, level=1))
    for lvl in levels:
        if lvl > 1:
            toks = rng.integers(0, vocab, lo).astype(np.int32)
            plans.append(
                T.Planned(idx=len(plans), due_s=0.0, prompt=toks, max_new=8, level=lvl)
            )
    strikes = mix.get("strikes_per_s", 0) > 0
    for p in plans:
        req = make_request(p, "w")
        if not engine.submit(req):
            raise RuntimeError(f"warm-up request {req.id} rejected")
        engine.pump(max_ticks=2)
        if strikes and p.level > 1:
            rec = engine.requests[req.id]
            engine.pump(max_ticks=2, faults=arm(sys_, req))
            if rec.faults != 1:
                raise RuntimeError("warm-up strike was not detected and repaired")
        engine.pump()
    return {"requests": len(plans)}


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------
def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (defined with infinities too)."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def end_to_end(loop: Loop) -> dict:
    w0, w1 = loop.window
    due = [t for t in loop.tracks if w0 <= t.plan.due_s < w1]
    ttft = [
        (t.times[0] - t.plan.due_s) if (t.times and t.status == "done") else math.inf
        for t in due
    ]
    gaps = [b - a for t in due for a, b in zip(t.times, t.times[1:])]
    out_tokens = sum(1 for t in loop.tracks for x in t.times if w0 <= x < w1)
    repair = [s for _, s, ok, _ in loop.strikes if ok]
    late = [t.sent_s - t.plan.due_s for t in due]
    return {
        "ttft_p80_s": nearest_rank(ttft, 0.80),
        "itl_p99_s": nearest_rank(gaps, 0.99),
        "out_tokens_per_s": out_tokens / (w1 - w0),
        "repair_p50_s": nearest_rank(repair, 0.50) if repair else None,
        "_counts": {
            "requests": len(due),
            "gaps": len(gaps),
            "strikes": len(loop.strikes),
            "lateness_p99_s": nearest_rank(late, 0.99),
            "lateness_max_s": max(late) if late else 0.0,
        },
    }


def served(loop: Loop) -> list[C.Served]:
    w0, w1 = loop.window
    return [
        C.Served(rid=t.rid, prompt=t.plan.prompt, tokens=t.tokens, struck=t.struck)
        for t in loop.tracks
        if w0 <= t.plan.due_s < w1 and t.status == "done"
    ]


def failures(loop: Loop, vocab: int) -> dict:
    w0, w1 = loop.window
    due = [t for t in loop.tracks if w0 <= t.plan.due_s < w1]
    lost = sum(
        1 for t in due if t.status != "done" or len(t.tokens) != t.plan.max_new
    )
    oov = sum(1 for t in due for x in t.tokens if not 0 <= x < vocab)
    bad_strikes = sum(1 for *_, ok, _ in loop.strikes if not ok)
    return {"requests_lost": lost, "tokens_out_of_vocab": oov,
            "strikes_unrepaired": bad_strikes, "attempted": len(due) + len(loop.strikes)}


def memory_peak(n: int) -> int:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()[:n]
    ]
    return int(max(peaks))


def free(sys_: System) -> None:
    """Drop the engine and its resident state; the weights stay."""
    sys_.engine = None
    gc.collect()


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)
