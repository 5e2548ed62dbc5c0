"""Smoke run of MISO's main path on a TPU chip, in one process.

    python chip_smoke.py                # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips   # four chips: phase (c) only

(a) Executor front door: one DMR and one TMR cell of 2^22 float32 words
    per replica through ``miso.compile(backend="auto")``, which must pick
    the compiled Pallas epilogue (``lockstep_pallas``); one injected
    strike; final states and fault reports bitwise equal to the XLA
    ``lockstep`` back-end.
(b) Serving at published widths: internlm2-1.8b from seed-made weights
    through ``lm_engine_parts`` + ``miso.serve`` with the paged KV cache;
    8 staggered requests (none/DMR/TMR, every replicated request with an
    unreplicated twin), one strike on a DMR request's second replica slot.
    Twins must emit identical tokens, the strike must be detected,
    attributed and repaired, and the decode step must hold the compiled
    paged-attention kernel.
(c) Spatial placement on four chips against temporal placement of the
    same requests: replica slots on distinct devices, equal tokens.

The lines before the last are smoke numbers, not measurements.  The last
line is one JSON object: {"ok": true, "device": {...}}.  Without a TPU the
script exits non-zero and prints no JSON line.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api as miso  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.distributed.sharding import LOCAL  # noqa: E402
from repro.kernels.ops import on_tpu  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import arm_strike  # noqa: E402
from repro.models.lm_cells import ServeConfig  # noqa: E402
from repro.serving import DONE, Request  # noqa: E402
from repro.serving.lm import lm_engine_parts  # noqa: E402

ARCH = "internlm2-1.8b"
#: phase (a): float32 words per replica, 64 blocks of the 64Ki-word kernels
WORDS = 1 << 22


class SmokeError(RuntimeError):
    """A phase produced a wrong result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def say(*parts) -> None:
    print("[smoke]", *parts, flush=True)


def _bitwise_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def device_bytes(stat: str = "peak_bytes_in_use") -> int | None:
    """One of device 0's memory counters (None where it keeps none)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get(stat)


class CompileSeconds:
    """Duration listener summing JAX's XLA backend-compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += secs


def timed_phase(name: str, clock: CompileSeconds, fn, *args, **kw):
    """Run one phase; print its wall and backend-compile seconds."""
    t, c = time.perf_counter(), clock.total
    out = fn(*args, **kw)
    say(f"{name} wall {time.perf_counter() - t:.2f}s, of which backend "
        f"compile {clock.total - c:.2f}s; peak_bytes_in_use so far "
        f"{device_bytes()}")
    return out


# --------------------------------------------------------------------------
# (a) executor front door
# --------------------------------------------------------------------------
def replicated_program(level: int, words: int) -> miso.MisoProgram:
    """One cell of ``words`` float32 words per replica.  The transition
    multiplies by powers of two, so XLA and Pallas paths round alike."""
    p = miso.MisoProgram()
    p.add(miso.CellType(
        "a", lambda k: {"x": jax.random.normal(k, (words,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5
                      + jnp.roll(prev["a"]["x"], 1) * 0.5},
        redundancy=miso.RedundancyPolicy(level=level)))
    return p


def phase_executor(words: int, *, steps: int = 6, backend: str = "auto",
                   seed: int = 0) -> dict:
    """DMR and TMR cells through ``miso.compile(backend)``; returns smoke
    numbers per level.  ``backend`` must resolve to ``lockstep_pallas``,
    with compiled kernels exactly when a TPU is present."""
    out = {}
    key = jax.random.PRNGKey(seed)
    for level in (2, 3):
        prog = replicated_program(level, words)
        fault = miso.FaultSpec.at(step=2, cell_id=0, replica=1,
                                  index=words // 2 + 3, bit=21)
        exe = miso.compile(prog, backend=backend, donate=False)
        check(exe.name == "lockstep_pallas",
              f"backend {backend!r} resolved to {exe.name!r}")
        check(exe.metrics()["interpret"] is (not on_tpu()),
              f"interpret={exe.metrics()['interpret']} on "
              f"{jax.devices()[0].platform}")
        t0 = time.perf_counter()
        got = exe.run(exe.init(key), steps, start_step=0, faults=fault)
        jax.block_until_ready(got.states)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = exe.run(exe.init(key), steps, start_step=0, faults=fault)
        jax.block_until_ready(again.states)
        warm_s = time.perf_counter() - t0
        ref_exe = miso.compile(prog, backend="lockstep", donate=False)
        ref = ref_exe.run(ref_exe.init(key), steps, start_step=0,
                          faults=fault)
        check(_bitwise_equal(got.states, ref.states),
              f"level {level}: states differ from lockstep")
        check(_bitwise_equal(got.reports, ref.reports),
              f"level {level}: fault reports differ from lockstep")
        check(_bitwise_equal(got.states, again.states),
              f"level {level}: a second run differs")
        events = float(np.sum(np.asarray(got.reports["a"]["events"])))
        check(events >= 1, f"level {level}: strike not detected")
        out[level] = {"events": events, "first_run_s": first_s,
                      "warm_run_s": warm_s}
        say(f"(a) level {level}: {words} words/replica, backend={exe.name}, "
            f"interpret={exe.metrics()['interpret']}, events={events:g}, "
            f"first run {first_s:.2f}s (compile included), "
            f"warm run {warm_s:.4f}s for {steps} steps")
    return out


# --------------------------------------------------------------------------
# (b) / (c) serving
# --------------------------------------------------------------------------
def _policies(spatial: bool) -> dict:
    place = "spatial" if spatial else "temporal"
    return {"none": miso.RedundancyPolicy(),
            "dmr": miso.RedundancyPolicy(level=2, placement=place),
            "tmr": miso.RedundancyPolicy(level=3, placement=place)}


def make_prompts(cfg, *, seed: int, n_pairs: int, lens: tuple[int, int]):
    """One prompt per twin pair, lengths and tokens drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(lens[0], lens[1] + 1))
                         ).astype(np.int32)
            for _ in range(n_pairs)]


def serve_twins(cfg, scfg: ServeConfig, prompts, *, new_tokens: int,
                seed: int, tag: str, mesh=None) -> dict:
    """Serve every prompt twice: once replicated (DMR and TMR in turn) and
    once unreplicated, with one strike on the last DMR request's second
    replica slot.  Checks completion, twin parity and the strike's
    detection, attribution and repair; returns tokens and counters."""
    spatial = scfg.placement == "spatial"
    pol = _policies(spatial)
    pairs = [(Request(prompt=p, max_new_tokens=new_tokens,
                      policy=pol["dmr" if i % 2 == 0 else "tmr"]),
              Request(prompt=p.copy(), max_new_tokens=new_tokens,
                      policy=pol["none"]))
             for i, p in enumerate(prompts)]
    reqs = [r for pair in pairs for r in pair]
    prog, adapter = lm_engine_parts(cfg, scfg, LOCAL)
    engine = miso.serve(prog, adapter, miso.EngineConfig(
        placement=scfg.placement, mesh=mesh))
    engine.start(jax.random.PRNGKey(seed))

    # staggered: half now, half after a few ticks
    t0 = time.perf_counter()
    half = len(reqs) // 2
    for r in reqs[:half]:
        check(engine.submit(r), f"{r.id} rejected")
    engine.pump(max_ticks=3)
    for r in reqs[half:]:
        check(engine.submit(r), f"{r.id} rejected")
    victim = next(r for r in reversed(reqs) if r.policy.level == 2)
    fault = arm_strike(engine, cfg, scfg, victim)
    engine.pump(faults=fault)
    serve_s = time.perf_counter() - t0

    m = engine.metrics()
    res = {r.id: engine.result(r.id) for r in reqs}
    for r in reqs:
        check(res[r.id]["status"] == DONE,
              f"{r.id}: status {res[r.id]['status']}")
        check(res[r.id]["n_tokens"] == new_tokens,
              f"{r.id}: {res[r.id]['n_tokens']} tokens")
        check(all(0 <= t < cfg.vocab_size for t in res[r.id]["tokens"]),
              f"{r.id}: token out of vocabulary")
    for rep, twin in pairs:
        check(res[rep.id]["tokens"] == res[twin.id]["tokens"],
              f"{rep.id} (level {rep.policy.level}) and its twin {twin.id} "
              "emitted different tokens")
    check(res[victim.id]["faults"] >= 1 and victim.id in m["fault_totals"],
          f"strike on {victim.id} was not detected and attributed")
    check(set(m["request_faults"]) == {victim.id}
          and set(m["fault_totals"]) == {victim.id},
          f"faults recorded beyond the victim {victim.id}: "
          f"{m['request_faults']}")
    replica_slots = {r.id: res[r.id]["slots"] for r, _ in pairs}
    say(f"{tag} {scfg.placement}: "
        f"{m['done']}/{m['submitted']} done, {m['tokens_out']} tokens, "
        f"{m['ticks']} ticks; serve {serve_s:.2f}s (compiles included), busy {m['busy_s']:.2f}s; "
        f"strike on {victim.id} slot {res[victim.id]['slots'][1]} repaired "
        f"(events={m['fault_totals'][victim.id]['events']:g}); "
        f"prefill compiles={m['prefill_compiles']} buckets="
        f"{m['prefill_buckets']}"
        + (f"; pages free {m['pages_free']}/{m['pages_total']}, "
           f"page faults={m['page_faults']}" if m.get("paged") else ""))
    return {"engine": engine, "metrics": m, "pairs": pairs, "results": res,
            "replica_slots": replica_slots, "serve_s": serve_s}


def decode_step_text(engine, seed: int) -> str:
    """StableHLO text of the engine's compiled decode step, lowered from
    the resident state's shapes."""
    shapes = jax.eval_shape(engine.exe.init, jax.random.PRNGKey(seed))
    return jax.jit(lambda s: engine.exe.pure_step(s, 0)).lower(
        shapes).as_text()


def phase_serving(cfg, *, seed: int = 0, batch: int = 8, max_len: int = 2048,
                  page_size: int = 16, lens: tuple[int, int] = (64, 512),
                  new_tokens: int = 32, n_pairs: int = 4) -> dict:
    """(b): paged serving of ``cfg`` on one device."""
    scfg = ServeConfig(batch=batch, max_len=max_len, paged=True,
                       page_size=page_size)
    prompts = make_prompts(cfg, seed=seed, n_pairs=n_pairs, lens=lens)
    run = serve_twins(cfg, scfg, prompts, new_tokens=new_tokens, seed=seed,
                      tag="(b)")
    check(run["metrics"].get("paged") is True, "engine is not paged")
    text = decode_step_text(run["engine"], seed)
    kernels = text.count("tpu_custom_call")
    if on_tpu():
        check(kernels >= 1 and "paged_gqa_attention" in text,
              "decode step holds no compiled paged_gqa_attention kernel")
    say(f"(b) decode step: {kernels} tpu_custom_call op(s)")
    run["kernels"] = kernels
    return run


def slot_devices(engine, slots) -> list:
    """The device that holds each global slot of the decoder state."""
    leaf = engine._states[engine.adapter.cell]["tokens"]
    ax = engine.adapter.slot_axes["tokens"]
    out = []
    for s in slots:
        out.append(next(sh.device for sh in leaf.addressable_shards
                        if sh.index[ax].start is None
                        or sh.index[ax].start <= s < sh.index[ax].stop))
    return out


def phase_spatial(cfg, *, seed: int = 0, pods: int = 4, batch: int = 8,
                  max_len: int = 2048, lens: tuple[int, int] = (64, 512),
                  new_tokens: int = 32, n_pairs: int = 4) -> dict:
    """(c): the same requests under spatial placement on ``pods`` devices
    (dense cache) and under temporal placement on one device."""
    prompts = make_prompts(cfg, seed=seed, n_pairs=n_pairs, lens=lens)
    temporal = serve_twins(
        cfg, ServeConfig(batch=batch, max_len=max_len), prompts,
        new_tokens=new_tokens, seed=seed, tag="(c)")
    # free its weights and cache before the spatial run: the engine's
    # parts reference each other, so only the cycle collector frees them
    temporal["engine"] = None
    gc.collect()
    say("(c) after the temporal run: bytes_in_use "
        f"{device_bytes('bytes_in_use')}")
    mesh = make_mesh((pods, 1), ("pod", "data"),
                     devices=jax.devices()[:pods])
    spatial = serve_twins(
        cfg, ServeConfig(batch=batch, max_len=max_len, placement="spatial"),
        prompts, new_tokens=new_tokens, seed=seed, tag="(c)", mesh=mesh)
    eng = spatial["engine"]
    check(eng.exe.name == "spatial_lockstep",
          f"spatial engine runs {eng.exe.name!r}")
    for (rep, _), slots in zip(spatial["pairs"],
                               spatial["replica_slots"].values()):
        devs = slot_devices(eng, slots)
        say(f"(c) {rep.id} level {rep.policy.level}: slots {slots} on "
            + ", ".join(f"{d.platform}:{d.id}" for d in devs))
        check(len(set(devs)) == len(devs),
              f"{rep.id}: replica slots share a device")
    for (ts, tt), (ss, st) in zip(temporal["pairs"], spatial["pairs"]):
        for a, b in ((ts, ss), (tt, st)):
            check(temporal["results"][a.id]["tokens"]
                  == spatial["results"][b.id]["tokens"],
                  f"spatial {b.id} and temporal {a.id} emitted different "
                  "tokens")
    return {"temporal": temporal, "spatial": spatial}


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase (c): spatial vs temporal serving "
                         "on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of weights, states and prompts")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    count = len(jax.devices())
    if args.four_chips and count < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {count}",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    clock = CompileSeconds()
    jax.monitoring.register_event_duration_secs_listener(clock)
    say(f"device {dev.device_kind} x{count}, jax {jax.__version__}, "
        f"compile cache {cache}")
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            timed_phase("(c)", clock, phase_spatial, cfg, seed=args.seed)
        else:
            timed_phase("(a)", clock, phase_executor, WORDS, seed=args.seed)
            timed_phase("(b)", clock, phase_serving, cfg, seed=args.seed)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"total wall {time.perf_counter() - t0:.2f}s, peak_bytes_in_use "
        f"{device_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
