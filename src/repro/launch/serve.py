"""Serving driver.

Default path — the continuous-batching engine (``miso.serve``): one
resident slot-masked decoder; requests with mixed per-request
dependability policies join and leave the batch mid-stream; prints the
SLO surface (tokens/s, TTFT p50/p99, per-request faults).

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --reduced --slots 4 --requests 6 --mix none,dmr --decode 12

Prefill is bucketed (``--prefill-bucket-min``: one jit compile per
geometric bucket, not per distinct prompt length) and optionally chunked
(``--prefill-chunk``: the out-of-band forward is bounded to the chunk,
the prompt tail walks through the resident transition one token per
tick); ``prefill_compiles`` is printed from ``engine.metrics()``.

``--paged`` switches the resident KV cache to the paged pool
(``--page-size`` tokens per page): slots hold page lists into one shared
pool, admission checks free pages, and eviction is a page-table release —
the metrics line gains pages_total/pages_free/page_faults.

``--spec-k K`` turns on speculative decoding: every request asks for a
draft length of K, decode runs the verify walk (up to K+1 tokens commit
per tick — docs/serving.md), and the metrics line gains the
spec_ticks/spec_tokens_per_tick counters.  ``--spec-arch`` names a
reduced config for a real divergent draft (default: self-drafting).

``--strike`` arms one bit-flip against the first DMR request's replica
slot mid-decode and verifies it is detected, attributed to that request,
and repaired (the CI serving smoke runs this, both dense and --paged).
Combined with --spec-k, give --decode headroom (> 2*(K+1)) so the
victim is still resident when the flip lands.

``--static`` keeps the fixed-batch reference path: prefill a batch of
identical-length prompts, decode in one in-graph scan (optionally with
cell-level DMR/TMR on the whole decoder).

  PYTHONPATH=src python -m repro.launch.serve --static --arch mamba2-2.7b \
      --reduced --batch 4 --prompt-len 12 --decode 24
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api as miso
from repro.configs import get_config, get_reduced
from repro.core import RedundancyPolicy
from repro.distributed.sharding import LOCAL
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.lm_cells import (
    ServeConfig,
    install_prefill,
    make_serve_program,
)

POLICIES = {"none": RedundancyPolicy(),
            "dmr": RedundancyPolicy(level=2),
            "tmr": RedundancyPolicy(level=3)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--decode", type=int, default=24,
                    help="tokens per request (engine) / steps (static)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    # engine path
    ap.add_argument("--slots", type=int, default=4,
                    help="resident batch width of the engine")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--mix", default="none,dmr",
                    help="comma list of per-request policies to cycle "
                         "(none|dmr|tmr)")
    ap.add_argument("--strike", action="store_true",
                    help="inject one bit flip into the first DMR "
                         "request's replica slot and verify attribution")
    ap.add_argument("--placement", default="temporal",
                    choices=["temporal", "spatial"],
                    help="where replica slots live: temporal = batch "
                         "rows (host compare), spatial = the same slot "
                         "column on different mesh pods (O(1)-wire "
                         "cross-pod detect; needs >= --pods devices)")
    ap.add_argument("--pods", type=int, default=0,
                    help="mesh pods for --placement spatial (0 = one "
                         "pod per device, capped at 4)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: bound the out-of-band prefill "
                         "to this many tokens; the prompt tail walks "
                         "through the resident transition one token per "
                         "tick (0 = whole prompt)")
    ap.add_argument("--prefill-bucket-min", type=int, default=16,
                    help="smallest prefill compile bucket (geometric "
                         "ladder up to --max-len; 0 = exact-length "
                         "compiles)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: fixed-size pages in one shared "
                         "pool instead of per-slot contiguous cache")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged; must divide "
                         "--max-len)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft length k (tokens "
                         "proposed per tick; every request asks for it; "
                         "0 = plain decode)")
    ap.add_argument("--spec-arch", default="",
                    help="draft architecture for --spec-k (reduced "
                         "config name; empty = self-drafting)")
    # observability
    ap.add_argument("--trace-out", default="",
                    help="attach a tracer and export the run as Chrome "
                         "trace-event JSON to this path (open in "
                         "ui.perfetto.dev; see docs/observability.md)")
    ap.add_argument("--metrics-json", default="",
                    help="write the metrics-registry snapshot (JSON) to "
                         "this path on exit")
    # static path
    ap.add_argument("--static", action="store_true",
                    help="fixed-batch reference path (no engine)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--redundancy", default="none",
                    choices=["none", "dmr", "tmr"],
                    help="static path: cell-level policy on the decoder")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.static:
        static_main(cfg, args)
    else:
        engine_main(cfg, args)


# ===========================================================================
# continuous-batching engine path
# ===========================================================================
def engine_main(cfg, args):
    from repro.serving import DONE, Request
    from repro.serving.lm import lm_engine_parts

    spec = None
    if args.spec_k:
        from repro.models.lm_cells import SpecConfig

        spec = SpecConfig(draft_len=args.spec_k, draft_arch=args.spec_arch)
    spatial = args.placement == "spatial"
    mesh = None
    if spatial:
        n_dev = jax.device_count()
        pods = args.pods or min(4, n_dev)
        if n_dev % pods:
            raise SystemExit(
                f"--pods {pods} does not divide {n_dev} devices")
        if args.slots % pods:
            raise SystemExit(
                f"--slots {args.slots} must be a multiple of --pods {pods}")
        mesh = make_mesh((pods, n_dev // pods), ("pod", "data"))
    scfg = ServeConfig(batch=args.slots, max_len=args.max_len,
                       prefill_chunk=args.prefill_chunk,
                       prefill_bucket_min=args.prefill_bucket_min,
                       paged=args.paged, page_size=args.page_size,
                       spec=spec, placement=args.placement)
    prog, adapter = lm_engine_parts(cfg, scfg, LOCAL)
    tracer = miso.Tracer() if args.trace_out else None
    engine = miso.serve(prog, adapter, miso.EngineConfig(
        placement=args.placement, mesh=mesh, tracer=tracer))
    engine.start(jax.random.PRNGKey(args.seed))
    if spatial:
        print(f"placement: spatial ({engine.pods} pods x "
              f"{args.slots // engine.pods} slots, "
              f"backend={engine.exe.name})")

    rng = np.random.default_rng(args.seed + 1)
    mix = [m.strip() for m in args.mix.split(",") if m.strip()]
    policies = POLICIES
    if spatial:
        policies = {k: RedundancyPolicy(level=p.level, placement="spatial")
                    if p.level > 1 else p
                    for k, p in POLICIES.items()}
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(2, max(3, args.prompt_len + 1)))
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new_tokens=args.decode,
                            policy=policies[mix[i % len(mix)]],
                            spec=spec))

    # staggered submission: half now, half after a few ticks, so requests
    # genuinely join/leave the resident batch mid-stream
    t0 = time.time()
    for r in reqs[: max(1, len(reqs) // 2)]:
        engine.submit(r)
    engine.pump(max_ticks=3)
    for r in reqs[max(1, len(reqs) // 2):]:
        engine.submit(r)

    fault = None
    victim = next((r for r in reversed(reqs) if r.policy.level == 2), None)
    if args.strike:
        if victim is None:
            raise SystemExit("--strike needs a dmr request in --mix")
        fault = arm_strike(engine, cfg, scfg, victim)
    engine.pump(faults=fault)
    wall = time.time() - t0

    m = engine.metrics()
    print(f"engine: {m['done']}/{m['submitted']} requests done | "
          f"{m['tokens_out']} tokens in {wall:.2f}s "
          f"({m['tokens_out'] / max(wall, 1e-9):.1f} tok/s wall, "
          f"{m['tokens_per_s_busy']:.1f} tok/s busy, "
          f"util={m['utilization']:.0%}) | "
          f"ttft p50={m.get('ttft_p50_s', 0):.3f}s "
          f"p99={m.get('ttft_p99_s', 0):.3f}s")
    # the per-counter stats come straight from the metrics registry (the
    # same instruments --metrics-json snapshots and Prometheus scrapes)
    print("metrics:")
    print(engine.registry.render("serving_"))
    print(f"prefill: {m['prefill_compiles']} compiles "
          f"(buckets={m['prefill_buckets']}, chunk={m['prefill_chunk']}) | "
          f"defrag moves={m['defrag_moves']}")
    if m.get("paged"):
        print(f"paged: {m['pages_free']}/{m['pages_total']} pages free "
              f"(size={m['page_size']}) | page faults={m['page_faults']}")
    if args.spec_k:
        print(f"spec: k={args.spec_k} "
              f"draft={args.spec_arch or 'self'} | "
              f"{m['spec_tokens']} tokens over {m['spec_ticks']} verify "
              f"ticks ({m.get('spec_tokens_per_tick', 0):.2f}/tick, "
              f"min commit={m.get('spec_min_commit')})")
    for r in reqs:
        res = engine.result(r.id)
        mark = f" policy={r.policy.level}" if r.policy.level > 1 else ""
        print(f"  {r.id}: {res['status']} {res['n_tokens']} tok "
              f"faults={res['faults']}{mark} -> {res['tokens'][:8]}")
    bad = [r.id for r in reqs
           if engine.result(r.id)["status"] != DONE]
    if bad:
        raise SystemExit(f"requests did not complete: {bad}")
    if args.strike:
        res = engine.result(victim.id)
        if res["faults"] < 1 or victim.id not in m["fault_totals"]:
            raise SystemExit("strike was not attributed to its request")
        print(f"strike: detected, attributed to {victim.id}, repaired "
              f"(events={m['fault_totals'][victim.id]['events']:.0f})")
    if tracer is not None:
        if args.strike:
            # the dependability timeline must be IN the trace: the repair
            # instant on the struck request's own track
            evs = tracer.events()
            vtid = tracer.tid(victim.id)
            if not any(e.get("name") == "strike_repaired"
                       and e["tid"] == vtid for e in evs):
                raise SystemExit(
                    "strike repair event missing from trace")
        tracer.export(args.trace_out)
        print(f"trace: {tracer.emitted} events "
              f"({tracer.dropped} dropped) -> {args.trace_out}")
    if args.metrics_json:
        import json

        engine.metrics()  # refresh gauges before snapshotting
        with open(args.metrics_json, "w", encoding="utf-8") as f:
            json.dump(engine.registry.snapshot(), f, indent=1)
        print(f"metrics snapshot -> {args.metrics_json}")


def arm_strike(engine, cfg, scfg, victim):
    """Tick ``engine`` until ``victim`` (a DMR request) is resident with
    decode budget left, then return a ``FaultSpec`` that flips one bit of
    the "tokens" leaf in its SECOND replica slot on the next step.

    The flip fires one tick after the arming tick, and a speculative tick
    commits up to spec_k+1 tokens, so the victim needs that much budget
    headroom to still be resident when the strike lands (a speculating
    engine therefore wants ``max_new_tokens`` comfortably above
    2*(spec_k+1))."""
    from repro.models.lm_cells import (
        paged_serving_supported,
        paged_slot_decoder_init,
        resolve_draft_config,
        slot_decoder_init,
        spec_serving_supported,
    )
    from repro.serving import RUNNING

    spec = scfg.spec
    spec_k = spec.draft_len if spec is not None else 0
    rec = engine.requests[victim.id]
    for _ in range(10 * victim.max_new_tokens):
        if (rec.status == RUNNING
                and len(rec.tokens) + spec_k + 2 <= victim.max_new_tokens):
            break
        engine.pump(max_ticks=1)
    if rec.status != RUNNING:
        raise SystemExit("strike victim never became resident")

    # the flip targets the "tokens" leaf by FLAT INDEX: flatten the same
    # state layout the engine runs (paged trees order differently, and a
    # spec engine's decoder carries extra speculation leaves)
    dcfg, dlen = None, 0
    if spec is not None and spec_serving_supported(cfg):
        dcfg, dlen = resolve_draft_config(cfg, spec), spec.draft_len
    if scfg.paged and paged_serving_supported(cfg):
        example = jax.eval_shape(lambda: paged_slot_decoder_init(
            cfg, 2, scfg.max_len, scfg.page_size, 1, dcfg, dlen))
    else:
        example = jax.eval_shape(lambda: slot_decoder_init(
            cfg, 2, scfg.max_len, dcfg, dlen))
    flat, _ = jax.tree_util.tree_flatten_with_path(example)
    leaf_i = next(i for i, (p, _) in enumerate(flat)
                  if any(getattr(q, "key", None) == "tokens" for q in p))
    return miso.FaultSpec.at(
        step=engine.exe.metrics()["steps"] + 1,
        cell_id=engine.exe.program.cell_id("decoder"), leaf=leaf_i,
        index=rec.slots[1], bit=4)


# ===========================================================================
# static fixed-batch reference path
# ===========================================================================
def static_main(cfg, args):
    from repro.core.redundancy import canonical_state, replicate_state

    scfg = ServeConfig(batch=args.batch, max_len=args.max_len)
    policy = POLICIES[args.redundancy]
    prog = make_serve_program(cfg, scfg, LOCAL).with_policies(
        {"decoder": policy})
    states = prog.init_states(jax.random.PRNGKey(args.seed))

    # prefill: run the real batched prefill (forward + cache fill), then
    # install the cache into the decoder cell's state
    key = jax.random.PRNGKey(args.seed + 1)
    shape = (args.batch, args.prompt_len)
    if cfg.n_codebooks > 1:
        shape = shape + (cfg.n_codebooks,)
    prompts = jax.random.randint(key, shape, 0, cfg.vocab_size, jnp.int32)
    # prefill always reads the canonical (replica-0) view of the weights —
    # works whether or not a policy replicated the weights cell
    params = canonical_state(
        states["weights"], prog.cells["weights"].redundancy.level)["params"]
    t0 = time.time()
    vision = None
    if cfg.n_vision_tokens:
        vision = jnp.zeros((args.batch, min(cfg.n_vision_tokens,
                                            args.prompt_len), cfg.d_model),
                           cfg.compute_dtype)
    logits, cache, _ = jax.jit(
        lambda p, t: T.forward(cfg, p, t, ctx=LOCAL, fill_cache=True,
                               vision_embeds=vision)
    )(params, prompts)
    # pad the filled cache up to max_len capacity and install it into
    # EVERY decoder replica (under DMR/TMR the decoder state carries a
    # leading replica axis; replicas must start from the same prefill)
    filled = install_prefill(
        cfg, T.init_cache(cfg, args.batch, args.max_len), cache,
        args.prompt_len)
    dec = dict(canonical_state(states["decoder"], policy.level))
    dec["cache"] = filled
    dec["tokens"] = _first_token(cfg, logits)
    states = dict(states)
    states["decoder"] = replicate_state(dec, policy.level)
    t_prefill = time.time() - t0

    t1 = time.time()
    exe = miso.compile(prog, backend="lockstep", donate=False)
    res = exe.run(
        states, args.decode,
        collect=lambda st: (st["decoder"]["tokens"]
                            if policy.level == 1 else
                            jax.tree.map(lambda x: x[0],
                                         st["decoder"]["tokens"])),
    )
    reports = res.reports
    toks = jax.device_get(res.collected)
    t_decode = time.time() - t1
    print(f"prefill {args.prompt_len} tok x{args.batch}: {t_prefill:.2f}s | "
          f"decode {args.decode} steps: {t_decode:.2f}s "
          f"({args.decode*args.batch/max(t_decode,1e-9):.1f} tok/s)")
    seq = toks[:, 0].reshape(args.decode, -1)[:, 0]
    print("sample continuation (seq 0):", seq.tolist())
    if policy.level > 1:
        print("redundancy events:",
              float(reports["decoder"]["events"]))


def _first_token(cfg, logits):
    nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    if cfg.n_codebooks > 1:
        return nxt.reshape(nxt.shape[0], 1, cfg.n_codebooks)
    return nxt


if __name__ == "__main__":
    main()
