"""LM adapter for the continuous batcher: the slot-masked serve program
of ``models/lm_cells.py`` packaged as a ``SlotAdapter``.

    cfg = get_reduced("internlm2-1.8b")
    prog, adapter = lm_engine_parts(cfg, ServeConfig(batch=8, max_len=128))
    engine = miso.serve(prog, adapter)

Prefill is BUCKETED: prompts are right-padded to a small geometric
compile ladder (``ServeConfig.prefill_bucket_min`` doubling up to
``max_len``), so ``jit_prefill`` compiles once per bucket instead of once
per distinct prompt length — no recompile storm under real traffic.  The
padded positions are masked out of the filled cache by the forward's
``prompt_len`` argument, so a bucketed prefill is indistinguishable from
an exact-length one.  Recurrent archs (mamba/zamba) and the vision stub
fall back to exact-length compiles (padding folds into their state).

Prefill is optionally CHUNKED (``ServeConfig.prefill_chunk``): the
out-of-band forward covers at most ``prefill_chunk`` prompt tokens; the
tail rides into the slot's ``pending`` segment and is walked one token
per tick INSIDE the resident slot-masked transition, so admitting a long
prompt stalls the running batch for one bounded chunk forward instead of
the whole prompt.  ``prefill_chunk=0`` is the degenerate one-chunk case
(whole prompt out-of-band).

The engine surfaces ``prefill_compiles`` / ``prefill_buckets`` in
``metrics()`` via the adapter's ``stats`` hook.
"""

from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jit import named
from repro.distributed.sharding import LOCAL, ShardCtx
from repro.models.config import ModelConfig
from repro.models.lm_cells import (
    ServeConfig,
    make_slot_serve_program,
    paged_pool_pages,
    paged_serving_supported,
    paged_slot_decoder_init,
    prefill_bucket_ladder,
    prefill_slot_state,
    resolve_draft_config,
    slot_decoder_init,
    spec_serving_supported,
)

from .engine import EngineParts, SlotAdapter
from .request import Request
from .slots import infer_slot_axes


def lm_engine_parts(cfg: ModelConfig, scfg: ServeConfig, ctx: ShardCtx = LOCAL):
    """``EngineParts(program, adapter)`` for ``miso.serve``: the resident
    slot-masked LM serve program plus the glue the engine needs to run
    it.  (A NamedTuple — the historical ``prog, adapter = ...`` unpack
    keeps working.)"""
    prog = make_slot_serve_program(cfg, scfg, ctx)
    # paged KV: same gate the program builder uses — unsupported archs
    # keep the dense cache (mirrors the bucket carve-outs below), and say so
    paged = scfg.paged and paged_serving_supported(cfg)
    if scfg.paged and not paged:
        warnings.warn(
            f"{cfg.name}: the paged KV cache needs an attention-only, "
            "unwindowed model; serving from the dense cache", stacklevel=2)
    # speculative decoding: same silent-fallback pattern — archs that
    # cannot roll the cache position back keep plain decode, and any
    # per-request spec ask is then ignored (docs/serving.md)
    spec = scfg.spec if (scfg.spec is not None
                         and spec_serving_supported(cfg)) else None
    dcfg = resolve_draft_config(cfg, spec) if spec else None
    spec_len = spec.draft_len if spec else 0
    if paged:
        axes = None  # paged axes are inferred below, with the page pool
    else:
        axes = infer_slot_axes(
            lambda b: slot_decoder_init(cfg, b, scfg.max_len, dcfg, spec_len)
        )

    # bucket padding is maskable only for full-attention caches:
    # recurrent (mamba) segments fold padding into their state; the
    # vision-stub splice depends on the physical prompt length; and a
    # sliding-window fill keeps the trailing W positions of the PADDED
    # sequence, evicting real prompt KV the prompt_len scrub cannot
    # restore — all fall back to exact-length prefill compiles
    bucketable = (
        cfg.mixer_type != "mamba2" and not cfg.n_vision_tokens and not cfg.window
    )
    chunkable = not cfg.n_vision_tokens
    ladder = prefill_bucket_ladder(scfg) if bucketable else ()
    chunk = scfg.prefill_chunk if chunkable else 0
    if chunk > 0 and ladder:
        # honor the documented stall bound: a chunk-sized head must run
        # a chunk-sized forward, not round up to the ladder floor
        ladder = tuple(sorted(set(ladder) | {min(chunk, scfg.max_len)}))

    # jit keys its compilation cache on input shapes: the prompt head is
    # padded to a ladder bucket (pending tail is always max_len-shaped),
    # so one compile covers every prompt length that rounds up to it.
    # On the exact-length fallback the head is never padded, so
    # prompt_len masking is unnecessary (and recurrent archs reject it)
    def _prefill_impl(params, dparams, head, plen, pend, npend, spec_k, budget):
        return prefill_slot_state(
            cfg,
            scfg,
            params,
            head,
            ctx=ctx,
            prompt_len=plen if bucketable else None,
            pending=pend,
            n_pending=npend,
            draft_cfg=dcfg,
            draft_params=dparams,
            spec_k=spec_k if spec else None,
            budget=budget if spec else None,
        )

    jit_prefill = jax.jit(named(_prefill_impl, "prefill"))
    buckets_used: set = set()

    tail_dims = (cfg.n_codebooks,) if cfg.n_codebooks > 1 else ()

    def prefill(req: Request, states: dict):
        prompt = np.asarray(req.prompt, np.int32).reshape((-1,) + tail_dims)
        plen = int(prompt.shape[0])
        if chunk <= 0 or plen <= chunk:
            c0 = plen
        else:
            # grow the head past the chunk if needed so the tail always
            # fits the max_len pending segment (windowed archs serve
            # prompts longer than the cache; their whole-prompt path
            # admits them, chunking must too)
            c0 = max(chunk, plen - scfg.max_len)
        bucket = min((b for b in ladder if b >= c0), default=c0)
        # the bucket-sized forward is paid for regardless: cover as much
        # prompt as fits in it, shrinking the one-token-per-tick tail
        c0 = min(plen, bucket)
        head = np.zeros((bucket,) + tail_dims, np.int32)
        head[:c0] = prompt[:c0]
        pend = np.zeros((scfg.max_len,) + tail_dims, np.int32)
        n_pending = plen - c0
        pend[:n_pending] = prompt[c0:]
        params = states["weights"]["params"]
        dparams = states["weights"]["draft"] if dcfg is not None else None
        # per-request draft length: the request's ask clamped to the
        # resident draft's verify-walk width (0 = plain decode)
        spec_k = min(req.spec.draft_len, spec_len) if (spec and req.spec) else 0
        slot_state, first = jit_prefill(
            params,
            dparams,
            head,
            jnp.int32(c0),
            pend,
            jnp.int32(n_pending),
            jnp.int32(spec_k),
            jnp.int32(req.max_new_tokens),
        )
        buckets_used.add(bucket)
        if n_pending:
            # the head continuation is a truncated-prompt token: the real
            # first token is emitted by the tick that consumes the last
            # pending prompt token
            return slot_state, None, n_pending
        return slot_state, first, 0

    def validate(req: Request) -> Optional[str]:
        plen = int(np.asarray(req.prompt).shape[0])
        if plen + req.max_new_tokens > scfg.max_len and not cfg.window:
            return (
                f"prompt {plen} + budget {req.max_new_tokens} exceeds "
                f"cache capacity {scfg.max_len}"
            )
        if req.spec is not None and spec is not None:
            # one resident draft serves the whole engine: a request may
            # pick its draft LENGTH, not a different draft model
            if req.spec.draft_arch and req.spec.draft_arch != spec.draft_arch:
                return (
                    f"request draft_arch {req.spec.draft_arch!r} does not "
                    f"match the engine's resident draft "
                    f"{spec.draft_arch or 'self'!r}"
                )
        # a spec ask on a non-speculating engine degrades to plain
        # decode (same silent fallback as paged/bucketing carve-outs);
        # no pending-capacity check: prefill() grows the head chunk so
        # the uncovered tail never exceeds the max_len pending segment
        return None

    # paged-KV assembly: page table + surgery + demand-growth pre-tick
    table = None
    surgery = None
    pre_tick = None
    has_capacity = None
    if paged:
        from .paging import (
            PageTable,
            infer_paged_axes,
            make_pre_tick,
            paged_surgery,
        )

        psize = scfg.page_size
        n_pages = paged_pool_pages(scfg)
        table = PageTable(n_pages, psize, scfg.max_len // psize)
        axes = infer_paged_axes(
            lambda b: paged_slot_decoder_init(
                cfg, b, scfg.max_len, psize, n_pages, dcfg, spec_len
            )
        )

        def reserve_fn(req: Request) -> int:
            # worst-case pages of ONE replica slot: the request can write
            # positions [0, plen + max_new) at most (capped by the cache)
            return table.pages_for(
                min(req.prompt_len + req.max_new_tokens, scfg.max_len)
            )

        # the scrub template only reads non-pool leaves: a 1-page pool
        # keeps it tiny
        scrub_tmpl = paged_slot_decoder_init(
            cfg, 1, scfg.max_len, psize, 1, dcfg, spec_len
        )
        surgery = paged_surgery(
            table, "decoder", axes, scrub_tmpl, reserve_fn=reserve_fn
        )
        pre_tick = make_pre_tick(
            table,
            "decoder",
            scfg.batch,
            walk_chunk=max(1, chunk),
            draft_len=spec_len,
        )

        def has_capacity(req: Request) -> bool:
            return table.can_admit(req.n_slots * reserve_fn(req))

    def stats() -> dict:
        out = {
            "prefill_compiles": len(buckets_used),
            "prefill_buckets": list(ladder) if ladder else None,
            "prefill_chunk": chunk,
            "paged": paged,
            "spec_draft_len": spec_len,
        }
        if spec is not None:
            out["spec_draft_arch"] = spec.draft_arch or "self"
        if table is not None:
            out["pages_total"] = table.n_pages
            out["pages_free"] = table.free_pages
            out["page_faults"] = table.page_faults
            out["page_size"] = table.page_size
        return out

    def make_empty():
        if paged:
            return paged_slot_decoder_init(
                cfg, 1, scfg.max_len, scfg.page_size, 1, dcfg, spec_len
            )
        return slot_decoder_init(cfg, 1, scfg.max_len, dcfg, spec_len)

    def attach_tracer(tracer) -> None:
        # the paged pre-tick hook and join emit their own events; dense
        # engines have no adapter-side emitters (no-op)
        if pre_tick is not None:
            pre_tick.tracer = tracer
            surgery.join.tracer = tracer

    adapter = SlotAdapter(
        cell="decoder",
        n_slots=scfg.batch,
        slot_axes=axes,
        prefill=prefill,
        read_tokens=lambda dec: dec["tokens"],
        make_empty=make_empty,
        validate=validate,
        stats=stats,
        surgery=surgery,
        has_capacity=has_capacity,
        pre_tick=pre_tick,
        walk_chunk=max(1, chunk),
        contiguous_replicas=not paged,
        read_spec=(
            (lambda dec: (dec["spec_out"], dec["spec_n"])) if spec else None
        ),
        attach_tracer=attach_tracer,
    )
    return EngineParts(prog, adapter)
