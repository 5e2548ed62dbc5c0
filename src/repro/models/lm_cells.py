"""The LM training/serving stack as a MISO program (DESIGN.md §5).

Training:
    cell data     -- source cell (in-graph deterministic batches)
    cell trainer  -- state = (params, optimizer state, metrics);
                     transition = fwd + bwd + AdamW update, reading the data
                     cell's *previous* batch (double-buffered input pipeline)

Serving:
    cell weights  -- static cell (empty transition — the paper's StaticImage
                     pattern) holding the model parameters
    cell decoder  -- state = (KV/SSM cache, last tokens, position);
                     transition = one greedy decode step for the whole batch

Replication (paper §IV) then applies to the trainer/decoder cells through
the generic MISO machinery: `program.with_policies({"trainer": DMR...})`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from jax import shard_map

from repro.core import CellType, MisoProgram
from repro.data.pipeline import DataConfig, data_cell
from repro.distributed.collectives import compressed_psum_int8
from repro.distributed.sharding import LOCAL, ShardCtx
from repro.optim.adamw import OptConfig, apply_updates, init_opt_state
from .config import ModelConfig
from . import transformer as T


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    data: DataConfig
    opt: OptConfig = OptConfig()
    microbatches: int = 1
    grad_compression: str = "none"   # none | int8_ef (dense archs only)
    param_seed: int = 0


def _make_batch(cfg: ModelConfig, data_state: dict) -> dict:
    batch = {"tokens": data_state["tokens"]}
    if cfg.n_vision_tokens:
        batch["vision_embeds"] = data_state["vision_embeds"]
    return batch


def make_data_cell(cfg: ModelConfig, tcfg: TrainConfig) -> CellType:
    base = data_cell(tcfg.data)
    if not cfg.n_vision_tokens:
        return base

    # extend the source cell with the vision-frontend stub output
    def init(key):
        st = base.init(key)
        st["vision_embeds"] = 0.02 * jax.random.normal(
            jax.random.fold_in(st["key"], 77),
            (tcfg.data.batch, cfg.n_vision_tokens, cfg.d_model),
            jnp.float32,
        ).astype(cfg.compute_dtype)
        return st

    def transition(prev):
        st = base.transition(prev)
        st["vision_embeds"] = 0.02 * jax.random.normal(
            jax.random.fold_in(st["key"], 77),
            (tcfg.data.batch, cfg.n_vision_tokens, cfg.d_model),
            jnp.float32,
        ).astype(cfg.compute_dtype)
        return st

    return CellType(name=base.name, init=init, transition=transition,
                    instances=base.instances)


def make_trainer_cell(
    cfg: ModelConfig, tcfg: TrainConfig, ctx: ShardCtx = LOCAL,
    *, data_name: str = "data",
) -> CellType:
    loss = functools.partial(T.loss_fn, cfg, ctx=ctx)
    if tcfg.grad_compression == "int8_ef":
        # the compressed path runs the loss INSIDE a shard_map over the
        # data axes — sharding constraints may then only mention the
        # remaining (auto) axes
        loss = functools.partial(
            T.loss_fn, cfg,
            ctx=dataclasses.replace(ctx, manual_axes=tuple(ctx.data_axes)))

    def init(key):
        params = T.init_params(cfg, jax.random.fold_in(key, tcfg.param_seed))
        st = {
            "params": params,
            "opt": init_opt_state(params, tcfg.opt),
            "metrics": {
                "loss": jnp.float32(0), "grad_norm": jnp.float32(0),
                "lr": jnp.float32(0),
            },
        }
        if tcfg.grad_compression == "int8_ef":
            n = sum(p.size for p in jax.tree.leaves(params))
            pad = (-n) % (512 * _dp_size(ctx))
            st["ef"] = jnp.zeros((n + pad,), jnp.float32)
        return st

    def grads_plain(params, batch):
        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
            params, batch
        )
        return grads, metrics

    def grads_microbatched(params, batch):
        mb = tcfg.microbatches
        toks = batch["tokens"]
        B = toks.shape[0]
        assert B % mb == 0

        def body(acc, i):
            sl = {
                k: jax.lax.dynamic_slice_in_dim(v, i * (B // mb), B // mb, 0)
                for k, v in batch.items()
            }
            (l, m), g = jax.value_and_grad(loss, has_aux=True)(params, sl)
            acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / mb, acc, g
            )
            return acc, m

        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        grads, ms = jax.lax.scan(body, zero, jnp.arange(mb))
        metrics = jax.tree.map(lambda x: jnp.mean(x), ms)
        return grads, metrics

    def transition(prev):
        st = prev["trainer"]
        batch = _make_batch(cfg, prev[data_name])
        params = st["params"]
        gfn = grads_microbatched if tcfg.microbatches > 1 else grads_plain

        if tcfg.grad_compression == "int8_ef":
            grads, metrics, new_ef = _compressed_grads(
                gfn, params, batch, st["ef"], ctx
            )
        else:
            grads, metrics = gfn(params, batch)
            new_ef = None
        new_params, new_opt, info = apply_updates(
            params, grads, st["opt"], tcfg.opt
        )
        out = {
            "params": new_params,
            "opt": new_opt,
            "metrics": {
                "loss": metrics["loss"].astype(jnp.float32),
                "grad_norm": info["grad_norm"],
                "lr": info["lr"],
            },
        }
        if new_ef is not None:
            out["ef"] = new_ef
        return out

    return CellType(name="trainer", init=init, transition=transition,
                    reads=(data_name,))


def _dp_size(ctx: ShardCtx) -> int:
    n = 1
    if ctx.mesh is not None:
        for a in ctx.data_axes:
            n *= ctx.mesh.shape[a]
    return n


def _compressed_grads(gfn, params, batch, ef, ctx: ShardCtx):
    """Per-dp-shard grads + int8 error-feedback reduction, under a
    partial-manual shard_map over the data axes (tp stays auto)."""
    from jax.sharding import PartitionSpec as P

    dp = ctx.data_axes
    leaves, tdef = jax.tree.flatten(params)
    sizes = [p.size for p in leaves]
    n = sum(sizes)
    pad = ef.shape[0] - n

    def local(params, batch, ef):
        g, metrics = gfn(params, batch)
        flat = jnp.concatenate(
            [x.astype(jnp.float32).reshape(-1) for x in jax.tree.leaves(g)]
        )
        if pad:
            flat = jnp.pad(flat, (0, pad))
        mean, new_ef = compressed_psum_int8(flat, ef, dp)
        metrics = jax.tree.map(
            lambda x: jax.lax.pmean(x, dp if len(dp) > 1 else dp[0]), metrics
        )
        return mean, metrics, new_ef

    mean, metrics, new_ef = shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(P(), P(dp if len(dp) > 1 else dp[0]), P()),
        out_specs=(P(), P(), P()),
        axis_names=set(dp),
        check_vma=False,
    )(params, batch, ef)
    out, off = [], 0
    for p, s in zip(leaves, sizes):
        out.append(mean[off:off + s].reshape(p.shape))
        off += s
    return tdef.unflatten(out), metrics, new_ef


def make_train_program(
    cfg: ModelConfig, tcfg: TrainConfig, ctx: ShardCtx = LOCAL,
) -> MisoProgram:
    prog = MisoProgram()
    prog.add(make_data_cell(cfg, tcfg))
    prog.add(make_trainer_cell(cfg, tcfg, ctx))
    return prog


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative decoding (docs/serving.md): a small DRAFT model
    proposes up to ``draft_len`` tokens per tick and the resident decoder
    verifies them all in one chunk-walk pass; the accepted prefix commits
    into the KV cache and the first rejection rolls the position back.
    Greedy verification — the emitted token stream is bitwise-identical
    to non-speculative greedy decode (the parity gate of
    tests/test_spec.py).

    On ``ServeConfig.spec`` this sizes the resident draft cell (engine-
    wide ``draft_len`` = the verify-walk width K); on ``Request.spec`` it
    picks the per-request draft length (clamped to the engine's K).

    draft_arch       -- reduced-config name of the draft model; "" = the
                        target model itself (self-speculation: with the
                        default seed the draft IS the target, every
                        proposal is accepted, and the tick amortization
                        is measured at its ceiling — the bench case).
    draft_param_seed -- draft parameter seed; None = the serve config's
                        ``param_seed`` (self-speculation: identical
                        params).  Any other value de-correlates the
                        draft, exercising real rejections.
    """
    draft_len: int = 4
    draft_arch: str = ""
    draft_param_seed: int | None = None

    def __post_init__(self):
        if self.draft_len < 1:
            raise ValueError("draft_len must be >= 1")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch: int
    max_len: int          # cache capacity == shape seq_len
    param_seed: int = 0
    prefill_len: int = 0  # >0: dry-run-style warm cache position
    #: continuous batcher only (repro/serving): the out-of-band prefill
    #: forward is bounded to this many prompt tokens; the remainder is
    #: stored in the slot's ``pending`` buffer and walked one token per
    #: tick INSIDE the resident transition, so a long admission never
    #: stalls running requests for more than one chunk-sized forward.
    #: 0 = whole-prompt (the degenerate one-chunk case).
    prefill_chunk: int = 0
    #: smallest prefill compile bucket; prompts are right-padded to a
    #: geometric ladder (min, 2*min, ... max_len) so jit compiles once
    #: per BUCKET instead of once per distinct prompt length.  0 disables
    #: bucketing (exact-length compiles — recurrent archs fall back to
    #: this automatically, since padding folds into mamba state).
    prefill_bucket_min: int = 16
    #: explicit bucket ladder override (sorted lengths); () = geometric.
    prefill_buckets: tuple = ()
    #: paged KV cache (continuous batcher only): slot KV lives in
    #: fixed-size pages of one shared pool (``serving/paging.py``) instead
    #: of a dense per-slot ``max_len`` allocation, so admission is bounded
    #: by free *pages*, not free dense bytes.  Recurrent and windowed
    #: archs fall back to dense with a warning (``paged_serving_supported``).
    paged: bool = False
    #: tokens per KV page; ``max_len`` must be a multiple of it.
    page_size: int = 16
    #: total pages in the shared pool; 0 = batch * (max_len / page_size)
    #: (capacity-equivalent to the dense cache).
    page_budget: int = 0
    #: speculative decoding (continuous batcher only): a resident draft
    #: cell proposes up to ``spec.draft_len`` tokens per tick and the
    #: slot-masked decoder verifies them in one pass.  Archs that cannot
    #: roll the cache position back (recurrent, windowed, vision,
    #: multi-codebook — ``spec_serving_supported``) silently fall back
    #: to plain decode, mirroring the paged fallback above.
    spec: SpecConfig | None = None
    #: where a DMR/TMR request's replica slots live: "temporal" keeps
    #: them as batch rows of one device group (host fingerprint compare),
    #: "spatial" places the same slot COLUMN on different mesh pods under
    #: shard_map, so a hardware strike is confined to one pod and detect
    #: is an O(1)-wire cross-pod collective.  The serve *program* is
    #: identical either way — the placement only stamps a marker the
    #: spatial executor keys on.
    placement: str = "temporal"


def prefill_bucket_ladder(scfg: "ServeConfig") -> tuple:
    """The prefill compile-bucket ladder of a serve config: explicit
    override, or geometric doubling from ``prefill_bucket_min`` capped at
    ``max_len``; () when bucketing is disabled.  Explicit entries are
    clamped to ``max_len`` (the cache cannot install a longer fill) and
    ``max_len`` itself is always present (otherwise prompts above the
    largest entry would silently revert to one compile per length)."""
    if scfg.prefill_buckets:
        return tuple(sorted(
            {min(b, scfg.max_len) for b in scfg.prefill_buckets if b > 0}
            | {scfg.max_len}))
    if scfg.prefill_bucket_min <= 0:
        return ()
    ladder, b = [], min(scfg.prefill_bucket_min, scfg.max_len)
    while b < scfg.max_len:
        ladder.append(b)
        b *= 2
    ladder.append(scfg.max_len)
    return tuple(ladder)


def make_serve_program(
    cfg: ModelConfig, scfg: ServeConfig, ctx: ShardCtx = LOCAL,
) -> MisoProgram:
    def w_init(key):
        return {"params": T.init_params(
            cfg, jax.random.fold_in(key, scfg.param_seed))}

    weights = CellType(
        name="weights", init=w_init, transition=lambda prev: prev["weights"],
    )

    def d_init(key):
        cache = T.init_cache(cfg, scfg.batch, scfg.max_len)
        if scfg.prefill_len:
            cache["pos"] = jnp.full((scfg.batch,), scfg.prefill_len,
                                    jnp.int32)
        shape = (scfg.batch, 1)
        if cfg.n_codebooks > 1:
            shape = shape + (cfg.n_codebooks,)
        return {
            "cache": cache,
            "tokens": jnp.zeros(shape, jnp.int32),
            "n_decoded": jnp.zeros((), jnp.int32),
        }

    def d_transition(prev):
        st = prev["decoder"]
        logits, cache = T.decode_step(
            cfg, prev["weights"]["params"], st["cache"], st["tokens"],
            ctx=ctx,
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # greedy
        if cfg.n_codebooks == 1:
            nxt = nxt.reshape(st["tokens"].shape)
        return {
            "cache": cache,
            "tokens": nxt,
            "n_decoded": st["n_decoded"] + 1,
        }

    decoder = CellType(
        name="decoder", init=d_init, transition=d_transition,
        reads=("weights",), instances=scfg.batch,
    )
    prog = MisoProgram()
    prog.add(weights)
    prog.add(decoder)
    return prog


# --------------------------------------------------------------------------
# continuous-batching serving (repro/serving): slot-masked decoder
# --------------------------------------------------------------------------
def spec_state_leaves(draft_cfg: ModelConfig, batch: int, max_len: int,
                      draft_len: int) -> dict:
    """The extra decoder-state leaves of a speculating engine (all
    per-slot; zeros on free slots like every other leaf):

    draft_cache -- the draft model's own KV cache, ALWAYS dense (the
                   draft is small; paging it would buy nothing), even
                   when the target cache is paged.  Absent under true
                   self-speculation (``draft_cfg is None``): the draft
                   shares the target's pass and cache.
    spec_out    -- (B, K+1) tokens committed this tick, in emission
                   order; col 0 doubles as the plain-decode token.
    spec_n      -- committed count: a+1 for a slot that verified this
                   tick (a = accepted draft prefix), 0 otherwise — the
                   engine emits ``spec_out[:spec_n]`` (or falls back to
                   ``tokens`` when 0).
    spec_k      -- the slot's requested draft length (0 = no
                   speculation for this request).
    budget      -- the request's ``max_new_tokens`` (the in-graph clamp
                   needs it: speculation must stop exactly where the
                   non-speculative engine would).
    """
    st = {
        "spec_out": jnp.zeros((batch, draft_len + 1), jnp.int32),
        "spec_n": jnp.zeros((batch,), jnp.int32),
        "spec_k": jnp.zeros((batch,), jnp.int32),
        "budget": jnp.zeros((batch,), jnp.int32),
    }
    if draft_cfg is not None:
        st["draft_cache"] = T.init_cache(draft_cfg, batch, max_len)
    return st


def slot_decoder_init(cfg: ModelConfig, batch: int, max_len: int,
                      draft_cfg: ModelConfig | None = None,
                      draft_len: int = 0) -> dict:
    """Decoder-cell state for the continuous batcher: every leaf is
    per-slot (leading or embedded batch axis), so requests can join/leave
    individual slots between stream ticks.  ``active`` is the slot mask;
    free slots hold zeros and are never written by the transition.

    ``pending``/``p_head``/``p_len`` is the chunked-prefill prompt
    segment: the tail of a long prompt that was NOT covered by the
    out-of-band prefill chunk.  While ``p_head < p_len`` the transition
    feeds ``pending[p_head]`` (the next prompt token) instead of the last
    generated token and advances the cursor — admission itself becomes a
    sequence of ordinary lock-step transitions.

    ``draft_cfg``/``draft_len`` (speculative engines only) add the
    ``spec_state_leaves``."""
    shape = (batch, 1)
    pshape = (batch, max_len)
    if cfg.n_codebooks > 1:
        shape = shape + (cfg.n_codebooks,)
        pshape = pshape + (cfg.n_codebooks,)
    st = {
        "cache": T.init_cache(cfg, batch, max_len),
        "tokens": jnp.zeros(shape, jnp.int32),
        "active": jnp.zeros((batch,), jnp.bool_),
        "n_decoded": jnp.zeros((batch,), jnp.int32),
        "pending": jnp.zeros(pshape, jnp.int32),
        "p_head": jnp.zeros((batch,), jnp.int32),
        "p_len": jnp.zeros((batch,), jnp.int32),
    }
    if draft_len > 0:
        st.update(spec_state_leaves(draft_cfg, batch, max_len, draft_len))
    return st


def paged_serving_supported(cfg: ModelConfig) -> bool:
    """Archs whose serve cache can live in pages: pure-attention text
    models.  Recurrent state (mamba/zamba) is a fixed-size recurrence —
    nothing to page; sliding-window caches ring-wrap (a page would be
    rewritten mid-flight); the vision splice pins the physical prompt
    layout.  Callers fall back to the dense cache for these."""
    return (cfg.mixer_type != "mamba2" and not cfg.window
            and not cfg.n_vision_tokens)


def spec_serving_supported(cfg: ModelConfig) -> bool:
    """Archs whose serve slots can speculate: full-attention single-
    codebook text models.  Rejection rolls back by resetting ``pos`` —
    sound only because the decode read paths mask every cache lane past
    ``pos`` (dense: ``slot_pos <= pos``; paged: ``lane <= pos``) and the
    next write overwrites the lane before reading it.  Recurrent state
    (mamba/zamba) cannot be rewound; a sliding-window ring evicts real
    KV on the speculative writes; the vision splice pins the prompt
    layout; multi-codebook tokens break the scalar accept compare."""
    return (cfg.mixer_type != "mamba2" and not cfg.window
            and not cfg.n_vision_tokens and cfg.n_codebooks == 1)


def resolve_draft_config(
    cfg: ModelConfig, spec: SpecConfig
) -> ModelConfig | None:
    """The draft model's config: ``spec.draft_arch`` as a reduced config;
    the target config itself for ``draft_arch=""`` with a divergent
    ``draft_param_seed``; or ``None`` for TRUE self-speculation (empty
    arch, default seed) — the draft would be the target bit for bit, so
    its forward pass is redundant and the program shares the target's
    output instead of running a second model (no ``draft_cache`` leaves,
    no draft params).  A real draft must share the target's token space
    (its proposals are fed to the target embedding) and satisfy
    ``spec_serving_supported`` itself (its cache rolls back alongside
    the target's)."""
    if not spec.draft_arch:
        return None if spec.draft_param_seed is None else cfg
    from repro.configs import get_reduced

    dcfg = get_reduced(spec.draft_arch)
    if dcfg.vocab_size != cfg.vocab_size or dcfg.n_codebooks != 1:
        raise ValueError(
            f"draft arch {spec.draft_arch!r} vocab "
            f"{dcfg.vocab_size} does not match target {cfg.vocab_size}")
    if not spec_serving_supported(dcfg):
        raise ValueError(
            f"draft arch {spec.draft_arch!r} cannot speculate (recurrent/"
            "windowed/vision drafts cannot roll back)")
    return dcfg


def paged_pool_pages(scfg: ServeConfig) -> int:
    """Total pages in the shared pool for a serve config (``page_budget``
    override, else capacity-equivalent to the dense cache)."""
    return scfg.page_budget or scfg.batch * (scfg.max_len // scfg.page_size)


def paged_slot_decoder_init(cfg: ModelConfig, batch: int, max_len: int,
                            page_size: int, n_pages: int,
                            draft_cfg: ModelConfig | None = None,
                            draft_len: int = 0) -> dict:
    """Paged variant of ``slot_decoder_init``: the dense per-slot cache is
    replaced by shared page POOLS plus a per-slot page table ``pages``
    ((batch, max_len/page_size) int32 pool rows, -1 = unmapped).  Pool
    leaves carry no slot axis — every slot's KV bytes live wherever its
    page table points.  The speculative leaves (when present) stay dense:
    the draft cache is small and per-slot."""
    if max_len % page_size:
        raise ValueError(
            f"max_len ({max_len}) must be a multiple of page_size "
            f"({page_size}): the paged-decode kernel gathers whole pages")
    shape = (batch, 1)
    pshape = (batch, max_len)
    if cfg.n_codebooks > 1:
        shape = shape + (cfg.n_codebooks,)
        pshape = pshape + (cfg.n_codebooks,)
    st = {
        "cache": T.init_paged_cache(cfg, batch, n_pages, page_size),
        "tokens": jnp.zeros(shape, jnp.int32),
        "active": jnp.zeros((batch,), jnp.bool_),
        "n_decoded": jnp.zeros((batch,), jnp.int32),
        "pending": jnp.zeros(pshape, jnp.int32),
        "p_head": jnp.zeros((batch,), jnp.int32),
        "p_len": jnp.zeros((batch,), jnp.int32),
        "pages": jnp.full((batch, max_len // page_size), -1, jnp.int32),
    }
    if draft_len > 0:
        st.update(spec_state_leaves(draft_cfg, batch, max_len, draft_len))
    return st


def spec_k_eff(spec_k, budget, n_decoded, pos, max_len: int, draft_len: int):
    """Per-slot EFFECTIVE draft length for one tick — the clamp that
    keeps speculation observationally identical to plain decode:

      * ``budget - n_decoded - 2``: the tick commits at most a+1 <=
        k_eff+1 tokens and the host has already emitted ``n_decoded + 1``
        (the prefill continuation is token 1), so this bound makes the
        request finish on exactly the token the non-speculative engine
        would finish on;
      * ``max_len - 1 - pos``: the verify walk writes cache positions
        ``pos .. pos+k_eff`` — never past the dense capacity or the
        paged reservation (which covers ``prompt_len + budget``).

    The paged pre-tick hook (``serving/paging.py:make_pre_tick``) applies
    the SAME formula host-side to map pages ahead of the walk; the two
    must stay in lock-step or a verify sub-step would write an unmapped
    page."""
    room = jnp.minimum(budget - n_decoded - 2, max_len - 1 - pos)
    return jnp.clip(jnp.minimum(spec_k, room), 0, draft_len)


def make_slot_serve_program(
    cfg: ModelConfig, scfg: ServeConfig, ctx: ShardCtx = LOCAL,
) -> MisoProgram:
    """The serving engine's resident program: a static ``weights`` cell
    plus a *slot-masked* ``decoder`` cell (when ``scfg.spec`` is set the
    weights cell also carries the draft model's params).

    Unlike ``make_serve_program`` (fixed batch, every row decodes), the
    decoder here carries a per-slot ``active`` mask and gates every state
    write on it: an inactive slot's cache bytes, position, and last token
    are bit-for-bit frozen across the transition.  Because each batch
    row's computation is row-independent (matmul rows, per-row softmax,
    per-row argmax), an active slot's trajectory is bitwise-identical no
    matter which — or how many — other slots are occupied.  That is the
    isolation invariant the continuous batcher is built on, and it is
    what lets ``repro.serving`` scatter new prompt caches into free slots
    and evict finished ones mid-stream without perturbing anyone else.

    Speculative decoding (docs/serving.md) extends the chunk walk: the
    draft and the verify pass are FUSED into this one transition rather
    than split into two cells, because a MISO transition reads the
    *previous* buffer (§II double-buffering) — a separate draft cell
    would pipeline its proposals one tick behind the verifier and break
    greedy parity.  (Scheduling draft/verify as dependent tasks the way
    Fonseca et al.'s task-based runtime does is the taskgraph-backend
    notch in ROADMAP.md.)  Each tick, for every slot with ``spec_k > 0``:

      sub-step 0      feeds the last committed token; target emits g1,
                      draft proposes d1 (both read the same input);
      sub-step j>=1   feeds the draft's proposal d_j to BOTH models:
                      the target emits g_{j+1} (the verification) and
                      the draft chains d_{j+1} — proposal and verify
                      interleave, so the draft cache ingests exactly the
                      token stream the target does;
      commit          a = longest prefix with d_j == g_j; tokens
                      g_1..g_{a+1} commit (they are what greedy decode
                      would have produced one at a time), and both cache
                      positions roll back to pos0 + a + 1 — the lanes
                      past the rollback point are invisible to every
                      later read (``spec_serving_supported``) and are
                      overwritten before use.

    Everything is in-graph, so a §IV replay of the tick reproduces the
    accept/rollback bit-for-bit and per-request DMR/TMR works unchanged.
    """
    from repro.serving.slots import infer_slot_axes, mask_slots

    spec = scfg.spec if (scfg.spec is not None
                         and spec_serving_supported(cfg)) else None
    dcfg = resolve_draft_config(cfg, spec) if spec else None
    K = spec.draft_len if spec else 0
    d_seed = (scfg.param_seed if spec is None or spec.draft_param_seed is None
              else spec.draft_param_seed)

    # the draft params live INSIDE the weights cell (not a separate
    # cell): program init splits one key per cell, so adding a cell
    # would re-key the target weights and break bitwise parity between
    # a speculating engine and its plain reference.  True self-
    # speculation (dcfg None) has no draft params at all — the draft IS
    # the target, bit for bit, so the target's pass is shared.
    def w_init(key):
        st = {"params": T.init_params(
            cfg, jax.random.fold_in(key, scfg.param_seed))}
        if dcfg is not None:
            st["draft"] = T.init_params(
                dcfg, jax.random.fold_in(key, d_seed))
        return st

    weights = CellType(
        name="weights", init=w_init, transition=lambda prev: prev["weights"],
    )

    paged = scfg.paged and paged_serving_supported(cfg)
    if paged:
        from repro.serving.paging import infer_paged_axes, mask_slots_paged

        n_pages = paged_pool_pages(scfg)
        axes = infer_paged_axes(
            lambda b: paged_slot_decoder_init(
                cfg, b, scfg.max_len, scfg.page_size, n_pages, dcfg, K))
        mask_fn = mask_slots_paged

        def d_init(key):
            return paged_slot_decoder_init(
                cfg, scfg.batch, scfg.max_len, scfg.page_size, n_pages,
                dcfg, K)
    else:
        axes = infer_slot_axes(
            lambda b: slot_decoder_init(cfg, b, scfg.max_len, dcfg, K))
        mask_fn = mask_slots

        def d_init(key):
            return slot_decoder_init(cfg, scfg.batch, scfg.max_len, dcfg, K)

    # bounded k-token prefill walk: prefill_chunk > 1 drains up to k
    # pending prompt tokens per resident tick (k sub-steps; non-walking
    # slots step exactly once, in the first).  k = 1 is the PR-5
    # one-token-per-tick drain, bit for bit.
    k_walk = max(1, scfg.prefill_chunk if not cfg.n_vision_tokens else 0)
    # the verify walk needs K+1 sub-steps (one per draft token plus the
    # re-anchoring step on the last committed token); walkers still stop
    # at k_walk, verifiers at their per-slot k_eff
    n_sub = max(k_walk, K + 1) if spec else k_walk

    def sub_step(st, weights_params, j: int, draft_params=None,
                 verifying=None, k_eff=None):
        act = st["active"]
        # chunked prefill: slots still holding prompt tail feed the NEXT
        # PROMPT TOKEN into the step instead of their last argmax — the
        # cache builds through the ordinary decode path, one position per
        # sub-step, without ever stalling the other slots
        walking = act & (st["p_head"] < st["p_len"])
        # first sub-step: everyone active steps; later sub-steps advance
        # the prompt walkers (up to k_walk) and the verifiers (up to
        # their k_eff); plain decoding slots stay frozen — one emitted
        # token per tick, same as the 1-token walk
        if j == 0:
            elig = act
        elif spec:
            elig = (walking & (j < k_walk)) | (verifying & (j <= k_eff))
        else:
            elig = walking
        idx = jnp.clip(st["p_head"], 0, scfg.max_len - 1)
        if cfg.n_codebooks > 1:
            nxt_p = jnp.take_along_axis(
                st["pending"], idx[:, None, None], axis=1)
            wmask = walking[:, None, None]
        else:
            nxt_p = jnp.take_along_axis(st["pending"], idx[:, None], axis=1)
            wmask = walking[:, None]
        # verifiers carry the draft's previous proposal in the tokens
        # leaf (written below), so this one select feeds walkers their
        # prompt token, verifiers their d_j, and plain slots their last
        # argmax
        tok_in = jnp.where(wmask, nxt_p, st["tokens"])
        logits, cache = T.decode_step(
            cfg, weights_params, st["cache"], tok_in,
            ctx=ctx, active=elig, pages=st.get("pages"),
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # greedy
        if cfg.n_codebooks == 1:
            nxt = nxt.reshape(st["tokens"].shape)
        new = {
            "cache": cache,
            "tokens": nxt,
            "active": act,
            "n_decoded": st["n_decoded"]
            + (elig & ~walking).astype(jnp.int32),
            "pending": st["pending"],
            "p_head": st["p_head"] + (elig & walking).astype(jnp.int32),
            "p_len": st["p_len"],
        }
        if paged:
            new["pages"] = st["pages"]
        d_raw = None
        if spec:
            if dcfg is None:
                # true self-speculation: the draft would recompute the
                # target's exact pass, so its proposal IS the target's
                # argmax — no second model, no draft cache.  The walk
                # degenerates to a k+1-token greedy chain per tick; the
                # accept mask below is then all-ones by construction
                d_raw = nxt
            else:
                # the draft steps on the SAME input the target just
                # read: while walking it ingests prompt tokens (staying
                # position-synchronized), while verifying it chains its
                # own proposal
                elig_d = elig & (st["spec_k"] > 0)
                d_logits, d_cache = T.decode_step(
                    dcfg, draft_params, st["draft_cache"], tok_in,
                    ctx=ctx, active=elig_d,
                )
                d_raw = jnp.argmax(d_logits, axis=-1).astype(jnp.int32)
                d_raw = d_raw.reshape(st["tokens"].shape)
                new["draft_cache"] = d_cache
            # verifiers stash the proposal in the tokens leaf so the next
            # sub-step's tok_in select feeds it to both models; the
            # commit stage overwrites it with the last committed token
            new["tokens"] = jnp.where(verifying[:, None], d_raw, nxt)
            new["spec_out"] = st["spec_out"]
            new["spec_n"] = st["spec_n"]
            new["spec_k"] = st["spec_k"]
            new["budget"] = st["budget"]
        # gate the whole writeback on the eligibility mask: the attention
        # paths already mask their cache scatters, this covers every
        # remaining leaf (mamba states, positions, tokens) in one
        # structural select
        return mask_fn(elig, new, st, axes), nxt, d_raw

    def d_transition(prev):
        st = prev["decoder"]
        wp = prev["weights"]["params"]
        if not spec:
            for j in range(n_sub):
                st, _, _ = sub_step(st, wp, j)
            return st
        dwp = prev["weights"]["draft"] if dcfg is not None else None
        act = st["active"]
        walking0 = act & (st["p_head"] < st["p_len"])
        pos0 = st["cache"]["pos"]
        nd0 = st["n_decoded"]
        k_eff = spec_k_eff(st["spec_k"], st["budget"], nd0, pos0,
                           scfg.max_len, K)
        verifying = act & ~walking0 & (k_eff > 0)
        gs, ds = [], []
        for j in range(n_sub):
            st, g, d = sub_step(st, wp, j, dwp, verifying, k_eff)
            gs.append(g)
            ds.append(d)
        g_stack = jnp.concatenate(gs, axis=1)        # (B, n_sub) g_{j+1}
        d_stack = jnp.concatenate(ds, axis=1)        # (B, n_sub) d_{j+1}
        # accepted prefix: a = #{j >= 1 : d_1..d_j all == g_1..g_j}; the
        # raw argmaxes are compared (not the masked writebacks) and the
        # arange guard voids positions past k_eff
        m = (d_stack[:, :K] == g_stack[:, :K]) & \
            (jnp.arange(K)[None, :] < k_eff[:, None])
        a = jnp.cumprod(m.astype(jnp.int32), axis=1).sum(axis=1)  # (B,)
        # commit: emit g_1..g_{a+1}; the NEXT tick re-anchors on g_{a+1};
        # both cache positions roll back to pos0+a+1 — lanes past that
        # are invisible (pos masking) and overwritten before read
        last = jnp.take_along_axis(g_stack, a[:, None], axis=1)
        vm = verifying[:, None]
        commit_pos = (pos0 + a + 1).astype(pos0.dtype)
        st = dict(st)
        st["tokens"] = jnp.where(vm, last, st["tokens"])
        st["cache"] = {**st["cache"], "pos": jnp.where(
            verifying, commit_pos, st["cache"]["pos"])}
        if dcfg is not None:
            dpos = st["draft_cache"]["pos"]
            st["draft_cache"] = {**st["draft_cache"], "pos": jnp.where(
                verifying, commit_pos.astype(dpos.dtype), dpos)}
        st["n_decoded"] = jnp.where(verifying, nd0 + a + 1, st["n_decoded"])
        st["spec_out"] = jnp.where(act[:, None], g_stack[:, :K + 1],
                                   st["spec_out"])
        st["spec_n"] = jnp.where(act, jnp.where(verifying, a + 1, 0),
                                 st["spec_n"])
        return st

    decoder = CellType(
        name="decoder", init=d_init, transition=d_transition,
        reads=("weights",), instances=scfg.batch,
    )
    prog = MisoProgram()
    prog.add(weights)
    prog.add(decoder)
    if scfg.placement == "spatial":
        if paged:
            # the paged pool is one shared global table; splitting it
            # across pods needs per-pod page accounting (ROADMAP item).
            raise ValueError(
                "placement='spatial' does not support paged=True yet; "
                "use the dense cache for spatial serving")
        # marker keyed on by SpatialLockstepExecutor's serve mode: the
        # program itself is byte-identical to temporal serving — only
        # the executor wraps the step in shard_map over the slot axis.
        prog.spatial_serve = {
            "cell": "decoder", "axes": axes, "n_slots": scfg.batch,
        }
    return prog


def install_prefill(cfg: ModelConfig, full: dict, filled: dict,
                    plen) -> dict:
    """Copy a prefill cache into a max_len-capacity cache: pads every
    length-mismatched axis (slot_pos pads with -1 so padded slots read as
    empty) and sets pos = plen (scalar, may be traced: under bucketed
    prefill ``filled`` has bucket length while plen is the true prompt
    length — the in-bucket tail was already scrubbed by the forward's
    ``prompt_len`` mask).  Whole-prompt prefill is the degenerate
    one-chunk case of the chunked path (prefill_chunk=0)."""
    def seg(dst, src):
        def leaf(d, s):
            if d.shape == s.shape:
                return s.astype(d.dtype)
            # (..., plen, ...) -> slot into (..., max_len, ...) at axis
            # where shapes differ
            for ax in range(d.ndim):
                if d.shape[ax] != s.shape[ax]:
                    pad = [(0, d.shape[i] - s.shape[i]) if i == ax else (0, 0)
                           for i in range(d.ndim)]
                    fill = -1 if jnp.issubdtype(s.dtype, jnp.integer) else 0
                    return jnp.pad(s, pad,
                                   constant_values=fill).astype(d.dtype)
            return s.astype(d.dtype)

        return jax.tree.map(leaf, dst, src)

    return {"segments": [seg(d, s) for d, s in zip(full["segments"],
                                                   filled["segments"])],
            "pos": jnp.full_like(full["pos"], plen)}


def prefill_slot_state(
    cfg: ModelConfig, scfg: ServeConfig, params, prompt: jax.Array,
    *, ctx: ShardCtx = LOCAL, prompt_len=None, pending=None, n_pending=None,
    draft_cfg=None, draft_params=None, spec_k=None, budget=None,
) -> tuple[dict, jax.Array]:
    """Run the real prefill for ONE prompt (head chunk) and package it as
    a width-1 decoder slot state, ready to scatter into a free slot of
    the resident batch (``serving.slots.join_slot``).

    prompt: (P,) int32 (or (P, K) for multi-codebook archs).  P may be a
    compile BUCKET: ``prompt_len`` (scalar, traceable) is then the true
    head length — the forward masks padded cache positions and the first
    token is read at ``prompt_len - 1``, so one jit compile per bucket
    serves every length that rounds up to it.

    ``pending``/``n_pending`` (chunked prefill): the uncovered prompt
    tail, (max_len[, K]) int32 zero-padded + its true length; stored in
    the slot's pending segment for the resident transition to walk.
    Returns ``(slot_state, first_token)`` — first_token is the greedy
    continuation of the HEAD and is only meaningful (= the request's
    first emitted token) when nothing is pending; with a pending tail the
    real first token is emitted by the tick that consumes the last
    pending prompt token.

    ``spec_k``/``budget`` (speculative engines, non-None = speculating):
    land in the matching per-slot leaves (``spec_state_leaves``);
    ``draft_cfg``/``draft_params`` additionally make the REAL draft
    model prefill the SAME head in the same jit, so its cache starts
    position-synchronized with the target's (None = true self-
    speculation, no separate draft cache)."""
    tokens = prompt[None]                        # (1, P[, K])
    plen = tokens.shape[1] if prompt_len is None else prompt_len
    vision = None
    if cfg.n_vision_tokens:
        vision = jnp.zeros(
            (1, min(cfg.n_vision_tokens, tokens.shape[1]), cfg.d_model),
            cfg.compute_dtype)
    logits, cache, _ = T.forward(
        cfg, params, tokens, ctx=ctx, fill_cache=True,
        vision_embeds=vision,
        prompt_len=None if prompt_len is None else plen)
    full = T.init_cache(cfg, 1, scfg.max_len)
    last = jax.lax.dynamic_slice_in_dim(
        logits, jnp.asarray(plen, jnp.int32) - 1, 1, axis=1)
    first = jnp.argmax(last, axis=-1).astype(jnp.int32)
    if cfg.n_codebooks > 1:
        first = first.reshape(1, 1, cfg.n_codebooks)
    pshape = (1, scfg.max_len)
    if cfg.n_codebooks > 1:
        pshape = pshape + (cfg.n_codebooks,)
    if pending is None:
        pending = jnp.zeros(pshape, jnp.int32)
        n_pending = jnp.zeros((1,), jnp.int32)
    else:
        pending = jnp.asarray(pending, jnp.int32).reshape(pshape)
        n_pending = jnp.asarray(n_pending, jnp.int32).reshape((1,))
    st = {
        "cache": install_prefill(cfg, full, cache, plen),
        "tokens": first,
        "active": jnp.ones((1,), jnp.bool_),
        "n_decoded": jnp.zeros((1,), jnp.int32),
        "pending": pending,
        "p_head": jnp.zeros((1,), jnp.int32),
        "p_len": n_pending,
    }
    if spec_k is not None:
        k_cap = scfg.spec.draft_len
        st["spec_out"] = jnp.zeros((1, k_cap + 1), jnp.int32)
        st["spec_n"] = jnp.zeros((1,), jnp.int32)
        st["spec_k"] = jnp.asarray(spec_k, jnp.int32).reshape((1,))
        st["budget"] = jnp.asarray(budget, jnp.int32).reshape((1,))
        if draft_cfg is not None:
            _, d_cache, _ = T.forward(
                draft_cfg, draft_params, tokens, ctx=ctx, fill_cache=True,
                prompt_len=None if prompt_len is None else plen)
            d_full = T.init_cache(draft_cfg, 1, scfg.max_len)
            st["draft_cache"] = install_prefill(
                draft_cfg, d_full, d_cache, plen)
    return st, first
