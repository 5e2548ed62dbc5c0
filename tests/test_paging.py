"""The paged KV-cache subsystem (repro/serving/paging + kernels/paged_decode).

Two load-bearing properties:

  * PAGE-TABLE SOUNDNESS — alloc/free/evict as pure page-table ops never
    leak or double-map a page, and the reservation discipline guarantees
    an admission that passes ``can_admit`` can always reach its full
    token budget (demand growth never finds the pool empty).
  * BITWISE PARITY — a request decoded through the paged pool (fused
    Pallas gather+attention kernel, pages in arbitrary pool rows,
    including rows reused from evicted requests) emits tokens bitwise
    identical to the dense contiguous cache, for none/DMR/TMR policies,
    and its FaultLedger reports match too.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api as miso
from repro.serving import (
    DONE,
    QUEUED,
    PageTable,
    Request,
    ServingEngine,
    infer_paged_axes,
    mask_slots_paged,
)
from repro.serving.paging import POOL, dense_to_pool, pool_slot_view


# ---------------------------------------------------------------------------
# PageTable: soundness of the host-side manager
# ---------------------------------------------------------------------------
def check_invariants(t: PageTable):
    mapped = [r for rows in t._rows.values() for r in rows]
    assert len(mapped) == len(set(mapped)), "page double-mapped"
    assert not set(mapped) & set(t._free), "mapped page also on free list"
    assert len(mapped) + t.free_pages == t.n_pages, "pages leaked"


def test_page_table_alloc_free_reuse_never_leaks_or_double_maps():
    rng = np.random.default_rng(0)
    t = PageTable(n_pages=24, page_size=4, pages_per_slot=6)
    live: dict[int, int] = {}  # slot -> reserved pages
    for step in range(300):
        op = rng.integers(0, 3)
        if op == 0 and len(live) < 8:  # admit a new slot
            slot = next(s for s in range(8) if s not in live)
            reserve = int(rng.integers(1, 7))
            if t.can_admit(reserve):
                t.assign(slot, reserve)
                live[slot] = reserve
        elif op == 1 and live:  # grow a live slot
            slot = int(rng.choice(list(live)))
            want = int(rng.integers(0, live[slot] + 1)) * t.page_size
            t.grow_to(slot, want, demand=bool(rng.integers(0, 2)))
        elif op == 2 and live:  # evict a live slot
            slot = int(rng.choice(list(live)))
            t.release(slot)
            del live[slot]
        check_invariants(t)
    for slot in list(live):
        t.release(slot)
    assert t.free_pages == t.n_pages
    assert t._free == sorted(t._free)  # deterministic reuse order


def test_page_table_reservation_discipline():
    t = PageTable(n_pages=4, page_size=8, pages_per_slot=4)
    t.assign(0, 3)
    assert t.free_pages == 4 and t.available == 1
    assert t.can_admit(1) and not t.can_admit(2)
    with pytest.raises(RuntimeError, match="reservation"):
        t.assign(1, 2)  # over available, not free
    with pytest.raises(ValueError, match="already assigned"):
        t.assign(0, 1)
    # growth draws from the slot's own reservation
    t.grow_to(0, 17)  # 3 pages
    assert t.available == 1  # reservation fully consumed
    t.assign(1, 1)
    assert t.grow_to(1, 8) and t.available == 0


def test_admission_that_fits_in_free_pages_never_blocks_mid_decode():
    """The reservation guarantee: once ``can_admit`` passes, the slot can
    grow to its reserved worst case even if later admissions drained the
    free list to exactly the outstanding reservations."""
    t = PageTable(n_pages=8, page_size=4, pages_per_slot=4)
    t.assign(0, 4)
    assert t.can_admit(4)
    t.assign(1, 4)
    assert not t.can_admit(1)
    # interleaved demand growth to the full reservation must not raise
    for tokens in (4, 8, 12, 16):
        t.grow_to(0, tokens, demand=True)
        t.grow_to(1, tokens, demand=True)
    assert t.free_pages == 0 and t.page_faults == 8
    assert sorted(t.rows_of(0) + t.rows_of(1)) == list(range(8))


def test_grow_past_pages_per_slot_rejected():
    t = PageTable(n_pages=8, page_size=4, pages_per_slot=2)
    t.assign(0, 2)
    with pytest.raises(ValueError, match="pages_per_slot"):
        t.grow_to(0, 9)
    assert t.pages_for(0) == 0 and t.pages_for(1) == 1
    assert t.pages_for(4) == 1 and t.pages_for(5) == 2


def test_row_array_padding_and_release_returns_rows():
    t = PageTable(n_pages=6, page_size=2, pages_per_slot=3)
    t.assign(3, 3)
    t.grow_to(3, 3)  # 2 pages
    assert list(t.row_array(3)) == [0, 1, -1]
    assert sorted(t.release(3)) == [0, 1]
    assert t.rows_of(3) == [] and t.free_pages == 6


# ---------------------------------------------------------------------------
# layout transforms + axis inference
# ---------------------------------------------------------------------------
def _axes_state(b):
    return {
        "pool": jnp.zeros((2, 6, 4, 3)),  # width-independent
        "tokens": jnp.zeros((b, 1)),
        "deep": jnp.zeros((3, b, 5)),
    }


def test_infer_paged_axes_pool_sentinel():
    axes = infer_paged_axes(_axes_state)
    assert axes == {"pool": POOL, "tokens": 0, "deep": 1}
    # pool leaves pass the NEW value through the slot mask untouched
    act = jnp.array([True, False])
    new = {
        "pool": jnp.ones((2, 6, 4, 3)),
        "tokens": jnp.ones((2, 1)),
        "deep": jnp.ones((3, 2, 5)),
    }
    old = jax.tree.map(jnp.zeros_like, new)
    out = mask_slots_paged(act, new, old, axes)
    assert (out["pool"] == 1).all()
    assert out["tokens"][0, 0] == 1 and out["tokens"][1, 0] == 0


def test_dense_to_pool_roundtrip_and_unmapped_reads_zero():
    rng = np.random.default_rng(1)
    L, N, H, ps, d, P = 2, 6, 2, 4, 3, 2
    pool = jnp.asarray(rng.normal(size=(L, N, H, ps, d)), jnp.float32)
    dense = jnp.asarray(rng.normal(size=(L, 1, H, P * ps, d)), jnp.float32)
    rows = jnp.array([4, 1], jnp.int32)
    pool2 = dense_to_pool(pool, dense, rows)
    view = pool_slot_view(pool2, rows[None])
    assert jnp.array_equal(view, dense)
    # a -1 row is skipped on scatter and reads back zero on gather
    pool3 = dense_to_pool(pool, dense, jnp.array([4, -1], jnp.int32))
    assert jnp.array_equal(pool3[:, 1], pool[:, 1])  # untouched
    half = pool_slot_view(pool3, jnp.array([[4, -1]], jnp.int32))
    assert jnp.array_equal(half[:, :, :, :ps], dense[:, :, :, :ps])
    assert (half[:, :, :, ps:] == 0).all()


# ---------------------------------------------------------------------------
# fused paged-decode kernels vs the dense-equivalent references
# ---------------------------------------------------------------------------
def test_paged_gqa_kernel_bitwise_matches_ref():
    from repro.kernels.paged_decode import paged_gqa_attention
    from repro.kernels.ref import paged_gqa_ref

    rng = np.random.default_rng(2)
    B, Hq, Hkv, Dk, ps, P, N = 3, 4, 2, 8, 8, 4, 10
    q = jnp.asarray(rng.normal(size=(B, Hq, Dk)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(N, Hkv, ps, Dk)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(N, Hkv, ps, Dk)), jnp.float32)
    # slot 0: fully mapped, scattered rows; slot 1: partial; slot 2: one
    pages = jnp.array([[7, 2, 9, 0], [5, 3, -1, -1], [8, -1, -1, -1]], jnp.int32)
    pos = jnp.array([ps * 4 - 1, ps + 3, 0], jnp.int32)
    got = paged_gqa_attention(q, k_pool, v_pool, pages, pos)
    ref = paged_gqa_ref(q, k_pool, v_pool, pages, pos)
    assert got.dtype == ref.dtype
    assert jnp.array_equal(got, ref), "kernel diverged from reference"


def test_paged_mla_kernel_bitwise_matches_ref():
    from repro.kernels.paged_decode import paged_mla_attention
    from repro.kernels.ref import paged_mla_ref

    rng = np.random.default_rng(3)
    B, h, lora, rope, ps, P, N = 2, 4, 16, 8, 8, 2, 6
    q_lat = jnp.asarray(rng.normal(size=(B, h, lora)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(B, h, rope)), jnp.float32)
    ckv = jnp.asarray(rng.normal(size=(N, ps, lora)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(N, ps, rope)), jnp.float32)
    pages = jnp.array([[5, 1], [3, -1]], jnp.int32)
    pos = jnp.array([ps + 2, ps - 1], jnp.int32)
    scale = (lora + rope) ** -0.5
    got = paged_mla_attention(q_lat, q_rope, ckv, kr, pages, pos, scale=scale)
    ref = paged_mla_ref(q_lat, q_rope, ckv, kr, pages, pos, scale=scale)
    assert got.dtype == jnp.float32
    assert jnp.array_equal(got, ref), "MLA kernel diverged from reference"


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_paged_kernel_reads_stacked_pool_by_layer_bitwise(kind):
    """Each kernel handed every layer's pool, stacked, and a layer index
    returns bit for bit what it returns on that layer's pool alone."""
    from repro.kernels.paged_decode import paged_gqa_attention, paged_mla_attention

    rng = np.random.default_rng(4)
    L, ps, N = 3, 8, 10

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    pages = jnp.array([[7, 2, 9, 0], [5, 3, -1, -1], [8, -1, -1, -1]], jnp.int32)
    pos = jnp.array([ps * 4 - 1, ps + 3, 0], jnp.int32)
    B = pages.shape[0]
    if kind == "gqa":
        q = arr(B, 4, 8)
        pools = (arr(L, N, 2, ps, 8), arr(L, N, 2, ps, 8))

        def call(k, v, **kw):
            return paged_gqa_attention(q, k, v, pages, pos, **kw)
    else:
        q_lat, q_rope = arr(B, 4, 16), arr(B, 4, 8)
        pools = (arr(L, N, ps, 16), arr(L, N, ps, 8))

        def call(ckv, kr, **kw):
            return paged_mla_attention(q_lat, q_rope, ckv, kr, pages, pos, scale=0.2, **kw)

    outs = []
    for layer in range(L):
        got = call(*pools, layer=jnp.int32(layer))
        assert jnp.array_equal(got, call(*(p[layer] for p in pools))), layer
        outs.append(got)
    assert not jnp.array_equal(outs[0], outs[1])  # the index picks the layer


@pytest.mark.parametrize("heads", [2, 0], ids=["gqa", "mla"])
def test_page_write_sets_each_slot_token_and_drops_the_rest(heads):
    """``page_write`` into a stack of pools equals one indexed ``set`` per
    slot at (layer, row, lane); a slot that is not ``ok`` writes nothing,
    though its row is a real page."""
    from repro.models.layers import page_write

    rng = np.random.default_rng(5)
    L, N, ps, d = 3, 5, 4, 8
    shape = (L, N, heads, ps, d) if heads else (L, N, ps, d)
    pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    row = jnp.array([4, 0, 2, 1], jnp.int32)
    lane = jnp.array([3, 0, 1, ps - 1], jnp.int32)
    ok = jnp.array([True, True, False, True])
    new = jnp.asarray(rng.normal(size=(4, heads, d) if heads else (4, d)), jnp.float32)
    heads_ix = (slice(None),) if heads else ()
    for layer in range(L):
        got = page_write(pool, jnp.int32(layer), row, lane, ok, new)
        want = pool
        for b in np.flatnonzero(np.asarray(ok)):
            want = want.at[(layer, int(row[b])) + heads_ix + (int(lane[b]),)].set(new[b])
        assert jnp.array_equal(got, want), layer


# ---------------------------------------------------------------------------
# engine-level bitwise parity: paged vs dense through the real LM stack
# ---------------------------------------------------------------------------
def tiny_lm(**over):
    from repro.configs import get_reduced
    from repro.models.lm_cells import ServeConfig

    cfg = get_reduced("internlm2-1.8b")
    cfg = dc.replace(
        cfg, d_model=32, n_layers=2, d_ff=64, n_heads=2, n_kv_heads=1, vocab_size=128
    )
    return cfg, ServeConfig(batch=4, max_len=32, **over)


def lm_engine(cfg, scfg):
    from repro.serving.lm import lm_engine_parts

    prog, adapter = lm_engine_parts(cfg, scfg)
    eng = ServingEngine(prog, adapter)
    eng.start(jax.random.PRNGKey(0))
    return eng


def paged_cfg(scfg, page_size=8, budget=0):
    return dc.replace(scfg, paged=True, page_size=page_size, page_budget=budget)


@pytest.mark.parametrize("level", [1, 2, 3])
def test_paged_tokens_bitwise_equal_dense(level):
    """One request, none/DMR/TMR: the paged pool (shared pages, replica
    slots holding different pool rows) emits the same tokens as the dense
    contiguous cache — and the ledger stays clean both sides."""
    cfg, scfg = tiny_lm()
    pol = miso.RedundancyPolicy(level=level)
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size
    toks = {}
    for name, sc in (("dense", scfg), ("paged", paged_cfg(scfg))):
        eng = lm_engine(cfg, sc)
        req = Request(prompt=prompt, max_new_tokens=6, policy=pol)
        assert eng.submit(req)
        eng.pump()
        res = eng.result(req.id)
        assert res["status"] == DONE and res["faults"] == 0
        assert eng.metrics()["request_faults"] == {}
        toks[name] = res["tokens"]
    assert toks["paged"] == toks["dense"]


@pytest.mark.parametrize("plen", [7, 8, 9])
def test_paged_parity_at_page_boundary_lengths(plen):
    """Prompt lengths straddling a page boundary (page-1, page, page+1):
    the partial-last-page mask and the demand-map of the next page keep
    bitwise parity with dense."""
    cfg, scfg = tiny_lm()
    rng = np.random.default_rng(plen)
    prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
    toks = {}
    for name, sc in (("dense", scfg), ("paged", paged_cfg(scfg, page_size=8))):
        eng = lm_engine(cfg, sc)
        req = Request(
            prompt=prompt, max_new_tokens=4, policy=miso.RedundancyPolicy(level=2)
        )
        assert eng.submit(req)
        eng.pump()
        assert eng.result(req.id)["status"] == DONE
        toks[name] = eng.result(req.id)["tokens"]
    assert toks["paged"] == toks["dense"], f"diverged at plen={plen}"


def test_paged_parity_under_slot_churn_and_page_reuse():
    """More requests than the pool holds at once, mixed policies,
    staggered arrivals: slots AND pool pages are reused across tenants —
    every request still matches its dense twin bitwise (clean-on-map:
    stale bytes from evicted requests never leak)."""
    cfg, scfg = tiny_lm()
    rng = np.random.default_rng(11)
    levels = [1, 2, 1, 3, 2, 1]

    def rand_prompt():
        n = int(rng.integers(2, 9))
        return rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)

    prompts = [rand_prompt() for _ in levels]

    def run(sc):
        eng = lm_engine(cfg, sc)
        reqs = [
            Request(prompt=p, max_new_tokens=4, policy=miso.RedundancyPolicy(level=lv))
            for p, lv in zip(prompts, levels)
        ]
        for i, r in enumerate(reqs):
            assert eng.submit(r)
            if i % 2 == 1:
                eng.pump(max_ticks=2)  # arrivals interleave with decode
        eng.pump()
        assert all(eng.result(r.id)["status"] == DONE for r in reqs)
        assert eng.metrics()["request_faults"] == {}
        return [eng.result(r.id)["tokens"] for r in reqs], eng

    dense_toks, _ = run(scfg)
    # 8 pages of 8 tokens: at most 2 single-slot tenants resident at once
    paged_toks, eng = run(paged_cfg(scfg, page_size=8, budget=8))
    assert paged_toks == dense_toks
    m = eng.metrics()
    assert m["paged"] and m["pages_free"] == m["pages_total"] == 8


def test_paged_mla_tokens_bitwise_equal_dense():
    """The MLA latent cache (ckv/krope pools, absorbed-attention kernel)
    holds paged-vs-dense parity too."""
    from repro.configs import get_reduced

    cfg = get_reduced("deepseek-v3-671b")
    cfg = dc.replace(cfg, n_layers=2)
    from repro.models.lm_cells import ServeConfig

    scfg = ServeConfig(batch=2, max_len=32)
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size
    toks = {}
    for name, sc in (("dense", scfg), ("paged", paged_cfg(scfg))):
        eng = lm_engine(cfg, sc)
        req = Request(
            prompt=prompt, max_new_tokens=4, policy=miso.RedundancyPolicy(level=2)
        )
        assert eng.submit(req)
        eng.pump()
        res = eng.result(req.id)
        assert res["status"] == DONE and res["faults"] == 0
        toks[name] = res["tokens"]
    assert toks["paged"] == toks["dense"]


def test_paged_dmr_strike_detected_attributed_repaired():
    """A bit flip against a DMR request's replica slot in the PAGED
    engine: detected via the gathered dense-layout view, charged to the
    owning request with the struck replica localized, repaired — final
    tokens bitwise-equal to the clean dense run."""
    cfg, scfg = tiny_lm()
    prompt = np.arange(6, dtype=np.int32) % cfg.vocab_size
    pol = miso.RedundancyPolicy(level=2)

    ref_eng = lm_engine(cfg, scfg)
    ref_req = Request(prompt=prompt, max_new_tokens=6, policy=pol)
    assert ref_eng.submit(ref_req)
    ref_eng.pump()
    ref = ref_eng.result(ref_req.id)["tokens"]

    from repro.models.lm_cells import paged_slot_decoder_init

    eng = lm_engine(cfg, paged_cfg(scfg))
    req = Request(prompt=prompt, max_new_tokens=6, policy=pol)
    assert eng.submit(req)
    eng.pump(max_ticks=1)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        paged_slot_decoder_init(cfg, 2, scfg.max_len, 8, 1)
    )

    def is_tokens(path):
        return any(getattr(q, "key", None) == "tokens" for q in path)

    leaf_i = next(i for i, (p, _) in enumerate(flat) if is_tokens(p))
    fault = miso.FaultSpec.at(
        step=2,
        cell_id=eng.exe.program.cell_id("decoder"),
        leaf=leaf_i,
        index=eng.requests[req.id].slots[1],
        bit=3,
    )
    eng.pump(faults=fault)
    res = eng.result(req.id)
    assert res["status"] == DONE
    assert res["tokens"] == ref, "paged DMR tie-break failed to repair"
    assert res["faults"] == 1
    assert eng.ledger.totals[req.id]["events"] == 1.0
    assert eng.ledger.totals[req.id]["per_replica"][1] == 1.0
    # a replica resident: every step kept its input for the §IV replay
    assert eng.metrics()["pool_in_place_ticks"] == 0


def test_unreplicated_paged_step_writes_the_pool_in_place():
    """No replicated request resident: the engine hands the decoder state
    to the step, which writes the pool over its input.  The pre-step pool
    buffer is deleted by every step, every tick's span says
    ``pool_in_place`` 1, and the tokens still equal the dense twin's."""
    from repro.obs import Tracer
    from repro.serving.lm import lm_engine_parts

    cfg, scfg = tiny_lm()
    prompts = [np.arange(5 + i, dtype=np.int32) * (i + 2) % cfg.vocab_size for i in range(2)]

    def run(eng):
        reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
        assert eng.submit(reqs[0])
        eng.pump(max_ticks=2)
        assert eng.submit(reqs[1])  # joins mid-stream
        eng.pump()
        assert all(eng.result(r.id)["status"] == DONE for r in reqs)
        return [eng.result(r.id)["tokens"] for r in reqs]

    ref = run(lm_engine(cfg, scfg))
    tr = Tracer()
    prog, adapter = lm_engine_parts(cfg, paged_cfg(scfg))
    eng = ServingEngine(prog, adapter, miso.EngineConfig(tracer=tr))
    eng.start(jax.random.PRNGKey(0))
    step, deleted = eng.exe.step, []

    def spy(states, **kw):
        pool = states[eng.adapter.cell]["cache"]["segments"][0]["k"]
        out = step(states, **kw)
        deleted.append(pool.is_deleted())
        return out

    eng.exe.step = spy
    assert run(eng) == ref
    assert deleted and all(deleted)
    ticks = [e for e in tr.events() if e["ph"] == "X" and e["name"] == "tick"]
    assert [e["args"]["pool_in_place"] for e in ticks] == [1] * len(deleted)
    assert eng.metrics()["pool_in_place_ticks"] == len(deleted)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_keeps_one_weights_buffer_through_strike_repair(paged):
    """Joins, ticks, a DMR strike's replay and repair: the static weights
    cell is never copied — every state the engine holds shares the
    buffers ``start()`` made (one copy of the weights in device memory).
    The per-slot fingerprints equal a whole-batch hash of the same view."""
    from repro.core.redundancy import fingerprint
    from repro.launch.serve import arm_strike
    from repro.serving.paging import paged_read_slot, paged_view, view_axes_of
    from repro.serving.slots import slot_fingerprints

    cfg, scfg = tiny_lm()
    scfg = paged_cfg(scfg) if paged else scfg
    eng = lm_engine(cfg, scfg)
    weights = jax.tree.leaves(eng._states["weights"])
    pol = miso.RedundancyPolicy(level=2)
    reqs = [Request(prompt=np.arange(3 + i, dtype=np.int32), max_new_tokens=8,
                    policy=pol) for i in range(2)]
    for r in reqs:
        assert eng.submit(r)
    fault = arm_strike(eng, cfg, scfg, reqs[0])
    eng.pump(faults=fault)
    assert eng.result(reqs[0].id)["faults"] == 1
    assert all(a is b for a, b in zip(jax.tree.leaves(eng._states["weights"]),
                                      weights))

    dec = eng._states[eng.adapter.cell]
    axes = eng.adapter.slot_axes
    if paged:
        view, vaxes = paged_view(dec), view_axes_of(axes)
        got = slot_fingerprints(dec, vaxes, n=dec["pages"].shape[0],
                                read=paged_read_slot)
    else:
        view, vaxes = dec, axes
        got = slot_fingerprints(dec, axes)
    moved = jax.tree.map(lambda x, ax: jnp.moveaxis(x, ax, 0), view, vaxes)
    assert jnp.array_equal(got, jax.vmap(fingerprint)(moved))


def test_paged_chunked_prefill_walks_k_tokens_per_tick():
    """``prefill_chunk > 1`` drains k pending prompt tokens per resident
    tick (not one), and the chunked+paged run stays bitwise-equal to the
    whole-prompt dense run."""
    cfg, scfg = tiny_lm()
    prompt = np.arange(10, dtype=np.int32) % cfg.vocab_size

    ref_eng = lm_engine(cfg, scfg)
    ref_req = Request(prompt=prompt, max_new_tokens=4)
    assert ref_eng.submit(ref_req)
    ref_eng.pump()
    ref = ref_eng.result(ref_req.id)["tokens"]

    sc = paged_cfg(dc.replace(scfg, prefill_chunk=4, prefill_bucket_min=4))
    eng = lm_engine(cfg, sc)
    req = Request(prompt=prompt, max_new_tokens=4)
    assert eng.submit(req)
    eng.pump(max_ticks=1)  # admit: head 4 covered, 6 pending
    rec = eng.requests[req.id]
    assert rec.prefill_remaining == 2  # the tick walked 4 tokens, not 1
    eng.pump(max_ticks=1)
    assert rec.prefill_remaining == 0
    eng.pump()
    res = eng.result(req.id)
    assert res["status"] == DONE and res["tokens"] == ref


def test_paged_admission_waits_for_free_pages_then_completes():
    """Admission is gated on the page budget: a request whose reservation
    does not fit stays QUEUED (even with slots free) and is admitted once
    an eviction releases pages; the pool drains back to fully free."""
    cfg, scfg = tiny_lm()
    sc = paged_cfg(scfg, page_size=8, budget=2)
    eng = lm_engine(cfg, sc)
    rng = np.random.default_rng(5)

    def mk():
        return Request(
            prompt=rng.integers(0, cfg.vocab_size, size=4).astype(np.int32),
            max_new_tokens=8,  # 4 prompt + 8 new = 12 tokens -> 2 pages
        )

    a, b = mk(), mk()
    assert eng.submit(a) and eng.submit(b)
    eng.pump(max_ticks=2)
    assert eng.result(a.id)["status"] == "running"
    assert eng.result(b.id)["status"] == QUEUED  # slots free, pages not
    eng.pump()
    assert eng.result(a.id)["status"] == DONE
    assert eng.result(b.id)["status"] == DONE
    m = eng.metrics()
    assert m["pages_free"] == m["pages_total"] == 2
    assert m["page_faults"] > 0


def test_recurrent_arch_silently_falls_back_to_dense():
    """mamba2 has no paged KV (recurrent state, not a token cache):
    ``paged=True`` degrades to the dense path, warns, and still serves."""
    from repro.configs import get_reduced
    from repro.models.lm_cells import ServeConfig, paged_serving_supported

    cfg = get_reduced("mamba2-2.7b")
    assert not paged_serving_supported(cfg)
    with pytest.warns(UserWarning, match="serving from the dense cache"):
        eng = lm_engine(cfg, ServeConfig(batch=2, max_len=16, paged=True))
    req = Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=3)
    assert eng.submit(req)
    eng.pump()
    assert eng.result(req.id)["status"] == DONE
    m = eng.metrics()
    assert m["paged"] is False and "pages_total" not in m
