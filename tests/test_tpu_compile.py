"""TPU compile rehearsals: the main-path Pallas kernels at real widths,
compiled for a described (not attached) TPU v5e with ``interpret=False``.

Nothing runs: these tests catch what interpret mode cannot see — blocks
that break the (8, 128) tiling rule, slices Mosaic cannot prove aligned,
scratch that overruns VMEM — before any chip time is spent.  The topology
is described inside a module fixture (never at import), so only the
worker that runs this file loads the TPU compiler.
"""
import dataclasses as dc
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_step import dmr_compare, tmr_step
from repro.kernels.paged_decode import paged_gqa_attention, paged_mla_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.state_hash import state_hash
from repro.kernels.tmr_vote import tmr_vote

WORDS = 1 << 20  # word-stream kernels: 16 blocks of 64Ki words
BATCH, PAGE, MAX_LEN = 8, 16, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single-device sharding on the described chip, with JAX's persistent
    compilation cache off: a compile for a described chip is written to
    the cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; returns its HLO text."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_paged_gqa_attention_internlm2_widths(one_chip):
    cfg = get_config("internlm2-1.8b")
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    dk = cfg.d_model // hq
    p = MAX_LEN // PAGE
    pool = BATCH * p + 1
    bf = jnp.bfloat16
    text = _compile(
        functools.partial(paged_gqa_attention, interpret=False),
        _spec(one_chip, (BATCH, hq, dk), bf),
        _spec(one_chip, (pool, hkv, PAGE, dk), bf),
        _spec(one_chip, (pool, hkv, PAGE, dk), bf),
        _spec(one_chip, (BATCH, p), jnp.int32),
        _spec(one_chip, (BATCH,), jnp.int32),
    )
    assert "custom-call" in text


def test_paged_mla_attention_deepseek_widths(one_chip):
    cfg = get_config("deepseek-v3-671b")
    h, lora, rope = cfg.n_heads, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_dim
    p = MAX_LEN // PAGE
    pool = BATCH * p + 1
    bf = jnp.bfloat16
    _compile(
        functools.partial(paged_mla_attention, scale=0.1, interpret=False),
        _spec(one_chip, (BATCH, h, lora), bf),
        _spec(one_chip, (BATCH, h, rope), bf),
        _spec(one_chip, (pool, PAGE, lora), bf),
        _spec(one_chip, (pool, PAGE, rope), bf),
        _spec(one_chip, (BATCH, p), jnp.int32),
        _spec(one_chip, (BATCH,), jnp.int32),
    )


@pytest.mark.parametrize(
    "kernel,n_streams",
    [(dmr_compare, 2), (tmr_step, 3), (tmr_vote, 3), (state_hash, 1)],
    ids=["dmr_compare", "tmr_step", "tmr_vote", "state_hash"],
)
def test_word_stream_kernel_multi_block(one_chip, kernel, n_streams):
    words = _spec(one_chip, (WORDS,), jnp.uint32)
    _compile(functools.partial(kernel, interpret=False), *[words] * n_streams)


def test_ssd_scan_mamba2_widths(one_chip):
    cfg = get_config("mamba2-2.7b")
    s = cfg.ssm
    heads = s.expand * cfg.d_model // s.headdim
    seq = 2048
    bf = jnp.bfloat16
    _compile(
        functools.partial(ssd_scan, chunk=s.chunk, interpret=False),
        _spec(one_chip, (1, seq, heads, s.headdim), bf),
        _spec(one_chip, (1, seq, heads), jnp.float32),
        _spec(one_chip, (heads,), jnp.float32),
        _spec(one_chip, (1, seq, s.ngroups, s.state), bf),
        _spec(one_chip, (1, seq, s.ngroups, s.state), bf),
    )


def test_flash_attention_internlm2_prefill(one_chip):
    x = _spec(one_chip, (1, 16, MAX_LEN, 128), jnp.bfloat16)
    _compile(functools.partial(flash_attention, interpret=False), x, x, x)


#: an instruction that writes a whole KV pool (one layer's, or a stack)
#: by copying or slicing it: ``%name = bf16[...1536,8,16,128]{...} op(``
POOL_COPY = re.compile(
    r"%(\S*(?:copy|dynamic.slice|dynamic.update.slice)\S*) = "
    r"bf16\[(?:\d+,)?1536,8,16,128\]\S* (\S+)\("
)


def test_paged_decode_step_updates_the_donated_pool_in_place(one_chip, monkeypatch):
    """The serving step at the chat cell's pool (16 slots, 1536 pages of
    16, internlm2 widths, 2 layers), its decoder cell donated: the pool
    inputs are aliased to the outputs, and no instruction copies or slices
    a pool, stacked or per layer."""
    from repro.core.executor import compile as compile_program
    from repro.core.fault import FaultSpec
    from repro.core.jit import forwarding_jit
    from repro.kernels import ops
    from repro.models.lm_cells import ServeConfig
    from repro.serving.lm import lm_engine_parts

    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # compiled kernels
    cfg = dc.replace(get_config("internlm2-1.8b"), n_layers=2)
    scfg = ServeConfig(batch=16, max_len=4096, paged=True, page_size=16, page_budget=1536)
    prog, _ = lm_engine_parts(cfg, scfg)
    exe = compile_program(prog, backend="lockstep")
    states = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(prog.init_states, jax.random.PRNGKey(0)),
    )
    pool = states["decoder"]["cache"]["segments"][0]
    assert pool["k"].shape == (2, 1536, cfg.n_kv_heads, 16, cfg.d_model // cfg.n_heads)
    args = (states, _spec(one_chip, (), jnp.int32), FaultSpec.none())
    donate = ({"decoder": True, "weights": False}, False, False)
    text = (
        forwarding_jit(exe.step_fn, name="lockstep_step")
        .lower(*args, donate=donate)
        .compile()
        .as_text()
    )
    leaves = jax.tree.leaves(args)
    at = [i for i, x in enumerate(leaves) if x is pool["k"] or x is pool["v"]]
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    given = {int(i) for i in re.findall(r"\((\d+), \{\}, may-alias\)", aliased)}
    assert len(at) == 2 and set(at) <= given, (at, aliased)
    assert "paged_gqa_attention" in text
    assert not POOL_COPY.findall(text), POOL_COPY.findall(text)
