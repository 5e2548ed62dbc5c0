"""The reference and its counts: operations and bytes against shapes
worked out by hand, and the float32 forward against the program's own
forward at a tiny width (the program runs in float32 there, so the two
must agree to float32 rounding)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness as H
from benchmarks.chip.reference import transformer as R
from benchmarks.chip.tests.conftest import GELU_MQA, ROOT, tiny_cell
from repro.models import transformer as TF
from repro.models.config import ModelConfig


def _model(name):
    return json.loads((ROOT / f"benchmarks/chip/configs/{name}.json").read_text())["model"]


def _weights(m, seed):
    cfg = ModelConfig(**m)
    shapes = jax.eval_shape(lambda k: TF.init_params(cfg, k), jax.random.PRNGKey(0))
    return H.make_weights(shapes, seed)


def test_internlm2_counts_by_hand():
    m = _model("internlm2-1.8b")
    # q, o: 2048x2048; k, v: 2048x1024; three 2048x8192 MLP matrices
    assert R.layer_matmul_params(m) == 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert R.kv_bytes_per_token(m) == 96 * 1024
    # one token over 1000 keys: 24 x (2 x 62914560 + 4 x 16 x 128 x 1000)
    assert R.token_flops(m, 1000) == 24 * (125829120 + 8192000)
    assert R.unembed_flops(m) == 2 * 2048 * 92544
    # 10 live keys: keys and values once, plus q and o of 16 heads
    assert R.paged_attention_bytes(m, 10) == 10 * 98304 + 24 * 2 * 16 * 128 * 2
    # causal prefill of 4 tokens attends over 1+2+3+4 keys
    assert R.prefill_flops(m, 4) == 24 * (2 * 62914560 * 4 + 4 * 16 * 128 * 10) + R.unembed_flops(m)


def test_granite_counts_by_hand():
    # granite-20b-code's published widths, one pipeline stage of 8 layers
    m = dict(n_layers=8, d_model=6144, n_heads=48, n_kv_heads=1, d_ff=24576,
             vocab_size=49152, mlp_act="gelu")
    assert R.layer_matmul_params(m) == 2 * 6144 * 6144 + 2 * 6144 * 128 + 2 * 6144 * 24576
    assert R.kv_bytes_per_token(m) == 4 * 1024
    assert R.paged_attention_flops(m, 100) == 8 * 4 * 48 * 128 * 100


@pytest.mark.parametrize("model", [{}, GELU_MQA], ids=["swiglu-gqa", "gelu-bias-mqa"])
def test_reference_matches_the_program_in_float32(model):
    m = {**tiny_cell(model=model).config["model"], "dtype": "float32"}
    params = _weights(m, 3)
    tokens = np.random.default_rng(0).integers(0, m["vocab_size"], 37)
    with jax.default_matmul_precision("highest"):
        prog, _, _ = TF.forward(ModelConfig(**m), params, jnp.asarray(tokens)[None])
        rows = np.arange(37)
        ref = R.logits_at(m, params, tokens, rows, pad_to=16, block=16)
    np.testing.assert_allclose(ref, np.asarray(prog[0]), atol=2e-4, rtol=2e-4)


def test_padding_does_not_move_earlier_positions():
    m = tiny_cell().config["model"]
    params = _weights(m, 4)
    tokens = np.arange(20) % m["vocab_size"]
    a = R.logits_at(m, params, tokens, np.arange(20), pad_to=32, block=32)
    b = R.logits_at(m, params, tokens, np.arange(20), pad_to=64, block=16)
    np.testing.assert_allclose(a, b, atol=1e-4)


def test_float8_control_moves_the_logits():
    m = tiny_cell().config["model"]
    params = _weights(m, 5)
    tokens = np.arange(30) % m["vocab_size"]
    a = R.logits_at(m, params, tokens, np.arange(30), pad_to=32, block=32)
    b = R.logits_at(m, params, tokens, np.arange(30), quant="fp8", pad_to=32, block=32)
    assert np.abs(a - b).max() > 1e-2
