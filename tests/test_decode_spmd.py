"""Flash-decoding shard_map (distributed/decode.py) vs the local oracle.

The sharded decode path must be numerically equivalent to the single-device
decode step.  shard_map needs >1 device, and jax pins the device count at
first init, so the comparison runs in a subprocess with 8 forced host
devices covering the three cache layouts:

  * head-sharded  (n_kv_heads % tp == 0)
  * seq-sharded   (n_kv_heads not divisible, cache length % tp == 0)
  * MLA latent    (sequence-sharded latent cache)
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.distributed.sharding import LOCAL, ShardCtx
from repro.launch.mesh import make_ctx, make_mesh
from repro.models import transformer as T

mesh = make_mesh((2, 4), ("data", "model"))

def run_case(arch, ep2d=False, **over):
    cfg = get_reduced(arch)
    if over:
        cfg = dataclasses.replace(cfg, **over)
    B, plen, cap = 4, 12, 32
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, plen), 0,
                              cfg.vocab_size, jnp.int32)

    def decode_n(ctx, n=3):
        # prefill via forward(fill_cache) into a cap-slot cache
        logits, fcache, _ = T.forward(cfg, params, toks, ctx=LOCAL,
                                      fill_cache=True)
        cache = T.init_cache(cfg, B, cap)
        def fit(d, s):
            if d.shape == s.shape:
                return s.astype(d.dtype)
            pad = [(0, a - b) for a, b in zip(d.shape, s.shape)]
            fill = -1 if jnp.issubdtype(s.dtype, jnp.integer) else 0
            return jnp.pad(s, pad, constant_values=fill).astype(d.dtype)
        cache = {
            "segments": [jax.tree.map(fit, d, s) for d, s in
                         zip(cache["segments"], fcache["segments"])],
            "pos": jnp.full((B,), plen, jnp.int32),
        }
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        outs = []
        step = jax.jit(lambda c, t: T.decode_step(cfg, params, c, t,
                                                  ctx=ctx))
        for _ in range(n):
            logits, cache = step(cache, tok)
            tok = jnp.argmax(logits[:, -1:, :].reshape(B, 1, -1),
                             -1).astype(jnp.int32)
            outs.append(logits)
        return jnp.stack(outs)

    ref = decode_n(LOCAL)
    ctx = make_ctx(mesh, vocab_size=cfg.vocab_size, d_model=cfg.d_model,
                   decode_shardmap=True, serve_ep2d=ep2d)
    with mesh:
        got = decode_n(ctx)
    err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                - got.astype(jnp.float32))))
    rel = err / max(float(jnp.max(jnp.abs(ref))), 1e-9)
    return {"max_abs": err, "max_rel": rel}

out = {}
# head-sharded: kv=4 divides tp=4
out["head_sharded"] = run_case("musicgen-large", n_heads=4, n_kv_heads=4,
                               d_model=64, n_layers=2, d_ff=128,
                               vocab_size=128, n_codebooks=1)
# seq-sharded: kv=2 does not divide tp=4; cap=32 divides
out["seq_sharded"] = run_case("internlm2-1.8b", n_heads=4, n_kv_heads=2,
                              d_model=64, n_layers=2, d_ff=128,
                              vocab_size=128)
# MLA latent cache
out["mla"] = run_case("deepseek-v3-671b")
# serve-mode EP2D expert layout (1 expert slice per chip, tokens gathered)
out["moe_ep2d"] = run_case("granite-moe-1b-a400m", ep2d=True)
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(
        pathlib.Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("case", ["head_sharded", "seq_sharded", "mla",
                                  "moe_ep2d"])
def test_decode_shardmap_matches_local(child_result, case):
    r = child_result[case]
    # bf16 compute: logits agree to bf16 resolution
    assert r["max_rel"] < 3e-2, r
