"""Shared pieces of the benchmark's CPU tests: a cell of the real
benchmark cut to a few layers of width 64, so the harness, the reference
and the comparison run end to end on the CPU in seconds."""

import copy
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness as H  # noqa: E402

TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab_size=256)
TINY_SERVE = dict(batch=6, max_len=64, page_budget=24)
TINY_MIX = dict(
    rate_per_s=6.0,
    lead_s=0.5,
    tail_s=20,
    prompt={"median": 12, "sigma": 0.5, "min": 4, "max": 40},
    output={"median": 6, "sigma": 0.5, "min": 3, "max": 20},
)
#: the chat mix with half of its requests replicated in time and one
#: strike a second: the redundant path a later cell adds as data
REDUNDANT = dict(policies={"none": 0.5, "dmr": 0.25, "tmr": 0.25}, strikes_per_s=1.0)
#: a GELU MLP with q/k/v biases on one KV head, as granite-20b-code has
GELU_MQA = dict(mlp_act="gelu", use_bias=True, n_kv_heads=1, tie_embeddings=True)


def tiny_cell(workload: str = "internlm2-1.8b.chat", *, redundant: bool = False,
              model: dict | None = None) -> H.Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = H.load_cell(bench, workload)
    cell.config = copy.deepcopy(cell.config)
    m = cell.config["model"]
    m.update(TINY_MODEL, n_kv_heads=min(m["n_kv_heads"], 2))
    m.update(model or {})
    cell.config["serve"].update(TINY_SERVE)
    cell.mix = {**cell.mix, **TINY_MIX, **(REDUNDANT if redundant else {})}
    if redundant:
        cell.e2e = cell.e2e + [{"name": "repair_p50_s", "unit": "s"}]
    return cell


@pytest.fixture
def tiny():
    return tiny_cell
