"""The comparison that decides ``correct``, driven end to end on the CPU
at a tiny width: sound runs pass, the float8 control reads far above the
program and comes out not correct, and a run whose timed path is broken underneath comes out
``correct: false`` for each fault a serving cell can have."""

import dataclasses
import math
import subprocess
import sys

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness as H
from benchmarks.chip import run as RUN
from benchmarks.chip.tests.conftest import ROOT, tiny_cell

SEED = 2**31 + 11


def _run(cell, monkeypatch=None, breaks=None):
    if breaks is not None:
        build = H.build

        def broken_build(*a, **kw):
            sys_ = build(*a, **kw)
            breaks(sys_)
            return sys_

        monkeypatch.setattr(H, "build", broken_build)
    return RUN.run_cell(cell, SEED, 2.0, False, t_start=0.0, control=True)


@pytest.mark.parametrize("redundant", [False, True], ids=["chat", "chat-redundant"])
def test_sound_run_is_correct_and_control_reads_far_higher(redundant):
    res = _run(tiny_cell(redundant=redundant))
    out, info = res["result"], res["info"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and info["compiles_in_window"] == 0
    gap = out["checks"]["logit_gap"]["value"]
    assert info["control_gap"] >= 3 * gap
    # the control goes through the same checks and limits, and fails them
    assert info["control_correct"] is False
    assert list(out)[-1] == "checks"
    if redundant:
        assert info["strikes"] >= 1 and out["checks"]["strikes_unrepaired"]["value"] == 0
        assert math.isfinite(out["metrics"]["repair_p50_s"]["value"])


def _alter_tokens(sys_):
    """A token altered where it is produced: every harvested token + 1."""
    eng = sys_.engine
    read = eng.adapter.read_tokens
    vocab = sys_.cfg.vocab_size
    eng.adapter = dataclasses.replace(
        eng.adapter, read_tokens=lambda dec: (read(dec) + 1) % vocab
    )


def _frozen_step(sys_):
    """A step that returns its state unchanged."""
    exe = sys_.engine.exe
    step = exe.step

    def frozen(states, **kw):
        _, reports = step(states, **kw)
        return states, reports

    exe.step = frozen


@pytest.mark.parametrize("fault", [_alter_tokens, _frozen_step])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    res = _run(tiny_cell(), monkeypatch, fault)
    assert res["result"]["correct"] is False


def test_no_tpu_exits_2_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "internlm2-1.8b.chat"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode == 2 and proc.stdout.strip() == ""


def test_bare_benchmark_directory_fails_without_result(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "internlm2-1.8b.chat"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_nearest_rank_counts_failures_as_late():
    assert H.nearest_rank([1.0, 2.0, math.inf], 0.5) == 2.0
    assert H.nearest_rank([1.0] * 9 + [math.inf], 0.9) == 1.0
    assert H.nearest_rank([1.0] * 8 + [math.inf] * 2, 0.9) == math.inf
    assert jnp.isinf(H.nearest_rank([math.inf], 0.9))
