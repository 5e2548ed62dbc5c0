"""``chip_smoke.py`` on the CPU: its phase functions at a reduced
internlm2-1.8b with the Pallas kernels in interpret mode, its refusal to
run without a TPU, and the compile-cache placement its ``main()`` makes."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import get_reduced

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

SMALL = dict(max_len=64, lens=(4, 12), new_tokens=8)


def test_phase_executor_multi_block_parity():
    # 2^17 words = two 64Ki-word blocks per replica stream
    out = chip_smoke.phase_executor(1 << 17, backend="lockstep_pallas")
    assert out[2]["events"] >= 1 and out[3]["events"] == 1


def test_phase_serving_paged_twins_and_strike():
    run = chip_smoke.phase_serving(get_reduced("internlm2-1.8b"), **SMALL)
    m = run["metrics"]
    assert m["paged"] is True
    assert m["done"] == m["submitted"] == 8
    assert run["kernels"] == 0  # interpret mode lowers to plain HLO


_SPATIAL_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path.insert(0, %(root)r)
import chip_smoke
from repro.configs import get_reduced

out = chip_smoke.phase_spatial(get_reduced("internlm2-1.8b"), **%(small)r)
print(json.dumps({"backend": out["spatial"]["engine"].exe.name}))
"""


def test_phase_spatial_on_four_virtual_devices():
    """Phase (c) on four forced host devices (a fresh process: the device
    count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _SPATIAL_CHILD % {"root": str(ROOT), "small": SMALL}],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"backend": "spatial_lockstep"}


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line
    assert "no TPU" in out.err


def test_compile_cache_env_wins_else_checkout(monkeypatch):
    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(
        compile_cache.jax.config, "update", lambda k, v: calls.append((k, v))
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
