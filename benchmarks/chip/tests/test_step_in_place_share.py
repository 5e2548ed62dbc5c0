"""The reader of ``step_in_place_share``: on synthetic ``tick`` spans, on a
program whose ticks carry no ``pool_in_place`` (it reads nothing), and on
tiny traced engine runs through the harness on the CPU, all unreplicated
(every step in place) and half replicated (fewer)."""

import types

import pytest

from benchmarks.chip import harness as H
from benchmarks.chip import traffic as T
from benchmarks.chip.tests.conftest import tiny_cell

read = H.load_module(H.HERE / "metrics" / "step_in_place_share.py").read


def tick(ts, **args):
    return {"ph": "X", "name": "tick", "ts": ts, "dur": 10, "args": args}


def r_of(spans):
    return types.SimpleNamespace(spans=spans)


def test_share_of_ticks_marked_in_place():
    spans = [tick(0, pool_in_place=1), tick(20, pool_in_place=0),
             tick(40, pool_in_place=1), tick(60, pool_in_place=1)]
    assert read(r_of(spans)) == pytest.approx(75.0)


def test_ticks_without_the_arg_read_nothing():
    assert read(r_of([tick(0, dispatch_us=1.0, harvest_us=1.0)])) is None
    assert read(r_of([])) is None


@pytest.mark.parametrize("redundant", [False, True], ids=["chat", "half-replicated"])
def test_share_on_a_tiny_engine_run(redundant):
    from repro.obs import Tracer

    cell = tiny_cell(redundant=redundant)
    tracer = Tracer(capacity=H.TRACER_CAPACITY)
    sys_ = H.build(cell.config, 11, tracer=tracer)
    H.warm_up(sys_, cell.mix, 11)
    plan = T.plan(cell.mix, seed=11, window_s=1.5, vocab=sys_.cfg.vocab_size)
    loop = H.run_loop(sys_, cell.mix, plan, seed=11, window_s=1.5, hard_s=20)
    assert loop.ticks > 0
    share = read(r_of([e for e in tracer.events() if e.get("ph") == "X"]))
    if redundant:
        assert 0.0 <= share < 100.0
    else:
        assert share == 100.0
