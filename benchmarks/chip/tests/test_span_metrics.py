"""The readers of the engine's host-sync, queue-wait and slot-surgery
metrics, on synthetic spans and device traces, on a program without
those spans or program names (they read nothing), and on the spans of a
tiny engine run through the harness on the CPU."""

import types

import pytest

from benchmarks.chip import devtrace as D
from benchmarks.chip import harness as H
from benchmarks.chip import traffic as T
from benchmarks.chip.tests.conftest import tiny_cell

METRICS = H.HERE / "metrics"


def reader(name):
    return H.load_module(METRICS / f"{name}.py").read


def X(name, ts, dur, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "args": args}


def device(modules, devices=1):
    return D.DeviceTrace(
        window_s=1.0,
        busy_s=0.5,
        devices=devices,
        ops={},
        modules=modules,
        idle_by_host={},
    )


def r_of(spans, dev=None):
    return types.SimpleNamespace(spans=spans, device=dev)


TICKS = [X("tick", 0, 40_000), X("tick", 50_000, 40_000)]


def test_host_sync_sums_reads_but_not_the_step_reports():
    spans = TICKS + [
        X("sync.step_reports", 100, 30_000),
        X("sync.page_state", 200, 1_000),
        X("sync.tokens", 35_000, 2_000),
        X("sync.tokens", 85_000, 3_000),
        X("postprocess", 34_000, 6_000),
    ]
    assert reader("host_sync_ms_per_tick")(r_of(spans)) == pytest.approx(3.0)


def test_queue_wait_is_the_mean_wait():
    spans = TICKS + [X("queue_wait", 0, 10_000), X("queue_wait", 5, 30_000)]
    assert reader("queue_wait_ms")(r_of(spans)) == pytest.approx(20.0)


def test_slot_surgery_reads_named_programs_per_tick_and_chip():
    mods = {
        "jit_paged_grow": 0.004,
        "jit_paged_install": 0.002,
        "jit_slot_copy": 0.002,
        "jit_lockstep_step": 0.05,
        "jit_prefill": 0.01,
    }
    got = reader("slot_surgery_ms_per_tick")(r_of(TICKS, device(mods, devices=2)))
    assert got == pytest.approx(2.0)


@pytest.mark.parametrize(
    "name", ["host_sync_ms_per_tick", "queue_wait_ms", "slot_surgery_ms_per_tick"]
)
def test_a_program_without_the_spans_reads_nothing(name):
    """Only ``tick`` and ``prefill`` spans, and every program named
    ``jit_computed``: what a program without these spans and names gives."""
    spans = TICKS + [X("prefill", 10, 5_000, prompt_len=100)]
    r = r_of(spans, device({"jit_computed": 0.2, "jit__lambda": 0.01}))
    assert reader(name)(r) is None
    assert reader(name)(r_of([], device({}))) is None


def test_span_readers_on_a_tiny_engine_run():
    """The harness's open loop over a traced tiny engine: the readers find
    their spans in the program's own ring."""
    from repro.obs import Tracer

    cell = tiny_cell()
    tracer = Tracer(capacity=H.TRACER_CAPACITY)
    sys_ = H.build(cell.config, 7, tracer=tracer)
    H.warm_up(sys_, cell.mix, 7)
    plan = T.plan(cell.mix, seed=7, window_s=1.5, vocab=sys_.cfg.vocab_size)
    loop = H.run_loop(sys_, cell.mix, plan, seed=7, window_s=1.5, hard_s=20)
    assert loop.ticks > 0
    spans = [e for e in tracer.events() if e.get("ph") == "X"]
    r = r_of(spans)
    sync = reader("host_sync_ms_per_tick")(r)
    wait = reader("queue_wait_ms")(r)
    assert sync is not None and sync > 0
    assert wait is not None and wait >= 0
    # one wait for every request served: warm-up and the loop's own
    done = sum(t.status == "done" for t in loop.tracks)
    assert sum(e["name"] == "queue_wait" for e in spans) >= done > 0
