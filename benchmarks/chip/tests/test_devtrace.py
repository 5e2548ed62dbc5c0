"""The trace reduction: interval arithmetic on synthetic events, and the
whole reduction on a short trace recorded on a TPU v5e chip
(``data/small.xplane.pb``: the events that start in 0.3 s of a traced
window of the chat cell, cut from the recorded ``.xplane.pb``)."""

import pathlib

import pytest

from benchmarks.chip import devtrace as D

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    assert D._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]


def test_host_label_is_the_innermost_open_event():
    spans = sorted([(0, 100, "bench_pump"), (10, 50, "np.asarray"), (60, 70, "x")])
    starts = [s for s, _, _ in spans]
    assert D._host_label(spans, starts, 20) == "np.asarray"
    assert D._host_label(spans, starts, 55) == "bench_pump"
    assert D._host_label(spans, starts, 150) == "(none)"


def test_module_name_drops_the_program_fingerprint():
    assert D._module_name("jit_computed(2348586625135240198)") == "jit_computed"


def test_reduce_a_recorded_chip_trace():
    t = D.reduce(SMALL, devices=1)
    assert 0.2 < t.window_s < 1.0
    assert 0 < t.busy_s <= t.window_s
    assert t.op_seconds("paged_gqa_attention") > 0
    assert t.module_seconds("^jit_computed$") > 0
    assert sum(t.ops.values()) >= t.busy_s * 0.99
    idle = t.window_s - t.busy_s
    assert sum(t.idle_by_host.values()) == pytest.approx(idle, rel=1e-6)
    b = t.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
