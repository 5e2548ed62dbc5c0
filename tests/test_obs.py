"""The observability layer (repro/obs): tracing + metrics registry.

Load-bearing properties:

  * ZERO-COST WHEN OFF — with no tracer attached (the default) the
    engine emits bitwise-identical tokens to a tracer-attached run, on
    every backend; the executor's ``on_event`` hook likewise never
    perturbs the trajectory.
  * VALID ON EXPORT — every exported trace passes the standalone schema
    checker (``tools/validate_trace.py``): spans balance, flow ids
    resolve, ring eviction and still-open spans are sanitized.
  * ORDERED TIMELINES — a strike's detect → attribute → repair instants
    appear in that order on the struck request's own track, linked by a
    flow arrow.
  * UNBIASED PERCENTILES — TTFT quantiles come from a streaming
    histogram observed at first-token time, so FIFO record retention
    (``retain_results``) no longer biases them toward recent requests.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api as miso
from repro.obs import Histogram, MetricsRegistry, Tracer
from repro.serving import DONE, EXPIRED, Request, ServingEngine

from test_serving import strike, toy_engine, toy_parts

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
from validate_trace import validate_events, validate_file  # noqa: E402


# ---------------------------------------------------------------------------
# metrics registry semantics
# ---------------------------------------------------------------------------
def test_counter_gauge_semantics():
    r = MetricsRegistry()
    c = r.counter("reqs_total", "requests")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)  # counters are monotone
    g = r.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    g.inc()
    assert g.value == 6
    # get-or-create returns the same instrument; kind conflicts raise
    assert r.counter("reqs_total") is c
    with pytest.raises(TypeError):
        r.gauge("reqs_total")


def test_histogram_streaming_quantiles():
    h = Histogram("lat", "latency")
    for v in [0.125, 0.125, 0.125, 0.25, 0.5]:
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(1.125)
    assert h.mean == pytest.approx(0.225)
    # quantiles are clamped to the observed range: p50 of a tight cluster
    # cannot fall below the smallest observation, p99 not above the max
    assert 0.125 <= h.quantile(0.5) <= 0.25
    assert h.quantile(0.99) <= 0.5
    assert h.quantile(0.0) == 0.125
    assert h.quantile(1.0) == 0.5
    assert h.quantile(0.5) <= h.quantile(0.99)  # monotone in q
    assert Histogram("empty").quantile(0.5) == 0.0


def test_histogram_overflow_bucket():
    h = Histogram("t", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(1e6)  # beyond the last bound -> +Inf bucket
    cum = h.cumulative()
    assert cum[-1][1] == 3
    assert h.quantile(1.0) == 1e6


def test_prometheus_exposition_format():
    r = MetricsRegistry()
    r.counter("tok_total", "tokens").inc(42)
    h = r.histogram("ttft_seconds", "ttft", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = r.to_prometheus()
    assert "# TYPE tok_total counter" in text
    assert "tok_total 42" in text
    assert "# TYPE ttft_seconds histogram" in text
    assert 'ttft_seconds_bucket{le="0.1"} 1' in text
    assert 'ttft_seconds_bucket{le="1"} 2' in text
    assert 'ttft_seconds_bucket{le="+Inf"} 2' in text
    assert "ttft_seconds_count 2" in text


def test_registry_snapshot_roundtrips_json():
    import json

    r = MetricsRegistry()
    r.counter("c").inc(2)
    r.gauge("g").set(-1.5)
    r.histogram("h").observe(0.01)
    snap = json.loads(json.dumps(r.snapshot()))
    assert snap["c"] == {"kind": "counter", "value": 2}
    assert snap["g"]["value"] == -1.5
    assert snap["h"]["count"] == 1 and snap["h"]["p50"] == 0.01


# ---------------------------------------------------------------------------
# tracer: schema validity, ring bounds, sanitized export
# ---------------------------------------------------------------------------
def test_tracer_export_passes_schema_checker():
    tr = Tracer()
    tr.begin("request", "r0", prompt_len=3)
    tr.instant("queued", "r0")
    with tr.span("tick", "engine", step=0):
        pass
    fid = tr.flow_id()
    tr.flow_start(fid, "r0", "strike")
    tr.flow_end(fid, "r0", "strike")
    tr.end("r0", "request")
    assert validate_events(tr.events()) == []


def test_tracer_export_file_roundtrip(tmp_path):
    tr = Tracer()
    tr.instant("hello", "engine")
    path = tmp_path / "trace.json"
    tr.export(path)
    assert validate_file(str(path)) == []


def test_tracer_auto_closes_open_spans_on_export():
    tr = Tracer()
    tr.begin("request", "r0")
    tr.begin("prefill_walk", "r0")  # nested, both still open
    evs = tr.events()
    assert validate_events(evs) == []
    # the ring still holds the open B's — export closed copies, state
    # is untouched and a later end() still balances
    tr.end("r0", "prefill_walk")
    tr.end("r0", "request")
    assert validate_events(tr.events()) == []


def test_tracer_ring_eviction_stays_valid():
    tr = Tracer(capacity=8)
    for i in range(50):
        tr.begin("span", "t")
        tr.end("t", "span")
        tr.instant("i", "t", n=i)
    assert tr.dropped == 50 * 3 - 8
    assert validate_events(tr.events()) == []


def test_tracer_drops_orphan_flow_halves():
    tr = Tracer(capacity=4)
    fid = tr.flow_id()
    tr.flow_start(fid, "a", "strike")
    for i in range(10):  # push the start out of the ring
        tr.instant("x", "a", n=i)
    tr.flow_end(fid, "a", "strike")
    evs = tr.events()
    assert validate_events(evs) == []
    assert not [e for e in evs if e["ph"] in ("s", "f")]


def test_tracer_track_interning_and_metadata():
    tr = Tracer()
    tr.instant("a", "engine")
    tr.instant("b", "r17")
    tr.instant("c", "engine")
    names = {
        e["args"]["name"]: e["tid"]
        for e in tr.events()
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert set(names) == {"engine", "r17"}
    engine_events = [
        e for e in tr.events() if e["ph"] == "i" and e["tid"] == names["engine"]
    ]
    assert len(engine_events) == 2


# ---------------------------------------------------------------------------
# executor on_event hook: all backends, zero-cost when absent
# ---------------------------------------------------------------------------
def _two_cell_program():
    def a_init(k):
        return {"x": jnp.linspace(0.0, 1.0, 8, dtype=jnp.float32)}

    def a_step(prev):
        return {"x": prev["a"]["x"] * 1.25 + 0.125}

    def b_init(k):
        return {"x": jnp.ones((8,), jnp.float32)}

    def b_step(prev):
        return {"x": prev["b"]["x"] * 0.5 + prev["a"]["x"]}

    p = miso.MisoProgram()
    p.add(miso.CellType("a", a_init, a_step))
    p.add(miso.CellType("b", b_init, b_step, reads=("a",)))
    return p


ALL_BACKENDS = ("lockstep", "lockstep_pallas", "host", "wavefront")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_on_event_bitwise_parity_all_backends(backend):
    """The hook observes; it must never perturb.  Final states with and
    without on_event are bitwise-identical."""
    prog = _two_cell_program()
    plain = miso.compile(prog, backend=backend)
    ref = plain.run(plain.init(jax.random.PRNGKey(0)), 6).states
    tr = Tracer()
    hooked = miso.compile(prog, backend=backend, on_event=tr.executor_hook())
    got = hooked.run(hooked.init(jax.random.PRNGKey(0)), 6).states
    ref_leaves = jax.tree.leaves(ref)
    got_leaves = jax.tree.leaves(got)
    assert all(np.array_equal(a, b) for a, b in zip(ref_leaves, got_leaves))
    assert tr.emitted > 0, "hooked run emitted no events"
    assert validate_events(tr.events()) == []


def test_on_event_step_timing_and_checkpoints():
    prog = _two_cell_program()
    tr = Tracer()
    seen = []
    cps = []
    hook = tr.executor_hook()

    def on_event(name, attrs):
        seen.append((name, dict(attrs)))
        hook(name, attrs)

    exe = miso.compile(
        prog,
        backend="host",
        on_event=on_event,
        checkpoint_cb=lambda t, s: cps.append(t),
        checkpoint_every=2,
    )
    exe.run(exe.init(jax.random.PRNGKey(0)), 4)
    steps = [a for n, a in seen if n == "step"]
    assert [a["step"] for a in steps] == [0, 1, 2, 3]
    assert all(a["dur_us"] >= a["device_us"] >= 0 for a in steps)
    assert [a["step"] for n, a in seen if n == "checkpoint"] == cps == [0, 2]
    # timed events render as X spans on the executor track
    xs = [e for e in tr.events() if e["ph"] == "X" and e["name"] == "step"]
    assert len(xs) == 4


def test_on_event_scan_segments_lockstep():
    prog = _two_cell_program()
    seen = []
    exe = miso.compile(
        prog, backend="lockstep", on_event=lambda n, a: seen.append((n, dict(a)))
    )
    exe.run(exe.init(jax.random.PRNGKey(0)), 6)
    segs = [a for n, a in seen if n == "scan_segment"]
    assert len(segs) == 1 and segs[0]["n_steps"] == 6


def test_on_event_wavefront_unit_steps():
    seen = []
    p = miso.MisoProgram()  # two independent cells -> two units
    unit_a = miso.CellType(
        "a", lambda k: {"x": jnp.float32(1.0)}, lambda pv: {"x": pv["a"]["x"] + 1.0}
    )
    unit_b = miso.CellType(
        "b", lambda k: {"x": jnp.float32(2.0)}, lambda pv: {"x": pv["b"]["x"] * 2.0}
    )
    p.add(unit_a)
    p.add(unit_b)
    exe = miso.compile(
        p, backend="wavefront", on_event=lambda n, a: seen.append((n, dict(a)))
    )
    exe.run(exe.init(jax.random.PRNGKey(0)), 3)
    units = [a for n, a in seen if n == "unit_step"]
    assert len(units) == 6  # 2 units x 3 steps
    assert {a["unit"] for a in units} == {0, 1}


def test_on_event_mismatch_and_recovery_host():
    """An injected DMR strike surfaces compare_mismatch and dmr_recovery
    events on the host backend's §IV loop."""
    cell = miso.CellType(
        "a",
        lambda k: {"x": jnp.zeros((4,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] + 1.0},
        redundancy=miso.RedundancyPolicy(level=2),
    )
    p = miso.MisoProgram()
    p.add(cell)
    seen = []
    exe = miso.compile(
        p, backend="host", on_event=lambda n, a: seen.append((n, dict(a)))
    )
    fault = miso.FaultSpec.at(step=1, cell_id=0, leaf=0, index=1, bit=20)
    exe.run(exe.init(jax.random.PRNGKey(0)), 3, faults=[fault])
    names = [n for n, _ in seen]
    mi = names.index("compare_mismatch")
    ri = names.index("dmr_recovery")
    assert mi < ri, "mismatch must be detected before recovery runs"
    assert seen[mi][1]["cell"] == "a" and seen[ri][1]["cell"] == "a"
    assert exe.recoveries == [(1, "a")]


def test_executor_export_metrics_into_registry():
    prog = _two_cell_program()
    exe = miso.compile(prog, backend="lockstep")
    exe.run(exe.init(jax.random.PRNGKey(0)), 4)
    r = MetricsRegistry()
    exe.export_metrics(r)
    assert r["executor_steps"].value == 4
    assert r["executor_recoveries_total"].value == 0


# ---------------------------------------------------------------------------
# engine: tracing-off bitwise parity (the zero-cost guarantee)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["lockstep", "lockstep_pallas", "host"])
def test_engine_tokens_bitwise_identical_with_tracer(backend):
    """The acceptance gate: tokens with a tracer attached are bitwise
    identical to the untraced default, on every serving-capable
    backend."""

    def run(tracer):
        eng = toy_engine(4, backend=backend, tracer=tracer)
        reqs = []
        for i in range(3):
            policy = miso.RedundancyPolicy(level=2 if i % 2 else 1)
            req = Request(prompt=[1.0 * i, 2.0], max_new_tokens=6, policy=policy)
            reqs.append(req)
        for r in reqs[:2]:
            assert eng.submit(r)
        eng.pump(max_ticks=2)
        assert eng.submit(reqs[2])
        eng.pump()
        return [eng.result(r.id)["tokens"] for r in reqs]

    ref = run(None)
    tr = Tracer()
    got = run(tr)
    assert got == ref, "tracer perturbed the emitted tokens"
    assert tr.emitted > 0
    assert validate_events(tr.events()) == []


def test_engine_strike_timeline_ordered_on_victim_track():
    """A DMR strike campaign: detect → attribute → repair instants land
    in order on the struck request's own track, the flow arrow resolves,
    and the repair names the §IV mechanism."""
    tr = Tracer()
    eng = toy_engine(4, tracer=tr)
    dmr = miso.RedundancyPolicy(level=2)
    victim = Request(prompt=[3.0, 1.0, 4.0], max_new_tokens=8, policy=dmr)
    bystander = Request(prompt=[9.0], max_new_tokens=8)
    assert eng.submit(victim) and eng.submit(bystander)
    eng.pump(max_ticks=1)
    eng.pump(faults=strike(eng, victim.id, replica=1, step=2))
    assert eng.result(victim.id)["status"] == DONE
    evs = tr.events()
    assert validate_events(evs) == []
    vtid = tr.tid(victim.id)
    timeline = [
        e
        for e in evs
        if e["tid"] == vtid and e["ph"] == "i" and e["name"].startswith("strike_")
    ]
    expected = ["strike_detected", "strike_attributed", "strike_repaired"]
    assert [e["name"] for e in timeline] == expected
    ts = [e["ts"] for e in timeline]
    assert ts == sorted(ts)
    assert timeline[2]["args"]["repair"] == "dmr_replay"
    # the flow arrow starts and ends on the victim's track
    flows = [e for e in evs if e["ph"] in ("s", "f")]
    assert {e["tid"] for e in flows} == {vtid}
    assert len({e["id"] for e in flows}) == 1
    # nothing leaked onto the bystander's track
    btid = tr.tid(bystander.id)
    assert not [e for e in evs if e["tid"] == btid and e["name"].startswith("strike_")]
    # the same campaign appears as X spans for the replay on the engine
    # track and as lifecycle spans for both requests
    assert [e for e in evs if e["ph"] == "X" and e["name"] == "dmr_replay"]
    assert len([e for e in evs if e["ph"] == "B" and e["name"] == "request"]) == 2


def test_engine_tmr_repair_event():
    tr = Tracer()
    eng = toy_engine(4, tracer=tr)
    tmr = miso.RedundancyPolicy(level=3)
    victim = Request(prompt=[2.0, 2.0], max_new_tokens=8, policy=tmr)
    assert eng.submit(victim)
    eng.pump(max_ticks=1)
    eng.pump(faults=strike(eng, victim.id, replica=2, step=2))
    assert eng.result(victim.id)["status"] == DONE
    rep = [e for e in tr.events() if e.get("name") == "strike_repaired"]
    assert len(rep) == 1 and rep[0]["args"]["repair"] == "tmr_vote"


def test_engine_lifecycle_spans_and_tick_split():
    tr = Tracer()
    eng = toy_engine(2, tracer=tr)
    req = Request(prompt=[1.0, 2.0], max_new_tokens=4)
    assert eng.submit(req)
    eng.pump()
    evs = tr.events()
    assert validate_events(evs) == []
    rtid = tr.tid(req.id)
    names = [e["name"] for e in evs if e["tid"] == rtid]
    for expected in ("request", "queued", "prefill", "admitted", "first_token", "done"):
        assert expected in names, f"missing {expected} on request track"
    ticks = [e for e in evs if e["ph"] == "X" and e["name"] == "tick"]
    assert ticks, "no tick spans"
    for e in ticks:
        a = e["args"]
        assert a["dispatch_us"] >= 0 and a["device_us"] >= 0
        assert e["dur"] >= a["dispatch_us"] + a["device_us"] - 1e-3


# ---------------------------------------------------------------------------
# engine metrics: registry exposition, TTFT bias fix, busy_s
# ---------------------------------------------------------------------------
def test_engine_registry_prometheus_surface():
    eng = toy_engine(2)
    req = Request(prompt=[1.0], max_new_tokens=3)
    assert eng.submit(req)
    eng.pump()
    m = eng.metrics()
    assert m["done"] == 1 and m["tokens_out"] == 3
    text = eng.registry.to_prometheus()
    assert "serving_tokens_emitted_total 3" in text
    assert "serving_requests_done_total 1" in text
    assert "# TYPE serving_ttft_seconds histogram" in text
    snap = eng.registry.snapshot()
    assert snap["serving_ttft_seconds"]["count"] == 1


def test_ttft_percentiles_survive_record_retention():
    """The percentile-bias fix: with retain_results=2 only the last two
    records survive, but the TTFT histogram still covers every request
    ever served."""
    clock = [0.0]

    def tick_clock():
        clock[0] += 0.125
        return clock[0]

    eng = toy_engine(2, retain_results=2, time_fn=tick_clock)
    n = 6
    for i in range(n):
        req = Request(prompt=[1.0 * (i + 1)], max_new_tokens=2)
        assert eng.submit(req)
        eng.pump()
    assert len(eng.requests) <= 2, "retention did not drop records"
    m = eng.metrics()
    assert eng.registry["serving_ttft_seconds"].count == n
    assert m["ttft_p50_s"] > 0
    assert m["ttft_p99_s"] >= m["ttft_p50_s"]
    assert m["done"] == n  # counters outlive the records too


def test_busy_vs_wall_split():
    clock = [0.0]

    def tick_clock():
        clock[0] += 0.125
        return clock[0]

    eng = toy_engine(2, time_fn=tick_clock)
    req = Request(prompt=[1.0], max_new_tokens=4)
    assert eng.submit(req)
    eng.pump()
    clock[0] += 100.0  # a long idle gap after the work finished
    m = eng.metrics()
    assert 0 < m["busy_s"] < m["wall_s"]
    assert m["utilization"] == pytest.approx(m["busy_s"] / m["wall_s"])
    # busy-throughput ignores the idle tail; wall-throughput pays it
    assert m["tokens_per_s_busy"] > m["tokens_per_s"]
    assert m["tokens_per_s_busy"] == pytest.approx(m["tokens_out"] / m["busy_s"])


def test_prefill_walk_span_closed_by_eviction():
    """A request evicted mid-prefill-walk (deadline) still exports a
    balanced trace: the walk span is closed before the lifecycle span."""
    clock = [0.0]

    def tick_clock():
        clock[0] += 0.125
        return clock[0]

    # chunked prefill through the real LM adapter is heavy; emulate the
    # walk with the toy adapter's 3-tuple prefill instead
    import dataclasses as dc

    prog, adapter = toy_parts(2)
    base_prefill = adapter.prefill

    def chunked(req, states):
        slot, tok = base_prefill(req, states)
        return slot, None, 5  # pretend 5 prompt-tail tokens remain

    adapter = dc.replace(adapter, prefill=chunked)
    tr = Tracer()
    eng = ServingEngine(prog, adapter, tracer=tr, time_fn=tick_clock)
    eng.start(jax.random.PRNGKey(0))
    req = Request(prompt=[1.0], max_new_tokens=4, deadline=0.7)
    assert eng.submit(req)
    eng.pump(max_ticks=3)
    assert eng.result(req.id)["status"] == EXPIRED
    evs = tr.events()
    assert validate_events(evs) == []
    rtid = tr.tid(req.id)
    walk = [e for e in evs if e["tid"] == rtid and e["name"] == "prefill_walk"]
    assert [e["ph"] for e in walk] == ["B", "E"]
