"""The tracer's spans on the profiler's clock, on the paged LM engine.

  * Every engine span is mirrored by a ``jax.profiler`` annotation of the
    same name, so a profiler capture holds the program's spans on its
    host line, nested as the work is (``step``, ``page_grow`` and
    ``postprocess`` inside each ``tick``), beside the serving programs,
    each under its own name.
  * With no tracer, no annotation is ever made.
  * Tracing leaves the paged engine's tokens bitwise unchanged.
"""

import dataclasses as dc
import glob

import jax
import numpy as np
import pytest

from repro import api as miso
from repro.obs import Tracer
from repro.serving import DONE, Request, ServingEngine

from test_obs import validate_events

#: the spans the paged engine emits on every tick that serves a request
TICK_SPANS = {
    "tick",
    "step",
    "page_grow",
    "postprocess",
    "sync.step_reports",
    "sync.page_state",
    "sync.tokens",
}


def paged_engine(tracer=None):
    from repro.configs import get_reduced
    from repro.models.lm_cells import ServeConfig
    from repro.serving.lm import lm_engine_parts

    cfg = get_reduced("internlm2-1.8b")
    cfg = dc.replace(
        cfg, d_model=32, n_layers=2, d_ff=64, n_heads=2, n_kv_heads=1, vocab_size=128
    )
    scfg = ServeConfig(batch=4, max_len=32, paged=True, page_size=8)
    prog, adapter = lm_engine_parts(cfg, scfg)
    eng = ServingEngine(prog, adapter, miso.EngineConfig(tracer=tracer))
    eng.start(jax.random.PRNGKey(0))
    return eng


def serve(eng, n=3, max_new=6):
    """Three prompts, one of them DMR, the last submitted mid-stream; each
    decodes across a page boundary (page size 8)."""
    reqs = []
    for i in range(n):
        prompt = (np.arange(5, dtype=np.int32) * (i + 3)) % 128
        pol = miso.RedundancyPolicy(level=2 if i == 1 else 1)
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new, policy=pol))
    for r in reqs[:-1]:
        assert eng.submit(r)
    eng.pump(max_ticks=2)
    assert eng.submit(reqs[-1])
    eng.pump()
    for r in reqs:
        assert eng.result(r.id)["status"] == DONE
    return [eng.result(r.id)["tokens"] for r in reqs]


def host_events(trace_dir):
    """(name, start_ns, end_ns) of every event on the host lines of the
    capture under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return out


def test_spans_and_program_names_reach_the_profiler_host_line(tmp_path):
    tr = Tracer()
    eng = paged_engine(tr)
    serve(eng)  # compile every program outside the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve(eng)
    finally:
        jax.profiler.stop_trace()
    evs = host_events(tmp_path)
    names = {n for n, _, _ in evs}
    assert TICK_SPANS | {"admit", "queue_wait", "check_replicas"} <= names
    assert "sync.fingerprints" in names and "sync.first_token" in names
    assert "sync.join_pos" in names
    for prog in ("lockstep_step", "paged_grow", "paged_install", "prefill"):
        assert f"PjitFunction({prog})" in names, prog
    assert "PjitFunction(computed)" not in names
    assert not [n for n in names if n.startswith("PjitFunction(") and "lambda" in n]
    # each tick holds its step, its page growth and its postprocess
    ticks = [(s, e) for n, s, e in evs if n == "tick"]
    assert ticks
    for inner in ("step", "page_grow", "postprocess"):
        spans = [(s, e) for n, s, e in evs if n == inner]
        for s, e in ticks:
            assert any(s <= a and b <= e for a, b in spans), inner
    # the ring holds the same spans under the same names
    ring = tr.events()
    assert validate_events(ring) == []
    xs = {e["name"] for e in ring if e["ph"] == "X"}
    assert TICK_SPANS | {"admit", "queue_wait", "prefill"} <= xs
    assert xs <= names
    waits = [e for e in ring if e["ph"] == "X" and e["name"] == "queue_wait"]
    assert len(waits) == 6  # one per request, each on the request's own track
    assert tr.tid("engine") not in {e["tid"] for e in waits}


def test_no_annotation_is_made_without_a_tracer(monkeypatch):
    class Refused:
        def __init__(self, *a, **k):
            raise AssertionError("TraceAnnotation made with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    eng = paged_engine(None)
    toks = serve(eng)
    assert all(len(t) == 6 for t in toks)
    with pytest.raises(AssertionError):  # the patch is what a span uses
        Tracer().span("tick", "engine").start()


def test_paged_tokens_bitwise_identical_with_tracer():
    ref = serve(paged_engine(None))
    tr = Tracer()
    got = serve(paged_engine(tr))
    assert got == ref, "tracer perturbed the paged engine's tokens"
    assert tr.emitted > 0
