"""The unified ``miso.compile()`` executor API: parity across back-ends,
auto back-end selection, the registry, and the deprecation shims."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api as miso


# ---------------------------------------------------------------------------
# shared 3-cell fixture: a self-coupled cell, a reader, and an independent
# cell (two weakly-connected components -> two wavefront units)
# ---------------------------------------------------------------------------
def three_cell_program():
    p = miso.MisoProgram()
    p.add(miso.CellType(
        "a", lambda k: {"x": jnp.linspace(0.0, 1.0, 8, dtype=jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 1.25 + 0.125}))
    p.add(miso.CellType(
        "b", lambda k: {"x": jnp.ones((8,), jnp.float32)},
        lambda prev: {"x": prev["b"]["x"] * 0.5 + prev["a"]["x"] * 2.0},
        reads=("a",)))
    p.add(miso.CellType(
        "c", lambda k: {"x": jnp.float32(1.0)},
        lambda prev: {"x": prev["c"]["x"] * 1.000001 + 0.5}))
    return p


def chain_program():
    """One weakly-connected component (a -> b): auto must pick lockstep."""
    p = miso.MisoProgram()
    p.add(miso.CellType("a", lambda k: {"x": jnp.float32(1.0)},
                        lambda prev: {"x": prev["a"]["x"] + 1.0}))
    p.add(miso.CellType("b", lambda k: {"x": jnp.float32(0.0)},
                        lambda prev: {"x": prev["b"]["x"] + prev["a"]["x"]},
                        reads=("a",)))
    return p


def _leaves_equal(t1, t2) -> bool:
    return all(np.array_equal(a, b)
               for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)))


ALL_BACKENDS = ("lockstep", "lockstep_pallas", "host", "wavefront")


# ---------------------------------------------------------------------------
# parity: all four back-ends produce bitwise-identical trajectories
# ---------------------------------------------------------------------------
def test_backend_parity_bitwise():
    prog = three_cell_program()
    steps = 7
    trajectories = {}
    finals = {}
    for backend in ALL_BACKENDS:
        exe = miso.compile(prog, backend=backend)
        states = exe.init(jax.random.PRNGKey(0))
        trajectories[backend] = [s for s, _ in exe.stream(states, steps)]
        exe2 = miso.compile(prog, backend=backend)
        finals[backend] = exe2.run(
            exe2.init(jax.random.PRNGKey(0)), steps).states
    for backend in ALL_BACKENDS[1:]:
        for t, (ref, got) in enumerate(zip(trajectories["lockstep"],
                                           trajectories[backend])):
            assert _leaves_equal(ref, got), (
                f"{backend} diverged from lockstep at step {t}")
        assert _leaves_equal(finals["lockstep"], finals[backend]), (
            f"{backend} .run() final state differs from lockstep")
    # stream and run agree with each other too
    assert _leaves_equal(trajectories["lockstep"][-1], finals["lockstep"])


def test_run_reports_and_metrics_uniform():
    prog = three_cell_program()
    for backend in ALL_BACKENDS:
        exe = miso.compile(prog, backend=backend)
        res = exe.run(exe.init(jax.random.PRNGKey(1)), 4)
        assert isinstance(res, miso.RunResult)
        assert set(res.reports) == {"a", "b", "c"}
        m = exe.metrics()
        assert m["backend"] == backend
        assert m["steps"] == 4
        assert m["recoveries"] == []


# ---------------------------------------------------------------------------
# lockstep_pallas: bitwise parity of the fused kernel path (interpret mode
# on CPU) under no-fault, DMR-detect, and TMR-vote runs
# ---------------------------------------------------------------------------
def replicated_program(level: int, compare: str = "bitwise"):
    """A replicated cell + an unreplicated reader.  Transition constants
    are powers of two so float math is exact (bitwise parity must not
    depend on how XLA fuses multiply-adds across program shapes)."""
    p = miso.MisoProgram()
    p.add(miso.CellType(
        "a", lambda k: {"x": jnp.linspace(0.0, 1.0, 8, dtype=jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5
                      + jnp.roll(prev["a"]["x"], 1) * 0.25},
        redundancy=miso.RedundancyPolicy(level=level, compare=compare)))
    p.add(miso.CellType(
        "b", lambda k: {"x": jnp.ones((8,), jnp.float32)},
        lambda prev: {"x": prev["b"]["x"] * 0.5 + prev["a"]["x"] * 2.0},
        reads=("a",)))
    return p


def _run_pair(prog, steps, faults=None):
    """(lockstep result, pallas result, lockstep exe, pallas exe)."""
    out = []
    for backend in ("lockstep", "lockstep_pallas"):
        exe = miso.compile(prog, backend=backend, donate=False)
        res = exe.run(exe.init(jax.random.PRNGKey(0)), steps, start_step=0,
                      faults=faults)
        out.extend([res, exe])
    return out[0], out[2], out[1], out[3]


@pytest.mark.parametrize("compare", ["bitwise", "hash"])
def test_lockstep_pallas_parity_dmr_detect(compare):
    """DMR: the strike diverges the replicas; states (diverged pair
    included) and fault reports must be bitwise-identical to lockstep."""
    prog = replicated_program(2, compare)
    fault = miso.FaultSpec.at(step=2, cell_id=0, replica=1, index=3, bit=21)
    ref, got, eref, egot = _run_pair(prog, 6, faults=fault)
    assert _leaves_equal(ref.states, got.states)
    assert _leaves_equal(ref.reports, got.reports)
    # detection + step attribution parity (divergence persists from step 2)
    assert eref.ledger.recent["a"] == egot.ledger.recent["a"]
    assert egot.ledger.recent["a"][0] == 2
    assert eref.metrics()["fault_totals"] == egot.metrics()["fault_totals"]


@pytest.mark.parametrize("compare", ["bitwise", "hash"])
def test_lockstep_pallas_parity_tmr_vote(compare):
    """TMR: the fused vote corrects in-graph; states, reports, ledger
    attribution, and replica localization all match lockstep bitwise."""
    prog = replicated_program(3, compare)
    fault = miso.FaultSpec.at(step=2, cell_id=0, replica=1, index=3, bit=21)
    ref, got, eref, egot = _run_pair(prog, 6, faults=fault)
    assert _leaves_equal(ref.states, got.states)
    assert _leaves_equal(ref.reports, got.reports)
    assert float(got.reports["a"]["events"]) == 1.0  # exactly one strike
    assert eref.ledger.recent["a"] == egot.ledger.recent["a"] == [2]
    # both paths localize the struck replica slot
    for exe in (eref, egot):
        exe.ledger.flagged.add("a")  # force suspects for slot check
        assert exe.metrics()["suspects"]["a"]["replica"] == 1


def test_lockstep_pallas_no_fault_reports_zero():
    prog = replicated_program(3)
    ref, got, _, egot = _run_pair(prog, 5)
    assert _leaves_equal(ref.states, got.states)
    assert _leaves_equal(ref.reports, got.reports)
    assert float(got.reports["a"]["events"]) == 0.0
    assert egot.metrics()["interpret"] is True  # CPU CI runs interpret mode


@pytest.mark.parametrize("level", [2, 3])
def test_lockstep_pallas_compare_every_matches_lockstep(level):
    """The inherited compare_every amortization: at matched k the fused
    path is bitwise-identical, and mid-window TMR strikes are silently
    corrected (vote runs every sub-step, counters only on the last)."""
    prog = replicated_program(level)
    for k in (1, 4):
        outs = {}
        for backend in ("lockstep", "lockstep_pallas"):
            exe = miso.compile(prog, backend=backend, compare_every=k,
                               donate=False)
            outs[backend] = exe.run(exe.init(jax.random.PRNGKey(0)), 8,
                                    start_step=0).states
        assert _leaves_equal(outs["lockstep"], outs["lockstep_pallas"]), k
    if level == 3:
        exe = miso.compile(prog, backend="lockstep_pallas", compare_every=4,
                           donate=False)
        res = exe.run(exe.init(jax.random.PRNGKey(0)), 8, start_step=0,
                      faults=miso.FaultSpec.at(step=1, cell_id=0, replica=0,
                                               index=3, bit=21))
        assert float(res.reports["a"]["events"]) == 0.0  # corrected, unseen


def test_lockstep_pallas_block_option_is_bitwise_stable():
    """Per-block partial combination is exact: any grid split produces the
    same states and reports."""
    prog = replicated_program(3)
    fault = miso.FaultSpec.at(step=1, cell_id=0, replica=2, index=5, bit=11)
    outs = []
    for block in (None, 128, 256):
        exe = miso.compile(prog, backend="lockstep_pallas", block=block,
                           donate=False)
        outs.append(exe.run(exe.init(jax.random.PRNGKey(0)), 4,
                            start_step=0, faults=fault))
    for other in outs[1:]:
        assert _leaves_equal(outs[0].states, other.states)
        assert _leaves_equal(outs[0].reports, other.reports)


# ---------------------------------------------------------------------------
# auto back-end selection
# ---------------------------------------------------------------------------
def test_auto_picks_wavefront_on_independent_units():
    exe = miso.compile(three_cell_program(), backend="auto")
    assert exe.name == "wavefront"
    # the SCC condensation has 2 independent units: {a, b} and {c}
    assert len(exe.program.graph().independent_groups()) == 2


def test_auto_picks_lockstep_on_single_component():
    exe = miso.compile(chain_program(), backend="auto")
    assert exe.name == "lockstep"  # CPU: the XLA lockstep flavor


def test_auto_prefers_pallas_fused_lockstep_on_tpu(monkeypatch):
    """auto resolves the lock-step flavor by accelerator: the Pallas-fused
    back-end on TPU (compiled kernels), XLA lockstep elsewhere."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    exe = miso.compile(chain_program(), backend="auto")
    assert exe.name == "lockstep_pallas"
    assert exe.interpret is False  # real kernels on the TPU path
    # compare_every forces a lock-step flavor too, never wavefront
    exe2 = miso.compile(three_cell_program(), backend="auto",
                        compare_every=4)
    assert exe2.name == "lockstep_pallas"
    monkeypatch.setattr(ops, "on_tpu", lambda: False)
    assert miso.compile(chain_program(), backend="auto").name == "lockstep"
    # named explicitly off-TPU, the kernels run in interpret mode
    exe3 = miso.compile(chain_program(), backend="lockstep_pallas")
    assert exe3.interpret is True


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        miso.compile(three_cell_program(), backend="quantum")


# ---------------------------------------------------------------------------
# registry: new back-ends plug in without touching call sites
# ---------------------------------------------------------------------------
def test_register_backend_roundtrip():
    from repro.core.executor import BACKENDS

    @miso.register_backend("_test_lockstep_twin")
    class Twin(miso.BACKENDS["lockstep"]):
        pass

    try:
        assert "_test_lockstep_twin" in miso.available_backends()
        exe = miso.compile(three_cell_program(),
                           backend="_test_lockstep_twin")
        assert exe.name == "_test_lockstep_twin"
        res = exe.run(exe.init(jax.random.PRNGKey(0)), 3)
        assert set(res.states) == {"a", "b", "c"}
    finally:
        del BACKENDS["_test_lockstep_twin"]


# ---------------------------------------------------------------------------
# compile() options
# ---------------------------------------------------------------------------
def test_policies_option_applies_selective_replication():
    exe = miso.compile(three_cell_program(), backend="host",
                       policies={"a": miso.RedundancyPolicy(level=2)})
    states = exe.init(jax.random.PRNGKey(0))
    assert states["a"]["x"].shape == (2, 8)  # replica axis
    fault = miso.FaultSpec.at(step=2, cell_id=exe.program.cell_id("a"),
                              replica=0, index=3, bit=20)
    exe.run(states, 5, faults=[fault])
    m = exe.metrics()
    assert m["fault_totals"]["a"]["events"] == 1.0
    assert m["recoveries"] == [(2, "a")]


def test_compare_every_matches_per_step_compare():
    prog = three_cell_program()
    e1 = miso.compile(prog, compare_every=1, donate=False)
    e4 = miso.compile(prog, compare_every=4, donate=False)
    s0 = e1.init(jax.random.PRNGKey(0))
    r1 = e1.run(s0, 8, start_step=0)
    r4 = e4.run(s0, 8, start_step=0)
    assert _leaves_equal(r1.states, r4.states)
    with pytest.raises(ValueError, match="multiple of compare_every"):
        e4.run(s0, 6, start_step=0)


def test_collect_stacks_per_step():
    exe = miso.compile(three_cell_program(), donate=False)
    s0 = exe.init(jax.random.PRNGKey(0))
    res = exe.run(s0, 5, start_step=0, collect=lambda st: st["a"]["x"])
    assert res.collected.shape == (5, 8)
    # the last collected frame is the final state
    assert np.array_equal(np.asarray(res.collected[-1]),
                          np.asarray(res.states["a"]["x"]))


def test_stream_respects_compare_every_stride():
    """One stream tick advances compare_every transitions — the step index
    window must not overlap between ticks (faults would re-inject)."""
    prog = chain_program()
    e4 = miso.compile(prog, compare_every=4, donate=False)
    s0 = e4.init(jax.random.PRNGKey(0))
    ticks = [s for s, _ in e4.stream(s0, 8, start_step=0)]
    assert len(ticks) == 2  # 8 transitions / stride 4
    assert e4.metrics()["steps"] == 8
    e1 = miso.compile(prog, compare_every=1, donate=False)
    ref = e1.run(e1.init(jax.random.PRNGKey(0)), 8, start_step=0).states
    assert _leaves_equal(ticks[-1], ref)
    with pytest.raises(ValueError, match="multiple of compare_every"):
        next(e4.stream(s0, 6, start_step=0))
    # a stream tick threads one FaultSpec: two strikes in one window is
    # an error, not a silent drop
    two = [miso.FaultSpec.at(step=1, cell_id=0, bit=20),
           miso.FaultSpec.at(step=2, cell_id=0, bit=20)]
    with pytest.raises(ValueError, match="faults fall in the step window"):
        next(e4.stream(s0, 4, start_step=0, faults=two))
    # ledger events from stream ticks land on the compare sub-step (t+k-1),
    # matching run()'s attribution
    ed = miso.compile(prog, compare_every=4, donate=False,
                      policies={"a": miso.RedundancyPolicy(
                          level=3, compare_every=4)})
    sd = ed.init(jax.random.PRNGKey(0))
    for _ in ed.stream(sd, 4, start_step=0,
                       faults=miso.FaultSpec.at(step=3, cell_id=0,
                                                replica=0, bit=20)):
        pass
    assert ed.ledger.recent.get("a") == [3]


def test_auto_drops_foreign_backend_hints():
    """auto may resolve to any back-end; hints for the others are dropped
    (window= on a program that resolves to lockstep) and compare_every
    forces the back-end that can honor it."""
    exe = miso.compile(chain_program(), backend="auto", window=8)
    assert exe.name == "lockstep"
    exe2 = miso.compile(three_cell_program(), backend="auto",
                        compare_every=4, window=8)
    assert exe2.name == "lockstep"  # wavefront can't amortize compares
    exe3 = miso.compile(three_cell_program(), backend="auto", window=8)
    assert exe3.name == "wavefront" and exe3.window == 8


def test_stream_is_resumable_midway():
    exe = miso.compile(three_cell_program(), backend="host")
    states = exe.init(jax.random.PRNGKey(0))
    it = exe.stream(states)  # unbounded serving stream
    states1, _ = next(it)
    states2, _ = next(it)
    ref = miso.compile(three_cell_program(), backend="host")
    expect = ref.run(ref.init(jax.random.PRNGKey(0)), 2).states
    assert _leaves_equal(states2, expect)


# ---------------------------------------------------------------------------
# Executor.stream across ALL back-ends: resumption mid-stream, compare_every
# amortization, report/ledger attribution parity, the swap hook, and the
# lifted checkpoint protocol (serving-subsystem satellites)
# ---------------------------------------------------------------------------
def dmr_program():
    p = miso.MisoProgram()
    p.add(miso.CellType(
        "a", lambda k: {"x": jnp.linspace(0.0, 1.0, 8, dtype=jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5
                      + jnp.roll(prev["a"]["x"], 1) * 0.25},
        redundancy=miso.RedundancyPolicy(level=2)))
    p.add(miso.CellType(
        "c", lambda k: {"x": jnp.float32(1.0)},
        lambda prev: {"x": prev["c"]["x"] * 0.5 + 0.5}))
    return p


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_stream_resumes_midway_on_every_backend(backend):
    """Tearing a stream down and opening a new one continues the same
    trajectory (the serving engine re-opens the stream every pump)."""
    exe = miso.compile(three_cell_program(), backend=backend)
    states = exe.init(jax.random.PRNGKey(0))
    it = exe.stream(states)
    for _ in range(3):
        states, _ = next(it)
    it.close()
    it2 = exe.stream(states)   # resumes at exe's internal step counter
    for _ in range(4):
        states, _ = next(it2)
    it2.close()
    ref = miso.compile(three_cell_program(), backend=backend)
    expect = ref.run(ref.init(jax.random.PRNGKey(0)), 7).states
    assert _leaves_equal(states, expect)
    assert exe.metrics()["steps"] == 7


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_stream_ledger_attribution_parity(backend):
    """A DMR strike observed through stream lands on the same ledger step
    with the same totals on every back-end (the host back-end additionally
    recovers, which must not change detection accounting)."""
    prog = dmr_program()
    fault = miso.FaultSpec.at(step=2, cell_id=0, replica=1, index=3, bit=21)
    exe = miso.compile(prog, backend=backend, donate=False)
    states = exe.init(jax.random.PRNGKey(0))
    for states, _ in exe.stream(states, 5, start_step=0, faults=fault):
        pass
    assert exe.ledger.recent["a"][0] == 2
    assert exe.ledger.totals["a"]["events"] >= 1.0
    if backend == "host":
        assert exe.recoveries[0] == (2, "a")   # §IV tie-break ran
        assert exe.ledger.totals["a"]["events"] == 1.0  # and re-synced


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_stream_compare_every_contract(backend):
    """compare_every amortization through stream: lock-step flavors fuse k
    transitions per tick (bitwise-equal to per-step compare); the per-step
    back-ends reject the option instead of silently mis-striding."""
    prog = chain_program()
    if backend in ("host", "wavefront"):
        with pytest.raises(ValueError, match="compare_every"):
            miso.compile(prog, backend=backend, compare_every=4)
        return
    e4 = miso.compile(prog, backend=backend, compare_every=4, donate=False)
    ticks = [s for s, _ in e4.stream(e4.init(jax.random.PRNGKey(0)), 8,
                                     start_step=0)]
    assert len(ticks) == 2 and e4.metrics()["steps"] == 8
    e1 = miso.compile(prog, backend=backend, donate=False)
    ref = e1.run(e1.init(jax.random.PRNGKey(0)), 8, start_step=0).states
    assert _leaves_equal(ticks[-1], ref)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_stream_swap_hook_swaps_state_between_ticks(backend):
    """The serving swap hook: states handed back before a tick replace the
    resident states (join/leave between ticks), and a None return keeps
    them untouched."""
    prog = chain_program()
    exe = miso.compile(prog, backend=backend)
    states = exe.init(jax.random.PRNGKey(0))
    seen = []

    def swap(t, st):
        seen.append(t)
        if t == 1:   # swap-in: overwrite cell a's state before tick 1
            st = dict(st)
            st["a"] = {"x": jnp.float32(100.0)}
            return st
        return None

    out = [s for s, _ in exe.stream(states, 3, start_step=0, swap=swap)]
    assert seen == [0, 1, 2]
    # tick 1 consumed the swapped-in value: b reads a's previous state
    assert float(out[1]["a"]["x"]) == 101.0
    assert float(out[2]["b"]["x"]) == float(out[1]["b"]["x"]) + 101.0


def test_checkpointed_lockstep_run_is_bitwise_identical(tmp_path):
    """checkpoint_cb is base-protocol now: the lockstep back-end splits
    its in-graph scan into segments at checkpoint boundaries; trajectory,
    reports, collect stacking, and ledger attribution are unchanged."""
    prog = dmr_program()
    fault = miso.FaultSpec.at(step=5, cell_id=0, replica=0, index=2, bit=20)
    plain = miso.compile(prog, donate=False)
    ref = plain.run(plain.init(jax.random.PRNGKey(0)), 8, start_step=0,
                    faults=fault, collect=lambda st: st["c"]["x"])
    snaps = []
    seg = miso.compile(prog, donate=False,
                       checkpoint_cb=lambda t, st: snaps.append(t),
                       checkpoint_every=2)
    got = seg.run(seg.init(jax.random.PRNGKey(0)), 8, start_step=0,
                  faults=fault, collect=lambda st: st["c"]["x"])
    assert snaps == [0, 2, 4, 6]
    assert _leaves_equal(ref.states, got.states)
    assert _leaves_equal(ref.reports, got.reports)
    assert np.array_equal(np.asarray(ref.collected),
                          np.asarray(got.collected))
    # divergence persists after a DMR strike (lockstep detects, host
    # corrects) — both runs attribute the same event steps
    assert plain.ledger.recent["a"] == seg.ledger.recent["a"] == [5, 6, 7]


def test_checkpoint_snapshots_stay_live_and_resumed_runs_stay_aligned():
    """Two regressions: (1) a cb that RETAINS the snapshot must not see
    its buffers donated away by the following scan segment; (2) a run
    resumed from a step that is not a checkpoint multiple still fires on
    the same t % every == 0 grid as the per-step back-ends."""
    prog = chain_program()
    for backend in ("lockstep", "host"):
        snaps = []
        exe = miso.compile(prog, backend=backend,
                           checkpoint_cb=lambda t, st: snaps.append((t, st)),
                           checkpoint_every=2)   # lockstep: donate defaults on
        s0 = exe.init(jax.random.PRNGKey(0))
        r = exe.run(s0, 3)          # steps 0..2, leaves _t = 3
        exe.run(r.states, 4)        # resumes at 3: grid points are 4, 6
        assert [t for t, _ in snaps] == [0, 2, 4, 6], backend
        # every retained snapshot is still readable (no donated buffers)
        vals = [float(st["a"]["x"]) for _, st in snaps]
        assert vals == [1.0, 3.0, 5.0, 7.0], backend


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_stream_checkpoints_on_every_backend(backend):
    """Base-protocol checkpointing through stream: every back-end
    snapshots the pre-tick buffer at the configured cadence."""
    snaps = []
    exe = miso.compile(three_cell_program(), backend=backend,
                       checkpoint_cb=lambda t, st: snaps.append(
                           (t, float(st["c"]["x"]))),
                       checkpoint_every=2)
    states = exe.init(jax.random.PRNGKey(0))
    for states, _ in exe.stream(states, 4, start_step=0):
        pass
    assert [t for t, _ in snaps] == [0, 2]
    assert snaps[0][1] == 1.0   # tick-0 snapshot is the initial state


def test_wavefront_run_rejects_checkpointing():
    exe = miso.compile(three_cell_program(), backend="wavefront",
                       checkpoint_cb=lambda t, st: None,
                       checkpoint_every=2)
    states = exe.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="consistent cut"):
        exe.run(states, 4)


@pytest.mark.parametrize("backend", ["lockstep", "lockstep_pallas", "host"])
def test_pure_step_replays_without_side_effects(backend):
    """pure_step is the §IV third execution: same output as step, but no
    ledger entries and no step-counter advance (the serving engine's DMR
    tie-break depends on both)."""
    prog = dmr_program()
    exe = miso.compile(prog, backend=backend, donate=False)
    states = exe.init(jax.random.PRNGKey(0))
    replay, _ = exe.pure_step(states, 0)
    stepped, _ = exe.step(states, step_idx=0)
    assert _leaves_equal(replay, stepped)
    assert exe.metrics()["steps"] == 1      # only step() advanced
    # and the replay ignored nothing it shouldn't: a second replay of the
    # SAME window is identical (pure)
    replay2, _ = exe.pure_step(states, 0)
    assert _leaves_equal(replay, replay2)
    # compare=False: identical trajectory with the compare statically
    # elided (the straggler policy's adopt path) — reports stay zero
    nocmp, rep = exe.pure_step(states, 0, compare=False)
    assert _leaves_equal(nocmp, replay)
    assert float(rep["a"]["events"]) == 0.0


def test_pure_step_unsupported_on_wavefront():
    exe = miso.compile(three_cell_program(), backend="wavefront")
    with pytest.raises(NotImplementedError, match="replay"):
        exe.pure_step(exe.init(jax.random.PRNGKey(0)), 0)


# ---------------------------------------------------------------------------
# run_campaign: stacked-FaultSpec multi-fault runs in one dispatch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["lockstep", "lockstep_pallas", "host"])
def test_run_campaign_matches_sequential_runs(backend):
    """N FaultSpecs -> a leading campaign axis, bitwise-equal to N
    sequential runs, with no ledger entries and no counter advance (the
    vmap'd-inject path on the lock-step flavors; a pure_step loop on the
    host back-end)."""
    prog = dmr_program()
    faults = [miso.FaultSpec.at(step=s, cell_id=0, replica=r, index=3,
                                bit=21)
              for s, r in ((1, 0), (3, 1), (9, 0))]  # last never fires
    exe = miso.compile(prog, backend=backend, donate=False)
    s0 = exe.init(jax.random.PRNGKey(0))
    camp = exe.run_campaign(s0, 6, faults, start_step=0)
    assert exe.metrics()["steps"] == 0          # no side effects
    assert exe.ledger.totals == {}
    seq = []
    for f in faults:
        ref = miso.compile(prog, backend="lockstep", donate=False)
        seq.append(ref.run(ref.init(jax.random.PRNGKey(0)), 6,
                           start_step=0, faults=f).states)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *seq)
    assert _leaves_equal(camp.states, stacked)
    ev = np.asarray(camp.reports["a"]["events"])
    assert list(ev) == [5.0, 3.0, 0.0]          # divergence persists (DMR)


def test_run_campaign_collect_and_errors():
    prog = dmr_program()
    exe = miso.compile(prog, donate=False)
    s0 = exe.init(jax.random.PRNGKey(0))
    faults = [miso.FaultSpec.at(step=0, cell_id=0, bit=20),
              miso.FaultSpec.at(step=2, cell_id=0, bit=20)]
    res = exe.run_campaign(s0, 4, faults, start_step=0,
                           collect=lambda st: st["c"]["x"])
    assert res.collected.shape == (2, 4)        # (campaign, step)
    with pytest.raises(ValueError, match="at least one"):
        exe.run_campaign(s0, 4, [], start_step=0)
    e4 = miso.compile(prog, compare_every=4, donate=False)
    with pytest.raises(ValueError, match="multiple of compare_every"):
        e4.run_campaign(s0, 6, faults, start_step=0)


def test_run_campaign_unsupported_on_wavefront():
    exe = miso.compile(three_cell_program(), backend="wavefront")
    with pytest.raises(NotImplementedError, match="replay"):
        exe.run_campaign(exe.init(jax.random.PRNGKey(0)), 2,
                         [miso.FaultSpec.at(step=0, cell_id=0)])


# ---------------------------------------------------------------------------
# deprecation shims (one release of backwards compatibility)
# ---------------------------------------------------------------------------
def test_deprecated_names_warn_and_match_new_api():
    from repro.core import (
        HostRunner, WavefrontRunner, compile_step, run_scan,
    )

    prog = three_cell_program()
    s0 = prog.init_states(jax.random.PRNGKey(0))
    new = miso.compile(prog, donate=False).run(s0, 4, start_step=0)

    with pytest.warns(DeprecationWarning):
        old_final, old_reports, _ = run_scan(prog, s0, 4)
    assert _leaves_equal(old_final, new.states)

    with pytest.warns(DeprecationWarning):
        runner = HostRunner(prog)
    assert _leaves_equal(runner.run(s0, 4), new.states)
    assert runner.ledger.totals  # ledger attribute still reachable

    with pytest.warns(DeprecationWarning):
        wf = WavefrontRunner(prog, window=3)
    assert _leaves_equal(wf.run(s0, 4), new.states)
    # the old runner was idempotent: a second run starts at transition 0
    assert _leaves_equal(wf.run(s0, 4), new.states)
    assert wf.max_lead() >= 0 and len(wf.units) == 3

    with pytest.warns(DeprecationWarning):
        step = compile_step(prog)
    from repro.core import FaultSpec
    st1, _ = step(s0, jnp.int32(0), FaultSpec.none())
    assert set(st1) == {"a", "b", "c"}


def test_ledger_flags_permanent_fault_on_lockstep():
    """In-graph runs must attribute events to their true step so the
    windowed permanent-fault flagging works off-host too.  TMR re-syncs
    replicas in-graph, so each strike is exactly one ledger event."""
    prog = miso.MisoProgram()
    prog.add(miso.CellType(
        "a", lambda k: {"x": jnp.ones((4,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 1.5},
        redundancy=miso.RedundancyPolicy(level=3)))
    exe = miso.compile(prog, donate=False)
    states = exe.init(jax.random.PRNGKey(0))
    # a flaky device: one strike per run, three runs in a 12-step window
    for i in range(3):
        states = exe.run(states, 4,
                         faults=miso.FaultSpec.at(step=4 * i + 1, cell_id=0,
                                                  replica=1, bit=20)).states
    m = exe.metrics()
    assert m["fault_totals"]["a"]["events"] == 3.0
    assert m["flagged"] == ["a"]  # default threshold 3 within window 100
    assert exe.ledger.recent["a"] == [1, 5, 9]  # true step attribution
    assert m["suspects"]["a"]["replica"] == 1  # TMR localizes the slot


def test_ledger_step_attribution_on_wavefront():
    exe = miso.compile(three_cell_program(), backend="wavefront",
                       policies={"a": miso.RedundancyPolicy(level=3)})
    states = exe.init(jax.random.PRNGKey(0))
    exe.run(states, 5,
            faults=miso.FaultSpec.at(step=2, cell_id=0, replica=0, bit=20))
    assert exe.metrics()["fault_totals"]["a"]["events"] == 1.0
    assert exe.ledger.recent["a"] == [2]


def test_submodule_access_through_lazy_package():
    import importlib

    import repro

    assert repro.core.MisoProgram is miso.MisoProgram
    ckpt = importlib.import_module("repro.checkpoint.ckpt")
    assert hasattr(ckpt, "restore")
    with pytest.raises(AttributeError):
        repro.not_a_thing


def test_run_scan_shim_preserves_legacy_start_step_indexing():
    """Old run_scan started at transition start_step*compare_every; the
    shim must replay the same index stream (step-keyed faults depend on
    it)."""
    from repro.core import run_scan

    prog = miso.MisoProgram()
    prog.add(miso.CellType(
        "a", lambda k: {"x": jnp.ones((4,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 1.5},
        redundancy=miso.RedundancyPolicy(level=2)))
    s0 = prog.init_states(jax.random.PRNGKey(0))
    fault = miso.FaultSpec.at(step=9, cell_id=0, replica=0, bit=20)
    with pytest.warns(DeprecationWarning):
        # start_step=2, k=4 -> transitions 8..11: the step-9 strike
        # diverges the DMR replicas and the window-final compare sees it
        _, hit, _ = run_scan(prog, s0, 4, fault=fault,
                             compare_every=4, start_step=2)
    with pytest.warns(DeprecationWarning):
        # same call from transition 0 (transitions 0..3): never fires
        _, miss, _ = run_scan(prog, s0, 4, fault=fault,
                              compare_every=4, start_step=0)
    assert float(hit["a"]["events"]) == 1.0
    assert float(miss["a"]["events"]) == 0.0


def test_host_checkpoint_callback_roundtrips_bf16(tmp_path):
    """ckpt.callback plugs into the host back-end; restore reinterprets
    extension dtypes (np.save round-trips bfloat16 as raw void bytes)."""
    from repro.checkpoint import ckpt

    p = miso.MisoProgram()
    p.add(miso.CellType(
        "a", lambda k: {"x": jnp.ones((4,), jnp.bfloat16),
                        "y": jnp.float32(2.0)},
        lambda prev: {"x": prev["a"]["x"] + jnp.bfloat16(1.0),
                      "y": prev["a"]["y"] * 1.5}))
    exe = miso.compile(p, backend="host",
                       checkpoint_cb=ckpt.callback(tmp_path, blocking=True),
                       checkpoint_every=2)
    states = exe.init(jax.random.PRNGKey(0))
    final = exe.run(states, 5).states
    assert ckpt.latest_step(tmp_path) == 4
    like = miso.compile(p, backend="host").init(jax.random.PRNGKey(0))
    restored, step = ckpt.restore(tmp_path, like)
    assert step == 4
    assert restored["a"]["x"].dtype == jnp.bfloat16
    # the snapshot is the *previous* buffer at step 4; replay to 5 matches
    replay = miso.compile(p, backend="host").run(
        restored, 1, start_step=step).states
    assert _leaves_equal(replay, final)


def test_cell_id_lookup():
    prog = three_cell_program()
    assert [prog.cell_id(n) for n in ("a", "b", "c")] == [0, 1, 2]
    with pytest.raises(ValueError):
        prog.cell_id("nope")
    # with_policies rebuilds the program; ids must follow
    prog2 = prog.with_policies({"b": miso.RedundancyPolicy(level=2)})
    assert prog2.cell_id("b") == 1


# ---------------------------------------------------------------------------
# pass-through cells keep their buffers (no per-step copy of static state)
# ---------------------------------------------------------------------------
def static_cell_program():
    p = miso.MisoProgram()
    p.add(miso.CellType("w", lambda k: {"m": jnp.arange(16, dtype=jnp.float32)},
                        lambda prev: prev["w"]))
    p.add(miso.CellType("a", lambda k: {"x": jnp.zeros((16,), jnp.float32)},
                        lambda prev: {"x": prev["a"]["x"] + prev["w"]["m"]},
                        reads=("w",), redundancy=miso.RedundancyPolicy(level=2)))
    return p


@pytest.mark.parametrize("backend", ["lockstep", "lockstep_pallas"])
def test_step_forwards_static_cell_buffers(backend):
    prog = static_cell_program()
    exe = miso.compile(prog, backend=backend)
    s0 = exe.init(jax.random.PRNGKey(0))
    s1, _ = exe.step(s0)
    assert s1["w"]["m"] is s0["w"]["m"]
    # a strike armed on another cell still leaves the static cell alone
    strike = miso.FaultSpec.at(step=1, cell_id=prog.cell_id("a"), replica=1,
                               index=3, bit=2)
    s2, rep = exe.step(s1, fault=strike)
    assert s2["w"]["m"] is s0["w"]["m"]
    assert float(rep["a"]["events"]) == 1.0
    # the same compiled step, re-armed at a later step, does not retrace
    s3, rep = exe.pure_step(s2, 2, fault=miso.FaultSpec.at(
        step=2, cell_id=prog.cell_id("a"), replica=0, index=5, bit=1))
    assert float(rep["a"]["events"]) == 1.0
    # a strike on the static cell itself must land (it is unprotected)
    s4, _ = exe.step(s3, step_idx=3, fault=miso.FaultSpec.at(
        step=3, cell_id=prog.cell_id("w"), index=4, bit=30))
    assert s4["w"]["m"] is not s0["w"]["m"]
    assert not np.array_equal(s4["w"]["m"], s0["w"]["m"])


def test_fault_spec_static_cells():
    assert miso.FaultSpec.none().cells == ()
    assert miso.FaultSpec.at(step=0, cell_id=2).cells == (2,)
    traced = jax.jit(lambda c: miso.FaultSpec.at(step=0, cell_id=c).cells)
    assert traced(jnp.int32(2)) is None
    assert miso.FaultSpec.at(step=0, cell_id=np.int32(1)).may_strike(1)
    assert not miso.FaultSpec.none().may_strike(0)
