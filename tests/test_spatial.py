"""Spatial-DMR executor parity: ``backend="spatial_lockstep"`` must
bit-match temporal ``lockstep`` (states AND FaultLedger reports) for
no-fault / DMR-detect / TMR-vote / compare_every on a real multi-device
mesh, and the stacked-FaultSpec campaign path must match sequential runs.

The mesh needs >1 device and jax pins the device count at first init, so
the parity suite runs in a subprocess with 8 forced host devices (same
pattern as test_decode_spmd.py); the CI ``spmd`` job additionally runs
the in-process tests below under ``XLA_FLAGS`` with an explicit 3-axis
``(pod, data, model)`` mesh.  Error paths run on any device count.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import api as miso
from repro.launch.mesh import make_mesh

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from repro import api as miso
from repro.ft import elastic
from repro.launch.mesh import make_mesh


def replicated_program(level, compare, placement="spatial"):
    # transition constants are powers of two so float math is exact
    # (same fixture family as tests/test_executor.py); the unreplicated
    # reader "b" exercises the cross-pod canonical (replica-0) broadcast
    p = miso.MisoProgram()
    p.add(miso.CellType(
        "a", lambda k: {"x": jnp.linspace(0.0, 1.0, 8, dtype=jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5
                      + jnp.roll(prev["a"]["x"], 1) * 0.25},
        redundancy=miso.RedundancyPolicy(level=level, compare=compare,
                                         placement=placement)))
    p.add(miso.CellType(
        "b", lambda k: {"x": jnp.ones((8,), jnp.float32)},
        lambda prev: {"x": prev["b"]["x"] * 0.5 + prev["a"]["x"] * 2.0},
        reads=("a",)))
    return p


def mesh_for(level):
    if level == 2:
        return make_mesh((2, 2, 2), ("pod", "data", "model"))
    devs = np.array(jax.devices()[:6]).reshape(3, 2, 1)
    return Mesh(devs, ("pod", "data", "model"))


def leaves_equal(t1, t2):
    return all(np.array_equal(a, b)
               for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)))


def compiled_pair(prog, level, **kw):
    tmp = miso.compile(prog, backend="lockstep", donate=False, **kw)
    spa = miso.compile(prog, backend="spatial_lockstep", donate=False,
                       mesh=mesh_for(level), **kw)
    return tmp, spa


out = {}

# -- 4-way parity: {DMR, TMR} x {bitwise, hash}, fault + no-fault ---------
for level in (2, 3):
    for compare in ("bitwise", "hash"):
        prog = replicated_program(level, compare)
        fault = miso.FaultSpec.at(step=2, cell_id=0, replica=1, index=3,
                                  bit=21)
        case = {}
        for tag, faults in (("nofault", None), ("fault", fault)):
            tmp, spa = compiled_pair(prog, level)
            rt = tmp.run(tmp.init(jax.random.PRNGKey(0)), 6, start_step=0,
                         faults=faults)
            rs = spa.run(spa.init(jax.random.PRNGKey(0)), 6, start_step=0,
                         faults=faults)
            case[tag] = {
                "states": leaves_equal(rt.states, rs.states),
                "reports": leaves_equal(rt.reports, rs.reports),
                "recent": (tmp.ledger.recent.get("a")
                           == spa.ledger.recent.get("a")),
                "totals": (tmp.metrics()["fault_totals"]
                           == spa.metrics()["fault_totals"]),
                "events": float(rs.reports["a"]["events"]),
            }
        out[f"parity_l{level}_{compare}"] = case

# -- TMR localizes the struck replica through the ledger ------------------
prog = replicated_program(3, "hash")
tmp, spa = compiled_pair(prog, 3)
fault = miso.FaultSpec.at(step=2, cell_id=0, replica=1, index=3, bit=21)
spa.run(spa.init(jax.random.PRNGKey(0)), 6, start_step=0, faults=fault)
spa.ledger.flagged.add("a")
out["tmr_suspect_replica"] = spa.metrics()["suspects"]["a"]["replica"]

# -- compare_every amortization: bitwise-identical at matched k -----------
ce = {}
for level in (2, 3):
    prog = replicated_program(level, "hash")
    tmp, spa = compiled_pair(prog, level, compare_every=4)
    st = tmp.run(tmp.init(jax.random.PRNGKey(0)), 8, start_step=0).states
    ss = spa.run(spa.init(jax.random.PRNGKey(0)), 8, start_step=0).states
    ce[f"l{level}"] = leaves_equal(st, ss)
# a mid-window TMR strike is corrected silently (vote every sub-step,
# counters only on the last)
spa = miso.compile(replicated_program(3, "hash"),
                   backend="spatial_lockstep", mesh=mesh_for(3),
                   donate=False, compare_every=4)
res = spa.run(spa.init(jax.random.PRNGKey(0)), 8, start_step=0,
              faults=miso.FaultSpec.at(step=1, cell_id=0, replica=0,
                                       index=3, bit=21))
ce["tmr_midwindow_silent"] = float(res.reports["a"]["events"])
out["compare_every"] = ce

# -- mixed placement: temporal DMR cell pair-reads a spatial DMR cell -----
pm = miso.MisoProgram()
pm.add(miso.CellType(
    "a", lambda k: {"x": jnp.linspace(0.0, 1.0, 8, dtype=jnp.float32)},
    lambda prev: {"x": prev["a"]["x"] * 0.5
                  + jnp.roll(prev["a"]["x"], 1) * 0.25},
    redundancy=miso.RedundancyPolicy(level=2, placement="spatial")))
pm.add(miso.CellType(
    "t", lambda k: {"x": jnp.ones((8,), jnp.float32)},
    lambda prev: {"x": prev["t"]["x"] * 0.5 + prev["a"]["x"] * 0.25},
    reads=("a",),
    redundancy=miso.RedundancyPolicy(level=2, placement="temporal")))
fault = miso.FaultSpec.at(step=1, cell_id=0, replica=1, index=2, bit=20)
tmp, spa = compiled_pair(pm, 2)
rt = tmp.run(tmp.init(jax.random.PRNGKey(0)), 5, start_step=0, faults=fault)
rs = spa.run(spa.init(jax.random.PRNGKey(0)), 5, start_step=0, faults=fault)
out["mixed_placement"] = {
    "states": leaves_equal(rt.states, rs.states),
    "reports": leaves_equal(rt.reports, rs.reports),
}

# -- run_campaign: N strikes, one dispatch, parity with sequential runs ---
prog = replicated_program(2, "hash")
spa = miso.compile(prog, backend="spatial_lockstep", mesh=mesh_for(2),
                   donate=False)
s0 = spa.init(jax.random.PRNGKey(0))
faults = [miso.FaultSpec.at(step=s, cell_id=0, replica=r, index=3, bit=21)
          for s, r in ((1, 0), (3, 1), (9, 1))]   # the last never fires
camp = spa.run_campaign(s0, 6, faults, start_step=0)
steps_after_campaign = spa.metrics()["steps"]
assert spa.ledger.totals == {}
seq = [spa.run(spa.init(jax.random.PRNGKey(0)), 6, start_step=0,
               faults=f).states for f in faults]
stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *seq)
tmpc = miso.compile(prog, backend="lockstep", donate=False)
tcamp = tmpc.run_campaign(tmpc.init(jax.random.PRNGKey(0)), 6, faults,
                          start_step=0)
out["campaign"] = {
    "states_vs_sequential": leaves_equal(camp.states, stacked),
    "states_vs_temporal": leaves_equal(camp.states, tcamp.states),
    "events": [float(e) for e in np.asarray(camp.reports["a"]["events"])],
    "no_counter_advance": steps_after_campaign == 0,
}

# -- elastic: strike report from REAL trajectories ------------------------
rep = elastic.spatial_strike_report(spa, s0, 6, faults, start_step=0)
out["strike_report"] = rep

# TMR campaign: detection implies in-graph repair
spa3 = miso.compile(replicated_program(3, "hash"),
                    backend="spatial_lockstep", mesh=mesh_for(3),
                    donate=False)
rep3 = elastic.spatial_strike_report(
    spa3, spa3.init(jax.random.PRNGKey(0)), 6,
    [miso.FaultSpec.at(step=2, cell_id=0, replica=2, index=1, bit=19)],
    start_step=0)
out["strike_report_tmr"] = rep3

# -- elastic: straggler policy over REAL executor steps -------------------
# times force: step0 wait, step1 adopt (gap 4x > slack), steps 2+ wait.
# the strike lands on the ADOPTED step: its compare is skipped (deficit),
# and the next wait-step compare repays the deficit by detecting the
# persistent DMR divergence.
spa = miso.compile(prog, backend="spatial_lockstep", mesh=mesh_for(2),
                   donate=False)
s0 = spa.init(jax.random.PRNGKey(0))
policy = elastic.StragglerPolicy(mode="first_wins", slack=1.5)
times = [(1.0, 1.0), (1.0, 4.0), (1.0, 1.0), (1.0, 1.0)]
strike = miso.FaultSpec.at(step=1, cell_id=0, replica=1, index=3, bit=21)
final, stats, log = elastic.run_with_straggler_policy(
    spa, s0, 4, policy, times, faults=strike, start_step=0)
kinds = [(e["step"], e["kind"]) for e in log.events]
out["straggler"] = {
    "adopted": stats.adopted_fast,
    "waited": stats.waited,
    "deficit_repaid": stats.compare_deficit == 0,
    "kinds": kinds,
    # the adopted step hid the strike; detection lands on step 2's compare
    "first_detect": next((s for s, k in kinds if k == "detect"), None),
    "ledger_first": spa.ledger.recent.get("a", [None])[0],
}
# the trajectory itself must still be the reference one (adopt steps use
# the side-effect-free replay, not a different transition)
ref = miso.compile(prog, backend="spatial_lockstep", mesh=mesh_for(2),
                   donate=False)
rr = ref.run(ref.init(jax.random.PRNGKey(0)), 4, start_step=0,
             faults=strike)
out["straggler"]["states_match_plain_run"] = leaves_equal(final, rr.states)

print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def spatial_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(
        pathlib.Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("level", [2, 3])
@pytest.mark.parametrize("compare", ["bitwise", "hash"])
def test_spatial_parity_bitwise(spatial_result, level, compare):
    """states AND FaultLedger reports bit-match temporal lockstep, with
    and without an injected strike, in both compare modes."""
    case = spatial_result[f"parity_l{level}_{compare}"]
    for tag in ("nofault", "fault"):
        for key in ("states", "reports", "recent", "totals"):
            assert case[tag][key], (level, compare, tag, key)
    assert case["nofault"]["events"] == 0.0
    # DMR detects (divergence persists: steps 2..5), TMR corrects once
    assert case["fault"]["events"] == (4.0 if level == 2 else 1.0)


def test_spatial_tmr_localizes_struck_replica(spatial_result):
    assert spatial_result["tmr_suspect_replica"] == 1


def test_spatial_compare_every_matches_temporal(spatial_result):
    ce = spatial_result["compare_every"]
    assert ce["l2"] and ce["l3"]
    assert ce["tmr_midwindow_silent"] == 0.0  # corrected, unseen


def test_spatial_mixed_placement_parity(spatial_result):
    """A temporal DMR cell pair-reading a spatial DMR cell (the gathered
    replica-axis read path) stays bitwise-identical to pure temporal."""
    assert spatial_result["mixed_placement"]["states"]
    assert spatial_result["mixed_placement"]["reports"]


def test_spatial_run_campaign_matches_sequential(spatial_result):
    """The stacked-FaultSpec vmap'd campaign: one dispatch, bitwise-equal
    to N sequential runs, on both placements, with no side effects."""
    c = spatial_result["campaign"]
    assert c["states_vs_sequential"]
    assert c["states_vs_temporal"]
    assert c["events"] == [5.0, 3.0, 0.0]  # step-9 strike never fires
    assert c["no_counter_advance"]


def test_elastic_strike_report_from_real_runs(spatial_result):
    """ft/elastic summarizes REAL campaign trajectories: DMR detects but
    cannot repair in-graph; TMR detection implies voted repair."""
    rep = spatial_result["strike_report"]
    assert [r["detected"] for r in rep] == [True, True, False]
    assert all(not r["repaired"] for r in rep)  # DMR: detect-only
    assert rep[0]["events"]["a"] > 0
    rep3 = spatial_result["strike_report_tmr"]
    assert rep3[0]["detected"] and rep3[0]["repaired"]


def test_elastic_straggler_policy_against_real_executor(spatial_result):
    """The straggler simulation's decisions, applied to a real spatial
    executor: an adopted (compare-skipped) step hides the strike, the next
    wait-step compare repays the deficit by detecting it, and the
    trajectory is bitwise-identical to an undisturbed run."""
    s = spatial_result["straggler"]
    assert s["adopted"] == 1 and s["waited"] == 3
    assert s["deficit_repaid"]
    assert [1, "adopt"] in s["kinds"]
    assert s["first_detect"] == 2       # not 1: that compare was skipped
    assert s["ledger_first"] == 2
    assert [2, "repay"] in s["kinds"]
    assert s["states_match_plain_run"]


# ---------------------------------------------------------------------------
# error paths (any device count)
# ---------------------------------------------------------------------------
def spatial_program(level=2):
    p = miso.MisoProgram()
    p.add(miso.CellType(
        "a", lambda k: {"x": jnp.ones((4,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5},
        redundancy=miso.RedundancyPolicy(level=level, placement="spatial")))
    return p


def test_spatial_requires_mesh():
    with pytest.raises(ValueError, match="mesh"):
        miso.compile(spatial_program(), backend="spatial_lockstep")


def test_spatial_requires_pod_axis():
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="no 'pod' axis"):
        miso.compile(spatial_program(), backend="spatial_lockstep",
                     mesh=mesh)


def test_spatial_requires_matching_pod_count():
    mesh = make_mesh((1,), ("pod",))
    with pytest.raises(ValueError, match="must match"):
        miso.compile(spatial_program(level=2), backend="spatial_lockstep",
                     mesh=mesh)


def test_spatial_requires_spatial_cells():
    mesh = make_mesh((1,), ("pod",))
    prog = miso.MisoProgram()
    prog.add(miso.CellType(
        "a", lambda k: {"x": jnp.ones((4,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5},
        redundancy=miso.RedundancyPolicy(level=2)))   # temporal
    with pytest.raises(ValueError, match="no placement='spatial'"):
        miso.compile(prog, backend="spatial_lockstep", mesh=mesh)


def test_make_spatial_ctx_constrains_nothing_inside_manual_body():
    """Transitions running inside the spatial executor's full-manual
    shard_map get a ShardCtx whose every axis is manual: sharding
    constraints drop to no-ops instead of emitting specs the manual
    region would reject, and the pod axis never carries data."""
    from repro.launch.mesh import make_spatial_ctx

    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    ctx = make_spatial_ctx(mesh)
    assert ctx.data_axes == ("data",)          # pod holds replicas
    assert ctx.manual_axes == ("pod", "data", "model")
    x = jnp.ones((4, 4))
    assert ctx.constrain(x, "dp", "tp") is x   # identity, no constraint


def test_auto_does_not_pick_spatial_without_fitting_mesh():
    """auto only resolves to the spatial back-end when the mesh can place
    one replica per pod; otherwise the policy stays a temporal request,
    with a warning."""
    mesh = make_mesh((1,), ("pod",))
    with pytest.warns(UserWarning, match="run temporally"):
        exe = miso.compile(spatial_program(level=2), backend="auto", mesh=mesh)
    assert exe.name == "lockstep"
    with pytest.warns(UserWarning, match="run temporally"):
        assert miso.compile(spatial_program(2), backend="auto").name == "lockstep"


# ---------------------------------------------------------------------------
# in-process tests for the CI spmd lane (XLA_FLAGS forces 8 host devices;
# plain tier-1 on one device skips these)
# ---------------------------------------------------------------------------
needs_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@needs_devices
def test_spatial_init_places_replicas_on_pods():
    """init shards the replica axis over the pod axis of the explicit
    3-axis mesh and replicates everything else."""
    from jax.sharding import PartitionSpec as P

    prog = miso.MisoProgram()
    prog.add(miso.CellType(
        "a", lambda k: {"x": jnp.ones((8,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5},
        redundancy=miso.RedundancyPolicy(level=2, placement="spatial")))
    prog.add(miso.CellType(
        "b", lambda k: {"x": jnp.ones((8,), jnp.float32)},
        lambda prev: {"x": prev["b"]["x"] * 0.5 + prev["a"]["x"]},
        reads=("a",)))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    exe = miso.compile(prog, backend="spatial_lockstep", mesh=mesh)
    states = exe.init(jax.random.PRNGKey(0))
    assert states["a"]["x"].shape == (2, 8)   # replica axis
    assert states["a"]["x"].sharding.spec == P("pod")
    assert states["b"]["x"].sharding.spec == P()
    m = exe.metrics()
    assert (m["placement"], m["pod_axis"], m["n_pods"]) == (
        "spatial", "pod", 2)


@needs_devices
def test_auto_mixed_spatial_levels_fall_back_to_temporal():
    """auto must always produce a runnable executor: if ANY spatial cell
    cannot put one replica per pod (here a level-3 cell on a 2-pod axis),
    the whole program stays on the temporal fallback instead of tripping
    the spatial back-end's constructor."""
    prog = miso.MisoProgram()
    prog.add(miso.CellType(
        "a", lambda k: {"x": jnp.ones((8,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5},
        redundancy=miso.RedundancyPolicy(level=2, placement="spatial")))
    prog.add(miso.CellType(
        "b", lambda k: {"x": jnp.ones((8,), jnp.float32)},
        lambda prev: {"x": prev["b"]["x"] * 0.5 + prev["a"]["x"] * 0.25},
        reads=("a",),
        redundancy=miso.RedundancyPolicy(level=3, placement="spatial")))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    exe = miso.compile(prog, backend="auto", mesh=mesh)
    assert exe.name == "lockstep"
    exe.run(exe.init(jax.random.PRNGKey(0)), 2)   # and it runs


@needs_devices
def test_auto_resolves_spatial_on_pod_mesh():
    prog = miso.MisoProgram()
    prog.add(miso.CellType(
        "a", lambda k: {"x": jnp.ones((8,), jnp.float32)},
        lambda prev: {"x": prev["a"]["x"] * 0.5},
        redundancy=miso.RedundancyPolicy(level=2, placement="spatial",
                                         compare="hash")))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    exe = miso.compile(prog, backend="auto", mesh=mesh)
    assert exe.name == "spatial_lockstep"
    res = exe.run(exe.init(jax.random.PRNGKey(0)), 3, start_step=0)
    assert float(res.reports["a"]["events"]) == 0.0
