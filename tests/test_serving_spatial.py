"""Spatial serving parity: ``EngineConfig(placement="spatial")`` puts a
DMR/TMR request's replica slots at the SAME slot column on DIFFERENT
mesh pods and detects strikes with one cross-pod collective per tick
(serving/spatial.py) instead of the host fingerprint walk.

The gate: tokens AND the engine's FaultLedger attribution must be
bitwise-identical to temporal replica-slot serving — for none/DMR/TMR
policies, healthy and with a mid-decode strike confined to one pod
(the struck request's pod-1 member).  The mesh needs multiple devices
and jax pins the device count at first init, so the parity run lives
in a subprocess with 8 forced host devices (same pattern as
tests/test_spatial.py).
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro import api as miso
from repro.launch.mesh import make_mesh

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax, jax.numpy as jnp

from repro import api as miso
from repro.launch.mesh import make_mesh
from repro.serving import Request, SlotAdapter, infer_slot_axes, mask_slots

SLOTS = 8
PODS = 4     # 2 columns per pod; TMR spans pods 0-2

# the toy slotted decoder of tests/test_serving.py: power-of-two float
# math (exact), position-dependent, row-independent
def toy_init(b):
    return {
        "x": jnp.zeros((b,), jnp.float32),
        "tokens": jnp.zeros((b, 1), jnp.int32),
        "active": jnp.zeros((b,), jnp.bool_),
        "pos": jnp.zeros((b,), jnp.int32),
    }

axes = infer_slot_axes(toy_init)


def parts(spatial):
    def d_transition(prev):
        st = prev["dec"]
        act = st["active"]
        x = st["x"] * prev["w"]["m"] + st["pos"].astype(jnp.float32)
        tok = (jnp.abs(x) * 64.0).astype(jnp.int32) % 1009
        new = {"x": x, "tokens": tok[:, None], "active": act,
               "pos": st["pos"] + 1}
        return mask_slots(act, new, st, axes)

    prog = miso.MisoProgram()
    prog.add(miso.CellType(
        "w", lambda k: {"m": jnp.float32(1.0) + jnp.float32(2.0) ** -3},
        lambda prev: prev["w"]))
    prog.add(miso.CellType(
        "dec", lambda k: toy_init(SLOTS), d_transition,
        reads=("w",), instances=SLOTS))
    if spatial:
        # the marker make_slot_serve_program sets under
        # ServeConfig(placement="spatial"): any slot-masked program
        # opts its decoder into pod placement the same way
        prog.spatial_serve = {"cell": "dec", "axes": axes,
                              "n_slots": SLOTS}

    def prefill(req, states):
        p = jnp.asarray(req.prompt, jnp.float32)
        x0 = jnp.sum(p) * jnp.float32(2.0) ** -6
        tok0 = (jnp.abs(x0) * 64.0).astype(jnp.int32) % 1009
        return {"x": x0[None], "tokens": tok0[None, None],
                "active": jnp.ones((1,), jnp.bool_),
                "pos": jnp.full((1,), p.shape[0], jnp.int32)
                }, tok0[None, None]

    adapter = SlotAdapter(
        cell="dec", n_slots=SLOTS, slot_axes=axes, prefill=prefill,
        read_tokens=lambda dec: dec["tokens"],
        make_empty=lambda: toy_init(1))
    return prog, adapter


def x_leaf_index():
    import jax.tree_util as jtu
    flat, _ = jtu.tree_flatten_with_path(toy_init(SLOTS))
    return next(i for i, (p, _) in enumerate(flat)
                if any(getattr(q, "key", None) == "x" for q in p))


def drive(placement, strike_level):
    spatial = placement == "spatial"
    mesh = (make_mesh((PODS, 8 // PODS), ("pod", "data"))
            if spatial else None)
    prog, adapter = parts(spatial)
    eng = miso.serve(prog, adapter,
                     miso.EngineConfig(placement=placement, mesh=mesh))
    eng.start(jax.random.PRNGKey(0))
    mkpol = lambda lv: miso.RedundancyPolicy(
        level=lv,
        placement="spatial" if (spatial and lv > 1) else "temporal")
    reqs = [Request(prompt=[3.0, 1.0], max_new_tokens=8, policy=mkpol(1)),
            Request(prompt=[4.0, 1.0], max_new_tokens=8, policy=mkpol(2)),
            Request(prompt=[2.0, 7.0], max_new_tokens=8, policy=mkpol(3)),
            Request(prompt=[5.0], max_new_tokens=8, policy=mkpol(1))]
    for r in reqs:
        assert eng.submit(r), placement
    eng.pump(max_ticks=2)          # everyone resident, mid-decode
    fault = None
    if strike_level:
        victim = reqs[1] if strike_level == 2 else reqs[2]
        rec = eng.requests[victim.id]
        # slots[1]: temporal = the anchor-adjacent replica row; spatial
        # = pod 1's member of the column -> the strike stays confined
        # to one pod
        fault = miso.FaultSpec.at(
            step=eng.exe.metrics()["steps"] + 1,
            cell_id=prog.cell_id("dec"), leaf=x_leaf_index(),
            index=rec.slots[1], bit=20)
    eng.pump(faults=fault)
    m = eng.metrics()
    return {
        "tokens": [eng.result(r.id)["tokens"] for r in reqs],
        "status": [eng.result(r.id)["status"] for r in reqs],
        "faults": [eng.result(r.id)["faults"] for r in reqs],
        "totals": [eng.ledger.totals.get(r.id) for r in reqs],
        "recent": [eng.ledger.recent.get(r.id) for r in reqs],
        "slots": [eng.result(r.id)["slots"] for r in reqs],
        "placement": m["placement"],
        "pods": m["pods"],
        "slots_per_pod": eng.exe.metrics().get("slots_per_pod"),
    }


out = {}
for tag, strike in (("none", 0), ("dmr", 2), ("tmr", 3)):
    t = drive("temporal", strike)
    s = drive("spatial", strike)
    out[tag] = {
        "tokens_equal": t["tokens"] == s["tokens"],
        "status": [t["status"], s["status"]],
        "faults": [t["faults"], s["faults"]],
        "totals_equal": t["totals"] == s["totals"],
        "recent_equal": t["recent"] == s["recent"],
        "t_totals": t["totals"],
        "s_totals": s["totals"],
        "s_slots": s["slots"],
        "placement": [t["placement"], s["placement"]],
        "pods": s["pods"],
        "slots_per_pod": s["slots_per_pod"],
    }
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def serving_spatial_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        env=env,
        capture_output=True,
        text=True,
        timeout=3000,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT") :])


@pytest.mark.parametrize("tag", ["none", "dmr", "tmr"])
def test_spatial_serving_token_parity(serving_spatial_result, tag):
    """Tokens bitwise-identical to temporal replica-slot serving for
    none/DMR/TMR, healthy and under a mid-decode strike."""
    case = serving_spatial_result[tag]
    assert case["tokens_equal"]
    assert all(st == "done" for run in case["status"] for st in run)


@pytest.mark.parametrize("tag", ["dmr", "tmr"])
def test_spatial_serving_ledger_parity(serving_spatial_result, tag):
    """FaultLedger attribution identical to temporal: same per-request
    fault counts, same per-replica (== per-pod) entries, same steps."""
    case = serving_spatial_result[tag]
    victim = 1 if tag == "dmr" else 2
    assert case["faults"][0] == case["faults"][1]    # temporal == spatial
    assert case["faults"][1][victim] == 1            # charged to the owner
    assert case["totals_equal"] and case["recent_equal"]
    # the ledger names the struck POD: replica index == pod index, and
    # the strike hit slots[1] (pod 1)
    per = case["s_totals"][victim]["per_replica"]
    assert per[1] > 0 and per[0] == 0 and per[2] == 0


def test_spatial_serving_no_false_positives(serving_spatial_result):
    case = serving_spatial_result["none"]
    assert case["faults"] == [[0, 0, 0, 0], [0, 0, 0, 0]]
    assert case["totals_equal"]


def test_spatial_serving_placement_surface(serving_spatial_result):
    """The engine reports its placement; spatial groups really are one
    column across pods (global slot p*spp + c per member pod)."""
    case = serving_spatial_result["none"]
    assert case["placement"] == ["temporal", "spatial"]
    assert case["pods"] == 4 and case["slots_per_pod"] == 2
    spp = case["slots_per_pod"]
    dmr, tmr = case["s_slots"][1], case["s_slots"][2]
    col = dmr[0]
    assert dmr == [p * spp + col for p in range(2)]
    col = tmr[0]
    assert tmr == [p * spp + col for p in range(3)]


def test_spatial_engine_requires_mesh_and_divisible_slots():
    """Config-time errors need no multi-device mesh (in-process)."""
    with pytest.raises(ValueError, match="mesh"):
        miso.EngineConfig(placement="spatial")
    cfg = miso.EngineConfig(placement="spatial", mesh=make_mesh((1,), ("pod",)))
    assert cfg.backend == "spatial_lockstep"  # auto-upgrade from lockstep
