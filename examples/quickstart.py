"""Quickstart: the MISO cell calculus in five minutes.

A MISO program is a set of *cells* — state + transition (paper §II).  You
write the program ONCE; `miso.compile()` retargets it to any execution
back-end without touching the source — the paper's central claim, surfaced
as a single API:

    exe = miso.compile(prog, backend="lockstep" | "host" | "wavefront"
                                      | "auto")
    states = exe.init(key)                 # replica axes included
    result = exe.run(states, n_steps)      # -> RunResult(states, reports)
    exe.metrics()                          # fault ledger / compare stats

This walkthrough compiles one tiny program four ways:

  1. backend="lockstep"  — the fused, jit-able production schedule,
  2. backend="auto"      — observes the dependency graph and (because this
     program has an independent cell) resolves to the barrier-free
     wavefront schedule (paper §III),
  3. backend="host" + DMR replication + an injected bit flip (paper §IV):
     the mismatch is detected, and the runtime's third tie-breaking
     execution repairs it,
  4. TMR on the lockstep back-end: corrected in-graph by majority vote.

Serving (continuous batching, per-request dependability, paged KV,
speculative decoding) has its own walkthrough:
examples/serve_walkthrough.py.

Run:  PYTHONPATH=src python examples/quickstart.py
      PYTHONPATH=src python examples/quickstart.py --backend lockstep_pallas
      PYTHONPATH=src python examples/quickstart.py --placement spatial

The --backend flag picks the lock-step flavor used below: "lockstep"
(XLA-fused) or "lockstep_pallas" (each replicated cell's compare/vote
fused into one Pallas kernel per step — the TPU fast path, interpret mode
elsewhere).  ``backend="auto"`` makes the same accelerator-based choice
(lockstep_pallas on TPU, lockstep on CPU/GPU) whenever the dependency
graph is a single unit; for THIS program auto resolves to the wavefront
schedule instead, because the lfsr cell is independent (section 3).

--placement spatial adds section 4b: the SAME program and the SAME policy
knob, but the replicas now live on distinct devices (one per "pod" mesh
axis member — the paper's "different processors and memories") and the
DMR compare becomes a 16-byte cross-pod fingerprint psum instead of an
O(state) exchange.  The example forces a 2-device CPU host platform so it
runs anywhere; on a real multi-pod mesh only the mesh line changes.
"""
import argparse
import os

args = argparse.ArgumentParser()
args.add_argument("--backend", default="lockstep",
                  choices=("lockstep", "lockstep_pallas"),
                  help="lock-step flavor (both are bitwise-identical)")
args.add_argument("--placement", default="temporal",
                  choices=("temporal", "spatial"),
                  help="replica placement for section 4: temporal (same "
                       "devices) or spatial (one replica per pod)")
_ns = args.parse_args()
BACKEND = _ns.backend
PLACEMENT = _ns.placement

print("""sections:
  1. cells + program     a heat rod (SIMD), a probe (MIMD), an
                         independent lfsr
  2. lockstep            one compile call, one in-graph scan
  3. backend="auto"      resolves to the barrier-free wavefront schedule
  4. DMR / TMR           an injected bit flip, detected and repaired
  4b. spatial placement  (--placement spatial) one replica per pod
  5. serving             -> examples/serve_walkthrough.py (continuous
                         batching, paged KV, speculative decoding)
""")
if PLACEMENT == "spatial":
    # spatial replicas need one device per pod; force a 2-device host
    # platform BEFORE jax initializes (real deployments have real pods).
    # Appended so a user's existing XLA_FLAGS survive.
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=2").strip()

import jax
import jax.numpy as jnp

from repro import api as miso
from repro.launch.mesh import make_mesh

# ---------------------------------------------------------------------------
# 1. A MISO program: a 1-D heat rod (SIMD stencil cell) + a probe cell (MIMD)
# ---------------------------------------------------------------------------
N = 64


def rod_init(key):
    t = jnp.zeros((N,), jnp.float32).at[N // 2].set(100.0)
    return {"t": t}


def rod_transition(prev):
    """Reads ONLY the previous state (paper §II read-prev/write-next)."""
    t = prev["rod"]["t"]
    left = jnp.roll(t, 1).at[0].set(t[0])
    right = jnp.roll(t, -1).at[-1].set(t[-1])
    return {"t": 0.25 * left + 0.5 * t + 0.25 * right}


def probe_init(key):
    return {"peak": jnp.float32(0), "mean": jnp.float32(0)}


def probe_transition(prev):
    # a *different* cell type (MIMD) reading the rod's previous state
    t = prev["rod"]["t"]
    return {"peak": jnp.max(t), "mean": jnp.mean(t)}


def standalone_init(key):
    return {"x": jnp.float32(1.0)}


def standalone_transition(prev):
    # no reads outside itself -> independent dependency component:
    # the wavefront back-end can run it ahead without a global barrier
    return {"x": prev["lfsr"]["x"] * 1.000001 + 0.5}


prog = miso.MisoProgram()
prog.add(miso.CellType("rod", rod_init, rod_transition, instances=N))
prog.add(miso.CellType("probe", probe_init, probe_transition,
                       reads=("rod",)))
prog.add(miso.CellType("lfsr", standalone_init, standalone_transition))
prog.validate()  # checks the §II single-output contract structurally

# ---------------------------------------------------------------------------
# 2. Lock-step execution: one compile call, one in-graph scan
# ---------------------------------------------------------------------------
exe = miso.compile(prog, backend=BACKEND)
states0 = exe.init(jax.random.PRNGKey(0))
final = exe.run(states0, 100, start_step=0).states
print(f"{BACKEND:<11}: after 100 steps  "
      f"peak={float(final['probe']['peak']):7.3f} "
      f"mean={float(final['probe']['mean']):6.3f} (heat diffused)")

# ---------------------------------------------------------------------------
# 3. backend="auto": the compiler observes the dependency graph.  The lfsr
#    cell is independent of rod/probe, so auto resolves to the wavefront
#    schedule (paper §III: no global barrier) — same program, same states.
# ---------------------------------------------------------------------------
wf = miso.compile(prog, backend="auto", window=4)
wfinal = wf.run(exe.init(jax.random.PRNGKey(0)), 100).states
same = jnp.allclose(wfinal["rod"]["t"], final["rod"]["t"])
m = wf.metrics()
print(f"auto       : resolved backend={m['backend']!r}, "
      f"identical result={bool(same)}, max unit lead={m['max_lead']} steps "
      "(>0 proves barrier-free overlap)")

# ---------------------------------------------------------------------------
# 4. Dependability (paper §IV): DMR + injected soft error.  The SAME program
#    compiles with a per-cell replication policy; the host back-end runs the
#    detect/tie-break recovery protocol in the loop.
# ---------------------------------------------------------------------------
dmr = miso.compile(prog, backend="host",
                   policies={"rod": miso.RedundancyPolicy(level=2)})
fault = miso.FaultSpec.at(step=50, cell_id=prog.cell_id("rod"),
                          replica=0, leaf=0, index=N // 2, bit=30)
dfinal = dmr.run(dmr.init(jax.random.PRNGKey(0)), 100, faults=[fault]).states
repaired = jnp.allclose(dfinal["rod"]["t"][0], final["rod"]["t"])
dm = dmr.metrics()
print(f"DMR        : bit flip at step 50 -> detected events="
      f"{dm['fault_totals']['rod']['events']:.0f}, "
      f"tie-break recoveries={len(dm['recoveries'])}, "
      f"final state repaired={bool(repaired)}")

# TMR corrects in-graph (majority vote), no host round-trip — so it runs on
# the fused lock-step back-end (with --backend lockstep_pallas the vote,
# per-replica counts, and state fingerprint are ONE Pallas kernel):
tmr = miso.compile(prog, backend=BACKEND,
                   policies={"rod": miso.RedundancyPolicy(level=3)})
tres = tmr.run(tmr.init(jax.random.PRNGKey(0)), 100, start_step=0,
               faults=fault)
ok = jnp.allclose(tres.states["rod"]["t"][0], final["rod"]["t"])
print(f"TMR        : corrected in-graph={bool(ok)} "
      f"(votes fixed {float(tres.reports['rod']['events']):.0f} strike)")

# ---------------------------------------------------------------------------
# 4b. (--placement spatial) The SAME policy knob, spatial placement: each
#     replica runs on its own pod (here: 2 forced host devices), and the
#     compare is a cross-pod collective — a 16-byte fingerprint psum
#     (compare="hash") instead of moving O(state) bytes.  backend="auto"
#     sees the placement request + a pod-axis mesh and resolves to the
#     spatial back-end; everything else (run/stream/faults/ledger) is the
#     inherited Executor protocol.
# ---------------------------------------------------------------------------
if PLACEMENT == "spatial":
    mesh = make_mesh((2,), ("pod",))
    sp = miso.compile(prog, backend="auto", mesh=mesh,
                      policies={"rod": miso.RedundancyPolicy(
                          level=2, placement="spatial", compare="hash")})
    sres = sp.run(sp.init(jax.random.PRNGKey(0)), 100, start_step=0,
                  faults=fault)
    sm = sp.metrics()
    srepaired = jnp.allclose(sres.states["rod"]["t"][0], final["rod"]["t"])
    print(f"spatial DMR: backend={sm['backend']!r} "
          f"pods={sm['n_pods']} compare=16-byte fingerprint psum; "
          f"strike detected at step {sp.ledger.recent['rod'][0]} "
          f"(repaired={bool(srepaired)}: DMR detects; repair is the "
          "host/serving tie-break)")
    # a whole fault campaign in ONE dispatch: the FaultSpecs stack and the
    # executor vmaps the injected sweep (Executor.run_campaign)
    rod = prog.cell_id("rod")
    campaign = [miso.FaultSpec.at(step=s, cell_id=rod, replica=s % 2,
                                  index=N // 2, bit=30)
                for s in (10, 40, 70)]
    camp = sp.run_campaign(sp.init(jax.random.PRNGKey(0)), 100, campaign,
                           start_step=0)
    ev = [float(e) for e in camp.reports["rod"]["events"]]
    print(f"campaign   : {len(campaign)} strikes, one vmap'd dispatch -> "
          f"per-strike detection events {ev}")

print("\nThe same program scales to the 512-chip mesh unchanged — see "
      "src/repro/launch/dryrun.py; new back-ends register with "
      "miso.register_backend without touching this file (the Pallas-fused "
      "lock-step plugged in exactly that way).")

print("\nNext: examples/serve_walkthrough.py — the same cells, served: "
      "continuous batching with per-request DMR/TMR, paged KV, and "
      "speculative decoding.")
