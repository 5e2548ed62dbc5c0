"""Benchmark harness — one bench per paper claim (the paper has no tables;
DESIGN.md §7 maps each of its four testable claims to a bench) plus the
roofline table from the dry-run artifacts.

  PYTHONPATH=src python -m benchmarks.run                 # all benches
  PYTHONPATH=src python -m benchmarks.run --only parallelization,fault
  PYTHONPATH=src python -m benchmarks.run --csv results/bench.csv

Output: one CSV row per measurement -> name,metric,value,derived
(wall-clock numbers are CPU-host measurements of the jitted programs; the
512-chip numbers live in the §Roofline table, which reads the dry-run
artifacts instead of timing).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS: list[tuple] = []

#: set by --smoke: tiny shapes/steps so the CI bench-smoke job finishes in
#: minutes while still exercising every code path (and all parity asserts).
SMOKE = False
#: set by --json-out: directory that receives the BENCH_*.json artifacts.
JSON_DIR = pathlib.Path(".")


def row(name: str, metric: str, value, derived: str = "") -> None:
    ROWS.append((name, metric, value, derived))
    print(f"{name},{metric},{value},{derived}", flush=True)


def timeit(fn, *args, n: int = 5, warmup: int = 2) -> float:
    """Median wall seconds of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


# ===========================================================================
# claim §III-a: SIMD data parallelism — many instances of one cell
# ===========================================================================
def bench_parallelization() -> None:
    """Paper §III: data parallelism = several instances of the same cell.
    The same MISO source runs (a) one instance at a time (the sequential
    semantics) and (b) vectorized across the instance axis (SIMD), which is
    how the mesh shards instances at scale."""
    from repro import api as miso

    N = 1 << 14
    SRC = """
    cell Blend {{
      var r:Float = 0;
      transition {{ r = .99 * r + .01 * other(this.pos).r; }}
    }}
    cell Static {{ var r:Float = 0; }}
    main  = new Blend({n})
    other = new Static({n})
    """
    rng = np.random.default_rng(0)
    prog = miso.compile_source(
        SRC.format(n=N), inputs={"other": {"r": rng.normal(size=N) * 100}})
    exe = miso.compile(prog, donate=False)
    states = exe.init(jax.random.PRNGKey(0))

    steps = 50
    vec = lambda st: exe.run(st, steps, start_step=0).states
    t_vec = timeit(vec, states)

    # sequential semantics: one instance per dispatch — the same source
    # compiled at width 1, which is the baseline the SIMD claim is against.
    prog1 = miso.compile_source(
        SRC.format(n=1), inputs={"other": {"r": rng.normal(size=1) * 100}})
    exe1 = miso.compile(prog1, donate=False)
    st1 = exe1.init(jax.random.PRNGKey(0))
    one = lambda st: exe1.run(st, steps, start_step=0).states
    t_one = timeit(one, st1)  # per-instance cost
    seq_est = t_one * N
    row("parallelization", "simd_instances", N)
    row("parallelization", "vectorized_s", round(t_vec, 4))
    row("parallelization", "sequential_est_s", round(seq_est, 2),
        "per-instance dispatch x N")
    row("parallelization", "simd_speedup_x", round(seq_est / t_vec, 1),
        "SIMD claim: instances vectorize")


# ===========================================================================
# claim §III-b: MIMD / no global barrier for independent cells
# ===========================================================================
def bench_mimd_wavefront() -> None:
    """Paper §III: cells without direct or indirect dependency need no
    global per-transition barrier.  A program with two independent chains
    (fast stencil / slow stencil) runs lock-step vs wavefront; the wavefront
    trace proves units proceed out of lock-step (max lead > 0) with
    identical final states."""
    from repro import api as miso
    from repro.core import CellType, MisoProgram

    def stencil_cell(name: str, n: int, work: int):
        def init(key):
            return {"t": jnp.linspace(0, 1, n, dtype=jnp.float32)}

        def transition(prev):
            t = prev[name]["t"]
            for _ in range(work):  # heavier transition = slower unit
                t = 0.25 * jnp.roll(t, 1) + 0.5 * t + 0.25 * jnp.roll(t, -1)
            return {"t": t}

        return CellType(name, init, transition, instances=n)

    prog = MisoProgram()
    prog.add(stencil_cell("fast", 1 << 10, work=1))
    prog.add(stencil_cell("slow", 1 << 10, work=16))

    steps = 32
    lock = miso.compile(prog, backend="lockstep", donate=False)
    states = lock.init(jax.random.PRNGKey(0))
    t_lock = timeit(lambda: lock.run(states, steps, start_step=0).states)
    # two independent chains -> "auto" observes the parallel nature of the
    # program and resolves to the wavefront back-end
    wf = miso.compile(prog, backend="auto", window=8)
    t0 = time.perf_counter()
    wf_final = jax.block_until_ready(wf.run(states, steps).states)
    t_wf = time.perf_counter() - t0
    lock_final = lock.run(states, steps, start_step=0).states
    same = all(
        bool(jnp.allclose(a, b))
        for a, b in zip(jax.tree.leaves(wf_final), jax.tree.leaves(lock_final))
    )
    m = wf.metrics()
    row("mimd_wavefront", "auto_backend", m["backend"],
        "compile(backend='auto') resolved")
    row("mimd_wavefront", "lockstep_s", round(t_lock, 4))
    row("mimd_wavefront", "wavefront_s", round(t_wf, 4),
        "same semantics, no global barrier")
    row("mimd_wavefront", "identical_result", same)
    row("mimd_wavefront", "max_unit_lead_steps", m["max_lead"],
        ">0 proves barrier-free overlap")
    row("mimd_wavefront", "dependency_units", m["units"])


# ===========================================================================
# claim §IV-a: replication overhead (DMR/TMR, temporal)
# ===========================================================================
def _small_train(redundancy, compare="bitwise", compare_every=1):
    import dataclasses as dc

    from repro import api as miso
    from repro.configs import get_reduced
    from repro.core import RedundancyPolicy
    from repro.data.pipeline import DataConfig
    from repro.models.lm_cells import TrainConfig, make_train_program
    from repro.optim.adamw import OptConfig

    cfg = get_reduced("internlm2-1.8b")
    cfg = dc.replace(cfg, d_model=128, n_layers=2, d_ff=384,
                     n_heads=2, n_kv_heads=1)
    tcfg = TrainConfig(
        data=DataConfig(batch=8, seq_len=128, vocab=cfg.vocab_size),
        opt=OptConfig(peak_lr=1e-3, warmup_steps=10, decay_steps=100),
    )
    pol = (RedundancyPolicy(level=redundancy, compare=compare,
                            compare_every=compare_every)
           if redundancy > 1 else RedundancyPolicy())
    prog = make_train_program(cfg, tcfg)
    exe = miso.compile(prog, policies={"trainer": pol},
                       compare_every=compare_every, donate=False)
    states = exe.init(jax.random.PRNGKey(0))
    steps = 4 * compare_every

    run = lambda st: exe.run(st, steps, start_step=0).states
    return run, states, steps


def bench_redundancy_overhead() -> None:
    """Paper §IV: state duplication + transition on both replicas.  Measures
    the per-step cost of redundancy level 1/2/3 on a real train step, plus
    the beyond-paper amortizations (hash compare, compare-every-k)."""
    base = None
    for level, label in ((1, "none"), (2, "dmr"), (3, "tmr")):
        run, states, steps = _small_train(level)
        t = timeit(run, states, n=3, warmup=1) / steps
        if level == 1:
            base = t
        row("redundancy_overhead", f"{label}_step_ms", round(t * 1e3, 2),
            f"overhead x{t / base:.2f} (theory x{level}.0)")
    for compare, k, label in (("hash", 1, "dmr_hash"),
                              ("bitwise", 4, "dmr_k4")):
        run, states, steps = _small_train(2, compare=compare,
                                          compare_every=k)
        t = timeit(run, states, n=3, warmup=1) / steps
        row("redundancy_overhead", f"{label}_step_ms", round(t * 1e3, 2),
            f"overhead x{t / base:.2f} (beyond-paper)")


# ===========================================================================
# claim §IV-b: fault detection / correction coverage
# ===========================================================================
def bench_fault_coverage() -> None:
    """Paper §IV: mismatch -> detected; third execution -> corrected.
    A campaign of random single-bit strikes against a DMR/TMR cell; reports
    detection and correction rates (should be 1.0) and the false-positive
    rate on a clean run (should be 0.0)."""
    from repro import api as miso
    from repro.core import (
        CellType, FaultSpec, MisoProgram, RedundancyPolicy,
    )

    N = 256

    def init(key):
        return {"x": jax.random.normal(key, (N,), jnp.float32)}

    def transition(prev):
        x = prev["c"]["x"]
        return {"x": 0.5 * x + jnp.tanh(jnp.roll(x, 1))}

    steps, n_faults = 24, 40
    rng = np.random.default_rng(1)

    # --- clean (unreplicated) reference trajectory --------------------------
    plain = MisoProgram().add(CellType("c", init, transition))
    clean_exe = miso.compile(plain, backend="host")
    clean = clean_exe.run(clean_exe.init(jax.random.PRNGKey(7)), steps).states

    # --- DMR: detect + tie-break correct -----------------------------------
    prog = MisoProgram().add(
        CellType("c", init, transition,
                 redundancy=RedundancyPolicy(level=2)))
    detected = corrected = 0
    for _ in range(n_faults):
        f = FaultSpec.at(step=int(rng.integers(steps)), cell_id=0,
                         replica=int(rng.integers(2)),
                         index=int(rng.integers(N)),
                         bit=int(rng.integers(32)))
        r = miso.compile(prog, backend="host")
        out = r.run(r.init(jax.random.PRNGKey(7)), steps, faults=[f]).states
        totals = r.metrics()["fault_totals"]
        detected += totals.get("c", {"events": 0})["events"] > 0
        corrected += bool(jnp.array_equal(out["c"]["x"][0], clean["c"]["x"]))
    row("fault_coverage", "dmr_detection_rate", detected / n_faults,
        f"{n_faults} random single-bit strikes")
    row("fault_coverage", "dmr_correction_rate", corrected / n_faults,
        "third-execution tie-break (paper §IV)")

    # --- TMR: in-graph majority vote ---------------------------------------
    prog3 = MisoProgram().add(
        CellType("c", init, transition,
                 redundancy=RedundancyPolicy(level=3)))
    exe3 = miso.compile(prog3, donate=False)
    st3 = exe3.init(jax.random.PRNGKey(7))
    voted = 0
    for _ in range(n_faults):
        f = FaultSpec.at(step=int(rng.integers(steps)), cell_id=0,
                         replica=int(rng.integers(3)),
                         index=int(rng.integers(N)),
                         bit=int(rng.integers(32)))
        res = exe3.run(st3, steps, start_step=0, faults=f)
        ok = bool(jnp.array_equal(res.states["c"]["x"][0], clean["c"]["x"]))
        voted += ok and float(res.reports["c"]["events"]) > 0
    row("fault_coverage", "tmr_vote_correction_rate", voted / n_faults,
        "in-graph majority vote")

    # --- false positives on a clean run -------------------------------------
    r = miso.compile(prog, backend="host")
    r.run(r.init(jax.random.PRNGKey(7)), steps)
    row("fault_coverage", "false_positive_rate",
        r.metrics()["fault_totals"].get("c", {"events": 0})["events"] / steps,
        "replicas of a pure transition are bit-identical")


# ===========================================================================
# claim §IV-c: selective replication (runtime-chosen, per cell)
# ===========================================================================
def bench_selective() -> None:
    """Paper §IV: 'Selective replication of key cells may also be applied by
    the runtime, in order to balance the fault tolerance and the overhead.'
    Same two-cell train program, four runtime policies, no code change."""
    from repro import api as miso
    from repro.core import RedundancyPolicy
    from repro.models.lm_cells import TrainConfig, make_train_program
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import OptConfig
    from repro.configs import get_reduced
    import dataclasses as dc

    cfg = get_reduced("internlm2-1.8b")
    cfg = dc.replace(cfg, d_model=128, n_layers=2, d_ff=384,
                     n_heads=2, n_kv_heads=1)
    tcfg = TrainConfig(
        data=DataConfig(batch=8, seq_len=128, vocab=cfg.vocab_size),
        opt=OptConfig())
    policies = {
        "none": {},
        "trainer_only": {"trainer": RedundancyPolicy(level=2)},
        "data_only": {"data": RedundancyPolicy(level=2)},
        "all_cells": {"trainer": RedundancyPolicy(level=2),
                      "data": RedundancyPolicy(level=2)},
    }
    base = None
    for label, pol in policies.items():
        exe = miso.compile(make_train_program(cfg, tcfg), policies=pol,
                           donate=False)
        states = exe.init(jax.random.PRNGKey(0))
        fn = lambda s, e=exe: e.run(s, 4, start_step=0).states
        t = timeit(fn, states, n=3, warmup=1) / 4
        if base is None:
            base = t
        row("selective", f"{label}_step_ms", round(t * 1e3, 2),
            f"overhead x{t / base:.2f}")


# ===========================================================================
# kernels: Pallas (interpret mode) vs pure-jnp oracle timing + allclose
# ===========================================================================
def bench_kernels() -> None:
    """Per-kernel correctness (vs ref.py oracle) at a benchmark shape.
    Pallas runs in interpret mode on CPU — correctness evidence, not TPU
    timing; TPU-shape tiling lives in the kernel BlockSpecs."""
    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    B, H, S, D = 2, 4, 512, 64
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.float32) * 0.1
               for kk in jax.random.split(key, 3))
    out_p = ops.attention(q, k, v, causal=True, pallas=True, interpret=True)
    out_r = ops.attention(q, k, v, causal=True, pallas=False)
    err = float(jnp.max(jnp.abs(out_p - out_r)))
    row("kernels", "flash_attn_max_err", f"{err:.2e}",
        f"shape {(B, H, S, D)} pallas(interpret) vs oracle")

    rep = {"w": jax.random.normal(key, (3, 1 << 12), jnp.float32),
           "b": jax.random.normal(key, (3, 64), jnp.float32)}
    voted_p, counts_p = ops.tmr_vote_pytree(rep, pallas=True, interpret=True)
    voted_r, counts_r = ops.tmr_vote_pytree(rep, pallas=False)
    row("kernels", "tmr_vote_exact",
        bool(all(jnp.array_equal(a, b) for a, b in
                 zip(jax.tree.leaves(voted_p), jax.tree.leaves(voted_r)))))

    x = {"s": jax.random.normal(key, (1 << 12,), jnp.float32)}
    row("kernels", "state_hash_exact",
        bool(jnp.array_equal(
            ops.fingerprint_fused(x, pallas=True, interpret=True),
            ops.fingerprint_fused(x, pallas=False))))


# ===========================================================================
# lockstep vs lockstep_pallas: fused-kernel back-end perf + parity
# ===========================================================================
def bench_lockstep_pallas() -> None:
    """Per-step wall time of the Pallas-fused lock-step back-end vs the XLA
    ``lockstep`` at DMR and TMR across state sizes, with bitwise parity
    asserted on every case (states AND fault reports, fault injected) — the
    CI bench-smoke job fails on any divergence.  Emits BENCH_lockstep.json,
    the perf-trajectory artifact the ROADMAP asks for.

    On CPU the kernels run in interpret mode: the timing documents the
    interpret-mode overhead (TPU timings come from running the same bench
    on a TPU host, where the fused path is the fast one).
    """
    from repro import api as miso
    from repro.core import CellType, FaultSpec, MisoProgram, RedundancyPolicy
    from repro.kernels.ops import on_tpu

    sizes = ((1 << 10, 1 << 12) if SMOKE
             else (1 << 12, 1 << 14, 1 << 16))
    steps = 4 if SMOKE else 16
    reps = 2 if SMOKE else 5
    cases = []
    for n in sizes:
        def init(key, n=n):
            return {"x": jax.random.normal(key, (n,), jnp.float32)}

        def transition(prev):
            x = prev["c"]["x"]
            return {"x": 0.5 * x + 0.25 * jnp.roll(x, 1)}

        for level, mode in ((2, "dmr"), (3, "tmr")):
            prog = MisoProgram().add(CellType(
                "c", init, transition,
                redundancy=RedundancyPolicy(level=level)))
            fault = FaultSpec.at(step=1, cell_id=0, replica=level - 1,
                                 index=n // 2, bit=20)
            times, finals, reports = {}, {}, {}
            for backend in ("lockstep", "lockstep_pallas"):
                exe = miso.compile(prog, backend=backend, donate=False)
                s0 = exe.init(jax.random.PRNGKey(0))
                t = timeit(
                    lambda exe=exe, s0=s0:
                        exe.run(s0, steps, start_step=0).states,
                    n=reps, warmup=1) / steps
                times[backend] = t
                res = exe.run(s0, steps, start_step=0, faults=fault)
                finals[backend] = res.states
                reports[backend] = res.reports
            # parity gate: bitwise-identical states and fault reports
            for la, lb in zip(jax.tree.leaves(finals["lockstep"]),
                              jax.tree.leaves(finals["lockstep_pallas"])):
                assert np.array_equal(np.asarray(la), np.asarray(lb)), (
                    f"state parity broke at {mode} n={n}")
            for la, lb in zip(jax.tree.leaves(reports["lockstep"]),
                              jax.tree.leaves(reports["lockstep_pallas"])):
                assert np.array_equal(np.asarray(la), np.asarray(lb)), (
                    f"report parity broke at {mode} n={n}")
            assert float(
                reports["lockstep_pallas"]["c"]["events"]) >= 1.0, (
                f"injected fault went undetected at {mode} n={n}")
            t_ls = times["lockstep"] * 1e3
            t_lp = times["lockstep_pallas"] * 1e3
            row("lockstep_pallas", f"{mode}_n{n}_lockstep_step_ms",
                round(t_ls, 3))
            row("lockstep_pallas", f"{mode}_n{n}_pallas_step_ms",
                round(t_lp, 3),
                f"x{t_ls / t_lp:.2f} vs lockstep; parity ok")
            cases.append({
                "mode": mode, "state_words": n, "steps": steps,
                "lockstep_step_ms": round(t_ls, 4),
                "lockstep_pallas_step_ms": round(t_lp, 4),
                "speedup_x": round(t_ls / t_lp, 3),
                "parity": True,
            })
    payload = {
        "bench": "lockstep_pallas",
        "jax": jax.__version__,
        "device": jax.default_backend(),
        "interpret": not on_tpu(),
        "smoke": SMOKE,
        "cases": cases,
    }
    JSON_DIR.mkdir(parents=True, exist_ok=True)
    out = JSON_DIR / "BENCH_lockstep.json"
    out.write_text(json.dumps(payload, indent=2) + "\n")
    row("lockstep_pallas", "json_artifact", str(out),
        f"{len(cases)} cases, all parity-gated")


# ===========================================================================
# spatial-DMR: fingerprint vs bitwise cross-pod compare (traffic + time)
# ===========================================================================
_SPATIAL_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from repro import api as miso
from repro.kernels import ops
from repro.launch.mesh import make_mesh

SIZES = %(sizes)r
STEPS = %(steps)d
REPS = %(reps)d

def timeit(fn, *args):
    for _ in range(1):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))

def mesh_for(level):
    if level == 2:
        return make_mesh((2, 2, 2), ("pod", "data", "model"))
    return Mesh(np.array(jax.devices()[:6]).reshape(3, 2, 1),
                ("pod", "data", "model"))

cases = []
for n in SIZES:
    def init(key, n=n):
        return {"x": jax.random.normal(key, (n,), jnp.float32)}

    def transition(prev):
        x = prev["c"]["x"]
        return {"x": 0.5 * x + 0.25 * jnp.roll(x, 1)}

    words = ops.word_layout(jax.eval_shape(
        init, jax.ShapeDtypeStruct((2,), jnp.uint32))).total
    for level, mode in ((2, "dmr"), (3, "tmr")):
        for compare in ("bitwise", "hash"):
            prog = miso.MisoProgram().add(miso.CellType(
                "c", init, transition,
                redundancy=miso.RedundancyPolicy(
                    level=level, compare=compare, placement="spatial")))
            exe = miso.compile(prog, backend="spatial_lockstep",
                               mesh=mesh_for(level), donate=False)
            s0 = exe.init(jax.random.PRNGKey(0))
            t = timeit(lambda: exe.run(s0, STEPS, start_step=0).states)
            # parity gate: bitwise-identical to the temporal reference
            ref = miso.compile(prog, backend="lockstep", donate=False)
            fault = miso.FaultSpec.at(step=1, cell_id=0, replica=level - 1,
                                      index=n // 2, bit=20)
            rs = exe.run(s0, STEPS, start_step=0, faults=fault)
            rr = ref.run(ref.init(jax.random.PRNGKey(0)), STEPS,
                         start_step=0, faults=fault)
            for la, lb in zip(jax.tree.leaves(rs.states),
                              jax.tree.leaves(rr.states)):
                assert np.array_equal(np.asarray(la), np.asarray(lb)), \
                    (mode, compare, n)
            assert float(rs.reports["c"]["events"]) >= 1.0, (mode, compare)
            # steady-state cross-pod receive bytes per pod per compare step
            if compare == "hash":
                wire = 16 if level == 2 else 16 * level
            else:
                wire = words * 4 * (level - 1)
            cases.append({
                "mode": mode, "compare": compare, "state_words": words,
                "step_ms": round(t / STEPS * 1e3, 4),
                "wire_bytes_per_compare": wire,
                "parity": True, "n": n,
            })
print("RESULT" + json.dumps({"cases": cases, "jax": jax.__version__}))
"""


def bench_spatial() -> None:
    """Cross-pod spatial-DMR compare cost: the 128-bit fingerprint psum
    (O(1) wire bytes) vs the paper-faithful full-bitwise exchange
    (O(state)), at DMR and TMR, on a forced-8-device CPU host mesh with
    the explicit 3-axis (pod, data, model) layout.  jax pins the device
    count at first init, so the measurement runs in a subprocess; every
    case is parity-gated against temporal lockstep (bitwise states +
    detected strike).  Emits BENCH_spatial.json — wall time documents the
    CPU-host trajectory, wire bytes the collective term a TPU deployment
    pays on ICI.
    """
    import os
    import subprocess
    import sys

    sizes = (1 << 10, 1 << 12) if SMOKE else (1 << 12, 1 << 14, 1 << 16)
    child = _SPATIAL_CHILD % {
        "sizes": tuple(sizes),
        "steps": 4 if SMOKE else 16,
        "reps": 2 if SMOKE else 5,
    }
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=2400)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    payload = json.loads(line[len("RESULT"):])
    for c in payload["cases"]:
        key = f"{c['mode']}_{c['compare']}_n{c['n']}"
        row("spatial", f"{key}_step_ms", c["step_ms"], "parity ok")
        row("spatial", f"{key}_wire_B_per_compare",
            c["wire_bytes_per_compare"],
            "cross-pod receive bytes/pod (fingerprint vs bitwise)")
    # headline: wire reduction of the fingerprint compare at the largest n
    big = [c for c in payload["cases"] if c["n"] == max(sizes)]
    bw = {(c["mode"], c["compare"]): c["wire_bytes_per_compare"]
          for c in big}
    for mode in ("dmr", "tmr"):
        row("spatial", f"{mode}_fingerprint_wire_reduction_x",
            round(bw[(mode, "bitwise")] / bw[(mode, "hash")], 1),
            "O(state) -> O(1) cross-pod compare traffic")
    payload.update({"bench": "spatial", "smoke": SMOKE,
                    "device": "cpu-host-8dev"})
    JSON_DIR.mkdir(parents=True, exist_ok=True)
    out = JSON_DIR / "BENCH_spatial.json"
    out.write_text(json.dumps(payload, indent=2) + "\n")
    row("spatial", "json_artifact", str(out),
        f"{len(payload['cases'])} cases, all parity-gated")


# ===========================================================================
# serving (spatial placement): replica slots on mesh pods, parity-gated
# ===========================================================================
_SPATIAL_SERVE_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import dataclasses as dc
import json
import numpy as np
import jax

from repro import api as miso
from repro.configs import get_reduced
from repro.launch.mesh import make_mesh
from repro.models.lm_cells import ServeConfig
from repro.serving import Request
from repro.serving.lm import lm_engine_parts
from repro.serving.spatial import detect_wire_bytes

SLOTS = 8
PODS = 4
DECODE = %(decode)d
LEVELS = (1, 2, 3, 1)

cfg = get_reduced("internlm2-1.8b")
cfg = dc.replace(cfg, d_model=32, n_layers=2, d_ff=64, n_heads=2,
                 n_kv_heads=1, vocab_size=128)

def drive(placement):
    mesh = (make_mesh((PODS, 8 // PODS), ("pod", "data"))
            if placement == "spatial" else None)
    scfg = ServeConfig(batch=SLOTS, max_len=32, placement=placement)
    prog, adapter = lm_engine_parts(cfg, scfg)
    eng = miso.serve(prog, adapter,
                     miso.EngineConfig(placement=placement, mesh=mesh))
    eng.start(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    mk = lambda n: rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
    warm = Request(prompt=mk(4), max_new_tokens=2)
    eng.submit(warm)
    eng.pump()                      # warm: compile prefill + step + detect
    busy0 = eng.metrics()["busy_s"]
    reqs = []
    for lv in LEVELS:
        pol = miso.RedundancyPolicy(
            level=lv,
            placement="spatial" if (placement == "spatial" and lv > 1)
            else "temporal")
        reqs.append(Request(prompt=mk(4), max_new_tokens=DECODE, policy=pol))
    for r in reqs:
        eng.submit(r)
    eng.pump()
    toks = [eng.result(r.id)["tokens"] for r in reqs]
    assert all(eng.result(r.id)["status"] == "done" for r in reqs)
    tps = len(reqs) * DECODE / (eng.metrics()["busy_s"] - busy0)
    return toks, tps

t_toks, t_tps = drive("temporal")
s_toks, s_tps = drive("spatial")
assert s_toks == t_toks, "spatial/temporal token divergence"
spp = SLOTS // PODS
print("RESULT" + json.dumps({
    "pods": PODS, "slots": SLOTS, "slots_per_pod": spp,
    "levels": list(LEVELS),
    "temporal_tokens_per_s": round(t_tps, 2),
    "spatial_tokens_per_s": round(s_tps, 2),
    "wire_bytes_per_tick_dmr": detect_wire_bytes(PODS, spp, False),
    "wire_bytes_per_tick_tmr": detect_wire_bytes(PODS, spp, True),
    "token_parity": True,
}))
"""


# ===========================================================================
# serving: continuous batcher under Poisson arrivals (tokens/s + TTFT SLO)
# ===========================================================================
def bench_serving() -> None:
    """Steady-state tokens/s and TTFT p50/p99 of the continuous-batching
    engine (miso.serve) under Poisson request arrivals at 2-3 load
    levels (offered load as a fraction of measured saturated capacity).
    Emits BENCH_serving.json; the CI bench-smoke job runs the smoke
    variant so the serving path is timed on every PR.

    CPU-host numbers document the trajectory, not TPU throughput; the
    interesting curves are the *ratios* (TTFT inflation as offered load
    approaches capacity)."""
    import dataclasses as dc

    from repro import api as miso
    from repro.configs import get_reduced
    from repro.models.lm_cells import ServeConfig
    from repro.serving import Request
    from repro.serving.lm import lm_engine_parts

    cfg = get_reduced("internlm2-1.8b")
    cfg = dc.replace(cfg, d_model=32 if SMOKE else 64, n_layers=2,
                     d_ff=64 if SMOKE else 128, n_heads=2, n_kv_heads=1,
                     vocab_size=128)
    slots = 4 if SMOKE else 8
    decode = 4 if SMOKE else 8
    n_req = 6 if SMOKE else 24
    plen = 4
    loads = (0.5, 1.5) if SMOKE else (0.5, 1.0, 1.5)
    scfg = ServeConfig(batch=slots, max_len=32)
    rng = np.random.default_rng(0)

    def new_engine():
        prog, adapter = lm_engine_parts(cfg, scfg)
        eng = miso.serve(prog, adapter, miso.EngineConfig())
        eng.start(jax.random.PRNGKey(0))
        return eng

    def mk_request():
        return Request(
            prompt=rng.integers(0, cfg.vocab_size, size=plen)
            .astype(np.int32),
            max_new_tokens=decode)

    # -- saturated capacity: keep every slot busy, measure tokens/s --------
    # throughput is tokens over BUSY time (the engine's tick-loop
    # occupancy), not wall time: host-side submit gaps between pumps
    # would otherwise deflate the measured capacity the load levels
    # below are scaled against
    eng = new_engine()
    for _ in range(slots):
        eng.submit(mk_request())
    eng.pump()                          # warmup: compile prefill + step
    busy0 = eng.metrics()["busy_s"]
    for _ in range(slots * 2):
        eng.submit(mk_request())
    eng.pump()
    cap_tps = (slots * 2 * decode) / (eng.metrics()["busy_s"] - busy0)
    row("serving", "slots", slots)
    row("serving", "saturated_tokens_per_s", round(cap_tps, 1),
        "all slots busy, steady state, busy-time based")

    cases = []
    for load in loads:
        lam = load * cap_tps / decode   # requests/s offered
        arrivals = np.cumsum(rng.exponential(1.0 / lam, size=n_req))
        eng = new_engine()
        eng.submit(mk_request())
        eng.pump()                      # warm: compile prefill + step
        t0 = time.perf_counter()
        i = 0
        reqs = []
        while i < n_req or eng.has_work():
            now = time.perf_counter() - t0
            while i < n_req and arrivals[i] <= now:
                r = mk_request()
                reqs.append(r)
                eng.submit(r)
                i += 1
            if eng.has_work():
                eng.pump(max_ticks=1)
            elif i < n_req:
                time.sleep(min(arrivals[i] - now, 0.01))
        wall = time.perf_counter() - t0
        ttfts = sorted(eng.requests[r.id].ttft for r in reqs)
        done = sum(1 for r in reqs
                   if eng.result(r.id)["status"] == "done")
        case = {
            "offered_load_x": load,
            "requests": n_req,
            "done": done,
            "tokens_per_s": round(n_req * decode / wall, 2),
            "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
            "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4),
        }
        cases.append(case)
        row("serving", f"load{load}_tokens_per_s", case["tokens_per_s"])
        row("serving", f"load{load}_ttft_p50_s", case["ttft_p50_s"],
            f"p99={case['ttft_p99_s']}s, {done}/{n_req} done")
        assert done == n_req, f"requests lost at load {load}"

    # -- mixed-length load through chunked + bucketed prefill --------------
    # short and long prompts interleaved; jit_prefill compiles once per
    # LADDER BUCKET (not per distinct length) and long admissions walk
    # their tail inside the resident transition, so short requests' TTFT
    # stays flat.  prefill_compiles <= ladder size is the tracked bound.
    scfg_mix = ServeConfig(batch=slots, max_len=64,
                           prefill_chunk=8, prefill_bucket_min=8)
    prog, adapter = lm_engine_parts(cfg, scfg_mix)
    eng = miso.serve(prog, adapter, miso.EngineConfig())
    eng.start(jax.random.PRNGKey(0))
    n_mix = 12 if SMOKE else 50
    mix_lens = [2, 5, 9, 17, 23, 33]
    reqs = []
    t0 = time.perf_counter()
    for i in range(n_mix):
        r = Request(
            prompt=rng.integers(0, cfg.vocab_size, size=mix_lens[
                i % len(mix_lens)]).astype(np.int32),
            max_new_tokens=decode)
        reqs.append(r)
        eng.submit(r)
        if i % 3 == 2:
            eng.pump(max_ticks=1)   # arrivals interleave with decode
    eng.pump()
    wall = time.perf_counter() - t0
    m = eng.metrics()
    done = sum(1 for r in reqs if eng.result(r.id)["status"] == "done")
    assert done == n_mix, "requests lost in mixed-length run"
    assert m["prefill_compiles"] <= len(m["prefill_buckets"]), (
        m["prefill_compiles"], m["prefill_buckets"])
    mixed = {
        "case": "mixed_length_chunked",
        "requests": n_mix,
        "prompt_lens": mix_lens,
        "prefill_chunk": m["prefill_chunk"],
        "prefill_buckets": m["prefill_buckets"],
        "prefill_compiles": m["prefill_compiles"],
        "tokens_per_s": round(m["tokens_out"] / wall, 2),
        "ttft_p50_s": round(m["ttft_p50_s"], 4),
        "ttft_p99_s": round(m["ttft_p99_s"], 4),
    }
    row("serving", "mixed_prefill_compiles", mixed["prefill_compiles"],
        f"<= {len(mixed['prefill_buckets'])} buckets over {n_mix} "
        f"mixed-length requests (chunk={mixed['prefill_chunk']})")
    row("serving", "mixed_ttft_p50_s", mixed["ttft_p50_s"],
        f"p99={mixed['ttft_p99_s']}s")
    # -- fixed cache-byte budget: paged vs dense residency ------------------
    # same KV pool bytes both sides (dense: 4 slots x 32 tokens; paged: 16
    # pages x 8 tokens shared by up to 16 slots).  Short requests reserve
    # one page each, so the paged engine keeps 4x the resident requests in
    # the same bytes — and must emit bitwise-identical tokens per request.
    budget_reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, size=4)
                .astype(np.int32), max_new_tokens=4)
        for _ in range(16)
    ]

    def run_budget(scfg_b):
        prog_b, adapter_b = lm_engine_parts(cfg, scfg_b)
        eng_b = miso.serve(prog_b, adapter_b, miso.EngineConfig())
        eng_b.start(jax.random.PRNGKey(0))
        clones = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
                  for r in budget_reqs]
        eng_b.submit(clones[0])
        eng_b.pump()                    # warm: compile prefill + step
        warm = eng_b.result(clones[0].id)["tokens"]
        for r in clones[1:]:
            eng_b.submit(r)
        peak = 0
        t0 = time.perf_counter()
        while eng_b.has_work():
            eng_b.pump(max_ticks=1)
            peak = max(peak, eng_b.metrics()["active_requests"])
        wall = time.perf_counter() - t0
        toks = [warm] + [eng_b.result(r.id)["tokens"] for r in clones[1:]]
        assert all(eng_b.result(r.id)["status"] == "done" for r in clones)
        return peak, round(15 * 4 / wall, 2), toks

    dense_peak, dense_tps, dense_toks = run_budget(
        ServeConfig(batch=4, max_len=32))
    paged_peak, paged_tps, paged_toks = run_budget(
        ServeConfig(batch=16, max_len=32, paged=True, page_size=8,
                    page_budget=16))
    assert paged_toks == dense_toks, "paged/dense token divergence"
    assert paged_peak >= 2 * dense_peak, (paged_peak, dense_peak)
    budget = {
        "case": "fixed_cache_byte_budget",
        "budget_token_slots": 128,
        "dense": {"batch": 4, "max_len": 32,
                  "peak_resident": dense_peak, "tokens_per_s": dense_tps},
        "paged": {"batch": 16, "max_len": 32, "page_size": 8,
                  "page_budget": 16,
                  "peak_resident": paged_peak, "tokens_per_s": paged_tps},
        "token_parity": True,
    }
    row("serving", "budget_peak_resident",
        f"{paged_peak}x paged vs {dense_peak}x dense",
        "same cache bytes (128 token-slots), bitwise-equal tokens")
    row("serving", "budget_tokens_per_s",
        f"paged {paged_tps} / dense {dense_tps}")

    # -- speculative decoding: accepted-prefix commits vs one-token ticks --
    # self-speculation (draft == target, bit for bit) accepts every
    # proposal, so each verify tick commits draft_len+1 tokens where the
    # plain engine commits one.  The per-tick cost (dispatch, host
    # bookkeeping, fingerprints) is paid once per COMMIT WINDOW instead
    # of once per token — this case measures that amortization on a
    # dispatch-dominated model, targeting >2x tokens/s; the tokens must
    # stay bitwise equal either way (the parity gate of docs/serving.md).
    from repro.models.lm_cells import SpecConfig

    cfg_spec = dc.replace(cfg, d_model=16, n_layers=1, d_ff=32)
    spec_k = 8
    spec_decode = 17 if SMOKE else 33
    spec_prompts = [rng.integers(0, cfg_spec.vocab_size, size=plen)
                    .astype(np.int32) for _ in range(slots)]

    def run_spec(scfg_s, ask):
        prog_s, adapter_s = lm_engine_parts(cfg_spec, scfg_s)
        eng_s = miso.serve(prog_s, adapter_s, miso.EngineConfig())
        eng_s.start(jax.random.PRNGKey(0))
        warm = Request(prompt=spec_prompts[0], max_new_tokens=2, spec=ask)
        eng_s.submit(warm)
        eng_s.pump()                    # warm: compile prefill + tick
        clones = [Request(prompt=p, max_new_tokens=spec_decode, spec=ask)
                  for p in spec_prompts]
        t0 = time.perf_counter()
        for r in clones:
            eng_s.submit(r)
        eng_s.pump()
        wall = time.perf_counter() - t0
        toks = [eng_s.result(r.id)["tokens"] for r in clones]
        assert all(eng_s.result(r.id)["status"] == "done" for r in clones)
        return round(slots * spec_decode / wall, 2), toks, eng_s.metrics()

    scfg_spec = ServeConfig(batch=slots, max_len=64)
    ref_tps, ref_toks, _ = run_spec(scfg_spec, None)
    spec_tps, spec_toks, m_spec = run_spec(
        dc.replace(scfg_spec, spec=SpecConfig(draft_len=spec_k)),
        SpecConfig(draft_len=spec_k))
    assert spec_toks == ref_toks, "speculative/greedy token divergence"
    speedup = round(spec_tps / ref_tps, 2)
    # hard regression gate (loose: CI machines vary in dispatch/compute
    # ratio); the tracked target is the recorded speedup_x staying >2
    assert speedup > 1.3, f"speculation stopped paying off: {speedup}x"
    speculation = {
        "case": "speculative_decoding",
        "draft": "self",
        "draft_len": spec_k,
        "requests": slots,
        "decode_tokens": spec_decode,
        "ref_tokens_per_s": ref_tps,
        "spec_tokens_per_s": spec_tps,
        "speedup_x": speedup,
        "spec_tokens_per_tick": m_spec["spec_tokens_per_tick"],
        "token_parity": True,
    }
    row("serving", "spec_tokens_per_s",
        f"{spec_tps} vs {ref_tps} plain ({speedup}x)",
        f"self-draft k={spec_k}, bitwise-equal tokens")
    row("serving", "spec_tokens_per_tick", m_spec["spec_tokens_per_tick"],
        f"ceiling {spec_k + 1}")

    # -- tracing overhead: the "observability is free" claim, measured -----
    # identical workload with the tracer off vs on; tokens must stay
    # bitwise identical and traced throughput within 5% of untraced.
    # Two things keep this gate honest on noisy CI machines:
    #   * the case runs at a REALISTIC model size (ticks ~8ms) rather
    #     than the smoke size, whose ~1.5ms ticks are Python-dispatch
    #     bound and would measure interpreter noise, not tracer cost
    #   * the statistic is the MEDIAN of adjacent off/on pair ratios:
    #     each pair shares its instantaneous background load, and the
    #     median shrugs off the scheduler outliers that make min-of-N
    #     or mean-based gates flake
    # The traced run's export lands next to the BENCH jsons so CI
    # uploads a real Perfetto-loadable artifact on every PR.
    tr_cfg = dc.replace(cfg, d_model=256, d_ff=512, n_layers=4,
                        n_heads=4, n_kv_heads=2, vocab_size=128)
    scfg_tr = ServeConfig(batch=slots, max_len=32)
    tr_decode = 16
    n_tr = slots * 2
    tr_prompts = [rng.integers(0, tr_cfg.vocab_size, size=plen)
                  .astype(np.int32) for _ in range(n_tr)]

    def build_obs(tracer):
        prog_t, adapter_t = lm_engine_parts(tr_cfg, scfg_tr)
        eng_t = miso.serve(prog_t, adapter_t,
                           miso.EngineConfig(tracer=tracer))
        eng_t.start(jax.random.PRNGKey(0))
        warm = Request(prompt=tr_prompts[0], max_new_tokens=2)
        eng_t.submit(warm)
        eng_t.pump()                    # warm: compile prefill + step
        return eng_t

    def timed_pass(eng_t):
        clones = [Request(prompt=p, max_new_tokens=tr_decode)
                  for p in tr_prompts]
        t0 = time.perf_counter()
        for r in clones:
            eng_t.submit(r)
        eng_t.pump()
        wall = time.perf_counter() - t0
        return wall, [eng_t.result(r.id)["tokens"] for r in clones]

    from repro.obs import Tracer

    # build each engine ONCE (compiles excluded); a small ring keeps the
    # live-dict population (and so gc pressure on BOTH modes) bounded
    trace = Tracer(capacity=4096)
    engs = {"off": build_obs(None), "on": build_obs(trace)}
    timed_pass(engs["off"])             # steady-state warm, untimed
    timed_pass(engs["on"])
    ratios = []
    walls: dict = {"off": [], "on": []}
    toks_by_mode: dict = {}
    for _ in range(10):
        w_off, toks_by_mode["off"] = timed_pass(engs["off"])
        w_on, toks_by_mode["on"] = timed_pass(engs["on"])
        walls["off"].append(w_off)
        walls["on"].append(w_on)
        ratios.append(w_on / w_off)
    assert toks_by_mode["on"] == toks_by_mode["off"], (
        "tracer perturbed the emitted tokens")
    srt = sorted(ratios)
    med_ratio = (srt[4] + srt[5]) / 2.0
    off_tps = n_tr * tr_decode / min(walls["off"])
    on_tps = n_tr * tr_decode / min(walls["on"])
    assert med_ratio <= 1.05, (
        f"tracing overhead above 5%: median pair ratio {med_ratio:.3f} "
        f"over {len(ratios)} off/on pairs")
    trace_out = JSON_DIR / "BENCH_serving_trace.json"
    JSON_DIR.mkdir(parents=True, exist_ok=True)
    trace.export(trace_out)
    tracing = {
        "case": "tracing_overhead",
        "requests": n_tr,
        "decode_tokens": tr_decode,
        "d_model": tr_cfg.d_model,
        "pairs": len(ratios),
        "tokens_per_s_off": round(off_tps, 2),
        "tokens_per_s_on": round(on_tps, 2),
        "overhead_pct": round(100.0 * (med_ratio - 1.0), 2),
        "token_parity": True,
        "trace_events": trace.emitted,
        "trace_artifact": str(trace_out),
    }
    row("serving", "tracing_overhead_pct", tracing["overhead_pct"],
        f"median of {len(ratios)} off/on pair ratios, "
        f"{on_tps:.1f} traced vs {off_tps:.1f} untraced tok/s best-case, "
        "bitwise-equal tokens (gate: <5%)")

    # -- spatial placement: replica slots on mesh pods ---------------------
    # a DMR/TMR request's replicas occupy the SAME slot column on
    # DIFFERENT pods; detection is the O(1)-wire fingerprint collective
    # across the pod axis instead of the host-side slot walk.  jax pins
    # the device count at first init, so the forced-8-device mesh run
    # lives in a subprocess; the child asserts bitwise token parity with
    # temporal replica-slot serving before reporting throughput.
    import os
    import subprocess
    import sys

    child = _SPATIAL_SERVE_CHILD % {"decode": 4 if SMOKE else 8}
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=2400)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    spatial = json.loads(line[len("RESULT"):])
    spatial["case"] = "spatial_placement"
    row("serving", "spatial_tokens_per_s",
        f"{spatial['spatial_tokens_per_s']} vs "
        f"{spatial['temporal_tokens_per_s']} temporal",
        f"{spatial['pods']} pods x {spatial['slots_per_pod']} slots/pod, "
        "bitwise-equal tokens")
    row("serving", "spatial_wire_B_per_tick",
        f"dmr {spatial['wire_bytes_per_tick_dmr']} / "
        f"tmr {spatial['wire_bytes_per_tick_tmr']}",
        "cross-pod detect bytes per pod per tick (fingerprint collectives)")

    payload = {
        "bench": "serving",
        "jax": jax.__version__,
        "device": jax.default_backend(),
        "smoke": SMOKE,
        "slots": slots,
        "decode_tokens": decode,
        "saturated_tokens_per_s": round(cap_tps, 2),
        "cases": cases,
        "mixed_length": mixed,
        "fixed_budget": budget,
        "speculation": speculation,
        "tracing": tracing,
        "spatial": spatial,
    }
    out = JSON_DIR / "BENCH_serving.json"
    out.write_text(json.dumps(payload, indent=2) + "\n")
    row("serving", "json_artifact", str(out),
        f"{len(cases)} load levels, poisson arrivals")


# ===========================================================================
# roofline table (from dry-run artifacts — the 512-chip numbers)
# ===========================================================================
def bench_roofline(dryrun_dir: str = "results/dryrun") -> None:
    """Reads the dry-run JSONs (compile-time cost/memory/collective
    analysis against the production meshes) and emits the roofline terms.
    This is the per-(arch x shape) baseline table of EXPERIMENTS.md."""
    d = pathlib.Path(dryrun_dir)
    recs = []
    for f in sorted(d.glob("baseline_*.json")):
        r = json.loads(f.read_text())
        if r.get("ok"):
            recs.append(r)
    if not recs:
        row("roofline", "records", 0, f"no dry-run artifacts in {d}")
        return
    for r in recs:
        roof = r["roofline"]
        name = f"{r['arch']}/{r['shape']}/{r['mesh']}"
        row("roofline", name,
            round(roof["roofline_fraction"], 4),
            f"dom={roof['dominant']} comp={roof['compute_s']*1e3:.1f}ms "
            f"mem={roof['memory_s']*1e3:.1f}ms "
            f"coll={roof['collective_s']*1e3:.1f}ms")
    fracs = [r["roofline"]["roofline_fraction"] for r in recs]
    row("roofline", "cells", len(recs),
        f"median_fraction={np.median(fracs):.3f}")


BENCHES = {
    "parallelization": bench_parallelization,
    "mimd_wavefront": bench_mimd_wavefront,
    "redundancy_overhead": bench_redundancy_overhead,
    "fault_coverage": bench_fault_coverage,
    "selective": bench_selective,
    "kernels": bench_kernels,
    "lockstep_pallas": bench_lockstep_pallas,
    "spatial": bench_spatial,
    "serving": bench_serving,
    "roofline": bench_roofline,
}


def main() -> None:
    global SMOKE, JSON_DIR
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated bench names (default: all)")
    ap.add_argument("--csv", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes/steps (CI bench-smoke job)")
    ap.add_argument("--json-out", default=".",
                    help="directory for BENCH_*.json artifacts")
    args = ap.parse_args()
    SMOKE = args.smoke
    JSON_DIR = pathlib.Path(args.json_out)
    names = [n for n in args.only.split(",") if n] or list(BENCHES)
    print("name,metric,value,derived")
    t0 = time.time()
    for n in names:
        BENCHES[n]()
    print(f"# total {time.time() - t0:.1f}s", flush=True)
    if args.csv:
        out = pathlib.Path(args.csv)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text("name,metric,value,derived\n" + "\n".join(
            ",".join(str(c) for c in r) for r in ROWS) + "\n")


if __name__ == "__main__":
    main()
