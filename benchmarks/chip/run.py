"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration file and its
traffic mix are found by name through ``BENCHMARK.json``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, taken by
the benchmark's own clock with tracing off; with ``--trace 1`` its
per-layer metrics, read from the program's tracer spans and from a
``jax.profiler`` trace of part of the window.  Either way the served
tokens are compared with the plain float32 reference afterwards, which
decides ``correct``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, every number compared beside its
limit (also the last lines of standard error).  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.

Two modes serve the building of the benchmark and are not part of its
runs:

    --rates r1,r2,...  after one set-up, offer the mix at each rate in
                       turn and report the queue at a quarter of the
                       window and at its end (the knee sweep);
    --seeds a,b,...    the comparison on several seeds in one process,
                       each with its own weights and traffic.

``--control`` also judges the float8 control (the reference computed
with float8 operands, in the program's place) by the same checks and
limits as the program, and reports its reading and its ``correct``
among the counts on standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from benchmarks.chip import correct as C  # noqa: E402
from benchmarks.chip import devtrace  # noqa: E402
from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import traffic as T  # noqa: E402

HERE = ROOT / "benchmarks" / "chip"
#: at most this many requests go into the sample the reference reads
SAMPLE_REQUESTS = 24


def use_compile_cache() -> str:
    """The program's persistent compilation cache (its fixed place in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), keeping every program
    however quickly it compiled."""
    from repro.launch.compile_cache import use_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def finite(x) -> float:
    return float(x) if x is not None and math.isfinite(x) else 1e9


def reference_module(config: dict):
    return H.load_module(HERE / "reference" / f"{config['reference']}.py")


def compare(config: dict, params, sample, *, quant: str = "") -> tuple[float, int]:
    ref = reference_module(config)
    with jax.default_matmul_precision("highest"):
        return C.gaps(ref, config["model"], params, sample, quant=quant)


def judge(checks: dict, compared: int) -> bool:
    """``correct``: every number within its limit, and tokens compared."""
    return compared > 0 and all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(cell: H.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, control: bool = False) -> dict:
    """Set up, run the window, free the engine, compare.  Returns the
    result object (``metrics`` per ``trace``) and the raw readings."""
    from repro.obs import Tracer

    tracer = Tracer(capacity=H.TRACER_CAPACITY) if trace else None
    t_off = time.perf_counter() * 1e6 - tracer.now_us() if trace else 0.0
    sys_ = H.build(cell.config, seed, tracer=tracer)
    H.warm_up(sys_, cell.mix, seed)
    plan = T.plan(cell.mix, seed=seed, window_s=seconds, vocab=sys_.cfg.vocab_size)
    trace_dir = None
    if trace:
        trace_dir = ROOT / ".bench_trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.perf_counter() - t_start
    loop = H.run_loop(sys_, cell.mix, plan, seed=seed, window_s=seconds,
                      trace_dir=trace_dir, hard_s=cell.mix["tail_s"])
    e2e = H.end_to_end(loop)
    mem = H.memory_peak(cell.chips)
    fails = H.failures(loop, sys_.cfg.vocab_size)
    spans, dropped = [], 0
    if tracer is not None:
        dropped = tracer.dropped
        lo, hi = (x * 1e6 - t_off for x in loop.trace_span)
        spans = [e for e in tracer.events()
                 if e.get("ph") == "X" and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]
    params = sys_.params
    H.free(sys_)
    sample = C.draw_sample(H.served(loop), seed, SAMPLE_REQUESTS, sys_.ladder)
    t_ref = time.perf_counter()
    gap, n_cmp = compare(cell.config, params, sample)
    t_ref = time.perf_counter() - t_ref
    limit = cell.config["gap_limit"]
    checks = {
        "logit_gap": {"value": finite(gap), "limit": limit},
        "requests_lost": {"value": fails["requests_lost"], "limit": 0},
        "tokens_out_of_vocab": {"value": fails["tokens_out_of_vocab"], "limit": 0},
        "strikes_unrepaired": {"value": fails["strikes_unrepaired"], "limit": 0},
        "tracer_dropped": {"value": dropped, "limit": 0},
    }
    ok = judge(checks, n_cmp)
    ctl, ctl_ok = None, None
    if control:
        ctl, n_ctl = compare(cell.config, params, sample, quant="fp8")
        ctl_checks = {**checks, "logit_gap": {"value": finite(ctl), "limit": limit}}
        ctl_ok = judge(ctl_checks, n_ctl)
    failed = fails["requests_lost"] + fails["strikes_unrepaired"]
    device = {**device_info(), "memory_peak_bytes": mem}
    out = {"correct": bool(ok), "attempted": fails["attempted"], "failed": failed}
    counts = e2e.pop("_counts")
    if trace:
        dt = devtrace.reduce(devtrace.find_xplane(trace_dir), cell.chips)
        peaks = json.loads((HERE / "peaks.json").read_text())
        kind = device["kind"]
        if kind not in peaks:
            raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
        # what a per-layer reader gets: the model, the chips and their
        # peaks, the benchmark's per-tick book, the tracer's spans and the
        # reduced device trace of the traced window
        r = types.SimpleNamespace(
            model=cell.config["model"],
            chips=cell.chips,
            peak=peaks[kind],
            book=loop.book,
            spans=spans,
            device=dt,
            reference=reference_module(cell.config),
        )
        metrics = {}
        for m in cell.per_layer:
            v = H.load_module(HERE / "metrics" / f"{m['name']}.py").read(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=dt.busy_s, window_s=dt.window_s)
        out.update(metrics=metrics, device=device, breakdown=dt.breakdown())
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        vals = {**e2e, "setup_s": setup_s}
        metrics = {m["name"]: {"value": finite(vals.get(m["name"])), "unit": m["unit"]}
                   for m in cell.e2e}
        out.update(metrics=metrics, device=device)
    out["checks"] = checks
    info = {
        **counts,
        "ticks": loop.ticks,
        "compiles_in_window": loop.compiles,
        "lowerings_in_window": loop.lowerings,
        "strikes_unarmed": loop.unarmed,
        "queue_at_quarter": loop.queue_at.get(0.25),
        "queue_at_end": loop.queue_at.get(1.0),
        "compared_tokens": n_cmp,
        "sample_requests": len(sample),
        "setup_s": setup_s,
        "reference_s": t_ref,
        "control_gap": ctl,
        "control_correct": ctl_ok,
    }
    return {"result": out, "info": info, "e2e": e2e}


def report(res: dict) -> None:
    info, out = res["info"], res["result"]
    H.log(f"generator lateness p99 {info['lateness_p99_s']} s, "
          f"max {info['lateness_max_s']} s")
    H.log("counts " + json.dumps({k: v for k, v in info.items()
                                   if not k.startswith("lateness")}))
    for name, c in out["checks"].items():
        H.log(f"check {name} {c['value']} limit {c['limit']}")


def sweep(cell: H.Cell, seed: int, seconds: float, rates: list[float]) -> int:
    """The knee sweep: one set-up, one window per offered rate."""
    sys_ = H.build(cell.config, seed)
    H.warm_up(sys_, cell.mix, seed)
    for k, rate in enumerate(rates):
        mix = {**cell.mix, "rate_per_s": rate}
        plan = T.plan(mix, seed=seed + k, window_s=seconds, vocab=sys_.cfg.vocab_size)
        loop = H.run_loop(sys_, mix, plan, seed=seed + k, window_s=seconds,
                          prefix=f"s{k}_", hard_s=0.0)
        e2e = H.end_to_end(loop)
        counts = e2e.pop("_counts")
        print(json.dumps({"rate_per_s": rate, "queue_at_quarter": loop.queue_at.get(0.25),
                          "queue_at_end": loop.queue_at.get(1.0), **e2e, **counts,
                          "ticks": loop.ticks, "compiles_in_window": loop.compiles,
                          "memory_peak_bytes": H.memory_peak(cell.chips)}),
              flush=True)
        H.drain(sys_.engine)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = H.load_cell(bench, args.workload)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"run: cell {cell.name} needs {cell.chips} TPU chip(s); found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    H.log(f"{cell.name}: {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
          f"compile cache {cache}")
    if args.rates:
        return sweep(cell, args.seed, seconds, [float(r) for r in args.rates.split(",")])
    if args.seeds:
        t0 = T_START
        for s in (int(x) for x in args.seeds.split(",")):
            res = run_cell(cell, s, seconds, False, t_start=t0, control=args.control)
            out = res["result"]
            print(json.dumps({"seed": s, "correct": out["correct"],
                              "gap": out["checks"]["logit_gap"]["value"],
                              "control_gap": res["info"]["control_gap"],
                              "control_correct": res["info"]["control_correct"],
                              "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                              "info": res["info"],
                              "device": out["device"]}), flush=True)
            t0 = time.perf_counter()
        return 0
    res = run_cell(cell, args.seed, seconds, bool(args.trace), t_start=T_START,
                   control=args.control)
    report(res)
    print(json.dumps(res["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
