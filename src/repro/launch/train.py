"""End-to-end training driver.

The training loop is a MISO program (data cell -> trainer cell) compiled
through ``miso.compile(prog, backend="host")``: per-step DMR tie-breaks,
fault-ledger accounting, and async checkpoints of the immutable previous
buffer.  Fail-stop recovery is built in: rerunning with the same --ckpt-dir
resumes from the latest intact checkpoint (use --simulate-failure N to
watch a crash + restart).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --reduced --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-2.7b --reduced \
      --steps 20 --redundancy dmr --inject-fault 7
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from repro import api as miso
from repro.checkpoint import ckpt
from repro.configs import get_config, get_reduced
from repro.core import FaultLedger, FaultSpec, RedundancyPolicy
from repro.data.pipeline import DataConfig, bigram_optimal_xent
from repro.distributed.sharding import LOCAL
from repro.launch.compile_cache import use_compile_cache
from repro.models.lm_cells import TrainConfig, make_train_program
from repro.optim.adamw import OptConfig


def build(args):
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.d_model:
        import dataclasses

        cfg = dataclasses.replace(
            cfg, d_model=args.d_model, n_layers=args.layers or cfg.n_layers,
            d_ff=args.d_model * 4,
        )
    tcfg = TrainConfig(
        data=DataConfig(batch=args.batch, seq_len=args.seq,
                        vocab=cfg.vocab_size, kind=args.data,
                        n_codebooks=cfg.n_codebooks, seed=args.seed),
        opt=OptConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                      decay_steps=max(args.steps, 2 * args.warmup)),
        microbatches=args.microbatches,
    )
    policy = {
        "none": RedundancyPolicy(),
        "dmr": RedundancyPolicy(level=2),
        "dmr_hash": RedundancyPolicy(level=2, compare="hash"),
        "tmr": RedundancyPolicy(level=3),
    }[args.redundancy]
    prog = make_train_program(cfg, tcfg, LOCAL).with_policies(
        {"trainer": policy})
    return cfg, tcfg, prog


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (custom-size run)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data", default="bigram", choices=["bigram", "uniform"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--redundancy", default="none",
                    choices=["none", "dmr", "dmr_hash", "tmr"])
    ap.add_argument("--inject-fault", type=int, default=-1,
                    help="flip a bit in replica 0's output at this step")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--simulate-failure", type=int, default=-1)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--log-file", default="")
    args = ap.parse_args()
    use_compile_cache()

    cfg, tcfg, prog = build(args)
    prog.validate()
    n_params = cfg.n_params()
    print(f"arch={cfg.name} params~{n_params/1e6:.1f}M "
          f"batch={args.batch} seq={args.seq} steps={args.steps} "
          f"redundancy={args.redundancy}")
    if args.data == "bigram":
        floor = bigram_optimal_xent(tcfg.data)
        print(f"bigram entropy floor: {floor:.3f} nats "
              f"(uniform: {jnp.log(cfg.vocab_size):.3f})")

    states = prog.init_states(jax.random.PRNGKey(args.seed))
    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        states, start_step = ckpt.restore(args.ckpt_dir, states)
        print(f"restored checkpoint at step {start_step}")

    log_rows = []

    exe = miso.compile(
        prog, backend="host", ledger=FaultLedger(),
        checkpoint_cb=(ckpt.callback(args.ckpt_dir) if args.ckpt_dir
                       else None),
        checkpoint_every=args.ckpt_every if args.ckpt_dir else 0,
    )
    faults = []
    if args.inject_fault >= 0:
        faults.append(FaultSpec.at(
            step=args.inject_fault, cell_id=prog.cell_id("trainer"),
            replica=0, leaf=5, index=11, bit=19))

    t0 = time.time()
    tokens_per_step = args.batch * args.seq
    step = start_step
    try:
        while step < args.steps:
            n = min(args.log_every, args.steps - step)
            if (args.simulate_failure >= 0
                    and step <= args.simulate_failure < step + n):
                n = args.simulate_failure - step + 1
            states = exe.run(states, n, faults=faults,
                             start_step=step).states
            step += n
            m = jax.device_get(states["trainer"]["metrics"])
            loss = float(m["loss"].reshape(-1)[0])
            gn = float(m["grad_norm"].reshape(-1)[0])
            dt = time.time() - t0
            tps = tokens_per_step * (step - start_step) / max(dt, 1e-9)
            row = {"step": step, "loss": round(loss, 4),
                   "grad_norm": round(gn, 3),
                   "tokens_per_s": round(tps, 1),
                   "recoveries": len(exe.recoveries)}
            log_rows.append(row)
            print(json.dumps(row), flush=True)
            if args.simulate_failure >= 0 and step > args.simulate_failure:
                print(f"simulated fail-stop at step {step} — "
                      "restarting from checkpoint")
                if not args.ckpt_dir:
                    raise SystemExit("--simulate-failure needs --ckpt-dir")
                states = prog.init_states(jax.random.PRNGKey(args.seed))
                states, restored = ckpt.restore(args.ckpt_dir, states)
                step = restored
                args.simulate_failure = -1
    finally:
        if args.log_file:
            m = exe.metrics()
            pathlib.Path(args.log_file).write_text(
                json.dumps({
                    "config": vars(args), "rows": log_rows,
                    "ledger": m["fault_totals"],
                    "recoveries": m["recoveries"],
                }, indent=1))
    if exe.ledger.flagged:
        print("permanent-fault suspects:", exe.metrics()["suspects"])
    print(f"done: {step} steps in {time.time()-t0:.1f}s; "
          f"final loss {log_rows[-1]['loss'] if log_rows else float('nan')}")


if __name__ == "__main__":
    main()
