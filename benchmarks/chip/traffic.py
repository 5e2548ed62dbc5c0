"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``traffic/<name>.json``) fixes the arrival process, the length
distributions, the redundancy-policy shares and the strike rate.  The
*set* of sizes, gaps and policies of each phase (lead, window, tail) is
drawn once from the mix's own ``shape_seed``, so every ``--seed`` serves
the same work in the window; ``--seed`` only shuffles its order, draws
the token ids and picks strike victims.  The program under test receives only the generated requests.

Mix keys (all numbers):

  rate_per_s      offered arrivals per second (open loop, Poisson)
  lead_s          arrivals before the measured window opens, so the
                  window starts on a loaded system
  tail_s          arrivals generated past the window's end, so the last
                  requests due in it see the same load as the first
  prompt, output  {"median", "sigma", "min", "max"}: log-normal token
                  counts, clipped
  policies        {"none": share, "dmr": share, "tmr": share}, replica
                  slots placed in time
  strikes_per_s   bit flips armed on resident replicated requests
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

LEVELS = {"none": 1, "dmr": 2, "tmr": 3}


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it (times from run start)."""

    idx: int
    due_s: float
    prompt: np.ndarray
    max_new: int
    level: int


def _seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _levels(mix: dict, n: int) -> np.ndarray:
    """Exactly the mix's policy shares (largest remainder), in a fixed
    order that the seed later shuffles."""
    shares = mix.get("policies", {"none": 1.0})
    total = sum(shares.values())
    raw = {k: n * v / total for k, v in shares.items()}
    counts = {k: int(math.floor(v)) for k, v in raw.items()}
    for k in sorted(raw, key=lambda k: counts[k] - raw[k])[: n - sum(counts.values())]:
        counts[k] += 1
    return np.concatenate(
        [np.full(c, LEVELS[k], np.int64) for k, c in sorted(counts.items())]
    )


def shapes(mix: dict, n: int, phase: int):
    """The work every seed shares in one phase: ``n`` gaps, prompt and
    output lengths and policy levels, from ``shape_seed``."""
    shape = _seed_rng(mix["shape_seed"], phase)
    gaps = shape.exponential(1.0 / mix["rate_per_s"], n)
    plens = _lognormal(shape, mix["prompt"], n)
    outs = _lognormal(shape, mix["output"], n)
    return gaps, plens, outs, _levels(mix, n)


def plan(mix: dict, *, seed: int, window_s: float, vocab: int) -> list[Planned]:
    """Every request of one run, ordered by due time.  Due times start at
    0; the window is ``[lead_s, lead_s + window_s)``.  The lead, the
    window and the tail each hold exactly ``rate_per_s`` times their
    length in requests, with gaps scaled to fill the phase, so the seed
    moves no request into or out of the window."""
    rng = _seed_rng(seed, 1)
    out, t0 = [], 0.0
    for phase, dur in enumerate((mix["lead_s"], window_s, mix["tail_s"])):
        n = int(round(dur * mix["rate_per_s"]))
        if n == 0:
            t0 += dur
            continue
        gaps, plens, outs, levels = shapes(mix, n, phase)
        gaps = gaps[rng.permutation(n)] * (dur / gaps.sum())
        levels = levels[_seed_rng(mix["shape_seed"], 50 + phase).permutation(n)]
        order = rng.permutation(n)
        plens, outs, levels = plens[order], outs[order], levels[order]
        due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for i in range(n):
            toks = rng.integers(0, vocab, int(plens[i]), dtype=np.int64)
            out.append(
                Planned(
                    idx=len(out),
                    due_s=float(due[i]),
                    prompt=toks.astype(np.int32),
                    max_new=int(outs[i]),
                    level=int(levels[i]),
                )
            )
        t0 += dur
    return out


def strike_times(mix: dict, *, window_s: float) -> list[float]:
    """Due times of the armed strikes: evenly spaced over the window."""
    rate = mix.get("strikes_per_s", 0.0)
    if rate <= 0:
        return []
    k = int(math.floor(window_s * rate))
    return [mix["lead_s"] + (j + 0.5) / rate for j in range(k)]


def prompt_range(mix: dict) -> tuple[int, int]:
    return int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
