"""The comparison that decides ``correct``: served tokens against the plain
float32 reference.

Once the window has closed, a sample of the finished requests is drawn
from the seed: the longest request, a few struck ones, one request from
each prefill bucket the window reached, and others until some hundreds
of served tokens are covered.  The reference
runs once over each prompt followed by its served tokens.  For every
served token, the gap is how far the reference's logit of that token lies
below the reference's best logit at that position; greedy decoding in the
configured precision keeps that gap at rounding size.  The number compared
is the widest gap of the sample.

The control puts the reference in the program's place at the next lower
precision (float8): at the same positions it reads the gap of the token
that the float8 forward ranks first.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: the sample covers at least this many served tokens
SAMPLE_TOKENS = 256
#: and draws at most this many struck requests into it
SAMPLE_STRUCK = 2


@dataclasses.dataclass
class Served:
    rid: str
    prompt: np.ndarray
    tokens: list
    struck: bool = False


def draw_sample(
    done: list[Served], seed: int, max_requests: int, buckets: tuple
) -> list[Served]:
    if not done:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    longest = max(done, key=lambda s: (len(s.prompt) + len(s.tokens), s.rid))
    rest = [s for s in done if s is not longest]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    pick = [longest] + [s for s in rest if s.struck][:SAMPLE_STRUCK]

    def bucket(s):
        return min((b for b in buckets if b >= len(s.prompt)), default=0)

    for b in sorted({bucket(s) for s in rest} - {bucket(s) for s in pick}):
        pick.append(next(s for s in rest if bucket(s) == b))
    total = sum(len(s.tokens) for s in pick)
    for s in rest:
        if total >= SAMPLE_TOKENS or len(pick) >= max_requests:
            break
        if s not in pick:
            pick.append(s)
            total += len(s.tokens)
    return pick


def gaps(ref, model: dict, params, sample: list[Served], *, quant: str = ""):
    """Per request: the gap of every served token (program), or with
    ``quant`` the gap of the token the lower-precision forward ranks first
    (control).  Returns (widest gap, tokens compared)."""
    widest, count = 0.0, 0
    for s in sample:
        toks = np.asarray(s.tokens, np.int64)
        seq = np.concatenate([np.asarray(s.prompt, np.int64), toks[:-1]])
        rows = np.arange(len(s.prompt) - 1, len(seq))
        exact = ref.logits_at(model, params, seq, rows)
        if quant:
            chosen = np.argmax(ref.logits_at(model, params, seq, rows, quant=quant), -1)
        else:
            chosen = toks
        inside = (chosen >= 0) & (chosen < exact.shape[-1])
        picked = exact[np.arange(len(rows)), np.where(inside, chosen, 0)]
        g = np.where(inside, exact.max(-1) - picked, np.inf)
        widest = max(widest, float(g.max()))
        count += len(rows)
    return widest, count
