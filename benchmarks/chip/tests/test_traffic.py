"""The generator: a seed reorders a fixed set of work and nothing more."""

import collections

import numpy as np

from benchmarks.chip import traffic as T
from benchmarks.chip.tests.conftest import tiny_cell


def _mix():
    return tiny_cell(redundant=True).mix


def test_same_seed_same_requests():
    a = T.plan(_mix(), seed=5, window_s=4, vocab=256)
    b = T.plan(_mix(), seed=5, window_s=4, vocab=256)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_puts_the_same_work_in_the_window():
    mix = _mix()
    w0, w1 = mix["lead_s"], mix["lead_s"] + 4

    def window(seed):
        p = T.plan(mix, seed=seed, window_s=4, vocab=256)
        return [x for x in p if w0 <= x.due_s < w1]

    a, b = window(1), window(2**31 + 77)
    assert len(a) == len(b) == round(4 * mix["rate_per_s"])
    key = lambda ps: sorted((len(x.prompt), x.max_new, x.level) for x in ps)
    assert key(a) == key(b)
    assert [x.due_s for x in a] != [x.due_s for x in b]


def test_policy_shares_are_exact():
    mix = _mix()
    levels = T._levels(mix, 1000)
    c = collections.Counter(levels.tolist())
    assert c == {1: 500, 2: 250, 3: 250}


def test_strikes_evenly_spaced_inside_the_window():
    mix = {**_mix(), "strikes_per_s": 2.0, "lead_s": 1.0}
    t = T.strike_times(mix, window_s=3)
    assert len(t) == 6 and t[0] == 1.25 and t[-1] == 3.75
