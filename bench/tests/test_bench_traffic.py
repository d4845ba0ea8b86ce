"""The traffic generator is deterministic by seed, clips as each mix
declares, and gives every seed the same requests at the same times, with
contents drawn from the seed."""
import json
import os

import pytest

from bench.harness import traffic as TF

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))
BIG = 2 ** 31 + 12345


def _mix(name):
    return TF.load(os.path.join(BENCH, "traffic", name + ".json"))


def _draw(mix, seed):
    """(prompt length, output length[, due time], first token id) rows."""
    if mix["loop"] == "open":
        ph = TF.open_loop(mix, seed, 10.0, 151936, 5.0)
        return [(len(p), o, d, p[0]) for n in ("preroll", "window", "tail")
                for p, o, d in zip(ph[n].prompts, ph[n].outputs, ph[n].due)]
    streams = TF.closed_loop(mix, seed, 4, 5, 151936)
    return [(len(p), o, p[0]) for s in streams for p, o in s]


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_and_clipped(name):
    mix = _mix(name)
    a, b, c = _draw(mix, BIG), _draw(mix, BIG), _draw(mix, 7)
    assert a == b
    assert [r[-1] for r in a] != [r[-1] for r in c]   # contents differ
    for row in a:
        assert mix["prompt"]["min"] <= row[0] <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= row[1] <= mix["output"]["max"]
    assert sorted(r[:2] for r in a) != [r[:2] for r in a]   # shuffled


@pytest.mark.parametrize("name", MIXES)
def test_same_schedule_every_seed(name):
    mix = _mix(name)
    a, c = _draw(mix, BIG), _draw(mix, 7)
    assert [r[:-1] for r in a] == [r[:-1] for r in c]


def test_open_loop_phases():
    mix = {"rate_per_s": 4.0, "preroll_s": 5.0,
           "prompt": {"median": 100, "sigma": 0.5, "min": 10, "max": 300},
           "output": {"median": 10, "sigma": 0.5, "min": 2, "max": 40}}
    ph = TF.open_loop(mix, 3, 30.0, 1000, 10.0)
    assert [len(ph[n].due) for n in ("preroll", "window", "tail")] == \
        [20, 120, 40]
    w = ph["window"].due
    assert w[0] == 5.0 and all(5.0 <= t < 35.0 for t in w)
    assert w == sorted(w)
    gaps = [b - a for a, b in zip(w, w[1:])]
    assert max(gaps) > 5 * min(gaps)               # Poisson-like bursts
    assert all(0 <= t < 1000 for p in ph["window"].prompts for t in p)


def test_stratified_lengths_follow_the_law():
    law = {"median": 1500, "sigma": 0.4, "min": 256, "max": 1920}
    x = sorted(TF.lengths(law, 101))
    assert x[50] == 1500          # the middle quantile is the median
    assert x[0] >= 256 and x[-1] == 1920
    assert json.dumps(x) == json.dumps(sorted(TF.lengths(law, 101, "b")))
