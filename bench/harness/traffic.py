"""The one traffic generator.  A mix is a parameter file
``bench/traffic/<mix>.json``; this module turns it and a seed into
requests with due times.

Parameters (all lengths in tokens, times in seconds):

``loop``            ``open`` (arrivals on a schedule, whatever the server
                    does) or ``closed`` (each client sends its next request
                    when its previous one finished)
``rate_per_s``      open loop: mean arrival rate (Poisson gaps)
``clients_per_slot`` closed loop: concurrent clients per cache slot
``preroll_s``       traffic that runs before the measured window opens
``prompt``, ``output``  length laws: ``{"median": m, "sigma": s}`` (log-normal
                    around median m) or ``{"mu": mu, "sigma": s}`` (log-normal
                    of underlying normal mean mu), clipped to ``[min, max]``

Lengths and gaps are stratified: each phase's n draws are the n quantiles
(i + 1/2) / n of the law, in one fixed shuffled order.  The order is the
same for every seed, so every run offers the same requests at the same
times -- one realization of the Poisson process, with its bursts -- and
the seed draws what they say (token ids, uniform over the vocabulary) and
the model's weights.  An open loop near its knee queues on its bursts: with
a fresh order per seed, or the same order rotated by the seed, the p90 of
first-token times over ~80 requests moved by 2.8x between three seeds on
the chip and by 15-50% in a simulation of the scheduler (PERF.md), which
would leave no bound a check could hold.
"""
from __future__ import annotations

import json
import math
import random
from statistics import NormalDist

import numpy as np

_N01 = NormalDist()


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _quantiles(n: int, what: str):
    """The n quantile points (i + 1/2) / n in a fixed shuffled order;
    ``what`` names the sequence (gaps, prompt or output lengths)."""
    u = [(i + 0.5) / n for i in range(n)]
    random.Random(f"{what}/{n}").shuffle(u)
    return u


def lengths(law: dict, n: int, what: str = "len"):
    """n stratified log-normal lengths, clipped to [min, max]."""
    mu = math.log(law["median"]) if "median" in law else law["mu"]
    out = []
    for u in _quantiles(n, what):
        x = math.exp(mu + law["sigma"] * _N01.inv_cdf(u))
        out.append(int(min(max(int(x), law["min"]), law["max"])))
    return out


def gaps(n: int, span: float):
    """n stratified exponential gaps, scaled to sum to ``span``: a Poisson
    process conditioned on n arrivals in the span."""
    g = [-math.log(1.0 - u) for u in _quantiles(n, "gap")]
    k = span / sum(g)
    return [x * k for x in g]


class Phase:
    """Requests of one phase: prompts, output lengths, due times."""

    def __init__(self, prompts, outputs, due):
        self.prompts, self.outputs, self.due = prompts, outputs, due


def _phase(ids, mix, n, vocab, t0, span):
    pl = lengths(mix["prompt"], n, "prompt")
    ol = lengths(mix["output"], n, "output")
    due = []
    if span is not None:
        t = t0
        for g in gaps(n, span):
            due.append(t)          # first arrival at the phase start
            t += g
    prompts = [ids.integers(0, vocab, size=p, dtype=np.int32).tolist()
               for p in pl]
    return Phase(prompts, ol, due)


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              tail_s: float):
    """Phases ``preroll``, ``window`` and ``tail`` (traffic that keeps
    arriving while the window's requests drain), each with n = rate * span
    requests; due times are seconds from the start of the pre-roll."""
    ids = np.random.default_rng([seed & (2 ** 63 - 1), 1])
    rate = mix["rate_per_s"]
    spans = {"preroll": (0.0, mix["preroll_s"]),
             "window": (mix["preroll_s"], seconds),
             "tail": (mix["preroll_s"] + seconds, tail_s)}
    return {name: _phase(ids, mix, max(1, round(rate * span)), vocab, t0,
                         span)
            for name, (t0, span) in spans.items()}


def closed_loop(mix: dict, seed: int, clients: int, per_client: int,
                vocab: int):
    """One list of (prompt, output length) per client, drawn as one
    stratified population of clients * per_client requests."""
    ids = np.random.default_rng([seed & (2 ** 63 - 1), 2])
    ph = _phase(ids, mix, clients * per_client, vocab, 0.0, None)
    return [list(zip(ph.prompts[c::clients], ph.outputs[c::clients]))
            for c in range(clients)]
