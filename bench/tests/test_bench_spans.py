"""The readers of the program's ``engine/`` spans, on a small trace recorded
on a TPU v5e chip with them (``bench/tools/record_trace.py``: a tiny model,
two requests, four engine steps inside the ``bench/traced`` span), checked
against direct sweeps over the raw events; the queue reader on synthetic
records; and the readers' silence where the program has no spans."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import engine_spans as ES
from bench.harness import trace as TR
from bench.harness.cell import TraceContext, breakdown
from bench.harness.peaks import peaks
from bench.harness.spec import load_module
from bench.tests.test_bench_trace import TINY, TP1

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SPANS = os.path.join(HERE, "data", "traces", "tiny_tp1_spans.xplane.pb")
# the engine steps recorded (one chip): rows are (first position, new
# tokens, sampled positions)
STEPS = [{"i": i, "prefill": [], "decode": []} for i in range(4)] + [
    {"i": 4, "prefill": [(0, 256, 1)], "decode": []},
    {"i": 5, "prefill": [(0, 100, 1)], "decode": [(256, 1, 1), (100, 1, 1)]},
    {"i": 6, "prefill": [], "decode": [(257, 1, 1), (101, 1, 1)]},
    {"i": 7, "prefill": [], "decode": [(258, 1, 1), (102, 1, 1)]}]


class _Cell:
    config = TINY

    def module(self, part):
        return load_module(os.path.join(BENCH, part, "dense_transformer.py"),
                           part)


class _Drv:
    steps = STEPS


def _metric(name, ctx):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       name).read(ctx)


def _ctx(tr):
    return TraceContext(tr, _Drv(), _Cell(), peaks("TPU v5 lite"), 1)


@pytest.fixture(scope="module")
def ctx():
    return _ctx(ES.load(SPANS))


def _sweep(lo, hi, covered, uncovered=()):
    """Length of [lo, hi) inside some interval of ``covered`` and inside no
    interval of ``uncovered``, by a sweep over every boundary."""
    pts = sorted({lo, hi} | {x for a, b in list(covered) + list(uncovered)
                            for x in (a, b) if lo < x < hi})
    total = 0.0
    for a, b in zip(pts, pts[1:]):
        m = 0.5 * (a + b)
        if any(s <= m < e for s, e in covered) and \
                not any(s <= m < e for s, e in uncovered):
            total += b - a
    return total


def test_engine_spans_nest_in_the_benchmark_steps(ctx):
    steps = ES.in_stretch(ctx, "engine/step")
    bench = [e for e in ctx.trace.host if e.name == "bench/step"
             and ctx.lo <= e.start and e.end <= ctx.hi]
    assert len(steps) == len(bench) == 4
    for b, s in zip(bench, steps):
        assert b.start <= s.start and s.end <= b.end
        assert s.stats["kind"] in ("prefill", "decode", "mixed")
    names = {e.name for e in ctx.trace.host if e.name.startswith("engine/")}
    assert names >= {"engine/step", "engine/schedule", "engine/admit",
                     "engine/prepare", "engine/rng", "engine/dispatch",
                     "engine/sync", "engine/commit"}


def test_dispatch_spans_name_the_device_programs(ctx):
    mods = {p.name for s in ctx.steps for p in s["programs"]}
    progs = {"jit_" + e.stats["program"] for e in ctx.trace.host
             if e.name == "engine/dispatch"
             and ctx.lo <= e.start and e.end <= ctx.hi}
    assert progs and progs <= {m.split("(")[0] for m in mods}


def test_host_idle_share(ctx):
    idle = _metric("device_idle_share", ctx)
    host = _metric("device_idle_share.host", ctx)
    syncs = [(e.start, e.end) for e in ctx.trace.host
             if e.name == "engine/sync"]
    busy = [(e.start, e.end) for e in ctx.ops()]
    # idle: in the stretch, in no op; host-caused: and in no sync either
    want = _sweep(ctx.lo, ctx.hi, [(ctx.lo, ctx.hi)], busy + syncs)
    assert host == pytest.approx(100 * want / ctx.window_ns, rel=1e-9)
    assert 0 < host <= idle


def test_engine_host_ms(ctx):
    steps = ES.in_stretch(ctx, "engine/step")
    syncs = [(e.start, e.end) for e in ctx.trace.host
             if e.name == "engine/sync"]
    own = [_sweep(s.start, s.end, [(s.start, s.end)], syncs)
           for s in steps]
    got = _metric("engine_host_ms", ctx)
    assert got == pytest.approx(1e-6 * np.mean(own), rel=1e-9)
    mean_step = 1e-6 * np.mean([s.end - s.start for s in steps])
    assert 0 < got < mean_step


def test_breakdown_names_engine_phases(ctx):
    gaps = breakdown(ctx)["idle_gaps"]
    labels = [k.split(" (")[0] for k, _ in gaps]
    assert any(lab.startswith("engine/") for lab in labels)
    total = sum(v for _, v in gaps)
    assert all(v < 0.5 * total for k, v in gaps if k.startswith("bench/step"))


def test_readers_are_silent_without_engine_spans():
    """A program without the spans (or ``trace.load``, which keeps only
    ``bench/`` spans) gives them nothing to read; the loader then returns
    what ``trace.load`` does, so the older readers read the same."""
    plain = _ctx(TR.load(TP1))
    assert _metric("engine_host_ms", plain) is None
    assert _metric("device_idle_share.host", plain) is None
    assert _metric("queue_wait_p90_ms", plain) is None
    assert ES.spans(TP1) == []
    assert [(e.name, e.start) for e in ES.load(TP1).host] == \
        [(e.name, e.start) for e in TR.load(TP1).host]


def _rec(enq, adm):
    return SimpleNamespace(req=SimpleNamespace(enqueued_s=enq,
                                               admitted_s=adm))


def test_queue_wait_reader():
    read = load_module(os.path.join(BENCH, "metrics",
                                    "queue_wait_p90_ms.py"), "q").read
    waits = [0.0, 0.5, 0.01, 2.0, 0.25, 1.25, 0.0, 0.75, 3.5, 0.1, 0.2]
    recs = [_rec(10.0, 10.0 + w) for w in waits]
    recs.append(_rec(11.0, None))            # never admitted: left out
    ctx = SimpleNamespace(records=recs)
    assert read(ctx) == pytest.approx(1e3 * np.percentile(waits, 90))
    assert read(SimpleNamespace(records=[])) is None
    # a program that stamps nothing
    old = SimpleNamespace(req=SimpleNamespace())
    assert read(SimpleNamespace(records=[old])) is None
