#!/usr/bin/env python3
"""Run one cell with the program's ``engine/`` spans read, and time its
engine steps.

    python bench/tools/phases.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--out <file>]
    python bench/tools/phases.py --span-cost <n>

The run is ``bench/run.py``'s (``harness.cell.run_cell``), with two changes
made in this process only: the trace is loaded with the program's
``engine/`` host spans (``harness/engine_spans.load``), and the context the
metric readers see carries the window's requests (``records``).  With them
the readers ``queue_wait_p90_ms``, ``engine_host_ms`` and
``device_idle_share.host`` read the trace, and ``breakdown`` labels idle
gaps with engine phases.  Printed last, one JSON object: the result line,
and ``steps``: the wall time of ``Engine.step`` calls in the window (mean,
median, 99th percentile, longest; inside and outside the traced stretch, or,
untraced, over the stretch a traced run would trace), the window's queue
wait p90 from the program's admission stamps, how long starting and stopping
the profiler held the host, and, traced, each phase's mean and longest time
per step and the spans per step.

``--span-cost n`` times ``n`` spans on this host while no profile is
collected, bare and with stats, in microseconds per span.
"""
import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

T_START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

NEW = [("queue_wait_p90_ms", "ms"), ("engine_host_ms", "ms"),
       ("device_idle_share.host", "%")]
PHASES = ("engine/schedule", "engine/prepare", "engine/rng",
          "engine/dispatch", "engine/sync", "engine/commit")


def span_cost(n: int) -> dict:
    from repro.obs.spans import span
    out = {}
    for name, stats in (("bare", {}), ("stats", {"program": "decode_b3",
                                                 "first": 0})):
        t = time.perf_counter()
        for _ in range(n):
            with span("engine/x", **stats):
                pass
        out[name + "_us"] = (time.perf_counter() - t) / n * 1e6
    return out


def measure(cell, seed, seconds, trace, devices, t_start, log=print):
    """One run of ``cell`` as above; returns the JSON object."""
    from bench.harness import cell as C
    from bench.harness import engine_spans as ES
    from bench.harness import trace as TR
    from bench.harness.spec import load_module
    from repro.runtime.engine import Engine

    cell.per_layer = cell.per_layer + [{"name": n, "unit": u}
                                       for n, u in NEW]
    import jax
    seen, walls, profiler_s = {}, [], []
    saved = [(TR, "load"), (C.TraceContext, "__init__"),
             (C.Clients, "__init__"), (C.Tracer, "__init__"),
             (Engine, "step"), (jax.profiler, "start_trace"),
             (jax.profiler, "stop_trace")]
    saved = [(o, n, getattr(o, n)) for o, n in saved]
    load, init_ctx, init_drv, init_tracer, engine_step, start, stop = [
        f for _, _, f in saved]

    def timed(fn):
        def call(*args, **kw):
            t = time.perf_counter()
            fn(*args, **kw)
            profiler_s.append(time.perf_counter() - t)
        return call

    def ctx_init(self, tr, drv, *args, **kw):
        init_ctx(self, tr, drv, *args, **kw)
        self.records = [r for r in drv.recs.values() if r.phase == "window"]
        seen["ctx"] = self

    def drv_init(self, *args, **kw):
        init_drv(self, *args, **kw)
        seen["drv"] = self

    def tracer_init(self, *args, **kw):
        init_tracer(self, *args, **kw)
        seen["tracer"] = self

    def timed_step(self):
        t = time.perf_counter()
        r = engine_step(self)
        walls.append((t, time.perf_counter() - t))
        return r
    TR.load = lambda path: ES.with_spans(load(path), path)
    C.TraceContext.__init__ = ctx_init
    C.Clients.__init__ = drv_init
    C.Tracer.__init__ = tracer_init
    Engine.step = timed_step
    jax.profiler.start_trace = timed(start)
    jax.profiler.stop_trace = timed(stop)
    try:
        out = C.run_cell(cell, seed, seconds, trace, devices, t_start,
                         log=log)
    finally:
        for o, n, f in saved:
            setattr(o, n, f)

    drv, tracer = seen["drv"], seen.get("tracer")
    if tracer is not None and tracer.state == "done":
        on = (tracer.t_on, tracer.t_on + tracer.length)
    else:       # where run_cell would trace: a second into the window
        a = drv.w0 + min(1.0, seconds / 4)
        on = (a, a + min(3.0, seconds / 3))
    win = [(t, d) for t, d in walls if drv.w0 <= t < drv.w1]
    inside = [d for t, d in win if on[0] <= t and t + d <= on[1]]
    outside = [d for t, d in win if not on[0] <= t < on[1]]
    ds = np.asarray([d for _, d in win]) * 1e3
    med = float(np.median(ds)) if len(ds) else 0.0
    slow = ds[ds > 2 * med]

    def mean_ms(xs):
        return 1e3 * sum(xs) / len(xs) if xs else None
    queue = load_module(os.path.join(ROOT, "bench", "metrics",
                                     "queue_wait_p90_ms.py"), "queue").read
    steps = {"window_steps": len(win),
             "mean_ms": mean_ms([d for _, d in win]),
             "median_ms": med,
             "p99_ms": float(np.percentile(ds, 99)) if len(ds) else None,
             "max_ms": float(ds.max()) if len(ds) else None,
             "over_2x_median": int(len(slow)),
             "over_2x_median_excess_s": float((slow - med).sum()) * 1e-3,
             "stretch_steps": len(inside), "stretch_mean_ms": mean_ms(inside),
             "rest_mean_ms": mean_ms(outside),
             "queue_wait_p90_ms": queue(SimpleNamespace(records=[
                 r for r in drv.recs.values() if r.phase == "window"])),
             "profiler_start_stop_s": profiler_s}
    ctx = seen.get("ctx")
    if ctx is not None:
        eng_steps = [e for e in ES.in_stretch(ctx, "engine/step")
                     if "kind" in e.stats]
        n = len(eng_steps)
        steps["bench_step_mean_ms"] = mean_ms(
            [(s["t1"] - s["t0"]) * 1e-9 for s in ctx.steps])
        steps["engine_step_mean_ms"] = mean_ms(
            [(e.end - e.start) * 1e-9 for e in eng_steps])
        if n:
            lo, hi = eng_steps[0].start, eng_steps[-1].end
            inner = [e for e in ctx.trace.host
                     if e.name.startswith("engine/")
                     and lo <= e.start and e.end <= hi]
            steps["spans_per_step"] = len(inner) / n
            steps["phase_ms_per_step"] = {
                ph: 1e-6 * sum(e.end - e.start for e in inner
                               if e.name == ph) / n for ph in PHASES}
            steps["phase_max_ms"] = {
                ph: 1e-6 * max([e.end - e.start for e in inner
                                if e.name == ph], default=0)
                for ph in PHASES}
            steps["kinds"] = sorted({e.stats["kind"] for e in eng_steps})
    return {"result": out, "steps": steps}


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int)
    p.add_argument("--seconds", type=float)
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    p.add_argument("--span-cost", type=int, default=0)
    p.add_argument("--out", help="also write the JSON object here")
    a = p.parse_args()
    if a.span_cost:
        print(json.dumps({"span_cost": span_cost(a.span_cost)}))
        return
    from bench.harness.cell import set_compile_cache
    from bench.harness.spec import Cell, load_bench
    from bench.run import require_chips
    cell = Cell(load_bench(ROOT), a.workload)
    devices = require_chips(cell.chips)
    set_compile_cache(ROOT)
    res = measure(cell, a.seed, a.seconds, bool(a.trace), devices, T_START,
                  log=lambda m: print(m, flush=True))
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(res, f)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
