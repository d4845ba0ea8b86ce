#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: one process builds the cell once and
offers its traffic at each rate in turn, for ``--seconds`` each, printing
per rate the completions per second, TTFT p50/p90 and the waiting queue in
the first and second half of the window.  The knee is the highest rate
whose queue does not grow.

    python bench/tools/sweep.py --workload <cell> --seed <n> --seconds <s>
        --rates 3,4,5,6
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "bench"))

from run import require_chips  # noqa: E402
from bench.harness.spec import Cell, load_bench  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    a = p.parse_args()
    cell = Cell(load_bench(ROOT), a.workload)
    devices = require_chips(cell.chips)
    from bench.harness import cell as C
    C.set_compile_cache(ROOT)
    eng, params = C.build(cell, a.seed, devices)

    class Probe(C.Clients):
        def step(self):
            super().step()
            self.q.append((C.CLOCK(), len(self.eng.sched.waiting)))

    drv = Probe(eng, annotate=False)
    drv.q = []
    C.warm_up(eng, cell, drv)
    print(f"setup {C.CLOCK() - T_START:.1f} s", flush=True)
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        cell.mix["rate_per_s"] = rate
        drv = Probe(eng, annotate=False)
        drv.q = []
        drv._rid = 10 ** 6 * (i + 1)
        window = C.drive_open(drv, cell, a.seed + i, a.seconds, None)
        w0, w1 = drv.w0, drv.w1
        mid = 0.5 * (w0 + w1)
        q1 = [n for t, n in drv.q if w0 <= t < mid]
        q2 = [n for t, n in drv.q if mid <= t < w1]
        done = [r for r in drv.recs.values()
                if r.done and r.last is not None and w0 <= r.last < w1]
        ttft = [r.first - r.due for r in window if r.first is not None]
        row = {"rate": rate, "offered": len(window),
               "completed_per_s": len(done) / a.seconds,
               "ttft_p50_ms": 1e3 * C.percentile(ttft, 50),
               "ttft_p90_ms": 1e3 * C.percentile(ttft, 90),
               "itl_p95_ms": 1e3 * C.percentile(drv.gaps, 95),
               "queue_first_half": sum(q1) / max(len(q1), 1),
               "queue_second_half": sum(q2) / max(len(q2), 1),
               "queue_end": q2[-1] if q2 else 0,
               "steps": len(drv.steps)}
        print(json.dumps(row), flush=True)
        for r in list(eng.sched.waiting) + [x for x in eng.sched.active
                                            if x is not None]:
            eng.abort(r)


if __name__ == "__main__":
    main()
