#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from: for each seed
a whole run of the cell (short window, the cell's own load and sizes),
then the widest gap of the program's served tokens below the float32
reference's best, and the widest gap of the tokens the fp8 control puts
first at the same positions.  All seeds in one process.

    python bench/tools/readings.py --workload <cell> --seconds <s>
        --seeds 1,2,3
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
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--rate", type=float, default=None)
    a = p.parse_args()
    cell = Cell(load_bench(ROOT), a.workload)
    if a.rate is not None:
        cell.mix["rate_per_s"] = a.rate
    devices = require_chips(cell.chips)
    from bench.harness.cell import run_cell, set_compile_cache
    set_compile_cache(ROOT)
    prog, ctrl = [], []
    for seed in (int(s) for s in a.seeds.split(",")):
        t = time.perf_counter()
        out = run_cell(cell, seed, a.seconds, False, devices, t,
                       log=lambda m: None, control=True)
        g = out.get("program_max_logit_gap", float("nan"))
        prog.append(g)
        ctrl.append(out["checks"]["max_logit_gap"]["value"])
        print(json.dumps({"seed": seed, "program": g, "control": ctrl[-1],
                          "served_tokens": out.get("served_tokens"),
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "seconds": time.perf_counter() - t}), flush=True)
    print(json.dumps({"program_max": max(prog), "control_min": min(ctrl)}))


if __name__ == "__main__":
    main()
