#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are read from
``BENCHMARK.json`` and the files it names under ``bench/``.  The run builds
the model from the seed on the device, warms up every step program the
traffic can emit, drives the serving engine for ``--seconds`` on the host
clock, checks the served tokens against the plain float32 reference, and
prints one JSON object as the last line of standard output (with
``--trace 1``: the per-layer metrics from a profiler trace of a stretch of
the window, in a run of its own).  The numbers compared for ``correct``
close standard error, each beside its limit.

It needs the TPU chips the cell asks for: without them it exits non-zero
before it builds anything, and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def require_chips(n: int):
    """The first ``n`` TPU devices; exits non-zero without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform is "
                         f"{devs[0].platform!r}); the benchmark runs only on "
                         f"a TPU")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} TPU chips, found "
                         f"{len(devs)}")
    return devs[:n]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="check the fp8 control's tokens in place of the "
                        "served ones; such a run has to come out not "
                        "correct")
    args = p.parse_args(argv)

    from bench.harness.spec import Cell, load_bench
    cell = Cell(load_bench(ROOT), args.workload)
    devices = require_chips(cell.chips)

    from bench.harness.cell import run_cell, set_compile_cache
    cache = set_compile_cache(ROOT)
    print(f"compile cache {cache}", flush=True)

    def log(msg):
        print(msg, flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   T_START, log=log, control=bool(args.control))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
