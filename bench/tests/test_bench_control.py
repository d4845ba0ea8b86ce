"""The lower-precision control comes out not correct through the harness's
own check where the program comes out correct: at a tiny bf16 size on the
CPU, a run whose checked tokens are those the plain reference computed
with fp8 matmuls (the control) puts first fails the limit, and the same run
checking the served tokens passes it.  (At the cells' own sizes the
control was run on the chip through ``bench/run.py --control 1``; PERF.md
gives the readings.)"""
import time

import jax
import pytest

from bench.harness.cell import run_cell
from bench.harness.spec import Cell
from bench.tests import tiny_run


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("seed", [1, 2])
def test_control_fails_where_program_passes(seed, control):
    cell = Cell(tiny_run.bench("tiny-bf16", "tiny-open"),
                "tiny-bf16.tiny-open", data_dir=tiny_run.DATA)
    out = run_cell(cell, seed, 1.5, False, jax.devices(),
                   time.perf_counter(), log=lambda m: None, control=control)
    gap = out["checks"]["max_logit_gap"]
    assert out["correct"] is not control, out["checks"]
    if control:
        assert out["served_tokens"] >= 100
        assert gap["value"] > 2 * gap["limit"]
        assert out["program_max_logit_gap"] <= gap["limit"]
