"""The weave rule (DESIGN.md §14) pinned on the step programs the chip
benchmark compiles.

For every step program of the three cells of ``BENCHMARK.json`` — each
``(rows, chunk)`` prefill shape ``bench.harness.cell.prefill_shapes``
emits, plus the cell's decode step over all its slots —
``weave_decision_info`` and ``comm_mode_for`` must give the decision in
the table below.  The table was read off the engine before the weave
rule replaced the overlap-plan layer, so a change to the rule that would
recompile a benchmark program differently fails here first.
"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# cell -> program -> (weave, split, reason, axis, comm_norm mode)
PROGRAMS = {
    "qwen1.5-4b-tp1.code": {
        "prefill_b1_s256": (False, None, "below_min_tokens", "seq", "fused"),
        "prefill_b2_s256": (True, (128, 128), "split", "seq", "fused"),
        "prefill_b3_s256": (False, None, "below_wave_floor", "seq", "fused"),
        "prefill_b1_s512": (True, (256, 256), "split", "seq", "fused"),
        "prefill_b2_s512": (True, (256, 256), "split", "seq", "fused"),
        "prefill_b1_s768": (True, (256, 512), "split", "seq", "fused"),
        "prefill_b1_s1024": (True, (512, 512), "split", "seq", "fused"),
        "decode_b3": (False, None, "below_min_tokens", "batch", "fused"),
    },
    "qwen1.5-4b-tp1.batch_gen": {
        "prefill_b1_s256": (False, None, "below_min_tokens", "seq", "fused"),
        "prefill_b2_s256": (True, (128, 128), "split", "seq", "fused"),
        "prefill_b3_s256": (False, None, "below_wave_floor", "seq", "fused"),
        "prefill_b1_s512": (True, (256, 256), "split", "seq", "fused"),
        "prefill_b2_s512": (True, (256, 256), "split", "seq", "fused"),
        "decode_b3": (False, None, "below_min_tokens", "batch", "fused"),
    },
    "qwen3-14b-tp4-2x2.code": {
        "prefill_b1_s256": (False, None, "below_min_tokens", "seq", "fused"),
        "prefill_b2_s256": (True, (128, 128), "split", "seq", "fused"),
        "prefill_b3_s256": (False, None, "below_wave_floor", "seq", "fused"),
        "prefill_b4_s256": (True, (128, 128), "split", "seq", "fused"),
        "prefill_b5_s256": (False, None, "below_wave_floor", "seq", "fused"),
        "prefill_b6_s256": (True, (128, 128), "split", "seq", "fused"),
        "prefill_b7_s256": (False, None, "below_wave_floor", "seq", "fused"),
        "prefill_b8_s256": (True, (128, 128), "split", "seq", "fused"),
        "prefill_b1_s512": (True, (256, 256), "split", "seq", "fused"),
        "prefill_b2_s512": (True, (256, 256), "split", "seq", "fused"),
        "prefill_b3_s512": (True, (256, 256), "split", "seq", "fused"),
        "prefill_b4_s512": (True, (256, 256), "split", "seq", "fused"),
        "prefill_b1_s768": (True, (256, 512), "split", "seq", "fused"),
        "prefill_b2_s768": (True, (384, 384), "split", "seq", "fused"),
        "prefill_b1_s1024": (True, (512, 512), "split", "seq", "fused"),
        "prefill_b2_s1024": (True, (512, 512), "split", "seq", "fused"),
        "prefill_b1_s1280": (True, (512, 768), "split", "seq", "fused"),
        "prefill_b1_s1536": (True, (768, 768), "split", "seq", "fused"),
        "prefill_b1_s1792": (True, (768, 1024), "split", "seq", "fused"),
        "prefill_b1_s2048": (True, (1024, 1024), "split", "seq", "fused"),
        "decode_b32": (True, (16, 16), "split", "batch", "fused"),
    },
}

CASES = [(cell, prog) for cell, progs in PROGRAMS.items() for prog in progs]


def _cell(name):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from bench.harness.spec import Cell, load_bench
    return Cell(load_bench(REPO), name)


def _shape(program):
    """``prefill_b{rows}_s{chunk}`` -> (rows, chunk, False);
    ``decode_b{rows}`` -> (rows, 1, True)."""
    if program.startswith("decode_b"):
        return int(program[len("decode_b"):]), 1, True
    rows, chunk = program[len("prefill_b"):].split("_s")
    return int(rows), int(chunk), False


@pytest.mark.parametrize("cell,program", CASES,
                         ids=[f"{c}-{p}" for c, p in CASES])
def test_weave_rule_pins_bench_programs(cell, program):
    from repro.models.transformer import comm_mode_for, weave_decision_info
    c = _cell(cell)
    sv = c.config["serving"]
    pcfg = c.module("adapters").parallel_config(sv)
    tp = sv["tp"]
    rows, chunk, decode = _shape(program)
    info = weave_decision_info(rows, chunk, tp=tp, pcfg=pcfg, decode=decode)
    got = (info.weave, info.split, info.reason, info.axis,
           comm_mode_for(pcfg, rows * chunk, tp))
    assert got == PROGRAMS[cell][program]
    assert info.site == ("decode" if decode else "prefill")


def test_weave_rule_table_covers_every_bench_program():
    """The table holds exactly the programs each cell compiles: its
    prefill shapes and one decode step over all its slots."""
    _cell(next(iter(PROGRAMS)))          # puts the repo root on the path
    from bench.harness.cell import prefill_shapes
    from bench.harness.spec import load_bench
    names = [w["name"] for w in load_bench(REPO)["workloads"]]
    assert sorted(names) == sorted(PROGRAMS)
    for name in names:
        c = _cell(name)
        want = [f"prefill_b{r}_s{s}" for r, s in prefill_shapes(c)]
        want.append(f"decode_b{c.config['serving']['max_batch']}")
        assert sorted(PROGRAMS[name]) == sorted(want), name


def test_scheduler_config_plan_path_accepts_only_none():
    """There is no plan to load: ``plan_path`` stays for configurations
    that still carry the key, as None, and any path is refused."""
    from repro.runtime.scheduler import SchedulerConfig
    assert SchedulerConfig(plan_path=None).plan_path is None
    with pytest.raises(ValueError, match="plan_path"):
        SchedulerConfig(plan_path="benchmarks/plans/default.json")
