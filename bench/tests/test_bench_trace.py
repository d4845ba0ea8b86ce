"""The trace reduction, run on a small trace recorded on a TPU v5e chip
(``bench/tools/record_trace.py``: a tiny model, two requests, four engine
steps inside the ``bench/traced`` span), gives the numbers worked out from
the raw events by a direct sweep (in the comments)."""
import os

import pytest

from bench.harness import trace as TR
from bench.harness.cell import TraceContext
from bench.harness.peaks import peaks
from bench.harness.spec import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TP1 = os.path.join(HERE, "data", "traces", "tiny_tp1.xplane.pb")
TINY = {"name": "tiny", "kind": "dense_transformer", "hidden_size": 512,
        "intermediate_size": 1024, "num_hidden_layers": 2,
        "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 64,
        "vocab_size": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "torch_dtype": "bfloat16", "qkv_bias": False, "qk_norm": True,
        "serving": {"tp": 1}}
# the engine steps recorded (bench/tools/record_trace.py, one chip): rows
# are (first position, new tokens, sampled positions)
STEPS_TP1 = [{"i": i, "prefill": [], "decode": []} for i in range(4)] + [
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
    steps = STEPS_TP1


def _metric(name, ctx):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       name).read(ctx)


@pytest.fixture(scope="module")
def ctx():
    return TraceContext(TR.load(TP1), _Drv(), _Cell(), peaks("TPU v5 lite"),
                        1)


def test_stretch_and_steps(ctx):
    assert ctx.window_ns == 38_783_067
    assert [s["i"] for s in ctx.steps] == [4, 5, 6, 7]


def test_idle_share(ctx):
    # union of device-0 op intervals inside the stretch: 482,347 ns
    assert TR.length(ctx.busy()) == 482_347
    assert _metric("device_idle_share", ctx) == pytest.approx(
        100 * (1 - 482_347 / 38_783_067), rel=1e-12)


def test_program_times(ctx):
    # prefill_b1_s256 129,819 ns (step 4) + prefill_b1_s128 97,111 (step 5)
    # decode_b4 80,570 + 80,432 + 80,961 ns (steps 5, 6, 7)
    def dev(prefix):
        return sum(p.end - p.start for s in ctx.steps for p in s["programs"]
                   if p.name.startswith("jit_" + prefix))
    assert dev("prefill_b") == 129_819 + 97_111
    assert dev("decode_b") == 80_570 + 80_432 + 80_961
    need = (ctx.roofline_s([(0, 256, 1)]) + ctx.roofline_s([(0, 100, 1)]))
    assert _metric("prefill_roofline", ctx) == pytest.approx(
        100 * need / (226_930e-9), rel=1e-12)
    need = sum(ctx.roofline_s(s["decode"]) for s in ctx.steps[1:])
    assert _metric("decode_roofline", ctx) == pytest.approx(
        100 * need / (241_963e-9), rel=1e-12)


def test_step_mfu_is_a_share_of_the_step_wall_time(ctx):
    c = ctx.cost
    flops = sum(c.flops(TINY, s["prefill"]) + c.flops(TINY, s["decode"])
                for s in ctx.steps)
    wall = sum(s["t1"] - s["t0"] for s in ctx.steps) * 1e-9
    assert _metric("step_mfu", ctx) == pytest.approx(
        100 * flops / (wall * 197e12), rel=1e-12)


def test_prefill_step_mfu_reads_the_steps_that_carry_a_chunk(ctx):
    c = ctx.cost
    pre = [s for s in ctx.steps if s["prefill"]]
    assert 0 < len(pre) < len(ctx.steps)
    flops = sum(c.flops(TINY, s["prefill"]) + c.flops(TINY, s["decode"])
                for s in pre)
    wall = sum(s["t1"] - s["t0"] for s in pre) * 1e-9
    assert _metric("step_mfu.prefill", ctx) == pytest.approx(
        100 * flops / (wall * 197e12), rel=1e-12)


def test_no_collective_on_one_chip(ctx):
    assert _metric("collective_exposed_share", ctx) is None


def test_nesting_self_times_cover_busy(ctx):
    evs = ctx.ops()
    leaves, own = TR.nesting(evs)
    assert len(leaves) < len(evs)            # a while loop holds its body
    assert sum(own) == pytest.approx(
        TR.length(TR.union([(e.start, e.end) for e in evs])), rel=1e-12)


def test_interval_arithmetic():
    a = [(0, 10), (5, 12), (20, 30)]
    b = [(2, 3), (8, 22), (29, 40)]
    assert TR.union(a) == [(0, 12), (20, 30)]
    assert TR.subtract(a, b) == [(0, 2), (3, 8), (22, 29)]
    assert TR.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
