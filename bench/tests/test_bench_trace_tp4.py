"""The collective readers on a small trace recorded on a TPU v5e 2x2
(``bench/tools/record_trace.py`` on the four-chip host: the tiny model at
tensor parallelism 4, two requests, four engine steps inside the
``bench/traced`` span), checked against direct sweeps over the raw
events; and their silence on the one-chip trace, which holds no
collective."""
import os

import pytest

from bench.harness import trace as TR
from bench.harness.cell import TraceContext
from bench.harness.peaks import peaks
from bench.harness.spec import load_module
from bench.tests.test_bench_spans import _sweep
from bench.tests.test_bench_trace import TINY, TP1, _Cell, _Drv

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TP4 = os.path.join(HERE, "data", "traces", "tiny_tp4.xplane.pb")
# the engine steps recorded (record_trace.py's steps.json): four warm-up
# steps of the same shapes, then the four traced ones
STEPS_TP4 = [
    {"i": 0, "prefill": [(0, 256, 1)], "decode": []},
    {"i": 1, "prefill": [(0, 100, 1)], "decode": [(256, 1, 1), (100, 1, 1)]},
    {"i": 2, "prefill": [], "decode": [(257, 1, 1), (101, 1, 1)]},
    {"i": 3, "prefill": [], "decode": [(258, 1, 1), (102, 1, 1)]},
    {"i": 4, "prefill": [(0, 256, 1)], "decode": []},
    {"i": 5, "prefill": [(0, 100, 1)], "decode": [(256, 1, 1), (100, 1, 1)]},
    {"i": 6, "prefill": [], "decode": [(257, 1, 1), (101, 1, 1)]},
    {"i": 7, "prefill": [], "decode": [(258, 1, 1), (102, 1, 1)]}]


class _Cell4(_Cell):
    config = dict(TINY, serving={"tp": 4})


class _Drv4:
    steps = STEPS_TP4


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"), name)


@pytest.fixture(scope="module")
def ctx():
    return TraceContext(TR.load(TP4), _Drv4(), _Cell4(), peaks("TPU v5 lite"),
                        4)


def test_four_chips_and_four_steps(ctx):
    assert ctx.devices == [0, 1, 2, 3]
    assert [s["i"] for s in ctx.steps] == [4, 5, 6, 7]
    progs = [p.name.split("(")[0] for s in ctx.steps for p in s["programs"]]
    assert {"jit_prefill_b1_s256", "jit_prefill_b1_s128",
            "jit_decode_b4"} <= set(progs)


def test_the_trace_names_reduce_scatter_and_all_gather(ctx):
    opcodes = {TR.op_parts(e.name)[1] for e in ctx.leaf_ops()
               if TR.is_collective(e)}
    assert {"reduce-scatter", "all-gather", "all-reduce"} <= opcodes


def test_collective_exposed_share(ctx):
    ops = ctx.leaf_ops()
    coll = [(e.start, e.end) for e in ops if TR.is_collective(e)]
    other = [(e.start, e.end) for e in ops if not TR.is_collective(e)]
    busy = TR.length(ctx.busy())
    want = 100 * _sweep(ctx.lo, ctx.hi, coll, other) / busy
    got = _reader("collective_exposed_share").read(ctx)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0 < got <= 100


def test_collective_exposed_share_inside_prefill(ctx):
    r = _reader("collective_exposed_share.prefill")
    ops = ctx.leaf_ops()
    coll = [(e.start, e.end) for e in ops if r.is_collective(e)]
    other = [(e.start, e.end) for e in ops if not r.is_collective(e)]
    progs = [p for s in ctx.steps for p in s["programs"]
             if p.name.startswith("jit_prefill_")]
    assert len(progs) == 2
    exposed = sum(_sweep(p.start, p.end, coll, other) for p in progs)
    total = sum(p.end - p.start for p in progs)
    got = r.read(ctx)
    assert got == pytest.approx(100 * exposed / total, rel=1e-9)
    assert 0 < got <= 100


def test_collective_fusions_count_and_collective_matmuls_do_not():
    """Op names as the v5e 2x2 trace of qwen3-14b's prefill shows them."""
    r = _reader("collective_exposed_share.prefill")

    def ev(name):
        return TR.Event(0, 1, name, {})
    assert r.is_collective(ev(
        "%fusion.7 = bf16[264,5120]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[1024,"
        "5120]{1,0} %slice_bitcast_fusion), kind=kCustom, "
        "calls=%all-reduce-scatter.2"))
    assert r.is_collective(ev(
        "%reduce_scatter.58 = bf16[192,5120]{1,0} reduce-scatter(bf16[768,"
        "5120]{1,0} %slice_bitcast_fusion.1), channel_id=1"))
    assert not r.is_collective(ev(
        "%fusion.336 = (bf16[768,4352]{1,0}, bf16[192,5120]{1,0}) fusion("
        "bf16[192,5120]{1,0} %p), kind=kCustom, "
        "calls=%async_collective_fusion.336"))
    assert not r.is_collective(ev(
        "%fusion.294 = bf16[1024,4352]{1,0} fusion(bf16[1024,5120]{1,0} "
        "%async-collective-done, bf16[40,1,5120,4352]{3,2,1,0} %w), "
        "kind=kOutput, calls=%fused_computation.294"))


def test_no_collective_share_on_one_chip():
    one = TraceContext(TR.load(TP1), _Drv(), _Cell(), peaks("TPU v5 lite"), 1)
    assert _reader("collective_exposed_share").read(one) is None
    assert _reader("collective_exposed_share.prefill").read(one) is None


def test_dispatch_spans_carry_weave_and_comm():
    """The program's ``engine/dispatch`` spans on the chip name each step
    program's token parts and comm mode, as the engine's weave decision
    gives them for the recorded shapes (a 256-token weave unit at tp 4:
    no chunk of 256 or fewer tokens splits; every shape divides over 4
    chips, so every site runs fused)."""
    from bench.harness import engine_spans as ES
    got = [(e.stats["program"], e.stats["weave"], e.stats["comm"])
           for e in ES.spans(TP4) if e.name == "engine/dispatch"]
    assert got == [("prefill_b1_s256", 1, "fused"),
                   ("prefill_b1_s128", 1, "fused")] + \
        [("decode_b4", 1, "fused")] * 3
