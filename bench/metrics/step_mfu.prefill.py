"""Whole-step share of peak over the traced steps that carry a prefill
chunk (the steps a first token waits on): ``step_mfu``'s arithmetic
restricted to them, decode rows that ride the same step included.  It
bounds what ``prefill_roofline`` can claim end to end.  Moves
``ttft_p90_ms``."""
import os

from bench.harness.spec import load_module

_base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "step_mfu.py"), "metric_step_mfu_base")


def read(ctx):
    return _base.share(ctx, [st for st in ctx.steps if st["prefill"]])
