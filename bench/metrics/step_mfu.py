"""Whole-step share of peak: model FLOPs of the real tokens the traced
``Engine.step`` calls processed (bench/costs: weights, attention over each
token's real context, LM head at sampled positions), over the summed host
wall time of those steps x chips x peak bf16 FLOP/s.  Moves
``itl_p95_ms``."""


def share(ctx, steps):
    """The share over ``steps`` (entries of ``ctx.steps``); None where they
    did no work."""
    flops = wall = 0.0
    for st in steps:
        if not st["prefill"] and not st["decode"]:
            continue
        flops += ctx.cost.flops(ctx.cfg, st["prefill"])
        flops += ctx.cost.flops(ctx.cfg, st["decode"])
        wall += (st["t1"] - st["t0"]) * 1e-9
    if wall <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (wall * ctx.chips * ctx.peak["bf16_flops"])


def read(ctx):
    return share(ctx, ctx.steps)
