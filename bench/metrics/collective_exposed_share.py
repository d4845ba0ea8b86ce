"""Tensor-parallel collectives (``comm_norm``'s reduce-scatter and
all-gather, the sampler's reductions) left exposed on device 0: the time
covered by collective operations (synchronous ones, and the start/done ops
of asynchronous ones) and by no other operation, over the device's busy
time in the traced stretch.  Operations are the innermost ones of the
``XLA Ops`` line: a loop that holds them covers them and is not counted.  None where the trace holds no
collective (one chip).  Moves ``ttft_p90_ms``."""
from bench.harness import trace as TR


def read(ctx):
    ops = ctx.leaf_ops()
    coll = [e for e in ops if TR.is_collective(e)]
    busy = TR.length(ctx.busy())
    if not coll or busy <= 0:
        return None
    other = [e for e in ops if not TR.is_collective(e)]
    exposed = TR.subtract(TR.clip(coll, ctx.lo, ctx.hi),
                          TR.clip(other, ctx.lo, ctx.hi))
    return 100.0 * TR.length(exposed) / busy
