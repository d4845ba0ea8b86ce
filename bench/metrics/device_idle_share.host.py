"""The part of ``device_idle_share`` the host causes: device 0's idle time
in the traced stretch that no ``engine/sync`` span covers (the host was
scheduling, preparing inputs, dispatching or committing, not waiting on
the device), over the stretch.  The rest of the idle time is bubbles
between the device's own operations while the host waits.  It reads the
program's ``engine/`` spans among the trace's host events
(``harness/engine_spans.py``); None where there are none.  Moves
``itl_p95_ms``."""
from bench.harness import trace as TR


def read(ctx):
    syncs = [e for e in ctx.trace.host if e.name == "engine/sync"]
    if ctx.dev0 is None or ctx.window_ns <= 0 or not ctx.ops() or \
            not syncs:
        return None
    idle = TR.gaps(ctx.busy(), ctx.lo, ctx.hi)
    host = TR.subtract(idle, TR.clip(syncs, ctx.lo, ctx.hi))
    return 100.0 * TR.length(host) / ctx.window_ns
