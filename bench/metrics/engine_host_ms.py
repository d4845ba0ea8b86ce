"""Host time of ``Engine.step``: the mean, over the engine steps inside the
traced stretch, of each ``engine/step`` span's time not covered by its
``engine/sync`` spans (where the host waits for the device), in ms.  It
reads the program's ``engine/`` spans among the trace's host events
(``harness/engine_spans.py``); None where there are none.  Moves
``itl_p95_ms``."""
from bench.harness import engine_spans as ES
from bench.harness import trace as TR


def read(ctx):
    steps = [e for e in ES.in_stretch(ctx, "engine/step")
             if "kind" in e.stats]          # a step that ran a plan
    if not steps:
        return None
    syncs = [e for e in ctx.trace.host if e.name == "engine/sync"]
    own = [TR.length(TR.subtract([(s.start, s.end)],
                                 TR.clip(syncs, s.start, s.end)))
           for s in steps]
    return 1e-6 * sum(own) / len(own)
