"""Share of the traced stretch in which device 0 ran no operation:
1 - |union of XLA Ops intervals| / stretch.  Moves ``itl_p95_ms``."""
from bench.harness import trace as TR


def read(ctx):
    if ctx.dev0 is None or ctx.window_ns <= 0 or not ctx.ops():
        return None
    return 100.0 * (1.0 - TR.length(ctx.busy()) / ctx.window_ns)
