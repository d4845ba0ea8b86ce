"""Queue wait: p90, over the window's requests, of the time from a
request's first ``Engine.add_request`` to its first admission to a slot
(the program's host-clock stamps ``enqueued_s`` and ``admitted_s``), in
ms.  It reads ``ctx.records``, the window's requests; None where the
context carries none or the program stamps none.  Moves
``ttft_p90_ms``."""
from bench.harness.cell import percentile


def read(ctx):
    waits = []
    for rec in getattr(ctx, "records", None) or ():
        q = getattr(rec.req, "enqueued_s", None)
        a = getattr(rec.req, "admitted_s", None)
        if q is not None and a is not None:
            waits.append(a - q)
    return 1e3 * percentile(waits, 90) if waits else None
