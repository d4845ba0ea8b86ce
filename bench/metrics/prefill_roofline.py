"""Prefill step programs (``prefill_b*_s*``) against their roofline: the
summed least time of each traced prefill dispatch on one chip, max(FLOPs /
peak, bytes / HBM bandwidth) for its real tokens and real context
(bench/costs), over the summed device-0 time of those programs.  Moves
``ttft_p90_ms``."""


def read(ctx):
    return ctx.program_share("prefill_b", "prefill")
