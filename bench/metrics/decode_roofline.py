"""Decode step program (``decode_b*``) against its roofline: the least
time of each traced decode dispatch on one chip -- the weights per chip
plus the KV of each real row's real context, or its FLOPs if larger --
over the device-0 time of the program.  The slot cache makes the program
read every slot's whole ``max_len``, so this share starts low on purpose.
Moves ``itl_p95_ms``."""


def read(ctx):
    return ctx.program_share("decode_b", "decode")
