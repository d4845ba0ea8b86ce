"""Tensor-parallel collectives left exposed inside the prefill step
programs (``prefill_b*_s*``), where the weave splits a chunk into two
token halves so that one half's reduce-scatter and all-gather can run
under the other half's compute: the time on device 0 covered by
collective operations and by no other operation, inside the traced
steps' prefill programs (found as ``TraceContext.program_share`` finds
them), over those programs' device time.  Operations are the innermost
ones of the ``XLA Ops`` line.  None where the traced steps ran no
prefill program, or their prefill programs hold no collective (one
chip).  Moves ``ttft_p90_ms``.

A collective is what ``trace.is_collective`` names one (reduce-scatter,
all-gather, all-reduce, collective-permute and the async start/done
ops), and also a fusion whose called computation is a collective: on a
v5e 2x2 the compiler emits part of the prefill's reduce-scatters as
``%fusion.N = ... fusion(...), kind=kCustom, calls=%all-reduce-scatter.M``,
which the op's own name and opcode do not show.  A fusion that calls
``%async_collective_fusion.N`` is a matmul that carries an asynchronous
all-gather in flight: compute, not a collective."""
import re

from bench.harness import trace as TR

_CALLS = re.compile(r"calls=%(?:all-reduce-scatter|reduce-scatter|"
                    r"all-gather|all-reduce|all-to-all|collective-permute)"
                    r"\b")


def is_collective(ev) -> bool:
    return TR.is_collective(ev) or bool(_CALLS.search(ev.name))


def _prefill(name: str) -> bool:
    n = name.split("(")[0]
    return (n[4:] if n.startswith("jit_") else n).startswith("prefill_")


def read(ctx):
    progs = [p for st in ctx.steps for p in st["programs"]
             if _prefill(p.name)]
    if not progs:
        return None
    ops = ctx.leaf_ops()
    coll = [e for e in ops if is_collective(e)]
    other = [e for e in ops if not is_collective(e)]
    exposed = total = 0.0
    n_coll = 0
    for p in progs:
        inside = TR.clip(coll, p.start, p.end)
        n_coll += len(inside)
        exposed += TR.length(TR.subtract(inside,
                                         TR.clip(other, p.start, p.end)))
        total += p.end - p.start
    if not n_coll or total <= 0:
        return None
    return 100.0 * exposed / total
