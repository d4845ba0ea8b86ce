"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to intervals.

What is read:

* device planes ``/device:TPU:<n>``: the ``XLA Ops`` line (one event per
  HLO operation run on the chip) and the ``XLA Modules`` line (one event
  per compiled program run, named after the jitted function, e.g.
  ``jit_prefill_b1_s1024``);
* host planes: the benchmark's own ``TraceAnnotation`` spans, whose names
  start with ``bench/``.

Times are in nanoseconds on the profiler's clock, which it shares between
host and device planes.  Every reduction to a number lives here or in a
metric reader, so each PR computes the same number the same way.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

_DEV = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all|"
    r"allreduce|reducescatter|allgather|collective")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    start: float
    end: float
    name: str
    stats: dict


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]          # device id -> XLA Ops events
    modules: Dict[int, List[Event]]      # device id -> XLA Modules events
    host: List[Event]                    # bench/ spans


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:
        return {}


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = _DEV.match(plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                if line.name == "XLA Ops":
                    dst = ops.setdefault(dev, [])
                elif line.name == "XLA Modules":
                    dst = modules.setdefault(dev, [])
                else:
                    continue
                for ev in line.events:
                    dst.append(Event(ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name, _stats(ev)))
            elif plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        host.append(Event(ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          ev.name, _stats(ev)))
    for d in (ops, modules):
        for evs in d.values():
            evs.sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host)


def summary(path: str, n: int = 4) -> str:
    """Planes, lines and a few events of each: for looking at a trace by
    hand before trusting code written against it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:n]:
                out.append(f"    {ev.name!r} start {ev.start_ns} dur "
                           f"{ev.duration_ns} stats {_stats(ev)}")
    return "\n".join(out)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def clip(evs: List[Event], lo: float, hi: float) -> List[Interval]:
    return [(max(e.start, lo), min(e.end, hi)) for e in evs
            if e.end > lo and e.start < hi]


def union(ivs: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(ivs: List[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


def op_parts(name: str):
    """(instruction, opcode, result shape) of an ``XLA Ops`` event, whose
    name on a TPU is the HLO text ``%fusion.3 = bf16[3,2560]{..} fusion(..)``
    (a bare name elsewhere)."""
    if " = " not in name:
        return name, "", ""
    head, rest = name.split(" = ", 1)
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    shape = "" if rest.startswith("(") else _LAYOUT.sub(
        "", rest.split(" ", 1)[0])
    return head.lstrip("%"), opcode, shape


def short_name(name: str) -> str:
    """Instruction name without its number, with its result shape."""
    inst, _, shape = op_parts(name)
    return f"{re.sub(r'[.]\d+$', '', inst)} {shape}".strip()[:100]


def is_collective(ev: Event) -> bool:
    inst, opcode, _ = op_parts(ev.name)
    return bool(_COLLECTIVE.search(f"{inst} {opcode}".lower()))


def nesting(evs: List[Event]):
    """Events of one line may nest (a ``while`` holds its body's ops).
    Returns (leaves, self times): the events that hold no other event, and
    for every event its time not covered by the events nested in it."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i].start,
                                                   -evs[i].end))
    kids: Dict[int, List[Interval]] = {}
    stack: List[int] = []
    for i in order:
        e = evs[i]
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            kids.setdefault(stack[-1], []).append((e.start, min(e.end,
                                                  evs[stack[-1]].end)))
        stack.append(i)
    leaves = [e for i, e in enumerate(evs) if i not in kids]
    own = [evs[i].end - evs[i].start - length(union(kids.get(i, [])))
           for i in range(len(evs))]
    return leaves, own
