"""The program's own host spans in a profiler trace.

``Engine.step`` marks its phases with ``jax.profiler.TraceAnnotation``
spans named ``engine/...`` (``repro.obs.spans``): ``engine/step`` holding
``engine/schedule`` (with ``engine/admit``), then per dispatch
``engine/prepare`` (with ``engine/rng``), ``engine/dispatch``,
``engine/sync`` and ``engine/commit``.  ``trace.load`` keeps the
benchmark's ``bench/`` spans only; ``load`` here keeps both, in one sorted
``host`` list, so ``cell.breakdown`` labels each idle gap with the engine
phase it fell in and the readers of ``engine_host_ms`` and
``device_idle_share.host`` find their spans.  A program without the spans
gives the same trace as ``trace.load``.
"""
from __future__ import annotations

from typing import List

from bench.harness import trace as TR

PREFIX = "engine/"


def spans(path: str) -> List[TR.Event]:
    """The ``engine/`` events of the trace's host planes, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(TR.Event(ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        ev.name, TR._stats(ev)))
    out.sort(key=lambda e: e.start)
    return out


def with_spans(tr: TR.Trace, path: str) -> TR.Trace:
    """``tr``, loaded from ``path``, with the program's ``engine/`` spans
    among ``host``."""
    tr.host = sorted(tr.host + spans(path), key=lambda e: e.start)
    return tr


def load(path: str) -> TR.Trace:
    return with_spans(TR.load(path), path)


def in_stretch(ctx, name: str) -> List[TR.Event]:
    """Spans called ``name`` that lie inside the traced stretch."""
    return [e for e in ctx.trace.host
            if e.name == name and ctx.lo <= e.start and e.end <= ctx.hi]
