"""Measured-time profiler: wall-clock per-forward timings (DESIGN.md §13).

Everything else in obs/ runs on *virtual* time — the §9 sim roofline
prices each forward and the trace/metrics record those estimates.  The
``WallClockProfiler`` adds the missing ground truth: the engine hands it
each dispatch's wall time, taken at the same phase boundaries as its
profiler-clock spans (obs/spans.py) — from the start of ``engine/dispatch``
(the jitted call) to the end of ``engine/sync`` (the host's ``np.asarray``
of the outputs, which waits for the program) — and the profiler joins it
to the SAME ``WeaveAttribution`` record the engine emits for that forward,
so every sample carries (tokens, mode, split, method) next to its wall
seconds.  Device work queued on the host just before a dispatch (a slot
reset, pool fix-ups) is counted in that dispatch: nothing is drained
first, so timing never changes the run it measures.

Jit compilation is excluded by construction: the first
``warmup_per_key`` calls of each compiled shape signature
(kind, batch, seq) are flagged ``warmup=True`` and dropped from the
steady-state statistics (they still appear in ``samples`` for
inspection, and a ``profile/warmup_excluded`` counter records how many
were dropped).

Steady samples land in three places:

  * ``MetricsRegistry``: ``profile/forward_us{mode=...,weave=...}``
    histograms (microseconds);
  * the Chrome trace: a parallel ``<track> [measured]`` track with
    ``cat="measured"`` complete spans (1 tick = 1 wall second, matching
    the virtual-time scale) so Perfetto shows measured durations next to
    the virtual spans they ground;
  * ``steady_samples()``: the raw joined records that
    ``analysis.calibration.fit_calibration`` consumes.

The profiler is pull-only: it never changes what the engine computes, so
profiled and unprofiled runs are token- and step-identical —
tests/test_profiler.py asserts this over the 25-trace differential corpus.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.obs.attribution import WeaveAttribution
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import TraceRecorder

MEASURED_TRACK_SUFFIX = " [measured]"
MEASURED_CAT = "measured"


@dataclasses.dataclass(frozen=True)
class MeasuredForward:
    """One timed dispatch joined to its weave-attribution record."""
    key: Tuple           # (kind, b, s) — the compiled shape signature
    kind: str            # prefill | decode | verify | packed
    method: str          # tokenweave | fuseonly | reordered | vanilla
    weave: bool
    tokens_static: int   # b * s — what the split decision saw
    tokens_real: int     # non-pad tokens committed by this forward
    split: Optional[Tuple[int, int]]
    wall_s: float        # wall seconds: the call plus the wait for outputs
    est_makespan: float  # §9 roofline prediction under the DEFAULT HW
    warmup: bool         # jit compile / first call on this shape: excluded


class WallClockProfiler:
    """Records engine dispatch times joined to their attribution.

    Lifecycle (all driven by the engine, see runtime/engine.py):

      1. ``attach(registry, trace=..., track=...)`` binds the sinks;
      2. ``commit(att, wall_s)`` — called from the engine's single
         per-dispatch accounting site (``_note_forward``), right after the
         dispatch it times — records the joined ``MeasuredForward``.
    """

    def __init__(self, warmup_per_key: int = 1):
        self.warmup_per_key = max(int(warmup_per_key), 0)
        self.samples: List[MeasuredForward] = []
        self._seen: Dict[Tuple, int] = {}
        self._registry: Optional[MetricsRegistry] = None
        self._trace: Optional[TraceRecorder] = None
        self._track = "engine"

    # -- wiring ----------------------------------------------------------
    def attach(self, registry: Optional[MetricsRegistry] = None, *,
               trace: Optional[TraceRecorder] = None,
               track: str = "engine") -> "WallClockProfiler":
        self._registry = registry
        self._trace = trace
        self._track = track
        return self

    # -- join ------------------------------------------------------------
    def commit(self, att: Optional[WeaveAttribution],
               wall_s: float) -> None:
        """Join one dispatch's wall time to its attribution."""
        if att is None:
            return
        skey = (att.kind, att.b, att.s)
        seen = self._seen.get(skey, 0)
        self._seen[skey] = seen + 1
        warmup = seen < self.warmup_per_key
        self.samples.append(MeasuredForward(
            key=skey, kind=att.kind, method=att.method, weave=att.weave,
            tokens_static=att.tokens_static, tokens_real=att.tokens_real,
            split=att.split, wall_s=wall_s,
            est_makespan=att.est_makespan, warmup=warmup))
        if warmup:
            if self._registry is not None:
                self._registry.counter("profile/warmup_excluded").inc()
            return
        if self._registry is not None:
            self._registry.histogram(
                "profile/forward_us", mode=att.kind,
                weave="on" if att.weave else "off").observe(wall_s * 1e6)
        if self._trace is not None:
            args = att.args()
            args["measured_us"] = round(wall_s * 1e6, 3)
            args["est_makespan"] = att.est_makespan
            self._trace.complete(
                self._track + MEASURED_TRACK_SUFFIX,
                f"measured/{att.kind}", self._trace.now, wall_s,
                cat=MEASURED_CAT, args=args)

    # -- readout ---------------------------------------------------------
    def steady_samples(self) -> List[MeasuredForward]:
        """Samples past the per-shape warmup window (calibration input)."""
        return [s for s in self.samples if not s.warmup]

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-kind steady-state totals: count / total / mean wall sec."""
        out: Dict[str, Dict[str, float]] = {}
        for s in self.steady_samples():
            row = out.setdefault(s.kind, {"n": 0, "total_s": 0.0})
            row["n"] += 1
            row["total_s"] += s.wall_s
        for row in out.values():
            row["mean_s"] = row["total_s"] / row["n"]
        return out
