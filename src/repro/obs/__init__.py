"""Weave-aware observability layer (DESIGN.md §12, §13).

Five pieces; the virtual-clock ones cost nothing when off:

* ``metrics``      — typed registry (counters / gauges / histograms with
                     labels) that ``Engine``, ``OnlineServer`` and
                     ``ClusterServer`` publish through; ``snapshot()``
                     feeds the CI-gated benchmark metrics.
* ``trace``        — ``TraceRecorder`` structured events + nested spans on
                     the deterministic virtual clock, exported as
                     Chrome-trace / Perfetto JSON
                     (``export_chrome_trace``), one track per replica plus
                     a per-request lifecycle track.
* ``attribution``  — the per-forward weave-decision record: tokens seen,
                     threshold, split chosen, overlap method, and the
                     §9 sim-roofline estimate of compute / comm /
                     overlapped virtual time, so ``EngineStats.weave_rate``
                     is derivable from the trace (DESIGN.md §12).
* ``spans``        — ``span(name, **stats)``: host intervals on the
                     wall clock of a ``jax.profiler`` trace, in the same
                     file as the device's operations.  ``Engine.step``'s
                     phases are spans, always on: about 1 µs a span while
                     no profile is collected (~10 a step), nothing
                     recorded (DESIGN.md §12).
* ``profiler``     — ``WallClockProfiler`` joins each dispatch's wall time,
                     taken at the engine's ``engine/dispatch`` →
                     ``engine/sync`` span boundaries, to the attribution
                     record, feeding the ``analysis.calibration`` cost-model
                     fit and the ``[measured]`` trace track (DESIGN.md §13).
"""
from repro.obs.attribution import Attributor, WeaveAttribution
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               percentile)
from repro.obs.profiler import (MEASURED_CAT, MeasuredForward,
                                WallClockProfiler)
from repro.obs.spans import span
from repro.obs.trace import (TERMINAL_PHASES, TraceRecorder,
                             export_chrome_trace, validate_chrome_trace,
                             weave_counts_from_trace)

__all__ = [
    "Attributor", "WeaveAttribution",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "percentile",
    "MEASURED_CAT", "MeasuredForward", "WallClockProfiler", "span",
    "TERMINAL_PHASES", "TraceRecorder", "export_chrome_trace",
    "validate_chrome_trace", "weave_counts_from_trace",
]
