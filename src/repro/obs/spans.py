"""Profiler-clock spans (DESIGN.md §12).

``span(name, **stats)`` is ``jax.profiler.TraceAnnotation``.  While a
``jax.profiler`` trace is being collected, each span lands as a host event,
with its stats, in the same trace as the device's ``XLA Ops`` and ``XLA
Modules`` lines, on one clock.  While none is being collected a span records
nothing and costs about a microsecond, so the spans in the engine are always
on: "tracing off" means no profile is collected.

These are wall-clock intervals of what the host did, unlike the spans of
``obs.trace.TraceRecorder``, which run on the virtual clock with durations
priced by the sim.  The engine's spans (all named ``engine/...``):

* ``engine/step`` (stats ``i``, ``kind``, ``rows``, ``chunk``) holds
  ``engine/schedule`` (with one ``engine/admit``, stats ``rid`` and
  ``waited_ms``, for each request admitted) and, for each dispatch:
* ``engine/prepare``: numpy inputs, block tables and device puts, with
  ``engine/rng`` around the sampling-key split;
* ``engine/dispatch`` (stats ``program``, the jitted step's name;
  ``first``, 1 on a program's first dispatch in the engine; ``weave``, the
  token parts the program splits into, 1 or 2; ``comm``, the mode its
  ``comm_norm`` sites run: ``fused``, ``vanilla``, ``ring``, ...): the call
  until it returns;
* ``engine/sync``: the host waiting for the program's outputs;
* ``engine/commit``: counters and the per-request commits.
"""
import jax

span = jax.profiler.TraceAnnotation
