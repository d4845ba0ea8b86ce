"""Observability subsystem (src/repro/obs, DESIGN.md §12).

Covers the typed metrics registry (get-or-create, kind collisions,
snapshot flattening), the trace recorder and Chrome-trace export (span
nesting, per-track virtual-clock monotonicity, the validator actually
catching broken traces), the request-lifecycle invariant (exactly one
terminal event per admitted request — including a cancel landing while
the KV is mid-migration between replicas), and the two hard §12
invariants on the randomized differential corpus: tracing on vs off is
token- and step-count-IDENTICAL, and the weave rate recomputed from the
trace's per-forward attribution records equals ``EngineStats.weave_rate``
exactly.
"""
import numpy as np
import pytest

from repro.obs import (MetricsRegistry, TERMINAL_PHASES, TraceRecorder,
                       export_chrome_trace, percentile,
                       validate_chrome_trace, weave_counts_from_trace)
from repro.runtime.requests import Request, poisson_arrivals

from test_differential import _gen_trace, _drive


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_registry_get_or_create_and_kinds():
    reg = MetricsRegistry()
    c = reg.counter("engine/steps")
    assert reg.counter("engine/steps") is c
    c.inc()
    c.inc(3)
    assert c.value == 4
    g = reg.gauge("engine/weave_rate")
    g.set(0.25)
    g.set_max(0.1)           # running max keeps the larger value
    assert g.value == 0.25
    g.set_max(0.5)
    assert g.value == 0.5
    h = reg.histogram("latency/ttft")
    for v in (3.0, 1.0, 2.0):
        h.observe(v)
    assert h.count == 3 and h.total == 6.0
    assert h.percentile(0.5) == 2.0


def test_registry_kind_collision_is_typeerror():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="counter"):
        reg.gauge("x")


def test_counter_rejects_negative():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)


def test_registry_snapshot_flattening_and_labels():
    reg = MetricsRegistry()
    reg.counter("engine/steps").inc(7)
    reg.counter("engine/steps", replica="d0").inc(2)
    reg.histogram("latency/e2e").observe(4.0)
    snap = reg.snapshot()
    assert snap["engine/steps"] == 7.0
    assert snap["engine/steps{replica=d0}"] == 2.0
    assert snap["latency/e2e/count"] == 1.0
    assert snap["latency/e2e/p50"] == 4.0
    assert "latency/e2e/p90" in snap and "latency/e2e/p99" in snap


def test_percentile_matches_linear_interpolation():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(xs, 0.5) == 2.5
    assert percentile(xs, 0.0) == 1.0
    assert percentile(xs, 1.0) == 4.0
    assert percentile([], 0.5) == 0.0


# --------------------------------------------------------------------------
# recorder + export + validator (hand-built traces)
# --------------------------------------------------------------------------

def _forward_args(**over):
    a = dict(kind="prefill", weave=True, reason="split", tokens=64,
             tokens_real=64, threshold=32, split=[32, 32],
             method="tokenweave",
             est_compute=1.0, est_comm=0.5, est_overlapped=0.4)
    a.update(over)
    return a


def test_span_nesting_valid_trace():
    rec = TraceRecorder()
    rec.complete("eng", "step/packed", 0.0, 2.0, cat="step",
                 args={"step": 0, "forwards": 1})
    rec.complete("eng", "forward/packed", 0.0, 2.0, cat="forward",
                 args=_forward_args())
    rec.request_event(1, "queued", ts=0.0)
    rec.request_event(1, "admit", ts=0.5)
    rec.request_event(1, "finish", ts=2.0)
    assert validate_chrome_trace(export_chrome_trace(rec)) == []


def test_validator_catches_forward_escaping_its_step():
    rec = TraceRecorder()
    rec.complete("eng", "step/packed", 0.0, 1.0, cat="step")
    rec.complete("eng", "forward/packed", 0.5, 2.0, cat="forward",
                 args=_forward_args())
    fails = validate_chrome_trace(export_chrome_trace(rec))
    assert any("step" in f for f in fails)


def test_validator_catches_backwards_timestamps():
    rec = TraceRecorder()
    rec.complete("eng", "step/a", 5.0, 1.0, cat="step")
    rec.complete("eng", "step/b", 1.0, 1.0, cat="step")
    fails = validate_chrome_trace(export_chrome_trace(rec))
    assert any("backwards" in f for f in fails)


def test_validator_requires_attribution_keys():
    rec = TraceRecorder()
    a = _forward_args()
    del a["est_overlapped"]
    rec.complete("eng", "step/packed", 0.0, 1.0, cat="step")
    rec.complete("eng", "forward/packed", 0.0, 1.0, cat="forward", args=a)
    fails = validate_chrome_trace(export_chrome_trace(rec))
    assert any("est_overlapped" in f for f in fails)


def test_validator_catches_missing_terminal_for_admitted_request():
    rec = TraceRecorder()
    rec.request_event(3, "queued", ts=0.0)
    rec.request_event(3, "admit", ts=1.0)      # admitted, never finished
    fails = validate_chrome_trace(export_chrome_trace(rec))
    assert any("terminal" in f.lower() for f in fails)


def test_validator_catches_double_terminal():
    rec = TraceRecorder()
    rec.request_event(3, "queued", ts=0.0)
    rec.request_event(3, "admit", ts=1.0)
    rec.request_event(3, "finish", ts=2.0)
    rec.request_event(3, "cancel", ts=3.0)
    fails = validate_chrome_trace(export_chrome_trace(rec))
    assert any("terminal" in f.lower() for f in fails)


def test_export_merges_recorders_with_distinct_namespaces():
    a = TraceRecorder(request_ns="a/")
    b = TraceRecorder(request_ns="b/")
    for rec in (a, b):
        rec.request_event(0, "queued", ts=0.0)
        rec.request_event(0, "admit", ts=0.0)
        rec.request_event(0, "finish", ts=1.0)
    doc = export_chrome_trace([a, b])
    assert validate_chrome_trace(doc) == []
    names = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev.get("ph") == "M" and ev["name"] == "thread_name"}
    assert {"req a/0", "req b/0"} <= names


# --------------------------------------------------------------------------
# engine integration: zero-cost-off, lifecycle, weave attribution
# --------------------------------------------------------------------------

def test_tracing_is_off_by_default(tiny_engine_builder):
    eng = tiny_engine_builder(paged=True)
    assert eng.obs is None and eng._attributor is None
    eng.add_request(Request(rid=0, prompt=list(range(1, 9)),
                            max_new_tokens=3))
    eng.run()
    assert eng.stats.completed == 1      # stats work without a recorder


def test_offline_engine_trace_validates_and_attributes_every_forward(
        tiny_engine_builder):
    rec = TraceRecorder()
    eng = tiny_engine_builder(paged=True, packed=True, spec_gamma=2,
                              obs=rec)
    for i in range(3):
        eng.add_request(Request(rid=i, prompt=list(range(1, 20 + i)),
                                max_new_tokens=4))
    eng.run()
    doc = export_chrome_trace(rec)
    assert validate_chrome_trace(doc) == []
    w, n = weave_counts_from_trace(rec)
    assert n == eng.stats.forwards > 0
    assert w == eng.stats.weave_forwards
    # one step span per engine step
    steps = [ev for ev in rec.events
             if ev["kind"] == "span" and ev["cat"] == "step"]
    assert len(steps) == eng.stats.steps


def test_online_server_lifecycle_expiry_and_monotonic_clock(
        tiny_engine_builder):
    from repro.runtime.server import OnlineServer, ServerConfig, StepCost
    rec = TraceRecorder(request_ns="online/")
    eng = tiny_engine_builder(paged=True, packed=True, obs=rec)
    srv = OnlineServer(eng, ServerConfig(
        step_cost=StepCost(base=1.0, per_token=0.05),
        expire_on_deadline=True))
    rng = np.random.RandomState(4)
    reqs = [Request(rid=i,
                    prompt=list(rng.randint(0, 128, size=rng.randint(8, 30))),
                    max_new_tokens=6) for i in range(6)]
    for r in poisson_arrivals(reqs, rate=0.4, seed=9):
        r.deadline = r.arrival_time + 5.0    # tight: some expire
        srv.submit(r)
    srv.run()
    assert eng.stats.expired > 0, "deadline chosen to force expiry"
    doc = export_chrome_trace(rec)
    assert validate_chrome_trace(doc) == []
    # exactly one terminal event per request, arrival stamped at
    # arrival_time on the virtual clock
    by_rid = {}
    for ev in rec.events:
        if ev["kind"] == "request":
            by_rid.setdefault(ev["rid"], []).append(ev)
    assert len(by_rid) == 6
    for rid, evs in by_rid.items():
        terms = [e for e in evs if e["phase"] in TERMINAL_PHASES]
        assert len(terms) == 1, (rid, [e["phase"] for e in evs])
    arr = {ev["rid"]: ev["ts"] for ev in rec.events
           if ev["kind"] == "request" and ev["phase"] == "arrival"}
    for r in reqs:
        assert arr[f"online/{r.rid}"] == r.arrival_time


def test_cancel_mid_migration_emits_exactly_one_terminal(tiny_model):
    from repro.runtime.cluster import (ClusterConfig, ClusterServer,
                                       MigrationCost, Replica)
    from repro.runtime.engine import Engine
    from repro.runtime.scheduler import SchedulerConfig

    api, mesh, params = tiny_model
    rec = TraceRecorder(request_ns="cl/")

    def engine():
        return Engine(api, mesh, params,
                      SchedulerConfig(max_batch=4, chunk_tokens=48,
                                      max_len=96, prefill_bucket=16,
                                      paged=True, block_size=8),
                      obs=rec)

    reps = [Replica("p0", engine(), role="prefill"),
            Replica("d0", engine(), role="decode")]
    cs = ClusterServer(reps, ClusterConfig(
        router="round_robin",
        migration_cost=MigrationCost(base=1000.0)))
    req = Request(rid=0, prompt=list(range(1, 21)), max_new_tokens=8)
    req.arrival_time = 0.0
    cs.submit(req)
    cs.cancel(0, at=50.0)       # lands while the KV is "on the wire"
    assert cs.run() == [] and req.finish_reason == "cancelled"

    phases = [ev["phase"] for ev in rec.events if ev["kind"] == "request"]
    assert "handoff_export" in phases, "prefill side must park the handoff"
    assert phases.count("cancel") == 1
    assert sum(phases.count(p) for p in TERMINAL_PHASES) == 1
    assert validate_chrome_trace(export_chrome_trace(rec)) == []
    # replica tracks were renamed from the default
    tracks = {ev["track"] for ev in rec.events if ev["kind"] == "span"}
    assert tracks <= {"p0", "d0"}


# --------------------------------------------------------------------------
# the two hard §12 invariants, on the randomized differential corpus
# --------------------------------------------------------------------------

@pytest.mark.parametrize("trial", range(25))
def test_corpus_identity_and_trace_weave_rate(trial, tiny_engine_builder):
    """Tracing ON vs OFF must be token- and step-count-identical, and the
    weave rate recomputed from the trace's per-forward attribution spans
    must equal ``EngineStats.weave_rate`` EXACTLY — over the same 25
    seeded workloads (mixed prefill, prefix sharing, spec windows,
    mid-flight cancels) the differential harness replays."""
    rng = np.random.RandomState(1000 + trial)
    prompts, outs, gamma, cancels = _gen_trace(rng)
    kw = dict(max_batch=3, chunk_tokens=48, max_len=128, prefill_bucket=16,
              block_size=16, spec_gamma=gamma, paged=True, packed=True)

    eng_off = tiny_engine_builder(**kw)
    off = _drive(eng_off, prompts, outs, cancels)

    rec = TraceRecorder()
    eng_on = tiny_engine_builder(**kw, obs=rec)
    on = _drive(eng_on, prompts, outs, cancels)

    assert on == off, (trial, gamma, cancels)
    assert eng_on.stats.steps == eng_off.stats.steps
    assert eng_on.stats.forwards == eng_off.stats.forwards

    w, n = weave_counts_from_trace(rec)
    assert (w, n) == (eng_on.stats.weave_forwards, eng_on.stats.forwards)
    rate = w / n if n else 0.0
    assert rate == eng_on.stats.weave_rate
    # every forward carries a full attribution record (validator enforces
    # the required keys) and the whole export is schema-clean
    assert validate_chrome_trace(export_chrome_trace(rec)) == []
