"""Profiler-clock spans inside ``Engine.step`` (obs/spans.py) and the
host-clock admission stamps.

A profile is collected into ``tmp_path`` around a short run of a tiny
engine and read back with ``jax.profiler.ProfileData``.  Checked, on the
slot, paged, speculative and packed paths:

* every ``engine/step`` holds one ``engine/schedule`` and, for each
  dispatch, ``engine/prepare`` -> ``engine/dispatch`` -> ``engine/sync`` ->
  ``engine/commit`` in order, nested inside it, and those phases cover at
  least 95% of the steps' time;
* ``first=1`` marks only a program's first dispatch;
* the spans only observe: served tokens, step counts and every registry
  counter are identical with a profile collected and without one;
* ``admitted_s >= enqueued_s`` for every admitted request, and a request
  queued behind full slots waits longer than zero.
"""
import re

import jax
import pytest

from repro.runtime.requests import Request

PATHS = {
    "slot": dict(paged=False),
    "paged": dict(paged=True, block_size=16),
    "spec": dict(paged=True, block_size=16, spec_gamma=2),
    "packed": dict(paged=False, packed=True),
}
# direct children of one engine/step: the schedule, then each dispatch's
# phases; a prepare that ends in no dispatch (a verify step with nothing
# drafted falls back to decode, a paged decode whose rows were all
# preempted) stands alone
STEP_RE = re.compile(r"^S(P+DYC)*P*$")
# a program each path has to dispatch
PROGRAM = {"slot": "decode_b", "paged": "paged_decode_b",
           "spec": "paged_verify_s", "packed": "packed_t"}
LETTER = {"engine/schedule": "S", "engine/prepare": "P",
          "engine/dispatch": "D", "engine/sync": "Y", "engine/commit": "C"}


def _requests():
    # a repeating prompt gives the n-gram draft something to propose
    prompts = [list(range(3, 23)), [5, 6, 7, 8] * 9, list(range(40, 49)),
               list(range(60, 110))]
    return [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]


def _run(builder, kw, profile_dir=None):
    eng = builder(max_batch=2, chunk_tokens=48, prefill_bucket=16, **kw)
    reqs = _requests()
    if profile_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(profile_dir), profiler_options=opts)
    try:
        for r in reqs:
            eng.add_request(r)
        eng.run()
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
    return eng, reqs


def _engine_events(profile_dir):
    from jax.profiler import ProfileData
    [path] = list(profile_dir.glob("plugins/profile/*/*.xplane.pb"))
    evs = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine/"):
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return sorted(evs, key=lambda e: (e[0], -e[1]))


def _tree(evs):
    """Parent index of each event (-1 for a root), by interval nesting."""
    parent, stack = [], []
    for i, (a, b, _, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= a:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return parent


@pytest.fixture(scope="module")
def runs(tiny_engine_builder, tmp_path_factory):
    out = {}
    for name, kw in PATHS.items():
        d = tmp_path_factory.mktemp(f"profile_{name}")
        eng, reqs = _run(tiny_engine_builder, kw, d)
        out[name] = (eng, reqs, _engine_events(d))
    return out


@pytest.mark.parametrize("path", list(PATHS))
def test_phases_nest_in_order_inside_each_step(runs, path):
    eng, _, evs = runs[path]
    parent = _tree(evs)
    steps = [i for i, e in enumerate(evs) if e[2] == "engine/step"]
    assert len(steps) >= eng.stats.steps > 0
    assert all(parent[i] == -1 for i in steps)
    n_dispatch = 0
    for s in steps:
        kids = [i for i, p in enumerate(parent) if p == s]
        seq = "".join(LETTER[evs[i][2]] for i in kids)
        assert STEP_RE.match(seq), (path, seq)
        n_dispatch += seq.count("D")
        # every dispatch's prepare split the sampling key once
        for k, i in enumerate(kids):
            if evs[i][2] == "engine/prepare" and seq[k + 1:k + 2] == "D":
                inner = [evs[j][2] for j, p in enumerate(parent) if p == i]
                assert inner.count("engine/rng") == 1, inner
        # admissions happen while scheduling
        for j, p in enumerate(parent):
            if evs[j][2] == "engine/admit":
                assert evs[p][2] == "engine/schedule"
    assert n_dispatch == eng.stats.forwards


@pytest.mark.parametrize("path", list(PATHS))
def test_phases_cover_the_steps(runs, path):
    """Summed over the run's steps: a step of this tiny model takes a few
    ms, and on a loaded CPU one preemption of the thread between two
    phases can take more than 5% of one step."""
    _, _, evs = runs[path]
    parent = _tree(evs)
    steps = covered = 0
    for s, (a, b, name, _) in enumerate(evs):
        if name != "engine/step":
            continue
        steps += b - a
        covered += sum(evs[i][1] - evs[i][0]
                       for i, p in enumerate(parent) if p == s)
    assert covered >= 0.95 * steps, (path, covered, steps)


@pytest.mark.parametrize("path", list(PATHS))
def test_step_stats(runs, path):
    eng, _, evs = runs[path]
    stats = [e[3] for e in evs if e[2] == "engine/step" and "kind" in e[3]]
    assert len(stats) == eng.stats.steps
    assert [s["i"] for s in stats] == list(range(eng.stats.steps))
    kinds = {s["kind"] for s in stats}
    want = {"slot": {"prefill", "decode", "mixed"},
            "paged": {"prefill", "decode", "mixed"},
            "spec": {"prefill", "verify", "mixed"},
            "packed": {"packed"}}[path]
    assert kinds <= want
    assert all(s["rows"] >= 1 for s in stats)


@pytest.mark.parametrize("path", list(PATHS))
def test_first_marks_only_a_programs_first_dispatch(runs, path):
    _, _, evs = runs[path]
    seen = set()
    for _, _, name, st in evs:
        if name != "engine/dispatch":
            continue
        assert st["first"] == int(st["program"] not in seen), st
        seen.add(st["program"])
    assert any(p.startswith(PROGRAM[path]) for p in seen), seen


@pytest.mark.parametrize("path", list(PATHS))
def test_spans_only_observe(runs, tiny_engine_builder, path):
    eng_on, reqs_on, _ = runs[path]
    eng_off, reqs_off = _run(tiny_engine_builder, PATHS[path])
    assert [r.output for r in reqs_on] == [r.output for r in reqs_off]
    assert all(len(r.output) == 6 for r in reqs_on)
    assert eng_on.stats.steps == eng_off.stats.steps
    assert eng_on.metrics_snapshot() == eng_off.metrics_snapshot()


@pytest.mark.parametrize("path", list(PATHS))
def test_admission_stamps(runs, path):
    _, reqs, evs = runs[path]
    for r in reqs:
        assert r.enqueued_s is not None and r.admitted_s is not None
        assert r.admitted_s >= r.enqueued_s
    # two slots, four requests: the last waits for a slot to free
    assert reqs[-1].admitted_s - reqs[-1].enqueued_s > 0
    waited = {st["rid"]: st["waited_ms"] for _, _, n, st in evs
              if n == "engine/admit"}
    assert set(waited) == {r.rid for r in reqs}
    for r in reqs:
        assert waited[r.rid] == pytest.approx(
            (r.admitted_s - r.enqueued_s) * 1e3)


def test_stamps_need_no_tracer(tiny_engine_builder):
    """The stamps are set with no trace recorder attached and no profile
    collected."""
    eng, reqs = _run(tiny_engine_builder, PATHS["slot"])
    assert eng.obs is None
    assert all(r.admitted_s >= r.enqueued_s for r in reqs)
