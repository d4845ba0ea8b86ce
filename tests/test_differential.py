"""Randomized differential test harness (hypothesis-style but fully
deterministic, like test_splitting_props.py).

Seeded random traces — mixed prefill lengths, prefix-shared prompts,
spec-decode windows (γ ∈ {0..3}), and mid-flight cancellations — are
replayed through FIVE engine configurations:

  * two-dispatch over the paged block pool,
  * packed hybrid batching over the paged block pool,
  * two-dispatch over legacy slots,
  * packed over the paged pool with ``comm_mode="ring"`` (the ring
    AllReduce-RMSNorm hot path, DESIGN.md §2),
  * two-dispatch over legacy slots in the same ring mode,

asserting greedy token-IDENTITY across all five for every surviving
request — packed-ring vs packed-fused vs two-dispatch, on both KV
backends — plus invariant sweeps at every step and at end of trace:

  * ``PackedPlan.total_tokens <= chunk_tokens`` (the §6 budget),
  * a cache slot is only ever reassigned after its owner finished,
  * block refcounts return to zero and every table is released.

A second sweep replays other seeded traces through weave-on and
weave-off (``tokenweave=False``) engines on both KV backends: the weave
rule reshapes HOW a forward overlaps, never what it computes.

The harness must CATCH faults, not just pass: the last tests inject a
skipped block release / a budget overrun and assert the sweep trips.
"""
import dataclasses

import numpy as np
import pytest

from repro.runtime.paging import BlockManager
from repro.runtime.requests import Request, State
from repro.runtime.scheduler import PackedPlan

N_TRACES = 25


# --------------------------------------------------------------------------
# trace generation
# --------------------------------------------------------------------------

def _gen_trace(rng: np.random.RandomState):
    """One random workload: prompts (some sharing a random prefix),
    output budgets, a spec-decode gamma, and cancellation triggers
    ``rid -> n_tokens`` (cancel once the request has emitted n tokens —
    a per-request progress point, so the trigger is meaningful in every
    engine no matter how iterations interleave)."""
    n_req = int(rng.randint(2, 6))
    shared = list(rng.randint(0, 128, size=int(rng.randint(8, 24)))) \
        if rng.rand() < 0.5 else []
    prompts = []
    for _ in range(n_req):
        tail = list(rng.randint(0, 128, size=int(rng.randint(1, 40))))
        use_shared = shared and rng.rand() < 0.6
        prompts.append((shared + tail if use_shared else tail)[:96])
    outs = [int(rng.randint(2, 7)) for _ in range(n_req)]
    gamma = int(rng.choice([0, 0, 2, 3]))
    cancels = {}
    if rng.rand() < 0.4:
        rid = int(rng.randint(0, n_req))
        # 0 = cancel while still waiting/prefilling; >0 = mid-decode
        cancels[rid] = int(rng.randint(0, outs[rid]))
    return prompts, outs, gamma, cancels


# --------------------------------------------------------------------------
# instrumented driver
# --------------------------------------------------------------------------

def _drive(eng, prompts, outs, cancels, max_steps=500):
    """Step the engine manually with invariant checks woven between
    steps; apply cancellations when their progress trigger fires.
    Returns ``{rid: output}`` for requests that were not cancelled."""
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, outs))]
    for r in reqs:
        eng.add_request(r)

    orig_next = eng.sched.next_step

    def checked_next():
        plan = orig_next()
        if isinstance(plan, PackedPlan):
            assert plan.total_tokens <= eng.scfg.chunk_tokens, (
                f"packed budget violated: {plan.total_tokens} > "
                f"{eng.scfg.chunk_tokens}")
            assert plan.total_tokens == sum(s.n_tokens
                                            for s in plan.segments)
            slots = [s.req.slot for s in plan.segments]
            assert len(set(slots)) == len(slots)
        return plan

    eng.sched.next_step = checked_next

    slot_owner = {}           # slot -> Request that last held it
    pending_cancel = dict(cancels)
    for _ in range(max_steps):
        for rid, trigger in list(pending_cancel.items()):
            r = reqs[rid]
            if r.state != State.DONE and len(r.output) >= trigger:
                eng.abort(r)
                del pending_cancel[rid]
        if not eng.step():
            break
        # slot-reuse sweep: a slot changes hands only after its previous
        # owner reached a terminal state
        for slot, r in enumerate(eng.sched.active):
            if r is None:
                continue
            prev = slot_owner.get(slot)
            if prev is not None and prev is not r:
                assert prev.state == State.DONE, (
                    f"slot {slot} reassigned from live rid {prev.rid}")
            slot_owner[slot] = r
    assert eng.sched.all_done(), "trace did not drain"
    _check_end_state(eng)
    return {r.rid: r.output for r in reqs if r.rid not in cancels}


def _check_end_state(eng):
    """End-of-trace resource sweep."""
    if eng.block_mgr is None:
        return
    mgr = eng.block_mgr
    assert not mgr.tables, f"unreleased block tables: {list(mgr.tables)}"
    leaked = [b for b in range(mgr.alloc.num_blocks) if mgr.alloc.ref[b]]
    assert not leaked, f"blocks with nonzero refcount after drain: {leaked}"


# --------------------------------------------------------------------------
# the differential sweep
# --------------------------------------------------------------------------

@pytest.mark.parametrize("trial", range(N_TRACES))
def test_differential_trace(trial, tiny_engine_builder, tiny_pcfg):
    rng = np.random.RandomState(1000 + trial)
    prompts, outs, gamma, cancels = _gen_trace(rng)
    kw = dict(max_batch=3, chunk_tokens=48, max_len=128, prefill_bucket=16,
              block_size=16, spec_gamma=gamma)
    ring = dataclasses.replace(tiny_pcfg, comm_mode="ring")

    results = {}
    for name, cfg in (
            ("two_paged", dict(paged=True, packed=False)),
            ("packed_paged", dict(paged=True, packed=True)),
            ("two_legacy", dict(paged=False, packed=False)),
            # the ring-mode columns: the same traces with every comm_norm
            # site configured for the ring kernel, on both KV backends
            ("packed_ring", dict(paged=True, packed=True, pcfg=ring)),
            ("two_legacy_ring", dict(paged=False, packed=False,
                                     pcfg=ring))):
        eng = tiny_engine_builder(**kw, **cfg)
        results[name] = _drive(eng, prompts, outs, cancels)

    ref = results["two_paged"]
    for name in ("packed_paged", "two_legacy", "packed_ring",
                 "two_legacy_ring"):
        assert results[name] == ref, (
            trial, gamma, cancels, name, results[name], ref)
    # every surviving request ran to its full budget
    for rid, out in ref.items():
        assert len(out) == outs[rid]


# --------------------------------------------------------------------------
# weave on vs off (DESIGN.md §14): the split changes no token
# --------------------------------------------------------------------------

@pytest.mark.parametrize("trial", range(N_TRACES))
def test_weave_differential_trace(trial, tiny_engine_builder, tiny_pcfg):
    """Seeded random traces (mixed prefill, shared prefixes, spec
    windows, cancellations) through weave-on and weave-off engines on
    BOTH KV backends: greedy token-identity across all four, with the
    same invariant sweeps (packed budget, slot reuse, block refcounts)
    riding along."""
    rng = np.random.RandomState(9000 + trial)
    prompts, outs, gamma, cancels = _gen_trace(rng)
    kw = dict(max_batch=3, chunk_tokens=48, max_len=128, prefill_bucket=16,
              block_size=16, spec_gamma=gamma)
    off = dataclasses.replace(tiny_pcfg, tokenweave=False)

    results = {}
    for name, cfg in (
            ("weave_paged", dict(paged=True)),
            ("plain_paged", dict(paged=True, pcfg=off)),
            ("weave_slots", dict(paged=False)),
            ("plain_slots", dict(paged=False, pcfg=off))):
        eng = tiny_engine_builder(**kw, **cfg)
        results[name] = _drive(eng, prompts, outs, cancels)
        if name.startswith("plain"):
            assert eng.stats.weave_forwards == 0, name

    ref = results["weave_paged"]
    for name in ("plain_paged", "weave_slots", "plain_slots"):
        assert results[name] == ref, (trial, gamma, cancels, name)


# --------------------------------------------------------------------------
# wire-cluster column (DESIGN.md §15): the same traces through a single
# engine, an in-process cluster, and a loopback-wire cluster whose every
# envelope (and, disaggregated, every KV payload) crosses the frame codec
# --------------------------------------------------------------------------

def _drive_cluster(engines, prompts, outs, roles=None, wire=None):
    from repro.runtime.cluster import ClusterConfig, ClusterServer, Replica
    roles = roles or ["mixed"] * len(engines)
    reps = [Replica(f"r{i}", e, role=role)
            for i, (e, role) in enumerate(zip(engines, roles))]
    cs = ClusterServer(reps, ClusterConfig(router="round_robin", wire=wire))
    for i, (p, n) in enumerate(zip(prompts, outs)):
        cs.submit(Request(rid=i, prompt=list(p), max_new_tokens=n,
                          arrival_time=0.25 * i))
    done = cs.run()
    cs.check_quiescent()
    return {r.rid: r.output for r in done}


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "legacy"])
@pytest.mark.parametrize("trial", range(N_TRACES))
def test_differential_wire_cluster(trial, paged, tiny_engine_builder):
    rng = np.random.RandomState(3000 + trial)
    prompts, outs, _, _ = _gen_trace(rng)
    kw = dict(max_batch=3, chunk_tokens=48, max_len=128, prefill_bucket=16,
              block_size=16, paged=paged)

    single = tiny_engine_builder(**kw)
    for i, (p, n) in enumerate(zip(prompts, outs)):
        single.add_request(Request(rid=i, prompt=list(p), max_new_tokens=n))
    ref = {r.rid: r.output for r in single.run()}
    assert sorted(ref) == list(range(len(prompts)))

    inproc = _drive_cluster(
        [tiny_engine_builder(**kw) for _ in range(2)], prompts, outs)
    wired = _drive_cluster(
        [tiny_engine_builder(**kw) for _ in range(2)], prompts, outs,
        wire="loopback")
    assert inproc == ref, (trial, paged, "inproc")
    assert wired == ref, (trial, paged, "loopback")

    if paged:
        # disaggregated: every KV-migration payload crosses the codec too
        disagg = _drive_cluster(
            [tiny_engine_builder(**kw) for _ in range(2)], prompts, outs,
            roles=["prefill", "decode"], wire="loopback")
        assert disagg == ref, (trial, "disagg_loopback")


# --------------------------------------------------------------------------
# the harness must catch injected faults
# --------------------------------------------------------------------------

def test_harness_catches_skipped_block_release(tiny_engine_builder,
                                               monkeypatch):
    """Injected fault: ``free_request`` forgets to decref (the classic
    leak — table dropped, references kept).  The end-state refcount sweep
    must trip; a harness that cannot catch this is decoration."""
    def leaky_free(self, rid):
        self.tables.pop(rid, None)
        self._reg_cursor.pop(rid, None)

    monkeypatch.setattr(BlockManager, "free_request", leaky_free)
    rng = np.random.RandomState(7)
    prompts, outs, _, _ = _gen_trace(rng)
    eng = tiny_engine_builder(max_batch=3, chunk_tokens=48, max_len=128,
                              prefill_bucket=16, block_size=16, paged=True)
    with pytest.raises((AssertionError, RuntimeError)):
        _drive(eng, prompts, outs, cancels={})


def test_harness_catches_budget_overrun(tiny_engine_builder, monkeypatch):
    """Injected fault: the packed planner stops charging verify width
    against the budget, overpacking the token axis.  The per-plan
    ``total_tokens <= chunk_tokens`` sweep must trip."""
    from repro.runtime import scheduler as SCH

    orig = SCH.Scheduler._next_packed

    def overpack(self, prefilling):
        plan = orig(self, prefilling)
        if plan is not None:
            for s in plan.segments:
                if s.kind == "prefill":
                    # pretend the budget was bigger than it is
                    s.n_tokens += self.cfg.chunk_tokens
                    plan.total_tokens += self.cfg.chunk_tokens
        return plan

    monkeypatch.setattr(SCH.Scheduler, "_next_packed", overpack)
    eng = tiny_engine_builder(max_batch=3, chunk_tokens=48, max_len=128,
                              prefill_bucket=16, block_size=16,
                              paged=True, packed=True)
    prompts = [[int(x) for x in np.arange(20)], [5, 6, 7]]
    with pytest.raises(AssertionError, match="budget"):
        _drive(eng, prompts, [3, 3], cancels={})
