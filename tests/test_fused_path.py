"""Fused AllReduce-RMSNorm serving hot path, pinned fused-vs-ref
(DESIGN.md §2 ring mode; ISSUE 9).

``comm_norm(mode="ring")`` dispatches the single-kernel ring
AllReduce-RMSNorm (kernels/ring_ar_rmsnorm.py) wherever the backend can
run it, and walks a fallback ladder (ring -> fused composition ->
vanilla for ragged shards) everywhere else.  This tier pins the MODE —
not one rung — against ``kernels/ref.ring_ar_rmsnorm_ref`` and the
unfused vanilla composition, so the numerics hold identically whichever
rung fires (on CPU the Pallas interpreter drives the real kernel).

Also here: the fault-injection half of the tier — a planted
wrong-chunk-ownership ring schedule must be caught by the numerics pin,
and a budget-overcommitting plan entry (a budget that rounds to zero
ring lanes) must be caught by scripts/check_plan.py — plus the
engine-level integration: loading the committed fused plan changes no
token and surfaces per-site ``engine/site_fused_rate`` gauges.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import run_distributed
from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import fused_collectives as fc
from repro.core.splitting import MAX_RING_CHANNELS, ring_channels
from repro.distributed.context import CommCtx
from repro.kernels import ref as KREF
from repro.kernels.ref import ring_ar_rmsnorm_ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_PLAN = os.path.join(REPO, "benchmarks", "plans", "default.json")

_CHECK_PLAN = os.path.join(REPO, "scripts", "check_plan.py")
_spec = importlib.util.spec_from_file_location("check_plan", _CHECK_PLAN)
check_plan = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_plan)


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _comm_norm_tp1(mode, x, res, w, *, budget=1.0, use_pallas=True):
    """Run comm_norm on the 1-device mesh (the exact hot-path call)."""
    ctx = CommCtx(mode=mode, use_pallas=use_pallas, interpret=use_pallas,
                  comm_budget=budget)

    def f(xsh, r):
        return fc.comm_norm(xsh[0], r, w, ctx=ctx)

    g = jax.jit(jax.shard_map(f, mesh=_mesh11(),
                              in_specs=(P("model"), P("model")),
                              out_specs=(P(None), P("model")),
                              check_vma=False))
    return g(x[None], res)


def _check_ring_vs_ref_tp1(t, d, dtype, *, budget=1.0, tol=None):
    key = jax.random.PRNGKey(t * 1000 + d)
    x = jax.random.normal(key, (t, d), dtype)
    res = jax.random.normal(jax.random.PRNGKey(1), (t, d), dtype)
    w = (jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (d,))) + 0.5
         ).astype(dtype)
    o_ring, r_ring = _comm_norm_tp1("ring", x, res, w, budget=budget)
    o_van, r_van = _comm_norm_tp1("vanilla", x, res, w, use_pallas=False)
    ref_outs, ref_res = ring_ar_rmsnorm_ref([x], [res], w)
    tol = tol if tol is not None else (1e-5 if dtype == jnp.float32
                                       else 3e-2)
    for got, want in ((o_ring, ref_outs[0]), (o_ring, o_van),
                      (r_ring, ref_res[0]), (r_ring, r_van)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


# --------------------------------------------------------------------------
# ring-mode comm_norm vs ref oracle vs vanilla composition (tp=1,
# in-process — ragged token counts are legal at tp=1 and must still pin)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d", [(16, 32), (48, 64), (33, 128), (7, 256)])
def test_comm_norm_ring_matches_ref_and_vanilla_tp1(t, d, dtype):
    _check_ring_vs_ref_tp1(t, d, dtype)


@pytest.mark.parametrize("budget", [0.125, 0.5, 1.0])
def test_comm_norm_ring_budget_does_not_change_numerics(budget):
    """The ring-lane grant is a RESOURCE knob; any budget in (0, 1] must
    produce bit-compatible results (only throughput may differ)."""
    _check_ring_vs_ref_tp1(32, 64, jnp.float32, budget=budget)


# --------------------------------------------------------------------------
# the ragged fallback edge: t_local % tp != 0 gates ring -> vanilla
# --------------------------------------------------------------------------

def test_comm_ctx_ragged_falls_back_to_vanilla():
    from repro.models.transformer import _comm_ctx
    cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    pcfg = ParallelConfig()
    # divisible: the plan-forced ring mode goes through, budget and all
    ctx = _comm_ctx(pcfg, cfg, 32, 4, mode="ring", budget=0.5)
    assert ctx.mode == "ring" and ctx.comm_budget == 0.5
    # ragged (t_local % tp != 0): token-sharded layouts are impossible
    assert _comm_ctx(pcfg, cfg, 33, 4, mode="ring").mode == "vanilla"
    # degenerate (t_local < tp): same fallback
    assert _comm_ctx(pcfg, cfg, 3, 4, mode="ring").mode == "vanilla"
    # no plan override: pcfg.comm_mode rules, as before
    assert _comm_ctx(pcfg, cfg, 32, 4).mode == pcfg.comm_mode


# --------------------------------------------------------------------------
# multi-device: ring-mode comm_norm vs the ref oracle and the unfused
# vanilla composition (tp in {2, 4}, subprocess devices)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_comm_norm_ring_mode_multidevice(tp):
    run_distributed(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.context import CommCtx
from repro.core import fused_collectives as fc
from repro.kernels.ref import ring_ar_rmsnorm_ref
tp, T, d = {tp}, 48, 32
mesh = jax.make_mesh((1, tp), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)):
    xs = jax.random.normal(jax.random.PRNGKey(0), (tp, T, d), dtype)
    res = jax.random.normal(jax.random.PRNGKey(3), (T, d), dtype)
    w = (jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (d,))) + 0.5
         ).astype(dtype)
    def run(mode, use_pallas):
        ctx = CommCtx(mode=mode, use_pallas=use_pallas,
                      interpret=use_pallas, comm_budget=0.5)
        sharded = mode != 'vanilla'
        def f(xsh, r):
            return fc.comm_norm(xsh[0], r if sharded else r[0], w, ctx=ctx)
        res_in = res if sharded else jnp.broadcast_to(res[None], (tp, T, d))
        g = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P('model'), P('model')),
            out_specs=(P(None), P('model') if sharded else P(None)),
            check_vma=False))
        # fenced: eager dispatch racing an interpreted kernel deadlocks
        return jax.block_until_ready(g(xs, res_in))
    o_ring, r_ring = run('ring', True)
    o_van, r_van = run('vanilla', False)
    ref_outs, ref_res = ring_ar_rmsnorm_ref(
        [xs[i] for i in range(tp)],
        [res.reshape(tp, T // tp, d)[i] for i in range(tp)], w)
    np.testing.assert_allclose(np.asarray(o_ring, np.float32),
                               np.asarray(ref_outs[0], np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(o_ring, np.float32),
                               np.asarray(o_van, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(
        np.asarray(r_ring.reshape(T, d), np.float32),
        np.asarray(jnp.concatenate(ref_res, 0), np.float32),
        rtol=tol, atol=tol)
print('PASS')
""", n_devices=tp)


# --------------------------------------------------------------------------
# property sweep over tile shapes (hypothesis when available, the
# deterministic grid otherwise — never a skip)
# --------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @given(t=st.integers(1, 64), d=st.sampled_from((32, 64, 128, 256)),
           bf16=st.booleans())
    @settings(max_examples=25, deadline=None)
    def test_ring_comm_norm_tile_shape_sweep(t, d, bf16):
        _check_ring_vs_ref_tp1(t, d,
                               jnp.bfloat16 if bf16 else jnp.float32)
else:
    @pytest.mark.parametrize("t,d,bf16", [
        (1, 32, False), (7, 64, True), (16, 128, False), (33, 64, False),
        (56, 32, True), (64, 256, False)])
    def test_ring_comm_norm_tile_shape_sweep(t, d, bf16):
        _check_ring_vs_ref_tp1(t, d,
                               jnp.bfloat16 if bf16 else jnp.float32)


# --------------------------------------------------------------------------
# fault injection: the tier must CATCH a broken fused path, not just pass
# --------------------------------------------------------------------------

def test_numerics_pin_catches_wrong_chunk_ownership(monkeypatch):
    """Planted fault: a ring schedule whose devices norm the WRONG token
    chunk (ownership rotated by one).  Every chunk is still normed by
    exactly one device — shapes, reductions, and semaphore accounting all
    stay healthy — so only the numerics pin can catch it."""
    n, t, d = 4, 32, 64
    xs = [jax.random.normal(jax.random.PRNGKey(i), (t, d)) for i in range(n)]
    res = [jax.random.normal(jax.random.PRNGKey(10 + i), (t // n, d))
           for i in range(n)]
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(99), (d,))) + 0.5
    good, good_res = ring_ar_rmsnorm_ref(xs, res, w)

    monkeypatch.setattr(KREF, "_chunk_owner", lambda r, nd: (r + 1) % nd)
    bad, bad_res = ring_ar_rmsnorm_ref(xs, res, w)

    # the planted schedule still "works" structurally...
    assert bad[0].shape == good[0].shape
    # ...but the full normed stream disagrees with the true composition
    assert not np.allclose(np.asarray(bad[0]), np.asarray(good[0]),
                           rtol=1e-3, atol=1e-3)
    # and the residual shards each device keeps are the wrong tokens'
    assert not all(np.allclose(np.asarray(b), np.asarray(g), rtol=1e-3,
                               atol=1e-3)
                   for b, g in zip(bad_res, good_res))


def test_check_plan_rejects_budget_overcommit():
    """Planted fault: a fused plan entry whose budget rounds to ZERO ring
    lanes (an over-committed comm grant the kernel could never honor).
    ``PlanEntry.validate`` accepts any budget in (0, 1], so only the
    check_plan structural gate stands between this entry and the engine."""
    with open(DEFAULT_PLAN) as f:
        doc = json.load(f)
    assert check_plan.check_plan(doc) == []      # the committed plan is clean
    fused_idx = next(i for i, e in enumerate(doc["entries"])
                     if e["method"] in ("fused", "fused-unsplit"))
    bad = json.loads(json.dumps(doc))
    bad["entries"][fused_idx]["budget"] = 0.05   # ring_channels -> 0 lanes
    assert ring_channels(0.05) == 0
    failures = check_plan.check_plan(bad)
    assert failures and any("ring lanes" in f for f in failures)


def test_ring_channels_budget_mapping():
    """budget -> lane-count contract (the paper's 2-8 SM knob)."""
    assert ring_channels(1.0) == MAX_RING_CHANNELS
    assert ring_channels(0.5) == MAX_RING_CHANNELS // 2
    assert ring_channels(1.0 / MAX_RING_CHANNELS) == 1
    assert ring_channels(0.05) == 0      # deliberately NOT clamped: the
    #                                      plan gate must see the fault


# --------------------------------------------------------------------------
# engine integration: the committed fused plan is dispatchable end-to-end
# --------------------------------------------------------------------------

def test_engine_fused_plan_token_identity_and_fused_rate(
        tiny_engine_builder):
    """Loading the committed plan (whose tiny/tp1 entries are all
    fused/fused-unsplit) must change NO token vs the plan-free engine —
    the fallback ladder lands on numerically-identical rungs — while the
    per-site ``engine/site_fused_rate`` gauges surface that the fused
    path was selected at every decided site."""
    from repro.runtime.requests import Request

    def run(plan_path):
        eng = tiny_engine_builder(paged=True, packed=True,
                                  plan_path=plan_path)
        for i in range(3):
            # ragged prompt lengths: some forwards hit the t % tp edge
            eng.add_request(Request(rid=i, prompt=list(range(19 + 7 * i)),
                                    max_new_tokens=6))
        eng.run()
        outs = {r.rid: r.output for r in eng.sched.finished}
        return outs, eng.metrics_snapshot()

    base_outs, _ = run(None)
    plan_outs, snap = run(DEFAULT_PLAN)
    assert plan_outs == base_outs
    rates = {k: v for k, v in snap.items()
             if k.startswith("engine/site_fused_rate")}
    assert rates, f"no site_fused_rate gauges in {sorted(snap)}"
    assert all(v == 1.0 for v in rates.values()), rates
    # and without a plan there is no fused routing at all (the derived
    # rate gauges exist either way; they must read zero)
    _, base_snap = run(None)
    base_rates = {k: v for k, v in base_snap.items()
                  if k.startswith("engine/site_fused_rate")}
    assert all(v == 0.0 for v in base_rates.values()), base_rates
