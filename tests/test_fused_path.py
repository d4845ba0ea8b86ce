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
wrong-chunk-ownership ring schedule must be caught by the numerics pin —
plus the engine-level integration: an engine configured with
``comm_mode="ring"`` emits the same tokens as the ``fused`` one.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import run_distributed
from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import fused_collectives as fc
from repro.distributed.context import CommCtx
from repro.kernels import ref as KREF
from repro.kernels.ref import ring_ar_rmsnorm_ref


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _comm_norm_tp1(mode, x, res, w, *, use_pallas=True):
    """Run comm_norm on the 1-device mesh (the exact hot-path call)."""
    ctx = CommCtx(mode=mode, use_pallas=use_pallas, interpret=use_pallas)

    def f(xsh, r):
        return fc.comm_norm(xsh[0], r, w, ctx=ctx)

    g = jax.jit(jax.shard_map(f, mesh=_mesh11(),
                              in_specs=(P("model"), P("model")),
                              out_specs=(P(None), P("model")),
                              check_vma=False))
    return g(x[None], res)


def _check_ring_vs_ref_tp1(t, d, dtype, *, tol=None):
    key = jax.random.PRNGKey(t * 1000 + d)
    x = jax.random.normal(key, (t, d), dtype)
    res = jax.random.normal(jax.random.PRNGKey(1), (t, d), dtype)
    w = (jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (d,))) + 0.5
         ).astype(dtype)
    o_ring, r_ring = _comm_norm_tp1("ring", x, res, w)
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


# --------------------------------------------------------------------------
# the ragged fallback edge: t_local % tp != 0 gates ring -> vanilla
# --------------------------------------------------------------------------

def test_comm_ctx_ragged_falls_back_to_vanilla():
    from repro.models.transformer import _comm_ctx, comm_mode_for
    cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    pcfg = ParallelConfig(comm_mode="ring")
    # divisible: the configured ring mode goes through
    assert comm_mode_for(pcfg, 32, 4) == "ring"
    assert _comm_ctx(pcfg, cfg, 32, 4).mode == "ring"
    # ragged (t_local % tp != 0): token-sharded layouts are impossible
    assert comm_mode_for(pcfg, 33, 4) == "vanilla"
    assert _comm_ctx(pcfg, cfg, 33, 4).mode == "vanilla"
    # degenerate (t_local < tp): same fallback
    assert comm_mode_for(pcfg, 3, 4) == "vanilla"
    # tp=1 divides every token count: ring stays ring
    assert comm_mode_for(pcfg, 7, 1) == "ring"
    # the default fused mode falls back the same way
    assert comm_mode_for(ParallelConfig(), 32, 4) == "fused"
    assert comm_mode_for(ParallelConfig(), 33, 4) == "vanilla"


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
                      interpret=use_pallas)
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


# --------------------------------------------------------------------------
# engine integration: the ring mode is dispatchable end-to-end
# --------------------------------------------------------------------------

def test_engine_fused_plan_token_identity_and_fused_rate(
        tiny_engine_builder, tiny_pcfg):
    """An engine configured with ``comm_mode="ring"`` must emit the same
    tokens as the ``fused`` one — the fallback ladder lands on
    numerically-identical rungs — and every dispatch runs its
    ``comm_norm`` sites in the configured mode."""
    from repro.runtime.requests import Request

    def run(mode):
        pcfg = dataclasses.replace(tiny_pcfg, comm_mode=mode)
        eng = tiny_engine_builder(paged=True, packed=True, pcfg=pcfg)
        comms = set()
        dispatch = eng._dispatch

        def noting(fn, args, info, comm):
            comms.add(comm)
            return dispatch(fn, args, info, comm)

        eng._dispatch = noting
        for i in range(3):
            # ragged prompt lengths: some forwards hit the t % tp edge
            eng.add_request(Request(rid=i, prompt=list(range(19 + 7 * i)),
                                    max_new_tokens=6))
        eng.run()
        outs = {r.rid: r.output for r in eng.sched.finished}
        return outs, comms

    fused_outs, fused_comms = run("fused")
    ring_outs, ring_comms = run("ring")
    assert ring_outs == fused_outs
    # tp=1 divides every token count, so no dispatch falls back
    assert fused_comms == {"fused"} and ring_comms == {"ring"}
