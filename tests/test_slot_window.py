"""Slot decode/verify under the layer scan: each layer attends the old
cache plus the step's window read-only, and the window is written into the
stacked cache once, after the scan (``attention.attn_window``,
``kv_cache.insert_window``).

The reference is the scatter-then-attend form it replaced, kept here: each
layer scatters the window into its cache first, then attends the updated
cache.  It runs through the unrolled per-layer path with the same weights
and cache.  Logits are compared on the rows and window positions that are
live (a query at position -1 attends nothing and its output is unused);
the whole cache is compared after every step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.layers import attention as A
from repro.models import transformer as T
from repro.models.build import build_model
from repro.runtime import kv_cache as KC

MAX_LEN = 32

CFG = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=128, dtype="float32")
PCFG = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                      split_unit=16, tokenweave_min_tokens=32)


def _scatter_then_attend(params, x, cache, *, positions, cfg, lay, theta,
                         window=0, mrope_positions=None):
    """The replaced form: write the window into the layer cache, then
    attend the written cache (overwrite-before-query)."""
    q, k_new, v_new = A._project_qkv(params, x, cfg, lay,
                                     positions=positions, theta=theta,
                                     mrope_positions=mrope_positions)
    c = cache["k"].shape[1]
    slot = jnp.where(positions >= 0, positions % c, c).astype(jnp.int32)
    bidx = jnp.arange(x.shape[0])[:, None]
    k_c = cache["k"].at[bidx, slot].set(k_new, mode="drop")
    v_c = cache["v"].at[bidx, slot].set(v_new, mode="drop")
    p_c = cache["pos"].at[bidx, slot].set(positions.astype(jnp.int32),
                                          mode="drop")
    partial = A._decode_attn_math(params, q, k_c, v_c, p_c, positions,
                                  x_dtype=x.dtype, cfg=cfg, lay=lay,
                                  window=window)
    return partial, {"k": k_c, "v": v_c, "pos": p_c}


def _unstack(tree, n):
    return {f"layer_{i}": jax.tree.map(lambda a, i=i: a[i], tree)
            for i in range(n)}


def _stack(per_layer, n):
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[per_layer[f"layer_{i}"] for i in range(n)])


def _step_fn(mesh, api, verify: bool):
    step = T.verify_step if verify else T.decode_step

    def fn(p, c, t, pos):
        return step(p, t, c, cfg=api.cfg, pcfg=api.pcfg, positions=pos)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(api.specs(), api.cache_specs(), P(), P()),
        out_specs=(P(), api.cache_specs()), check_vma=False))


def _prefill(mesh, api, params, cache, prompts):
    """Prefill each row's prompt (rows with no prompt stay empty)."""
    b = len(prompts)
    s = max(len(p) for p in prompts)
    toks = np.zeros((b, s), np.int32)
    pos = np.full((b, s), -1, np.int32)
    for r, p in enumerate(prompts):
        toks[r, :len(p)] = p
        pos[r, :len(p)] = np.arange(len(p))

    def fn(p, c, t, ps):
        _, kv, _ = T.prefill(p, t, KC.gather_slots(c, jnp.arange(b)),
                             cfg=api.cfg, pcfg=api.pcfg, positions=ps)
        return KC.insert_chunk(c, kv, jnp.zeros((b,), jnp.int32))
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(api.specs(), api.cache_specs(), P(), P()),
        out_specs=api.cache_specs(), check_vma=False))(
            params, cache, jnp.asarray(toks), jnp.asarray(pos))


def _windows(case, b, lens, rng):
    """(tokens, positions) per step; -1 marks dead rows / draft slots."""
    steps = []
    if case == "verify_partial":
        # window at L..L+3, then one draft accepted: the next window starts
        # at L+2 with no draft, so the rejected draft's key at L+3 sits in
        # the cache at >= row_start; then a full window over it again
        for start, width in ((0, 4), (2, 1), (3, 4)):
            pos = np.full((b, 4), -1, np.int32)
            for r in range(b):
                pos[r, :width] = lens[r] + start + np.arange(width)
            steps.append(pos)
    else:
        n = 9 if case == "sliding" else 4
        for i in range(n):
            pos = np.asarray(lens, np.int32)[:, None] + i
            steps.append(np.where(np.asarray(lens)[:, None] > 0, pos, -1)
                         .astype(np.int32))
    return [(rng.randint(0, CFG.vocab_size, p.shape).astype(np.int32), p)
            for p in steps]


CASES = {
    # row 1 was never admitted: its positions are -1 on every step
    "inactive": dict(cfg=CFG, lens=[5, 0, 9]),
    # uniform sliding window: C == window == 6, the ring wraps twice
    "sliding": dict(cfg=dataclasses.replace(CFG, sliding_window=6),
                    lens=[4, 3, 5]),
    "verify_partial": dict(cfg=CFG, lens=[5, 7, 3]),
    # 16 rows cross the decode split's floor (2 x max(tp, 8) rows)
    "weave_split": dict(cfg=CFG, lens=[3 + (r % 5) for r in range(16)]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_window_decode_matches_scatter_then_attend(case, mesh11,
                                                   monkeypatch):
    cfg, lens = CASES[case]["cfg"], CASES[case]["lens"]
    b = len(lens)
    api = build_model(cfg, PCFG, tp=1)
    assert T.use_scan(cfg, PCFG)
    ref_api = build_model(cfg, dataclasses.replace(PCFG, scan_layers=False),
                          tp=1)
    params = api.init(jax.random.PRNGKey(0))
    ref_params = dict(params, layers=_unstack(params["layers"],
                                              cfg.num_layers))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n) for n in lens]
    cache = _prefill(mesh11, api, params, api.init_cache(b, MAX_LEN),
                     prompts)
    ref_cache = _unstack(cache, cfg.num_layers)
    verify = case == "verify_partial"
    step = _step_fn(mesh11, api, verify)
    if case == "weave_split":
        assert T.weave_decision_info(b, 1, tp=1, pcfg=PCFG,
                                     decode=True).split is not None
        unsplit = _step_fn(mesh11, build_model(
            cfg, dataclasses.replace(PCFG, tokenweave=False), tp=1), False)
    monkeypatch.setattr(A, "attn_verify", _scatter_then_attend)
    ref_step = _step_fn(mesh11, ref_api, verify)

    for toks, pos in _windows(case, b, lens, rng):
        toks, pos = jnp.asarray(toks), jnp.asarray(pos)
        if case == "weave_split":
            lu, cu = unsplit(params, cache, toks, pos)
        logits, cache = step(params, cache, toks, pos)
        ref_logits, ref_cache = ref_step(ref_params, ref_cache, toks, pos)
        live = np.asarray(pos) >= 0
        np.testing.assert_allclose(np.asarray(logits)[live],
                                   np.asarray(ref_logits)[live],
                                   rtol=2e-5, atol=2e-5)
        ref_stacked = _stack(ref_cache, cfg.num_layers)
        np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                      np.asarray(ref_stacked["pos"]))
        for name in ("k", "v"):
            np.testing.assert_allclose(np.asarray(cache[name]),
                                       np.asarray(ref_stacked[name]),
                                       rtol=2e-5, atol=2e-5)
        if case == "weave_split":
            np.testing.assert_allclose(np.asarray(lu)[live],
                                       np.asarray(logits)[live],
                                       rtol=1e-5, atol=1e-5)
            for name in ("k", "v", "pos"):
                np.testing.assert_allclose(np.asarray(cu[name]),
                                           np.asarray(cache[name]),
                                           rtol=1e-5, atol=1e-5)


def _scans(jaxpr):
    """Every scan equation in a jaxpr, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


@pytest.mark.parametrize("s_v", [1, 4])
def test_scanned_decode_returns_only_the_window(s_v, mesh11):
    """The layer scan of a slot decode (S_v 1) or verify (S_v 4) step
    outputs window-sized KV, never the per-layer cache: a scan output with
    the cache's (B, C, kvh, dh) tail would mean the layers restack it."""
    b = 3
    api = build_model(CFG, PCFG, tp=1)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: api.init_cache(b, MAX_LEN))
    tok = jax.ShapeDtypeStruct((b, s_v), jnp.int32)
    step = T.verify_step if s_v > 1 else T.decode_step

    def fn(p, c, t, pos):
        return step(p, t, c, cfg=CFG, pcfg=PCFG, positions=pos)
    sm = jax.shard_map(fn, mesh=mesh11,
                       in_specs=(api.specs(), api.cache_specs(), P(), P()),
                       out_specs=(P(), api.cache_specs()), check_vma=False)
    jaxpr = jax.make_jaxpr(sm)(params, cache, tok, tok).jaxpr
    scans = list(_scans(jaxpr))
    assert scans, "the decode step has no layer scan"
    layer_tail = cache["k"].shape[1:]                  # (B, C, kvh, dh)
    window = (CFG.num_layers, b, s_v) + cache["k"].shape[3:]
    outs = [tuple(v.aval.shape) for e in scans for v in e.outvars]
    assert all(s[-4:] != layer_tail for s in outs), outs
    assert window in outs, outs
