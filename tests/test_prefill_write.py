"""The prefill chunk's write into the stacked slot cache
(``kv_cache.insert_chunk``): each row's chunk is written in place, one
``dynamic_update_slice`` window per row (and a second for what wraps on a
ring); a chunk longer than a ring window goes in segments of at most C, in
order.

The reference is the scatter every chunk used to take, kept here: each
entry goes to slot ``(offset + j) % C`` of its row and pads (pos < 0) are
dropped.  Every leaf of the cache must come out bit for bit the same, over
a cache filled with live-looking entries, so a pad or a shifted window that
landed on another position would show.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models.build import build_model
from repro.runtime import kv_cache as KC
from repro.runtime.engine import Engine
from repro.runtime.requests import Request
from repro.runtime.scheduler import SchedulerConfig


def _scatter_reference(cache, kv_chunk, offsets, slot_ids=None,
                       max_len=None):
    """The replaced form: per segment of at most C entries, one
    ``[:, rows, slots]`` scatter of k, v and pos, pads dropped."""
    k, v, pos = kv_chunk
    _, b_sel, s = pos.shape
    c = cache["k"].shape[2]
    if slot_ids is None:
        slot_ids = jnp.arange(b_sel)
    bidx = slot_ids[:, None]
    for lo in range(0, s, c):
        ks, vs, ps = (k[:, :, lo:lo + c], v[:, :, lo:lo + c],
                      pos[:, :, lo:lo + c])
        seg = ps.shape[2]
        slots = (offsets[:, None] + lo + jnp.arange(seg)[None, :]) % c
        slots = jnp.where(ps[0] >= 0, slots, c)
        cache = {
            "k": cache["k"].at[:, bidx, slots].set(ks, mode="drop"),
            "v": cache["v"].at[:, bidx, slots].set(vs, mode="drop"),
            "pos": cache["pos"].at[:, bidx, slots].set(ps, mode="drop"),
        }
    return cache


N_L, B, H, DH = 3, 4, 2, 8


def _live_cache(rng, c):
    """Every slot holds a distinct live-looking entry."""
    return {"k": jnp.asarray(rng.randn(N_L, B, c, H, DH), jnp.bfloat16),
            "v": jnp.asarray(rng.randn(N_L, B, c, H, DH), jnp.bfloat16),
            "pos": jnp.asarray(rng.randint(0, 1000, (N_L, B, c)),
                               jnp.int32)}


def _chunk(rng, offsets, takes, s):
    """Each row's chunk: ``take`` real entries at positions offset.., then
    pads (pos -1) to the chunk's bucketed length s."""
    b_sel = len(offsets)
    pos = np.full((b_sel, s), -1, np.int32)
    for i, (o, t) in enumerate(zip(offsets, takes)):
        pos[i, :t] = o + np.arange(t)
    return (jnp.asarray(rng.randn(N_L, b_sel, s, H, DH), jnp.bfloat16),
            jnp.asarray(rng.randn(N_L, b_sel, s, H, DH), jnp.bfloat16),
            jnp.broadcast_to(jnp.asarray(pos), (N_L, b_sel, s)))


# (C, chunk s, slot ids, offsets, real tokens per row, max_len)
CASES = {
    # full-attention rows (C == max_len): offset 0, mid-row, and padded
    # tails that run past C (the window starts early and shifts)
    "b1_offset0": (16, 8, [2], [0], [8], 16),
    "b2_mid_row": (16, 8, [3, 1], [5, 4], [8, 6], 16),
    "b3_tail_past_c": (16, 8, [1, 3, 0], [12, 10, 9], [4, 6, 7], 16),
    "b3_chunk_is_c": (16, 16, [2, 0, 3], [0, 0, 0], [16, 5, 1], 16),
    "b2_unknown_max_len": (16, 8, [0, 2], [12, 3], [4, 8], None),
    # a ring (C == window 8 < max_len) holding the chunk: entries wrap to
    # the row's first slots, and pads after them must not clobber live ones
    "ring_chunk_le_c_wraps": (8, 4, [3, 1], [6, 13], [4, 2], 32),
    "ring_chunk_eq_c": (8, 8, [0, 2, 1], [5, 16, 3], [8, 3, 6], 32),
    # a ring shorter than the chunk: segments of C, in order, later
    # entries overwriting earlier ones (and pads in a later segment kept
    # off the live entries of an earlier one)
    "ring_chunk_gt_c": (8, 12, [2, 0], [3, 9], [12, 7], 32),
    "ring_chunk_gt_c_padded": (8, 20, [1, 3], [5, 2], [17, 9], 32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_insert_chunk_matches_scatter(case):
    c, s, slots, offsets, takes, max_len = CASES[case]
    rng = np.random.RandomState(len(case))
    cache = _live_cache(rng, c)
    kv = _chunk(rng, offsets, takes, s)
    args = (kv, jnp.asarray(offsets, jnp.int32),
            jnp.asarray(slots, jnp.int32))
    want = _scatter_reference(cache, *args)
    got = jax.jit(KC.insert_chunk, static_argnames="max_len")(
        cache, *args, max_len=max_len)
    for name in ("k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]), err_msg=name)


def test_inplace_write_is_a_dynamic_update_slice():
    """The write lowers to dynamic-update-slices of the cache and no
    scatter, for a chunk that fits a row and for one longer than a ring."""
    rng = np.random.RandomState(0)
    cache = _live_cache(rng, 16)
    kv = _chunk(rng, [3], [8], 8)
    args = (cache, kv, jnp.asarray([3], jnp.int32),
            jnp.asarray([1], jnp.int32))
    text = jax.jit(KC.insert_chunk, static_argnames="max_len").lower(
        *args, max_len=16).as_text()
    assert "dynamic_update_slice" in text and "scatter" not in text
    cache = _live_cache(rng, 8)
    kv = _chunk(rng, [3], [20], 20)
    text = jax.jit(KC.insert_chunk).lower(cache, kv, *args[2:]).as_text()
    assert "dynamic_update_slice" in text and "scatter" not in text


def _run_engine(api, mesh, params, prompts, **scfg_kw):
    eng = Engine(api, mesh, params, SchedulerConfig(**scfg_kw))
    for i, p in enumerate(prompts):
        eng.add_request(Request(rid=i, prompt=p, max_new_tokens=6))
    outs = {r.rid: r.output for r in eng.run()}
    return outs, eng.metrics.get("engine/prefill_write",
                                 form="inplace").value


TINY = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                   num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                   vocab_size=128, dtype="float32")


@pytest.mark.parametrize("window", [0, 24, 8])
def test_engine_tokens_match_scatter_write(window, mesh11, monkeypatch):
    """Greedy serving over prompts of several chunks (padded tails
    included), then decode: the same tokens as with the scatter write, at
    full attention, on a 24-slot ring that holds every chunk (16 tokens at
    most) and on an 8-slot ring that holds only the 8-token tails.  Every
    prefill dispatch is counted as an in-place write."""
    cfg = dataclasses.replace(TINY, sliding_window=window)
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=32)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(0, 128, size=n)) for n in (37, 21, 50)]
    scfg = dict(max_batch=3, chunk_tokens=16, max_len=64, prefill_bucket=8)
    outs, n_writes = _run_engine(api, mesh11, params, prompts, **scfg)
    assert n_writes > len(prompts), n_writes
    monkeypatch.setattr(KC, "insert_chunk", _scatter_reference)
    ref, _ = _run_engine(api, mesh11, params, prompts, **scfg)
    assert outs == ref
