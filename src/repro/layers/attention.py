"""Tensor-parallel GQA attention with chunked (flash-style) execution.

Head layout under TP
--------------------
``attention_layout`` decides how query/KV heads map onto the ``tp`` shards of
the model axis. TP degrees larger than the head count (e.g. gemma3-1b's 4
heads on a 16-way model axis) are handled by *replication groups*: the head
shards are replicated ``replicas`` times and the row-parallel output psum is
pre-scaled by 1/replicas, which keeps the math exact while every shard does
useful (if partially redundant) work. When ``attn_tp > num_kv_heads``, each
shard stores exactly one KV head (vLLM-style KV duplication), so the KV cache
stays sharded as far as the architecture allows.

Implementations
---------------
``impl='ref'``      full-score softmax (tests / tiny shapes)
``impl='chunked'``  lax.scan over q- and kv-blocks with running softmax — the
                    memory-efficient pure-jnp path used for CPU dry-run
                    lowering (Pallas cannot lower on the CPU backend)
``impl='pallas'``   kernels/flash_attention.py (TPU target)

All attention math runs per (batch, head) in fp32 accumulation.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.layers.norms import rms_norm
from repro.layers.rotary import apply_mrope, apply_rope

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


@dataclasses.dataclass(frozen=True)
class AttnLayout:
    tp: int            # model-axis size
    attn_tp: int       # head-sharding degree (divides tp)
    h_loc: int         # query heads per shard
    kv_store: int      # KV heads stored per shard
    replicas: int      # tp // attn_tp (redundant head-shard copies)
    num_heads: int
    num_kv_heads: int
    head_dim: int

    @property
    def o_scale(self) -> float:
        """Pre-psum scale correcting for replicated head shards."""
        return 1.0 / self.replicas


def attention_layout(tp: int, num_heads: int, num_kv_heads: int,
                     head_dim: int) -> AttnLayout:
    attn_tp = math.gcd(tp, num_heads)
    # shrink attn_tp until the GQA grouping divides cleanly
    while attn_tp > 1:
        if attn_tp <= num_kv_heads:
            if num_kv_heads % attn_tp == 0:
                break
        else:
            g = num_heads // num_kv_heads
            if attn_tp % num_kv_heads == 0 and g % (attn_tp // num_kv_heads) == 0:
                break
        attn_tp //= 2
    h_loc = num_heads // attn_tp
    kv_store = num_kv_heads // attn_tp if attn_tp <= num_kv_heads else 1
    return AttnLayout(tp=tp, attn_tp=attn_tp, h_loc=h_loc, kv_store=kv_store,
                      replicas=tp // attn_tp, num_heads=num_heads,
                      num_kv_heads=num_kv_heads, head_dim=head_dim)


def init_attention_params(key, cfg, tp: int, *, cross: bool = False):
    """Per-shard-leading-axis weights: every array's axis 0 has size tp."""
    lay = attention_layout(tp, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    d, dh = cfg.d_model, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    dtype = jnp.dtype(cfg.dtype)
    p = {
        "wq": (jax.random.normal(k1, (tp, d, lay.h_loc * dh)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (tp, d, lay.kv_store * dh)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (tp, d, lay.kv_store * dh)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (tp, lay.h_loc * dh, d)) * s).astype(dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((tp, lay.h_loc * dh), dtype)
        p["bk"] = jnp.zeros((tp, lay.kv_store * dh), dtype)
        p["bv"] = jnp.zeros((tp, lay.kv_store * dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((1, dh), dtype)
        p["k_norm"] = jnp.ones((1, dh), dtype)
    return p


def attention_param_specs(cfg, *, cross: bool = False):
    from jax.sharding import PartitionSpec as P
    specs = {k: P("model") for k in ("wq", "wk", "wv", "wo")}
    if cfg.qkv_bias and not cross:
        specs.update(bq=P("model"), bk=P("model"), bv=P("model"))
    if cfg.qk_norm:
        specs.update(q_norm=P(None), k_norm=P(None))
    return specs


# --------------------------------------------------------------------------
# core attention math. q: (B, Sq, kvh, g, dh); k/v: (B, Sk, kvh, dh)
# qpos: (B, Sq) absolute positions; kpos: (B, Sk) absolute positions of keys
# (-1 marks invalid/unwritten cache slots).
# --------------------------------------------------------------------------

def _mask(qpos, kpos, causal: bool, window):
    """window may be a python int or a traced scalar (<=0 means full)."""
    m = kpos[:, None, :] >= 0
    if causal:
        m &= qpos[:, :, None] >= kpos[:, None, :]
    if isinstance(window, (int, float)):
        if window > 0:
            m &= (qpos[:, :, None] - kpos[:, None, :]) < window
    else:
        m &= ((qpos[:, :, None] - kpos[:, None, :]) < window) | (window <= 0)
    return m  # (B, Sq, Sk)


def _attn_ref(q, k, v, qpos, kpos, *, causal, window, sm_scale):
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    mask = _mask(qpos, kpos, causal, window)  # (B, Sq, Sk)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p / jnp.maximum(denom, 1e-30),
                     v.astype(jnp.float32))
    return out.astype(q.dtype)


def _attn_chunked(q, k, v, qpos, kpos, *, causal, window, sm_scale,
                  block_q: int, block_kv: int):
    """Flash-style two-level blocked attention, O(bq*bkv) live scores."""
    b, sq, kvh, g, dh = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bkv = min(block_kv, sk)
    pq = (-sq) % bq
    pk = (-sk) % bkv
    q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    qpos_p = jnp.pad(qpos, ((0, 0), (0, pq)), constant_values=0)
    k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    kpos_p = jnp.pad(kpos, ((0, 0), (0, pk)), constant_values=-1)
    nq, nk = (sq + pq) // bq, (sk + pk) // bkv

    qb = jnp.moveaxis(q.reshape(b, nq, bq, kvh, g, dh), 1, 0)
    qpb = jnp.moveaxis(qpos_p.reshape(b, nq, bq), 1, 0)
    kb = jnp.moveaxis(k.reshape(b, nk, bkv, kvh, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nk, bkv, kvh, dh), 1, 0)
    kpb = jnp.moveaxis(kpos_p.reshape(b, nk, bkv), 1, 0)

    def q_step(_, qx):
        qblk, qp = qx  # (b, bq, kvh, g, dh), (b, bq)

        def kv_step(carry, kx):
            m_run, l_run, acc = carry
            kblk, vblk, kp = kx
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                                preferred_element_type=jnp.float32) * sm_scale
            msk = _mask(qp, kp, causal, window)
            logits = jnp.where(msk[:, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, bq, dh), jnp.float32)
        (m_f, l_f, acc), _ = lax.scan(kv_step, (m0, l0, a0), (kb, vb, kpb))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return None, jnp.moveaxis(out, 3, 1).astype(q.dtype)  # (b,bq,kvh,g,dh)

    _, outs = lax.scan(q_step, None, (qb, qpb))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq + pq, kvh, g, dh)
    return out[:, :sq]


def multihead_attention(q, k, v, qpos, kpos, *, causal: bool, window: int = 0,
                        impl: str = "chunked", block_q: int = 512,
                        block_kv: int = 1024, sm_scale: float | None = None,
                        interpret: bool = False):
    """q: (B, Sq, Hq, dh) grouped internally; k/v: (B, Sk, KVh, dh)."""
    b, sq, hq, dh = q.shape
    kvh = k.shape[2]
    g = hq // kvh
    qg = q.reshape(b, sq, kvh, g, dh)
    if sm_scale is None:
        sm_scale = dh ** -0.5
    if impl == "ref" or (impl == "chunked" and sq * k.shape[1] <= 256 * 256):
        out = _attn_ref(qg, k, v, qpos, kpos, causal=causal, window=window,
                        sm_scale=sm_scale)
    elif impl == "chunked":
        out = _attn_chunked(qg, k, v, qpos, kpos, causal=causal, window=window,
                            sm_scale=sm_scale, block_q=block_q,
                            block_kv=block_kv)
    elif impl == "pallas":
        from repro.kernels.flash_attention import flash_attention
        out = flash_attention(qg, k, v, qpos, kpos, causal=causal,
                              window=window, sm_scale=sm_scale,
                              block_q=block_q, block_kv=block_kv,
                              interpret=interpret)
    else:
        raise ValueError(f"unknown attention impl {impl!r}")
    return out.reshape(b, sq, hq, dh)


# --------------------------------------------------------------------------
# layer-level forward (inside shard_map; params carry the per-shard axis 0)
# --------------------------------------------------------------------------

def _sq(p):
    return jnp.squeeze(p, axis=0)


def _project_qkv(params, x, cfg, lay: AttnLayout, *, positions, theta,
                 mrope_positions=None):
    b, s, _ = x.shape
    dh = cfg.head_dim
    q = jnp.einsum("bsd,df->bsf", x, _sq(params["wq"]))
    k = jnp.einsum("bsd,df->bsf", x, _sq(params["wk"]))
    v = jnp.einsum("bsd,df->bsf", x, _sq(params["wv"]))
    if "bq" in params:
        q = q + _sq(params["bq"])
        k = k + _sq(params["bk"])
        v = v + _sq(params["bv"])
    q = q.reshape(b, s, lay.h_loc, dh)
    k = k.reshape(b, s, lay.kv_store, dh)
    v = v.reshape(b, s, lay.kv_store, dh)
    if "q_norm" in params:
        q = rms_norm(q, _sq(params["q_norm"]), cfg.norm_eps)
        k = rms_norm(k, _sq(params["k_norm"]), cfg.norm_eps)
    if cfg.mrope_sections and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections, theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections, theta)
    elif not cfg.learned_positions:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def attn_prefill(params, x, *, positions, cfg, lay: AttnLayout, theta,
                 causal: bool = True, window: int = 0,
                 kv_prefix: Optional[Tuple] = None, mrope_positions=None,
                 impl: str = "chunked", block_q: int = 512,
                 block_kv: int = 1024, interpret: bool = False):
    """Returns (partial_out (B,S,d) — pre-psum over TP, (k, v, kpos)).

    ``kv_prefix``: (k, v, kpos) from the prefix token-split — the suffix
    split's chunked-attention dependency (paper §3.1).
    """
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, lay, positions=positions,
                           theta=theta, mrope_positions=mrope_positions)
    kpos = positions
    if kv_prefix is not None:
        pk, pv, ppos = kv_prefix
        k_all = jnp.concatenate([pk, k], axis=1)
        v_all = jnp.concatenate([pv, v], axis=1)
        kpos_all = jnp.concatenate([ppos, kpos], axis=1)
    else:
        k_all, v_all, kpos_all = k, v, kpos
    out = multihead_attention(q, k_all, v_all, positions, kpos_all,
                              causal=causal, window=window, impl=impl,
                              block_q=block_q, block_kv=block_kv,
                              interpret=interpret)
    out = out.reshape(b, s, lay.h_loc * cfg.head_dim)
    partial = jnp.einsum("bsf,fd->bsd", out, _sq(params["wo"]))
    if lay.replicas > 1:
        partial = partial * lay.o_scale
    return partial, (k, v, kpos)


def attn_decode(params, x, cache, *, positions, cfg, lay: AttnLayout, theta,
                window: int = 0, mrope_positions=None, seq_axis=None):
    """Single-token decode against a (possibly ring-buffered) KV cache.

    cache: {"k": (B, C, kvh, dh), "v": ..., "pos": (B, C) int32 (-1 = empty)}.
    C = min(max_len, window) for sliding layers — the ring buffer IS the
    sliding window. ``seq_axis`` (axis name) enables context-parallel KV:
    each dp shard owns C_local slots; partial softmax stats are combined with
    pmax/psum (flash-decoding across chips).

    Without ``seq_axis`` this is exactly the S_v == 1 case of
    ``attn_verify``: ``attn_window`` over the old cache plus the new
    token, then the one slot scatter (shared epilogue).  Only the
    context-parallel branch, which scatters before it attends, lives here.
    """
    if seq_axis is None:
        return attn_verify(params, x, cache, positions=positions, cfg=cfg,
                           lay=lay, theta=theta, window=window,
                           mrope_positions=mrope_positions)
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(params, x, cfg, lay, positions=positions,
                                   theta=theta,
                                   mrope_positions=mrope_positions)
    c = cache["k"].shape[1]
    pos = positions[:, 0]  # (B,)

    # context parallel: slot `pos % (C_local * n)` lives on shard pos//C_local
    names = (seq_axis,) if isinstance(seq_axis, str) else tuple(seq_axis)
    n = 1
    me = jnp.zeros((), jnp.int32)
    for nm in names:
        n = n * lax.axis_size(nm)
        me = me * lax.axis_size(nm) + lax.axis_index(nm)
    gslot = (pos % (c * n)).astype(jnp.int32)
    owner = gslot // c
    lslot = gslot % c
    mine = (owner == me)[:, None, None]
    bidx = jnp.arange(b)
    k_upd = cache["k"].at[bidx, lslot].set(k_new[:, 0])
    v_upd = cache["v"].at[bidx, lslot].set(v_new[:, 0])
    p_upd = cache["pos"].at[bidx, lslot].set(pos.astype(jnp.int32))
    k_c = jnp.where(mine[..., None], k_upd, cache["k"])
    v_c = jnp.where(mine[..., None], v_upd, cache["v"])
    p_c = jnp.where(mine[:, :, 0], p_upd, cache["pos"])

    partial = _decode_attn_math(params, q, k_c, v_c, p_c, positions,
                                x_dtype=x.dtype, cfg=cfg, lay=lay,
                                window=window, seq_axis=seq_axis)
    return partial, {"k": k_c, "v": v_c, "pos": p_c}


def _decode_attn_math(params, q, k, v, kpos, positions, *, x_dtype, cfg,
                      lay: AttnLayout, window, seq_axis=None, window_kv=None):
    """Shared decode/verify epilogue: grouped-QK logits, masked stable
    softmax (optionally flash-decoding combined over a context-parallel
    ``seq_axis``), V accumulate, output projection.
    q: (B, Sq, h_loc, dh) grouped internally (Sq == 1 for plain decode,
    gamma+1 for the speculative verify window); k/v: (B, C, kvh, dh).
    ``window_kv``: (k_new, v_new, pos_new), the step's own keys, which
    join the cache's in the one softmax (``attn_window``); the cache is
    then read as it was before the step: only its keys below each row's
    first window position are visible."""
    b, sq = q.shape[0], q.shape[1]
    kvh = k.shape[2]
    g = lay.h_loc // kvh
    qg = q.reshape(b, sq, kvh, g, cfg.head_dim)

    def scores(kk, msk):
        # bf16 operands + f32 accumulation (MXU-native) — pre-casting the
        # cache to f32 would round-trip the whole KV through HBM at double
        # width
        lg = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kk,
                        preferred_element_type=jnp.float32) \
            * (cfg.head_dim ** -0.5)
        return jnp.where(msk[:, None, None], lg, NEG_INF)

    def weigh(p, vv):
        return jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vv.dtype), vv,
                          preferred_element_type=jnp.float32)

    msk = _mask(positions, kpos, True, window)  # (B, Sq, C)
    if window_kv is not None:
        msk &= kpos[:, None, :] < positions[:, :1, None]
    logits = scores(k, msk)
    m = jnp.max(logits, axis=-1)
    if window_kv is not None:
        k_new, v_new, pos_new = window_kv
        logits_new = scores(k_new, _mask(positions, pos_new, True, window))
        m = jnp.maximum(m, jnp.max(logits_new, axis=-1))
    if seq_axis is not None:
        m = lax.pmax(m, seq_axis)
    p = jnp.exp(logits - m[..., None])
    denom = jnp.sum(p, axis=-1)
    acc = weigh(p, v)
    if window_kv is not None:
        p_new = jnp.exp(logits_new - m[..., None])
        denom = denom + jnp.sum(p_new, axis=-1)
        acc = acc + weigh(p_new, v_new)
    if seq_axis is not None:
        # flash-decoding combine across context-parallel shards
        denom = lax.psum(denom, seq_axis)
        acc = lax.psum(acc, seq_axis)
    out = acc / jnp.maximum(denom, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, lay.h_loc * cfg.head_dim)
    partial = jnp.einsum("bsf,fd->bsd", out.astype(x_dtype),
                         _sq(params["wo"]))
    if lay.replicas > 1:
        partial = partial * lay.o_scale
    return partial


def attn_decode_paged(params, x, pool_layer, block_tables, *, positions, cfg,
                      lay: AttnLayout, theta, window: int = 0,
                      mrope_positions=None):
    """Single-token decode against one layer of the paged block pool.

    pool_layer: {"k": (nb, bs, kvh, dh), "v": ..., "pos": (nb, bs)} — the
    pool is SHARED across requests; each row of ``block_tables`` (B, nblk,
    int32, -1 = unallocated) maps a request's logical blocks to physical
    ones.  The new token scatters through the table (OOB-drop for inactive
    rows, pos < 0), then attention runs over the gathered rectangular
    (B, nblk*bs) view.  Sliding windows are enforced purely by the mask —
    paged layers have no ring buffer (DESIGN.md §7).  No ``seq_axis``:
    the shared block axis cannot shard over data, so the paged path is
    single-host (context-parallel decode stays on the slot path).

    Exactly the S_v == 1 case of ``attn_verify_paged`` (one scatter/gather
    implementation, shared epilogue).
    """
    return attn_verify_paged(params, x, pool_layer, block_tables,
                             positions=positions, cfg=cfg, lay=lay,
                             theta=theta, window=window,
                             mrope_positions=mrope_positions)


def attn_window(params, x, cache, *, positions, cfg, lay: AttnLayout,
                theta, window: int = 0, mrope_positions=None):
    """Read-only slot decode/verify attention: the old cache plus the window.

    x: (B, S_v, d) — one token (decode) or a causal window of gamma+1
    tokens (the pending decode input followed by the draft proposal;
    positions carry -1 for inactive rows and unused draft slots).
    cache: one layer's {"k": (B, C, kvh, dh), "v": ..., "pos": (B, C)} as
    it was before this step; it is read, never written.  One softmax runs
    over [old cache keys ; window keys]:

    * an old-cache key is visible iff 0 <= kpos < row_start (the row's
      first window position, ``positions[:, 0]``) and it passes the
      window mask — this hides what a scatter of the window would have
      overwritten: the ring slot's ``pos - C`` (C == window on a ring, so
      the window mask drops it), stale keys of rejected drafts at
      ``>= row_start``, and everything of an inactive row (row_start -1);
    * a window key is visible causally (its padding, position -1, never).

    Returns (partial_out (B, S_v, d) — pre-psum over TP, (k_new, v_new,
    pos_new)): the window's KV, for the caller to write at slot
    ``pos % C`` (``attn_verify`` per layer; the layer scan once for every
    layer, ``runtime/kv_cache.insert_window``).
    """
    q, k_new, v_new = _project_qkv(params, x, cfg, lay, positions=positions,
                                   theta=theta,
                                   mrope_positions=mrope_positions)
    pos_new = positions.astype(jnp.int32)
    partial = _decode_attn_math(params, q, cache["k"], cache["v"],
                                cache["pos"], positions, x_dtype=x.dtype,
                                cfg=cfg, lay=lay, window=window,
                                window_kv=(k_new, v_new, pos_new))
    return partial, (k_new, v_new, pos_new)


def attn_verify(params, x, cache, *, positions, cfg, lay: AttnLayout, theta,
                window: int = 0, mrope_positions=None):
    """Multi-token speculative-verify decode against one layer's slot cache.

    ``attn_window`` over the old cache plus the gamma+1 window (x:
    (B, S_v, d), positions -1 for inactive rows and unused draft slots),
    then the window's KV scatters into the layer's cache at slot
    ``pos % C`` (-1 writes are dropped).  Returns (partial_out, updated
    layer cache); the epilogue is shared verbatim with ``attn_decode``
    (the S_v == 1 case).

    Multi-token windows (S_v > 1) require full-attention layers: on a
    sliding-window ring buffer (C == window) the window's scatter evicts
    keys that a later query still needs once drafts are rejected.  The
    engine rejects spec decoding on the legacy backend for windowed
    models; the paged backend stores full-length KV and enforces windows
    by mask, so it is unaffected.  (S_v == 1 — plain ``attn_decode``
    delegating here — is safe for any layer kind: one write, one query.)
    """
    partial, (k_new, v_new, pos) = attn_window(
        params, x, cache, positions=positions, cfg=cfg, lay=lay,
        theta=theta, window=window, mrope_positions=mrope_positions)
    b = x.shape[0]
    c = cache["k"].shape[1]
    slot = jnp.where(pos >= 0, pos % c, c)
    bidx = jnp.arange(b)[:, None]
    k_c = cache["k"].at[bidx, slot].set(k_new, mode="drop")
    v_c = cache["v"].at[bidx, slot].set(v_new, mode="drop")
    p_c = cache["pos"].at[bidx, slot].set(pos, mode="drop")
    return partial, {"k": k_c, "v": v_c, "pos": p_c}


def attn_verify_paged(params, x, pool_layer, block_tables, *, positions, cfg,
                      lay: AttnLayout, theta, window: int = 0,
                      mrope_positions=None):
    """Multi-token speculative-verify decode against the paged block pool:
    the gamma+1 window scatters through the block-table indirection (the
    engine has already grown/COW'd every block the window touches), then
    attention runs over the gathered rectangular view with the causal mask
    ordering queries within the window.  Shares the epilogue with
    ``attn_decode_paged``; same single-host restriction (DESIGN.md §7).
    """
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(params, x, cfg, lay, positions=positions,
                                   theta=theta,
                                   mrope_positions=mrope_positions)
    nb, bs = pool_layer["pos"].shape
    pos = positions                                        # (B, S_v)

    blk = jnp.where(pos >= 0, pos // bs, 0)
    phys = jnp.take_along_axis(block_tables, blk, axis=1)  # (B, S_v)
    phys = jnp.where((pos >= 0) & (phys >= 0), phys, nb)   # OOB -> dropped
    off = jnp.where(pos >= 0, pos % bs, 0)
    k_c = pool_layer["k"].at[phys, off].set(k_new, mode="drop")
    v_c = pool_layer["v"].at[phys, off].set(v_new, mode="drop")
    p_c = pool_layer["pos"].at[phys, off].set(pos.astype(jnp.int32),
                                              mode="drop")

    bt = jnp.maximum(block_tables, 0)
    nblk = bt.shape[1]
    kvh = k_c.shape[2]
    kg = k_c[bt].reshape(b, nblk * bs, kvh, cfg.head_dim)
    vg = v_c[bt].reshape(b, nblk * bs, kvh, cfg.head_dim)
    pg = jnp.where(block_tables[:, :, None] >= 0, p_c[bt], -1)
    pg = pg.reshape(b, nblk * bs)

    partial = _decode_attn_math(params, q, kg, vg, pg, positions,
                                x_dtype=x.dtype, cfg=cfg, lay=lay,
                                window=window)
    return partial, {"k": k_c, "v": v_c, "pos": p_c}


def attn_packed(params, x, cache, *, positions, seg_slots, cfg,
                lay: AttnLayout, theta, window: int = 0):
    """Packed mixed-segment step against the slot KV cache (DESIGN.md §6).

    x: (1, T, d) — prefill-chunk segments, single-token decode slots, and
    speculative verify windows concatenated along one token axis.
    seg_slots: (T,) int32 cache row owning each token (-1 = padding);
    positions: (1, T) absolute query positions (-1 = padding).

    All T tokens scatter into their owning rows FIRST (scatter-then-
    attend; the slot decode/verify paths attend read-only instead,
    ``attn_window``), then every token
    attends its own row's full cache view with the causal mask ordering
    queries against both pre-existing context and same-step keys — so a
    segment's later tokens see its earlier ones, and tokens never see
    other segments (different rows).  The epilogue is shared verbatim with
    ``attn_decode``/``attn_verify`` via ``_decode_attn_math``.

    Full-attention layers only on this backend: a packed chunk's scatter
    into a sliding-window ring buffer (C == window) could evict a key an
    earlier query in the same step still needs — the engine rejects
    packed mode on windowed legacy models (the paged backend stores
    full-length KV and masks, so it is unaffected).

    Cost note: the per-token row gather materializes (T, C, kvh, dh) —
    tokens of the same segment repeat their request's KV read, a
    T-vs-B amplification over the rectangular paths.  It is the same
    order as the (T, heads, C) score tensor this pure-jnp emulation
    already materializes, so asymptotics are unchanged on the CPU
    target; the TPU production form is a varlen flash kernel that
    streams each segment's KV once (vLLM-style), which this function is
    the reference semantics for.
    """
    _, t, _ = x.shape
    q, k_new, v_new = _project_qkv(params, x, cfg, lay, positions=positions,
                                   theta=theta)
    bslots, c = cache["pos"].shape
    pos = positions[0]                                        # (T,)
    row = jnp.where((seg_slots >= 0) & (pos >= 0), seg_slots,
                    bslots)                                   # OOB -> dropped
    slot = jnp.where(pos >= 0, pos % c, 0)
    k_c = cache["k"].at[row, slot].set(k_new[0], mode="drop")
    v_c = cache["v"].at[row, slot].set(v_new[0], mode="drop")
    p_c = cache["pos"].at[row, slot].set(pos.astype(jnp.int32), mode="drop")

    # per-token gather of the owning row: (T, C, kvh, dh); padding tokens
    # read row 0 but their qpos == -1 masks every key
    rsafe = jnp.clip(seg_slots, 0, bslots - 1)
    kg = k_c[rsafe]
    vg = v_c[rsafe]
    pg = p_c[rsafe]
    partial = _decode_attn_math(params, q[0][:, None], kg, vg, pg,
                                pos[:, None], x_dtype=x.dtype, cfg=cfg,
                                lay=lay, window=window)       # (T, 1, d)
    return jnp.swapaxes(partial, 0, 1), {"k": k_c, "v": v_c, "pos": p_c}


def attn_packed_paged(params, x, pool_layer, block_tables, *, positions,
                      seg_slots, cfg, lay: AttnLayout, theta,
                      window: int = 0):
    """Packed mixed-segment step against the paged block pool: each token
    scatters through its owning request's block table (the engine has
    already allocated/grown/COW'd every block the plan touches), then
    attends the gathered rectangular view of that table.  Unlike paged
    decode, packed steps CAN weave: splits consume the pool sequentially
    (suffix split reads the prefix split's writes) instead of forking it
    across a batch split.  Same single-host restriction as every paged
    path (DESIGN.md §7), and the same per-token gather amplification /
    varlen-kernel production note as ``attn_packed``.
    """
    _, t, _ = x.shape
    q, k_new, v_new = _project_qkv(params, x, cfg, lay, positions=positions,
                                   theta=theta)
    nb, bs = pool_layer["pos"].shape
    pos = positions[0]                                        # (T,)
    rsafe = jnp.clip(seg_slots, 0, block_tables.shape[0] - 1)
    bt_tok = block_tables[rsafe]                              # (T, nblk)
    blk = jnp.where(pos >= 0, pos // bs, 0)
    phys = jnp.take_along_axis(bt_tok, blk[:, None], axis=1)[:, 0]
    valid = (pos >= 0) & (seg_slots >= 0) & (phys >= 0)
    phys = jnp.where(valid, phys, nb)                         # OOB -> dropped
    off = jnp.where(pos >= 0, pos % bs, 0)
    k_c = pool_layer["k"].at[phys, off].set(k_new[0], mode="drop")
    v_c = pool_layer["v"].at[phys, off].set(v_new[0], mode="drop")
    p_c = pool_layer["pos"].at[phys, off].set(pos.astype(jnp.int32),
                                              mode="drop")

    bt = jnp.maximum(bt_tok, 0)
    nblk = bt.shape[1]
    kvh = k_c.shape[2]
    kg = k_c[bt].reshape(t, nblk * bs, kvh, cfg.head_dim)
    vg = v_c[bt].reshape(t, nblk * bs, kvh, cfg.head_dim)
    pg = jnp.where(bt_tok[:, :, None] >= 0, p_c[bt], -1)
    pg = pg.reshape(t, nblk * bs)
    partial = _decode_attn_math(params, q[0][:, None], kg, vg, pg,
                                pos[:, None], x_dtype=x.dtype, cfg=cfg,
                                lay=lay, window=window)       # (T, 1, d)
    return jnp.swapaxes(partial, 0, 1), {"k": k_c, "v": v_c, "pos": p_c}


def attn_cross(params, x, enc_kv, *, cfg, lay: AttnLayout):
    """Whisper-style cross attention: q from decoder x, kv precomputed from
    the encoder output (enc_kv = (k, v, kpos))."""
    b, s, _ = x.shape
    dh = cfg.head_dim
    q = jnp.einsum("bsd,df->bsf", x, _sq(params["wq"])).reshape(
        b, s, lay.h_loc, dh)
    k, v, kpos = enc_kv
    qpos = jnp.zeros((b, s), jnp.int32)
    out = multihead_attention(q, k, v, qpos, kpos, causal=False, impl="ref"
                              if s * k.shape[1] <= 256 * 256 else "chunked")
    out = out.reshape(b, s, lay.h_loc * dh)
    partial = jnp.einsum("bsf,fd->bsd", out, _sq(params["wo"]))
    if lay.replicas > 1:
        partial = partial * lay.o_scale
    return partial


def project_cross_kv(params, enc_out, *, cfg, lay: AttnLayout):
    b, s, _ = enc_out.shape
    dh = cfg.head_dim
    k = jnp.einsum("bsd,df->bsf", enc_out, _sq(params["wk"])).reshape(
        b, s, lay.kv_store, dh)
    v = jnp.einsum("bsd,df->bsf", enc_out, _sq(params["wv"])).reshape(
        b, s, lay.kv_store, dh)
    kpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return k, v, kpos


def init_kv_cache(batch: int, max_len: int, cfg, tp: int, *, window: int = 0,
                  dtype=None, layers: int | None = None):
    """GLOBAL-shape KV cache pytree (L, B, C, kv_store*tp, dh) — the head
    axis shards over the model axis into per-shard kv_store heads (vLLM
    style KV duplication when kv_heads < tp). ``layers=0`` drops the
    leading layer axis (per-layer caches for unrolled models)."""
    lay = attention_layout(tp, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    c = min(max_len, window) if window > 0 else max_len
    nl = layers if layers is not None else cfg.num_layers
    dt = dtype or jnp.dtype(cfg.dtype)
    lead = () if nl == 0 else (nl,)
    h_global = lay.kv_store * tp
    return {
        "k": jnp.zeros(lead + (batch, c, h_global, cfg.head_dim), dt),
        "v": jnp.zeros(lead + (batch, c, h_global, cfg.head_dim), dt),
        "pos": jnp.full(lead + (batch, c), -1, jnp.int32),
    }
