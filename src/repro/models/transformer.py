"""Decoder-only transformer stack (dense / MoE / VLM families) with the
TokenWeave two-split weave built into the layer execution.

Everything in this module is written to run INSIDE ``jax.shard_map``: weights
carry a leading per-shard axis (size tp or 1), collectives are explicit, and
the AllReduce+RMSNorm slots go through ``core.fused_collectives.comm_norm``.

The weave (paper Fig. 8): with two token-splits s0/s1, ops are emitted in the
order

    attn(s0) ; AR-norm(s0) ; attn(s1) ; AR-norm(s1) ;
    ffn(s0)  ; AR-norm(s0) ; ffn(s1)  ; AR-norm(s1)

so each collective is data-independent of the compute op that follows it —
XLA's latency-hiding scheduler turns the collectives into start/done pairs
that overlap with the adjacent split's compute. The suffix split's attention
takes the prefix split's KV as ``kv_prefix`` (chunked attention, §3.1), and
the residual stream stays token-sharded across TP throughout (§3.2).

Residual-ordering invariant: each split's residual is created *in that
split's own flattened token order* (the split happens before the first
comm_norm), so every psum_scatter/all_gather pair within a split is
self-consistent and no cross-shard re-distribution is ever needed.

Norm-weight convention (off-by-one, like vLLM's fused add+norm): layer i's
post-FFN comm_norm applies layer i+1's input norm; ``norm_ffn`` of the last
layer is the final norm; ``params['norm_first']`` is layer 0's input norm,
applied by the embedding-side comm_norm.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import fused_collectives as fc
from repro.core.splitting import split_decision
from repro.distributed.context import CommCtx
from repro.layers import attention as A
from repro.layers import embedding as E
from repro.layers import mlp as M
from repro.layers import moe as X
from repro.runtime import kv_cache as KC


# --------------------------------------------------------------------------
# layer kinds (gemma3 local/global pattern etc.)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerKind:
    window: int          # 0 = full attention
    theta: float
    is_moe: bool = False


def layer_kinds(cfg: ModelConfig) -> List[LayerKind]:
    kinds = []
    for i in range(cfg.num_layers):
        if cfg.local_global_period:
            # gemma3: (period-1) local layers then 1 global, repeating
            is_global = (i % cfg.local_global_period) == cfg.local_global_period - 1
            kinds.append(LayerKind(
                window=0 if is_global else cfg.sliding_window,
                theta=cfg.rope_theta if is_global else
                (cfg.rope_theta_local or cfg.rope_theta),
                is_moe=cfg.is_moe))
        else:
            kinds.append(LayerKind(window=cfg.sliding_window,
                                   theta=cfg.rope_theta, is_moe=cfg.is_moe))
    return kinds


def uniform_kinds(cfg: ModelConfig) -> bool:
    ks = layer_kinds(cfg)
    return all(k == ks[0] for k in ks)


def use_scan(cfg: ModelConfig, pcfg: ParallelConfig) -> bool:
    return pcfg.scan_layers and uniform_kinds(cfg)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def init_layer_params(key, cfg: ModelConfig, tp: int, ep: int = 1):
    ka, kf = jax.random.split(key)
    dtype = jnp.dtype(cfg.dtype)
    p = {
        "attn": A.init_attention_params(ka, cfg, tp),
        "norm_attn": jnp.ones((1, cfg.d_model), dtype),
        "norm_ffn": jnp.ones((1, cfg.d_model), dtype),
    }
    if cfg.sandwich_norms:
        p["norm_attn_post"] = jnp.ones((1, cfg.d_model), dtype)
        p["norm_ffn_post"] = jnp.ones((1, cfg.d_model), dtype)
    if cfg.is_moe:
        p["moe"] = X.init_moe_params(kf, cfg, tp, ep)
    else:
        p["mlp"] = M.init_mlp_params(kf, cfg, tp)
    return p


def layer_param_specs(cfg: ModelConfig):
    from jax.sharding import PartitionSpec as P
    specs = {
        "attn": A.attention_param_specs(cfg),
        "norm_attn": P(None),
        "norm_ffn": P(None),
    }
    if cfg.sandwich_norms:
        specs["norm_attn_post"] = P(None)
        specs["norm_ffn_post"] = P(None)
    if cfg.is_moe:
        specs["moe"] = X.moe_param_specs(cfg)
    else:
        specs["mlp"] = M.mlp_param_specs(cfg)
    return specs


def init_params(key, cfg: ModelConfig, pcfg: ParallelConfig, tp: int,
                ep: int = 1):
    ke, kl = jax.random.split(key)
    keys = jax.random.split(kl, cfg.num_layers)
    if use_scan(cfg, pcfg):
        # generated already stacked: never a per-layer list AND its stack
        layers = jax.vmap(lambda k: init_layer_params(k, cfg, tp, ep))(keys)
    else:
        layers = {f"layer_{i}": init_layer_params(k, cfg, tp, ep)
                  for i, k in enumerate(keys)}
    return {
        "embedding": E.init_embedding_params(ke, cfg, tp),
        "norm_first": jnp.ones((1, cfg.d_model), jnp.dtype(cfg.dtype)),
        "layers": layers,
    }


def param_specs(cfg: ModelConfig, pcfg: ParallelConfig):
    from jax.sharding import PartitionSpec as P
    ls = layer_param_specs(cfg)
    if use_scan(cfg, pcfg):
        layers = jax.tree.map(lambda s: P(None, *s), ls,
                              is_leaf=lambda s: isinstance(s, P))
    else:
        layers = {f"layer_{i}": ls for i in range(cfg.num_layers)}
    return {"embedding": E.embedding_param_specs(cfg),
            "norm_first": P(None), "layers": layers}


# --------------------------------------------------------------------------
# single-layer body (one split)
# --------------------------------------------------------------------------

def _layer_split(lp, h, res, *, positions, mrope_positions, kind: LayerKind,
                 cfg, pcfg, ctx: CommCtx, lay, kv_prefix, cache_layer,
                 decode: bool, block_tables=None, packed_slots=None,
                 window_kv: bool = False):
    """One transformer layer on one token-split.

    Returns (h_next, res, new_kv or new_cache_layer, aux).  ``window_kv``
    (slot decode/verify under the layer scan): the cache layer is only
    read, and new_kv is the window's (k, v, pos).
    """
    aux = jnp.zeros((), jnp.float32)
    if packed_slots is not None:
        # packed mixed-segment step (DESIGN.md §6): cache_layer is the FULL
        # slot cache (or one layer of the paged pool when block_tables is
        # given); every token scatters into its owning row, then attends it
        if block_tables is not None:
            a_part, kv_out = A.attn_packed_paged(
                lp["attn"], h, cache_layer, block_tables,
                positions=positions, seg_slots=packed_slots, cfg=cfg,
                lay=lay, theta=kind.theta, window=kind.window)
        else:
            a_part, kv_out = A.attn_packed(
                lp["attn"], h, cache_layer, positions=positions,
                seg_slots=packed_slots, cfg=cfg, lay=lay, theta=kind.theta,
                window=kind.window)
    elif decode and block_tables is not None:
        # paged decode: cache_layer is one layer of the shared block pool;
        # the block-table indirection replaces per-slot rows (no seq_axis —
        # the shared pool cannot shard over data, DESIGN.md §7).  S > 1 is
        # the speculative gamma+1 verify window (DESIGN.md §8).
        paged_attn = (A.attn_verify_paged if h.shape[1] > 1
                      else A.attn_decode_paged)
        a_part, kv_out = paged_attn(
            lp["attn"], h, cache_layer, block_tables, positions=positions,
            cfg=cfg, lay=lay, theta=kind.theta, window=kind.window,
            mrope_positions=mrope_positions)
    elif decode and window_kv:
        # stacked slot cache under the layer scan: attend the old cache
        # plus the window; ``forward`` writes every layer's window once,
        # after the scan, so the scan never copies or restacks the cache
        a_part, kv_out = A.attn_window(
            lp["attn"], h, cache_layer, positions=positions, cfg=cfg,
            lay=lay, theta=kind.theta, window=kind.window,
            mrope_positions=mrope_positions)
    elif decode and h.shape[1] > 1:
        # legacy-slot speculative verify window (no seq_axis: the verify
        # scatter writes full rows locally; context-parallel KV keeps the
        # plain decode path)
        a_part, kv_out = A.attn_verify(
            lp["attn"], h, cache_layer, positions=positions, cfg=cfg,
            lay=lay, theta=kind.theta, window=kind.window,
            mrope_positions=mrope_positions)
    elif decode:
        seq_axis = (tuple(pcfg.dp_axes)
                    if pcfg.seq_shard_kv and kind.window == 0 else None)
        a_part, kv_out = A.attn_decode(
            lp["attn"], h, cache_layer, positions=positions, cfg=cfg, lay=lay,
            theta=kind.theta, window=kind.window,
            mrope_positions=mrope_positions, seq_axis=seq_axis)
    else:
        a_part, kv_out = A.attn_prefill(
            lp["attn"], h, positions=positions, cfg=cfg, lay=lay,
            theta=kind.theta, window=kind.window, kv_prefix=kv_prefix,
            mrope_positions=mrope_positions, impl=pcfg.attn_impl,
            block_q=pcfg.attn_block_q, block_kv=pcfg.attn_block_kv)

    b, s, d = h.shape
    h2_flat, res = fc.comm_norm(
        a_part.reshape(b * s, d), res, lp["norm_attn"][0], ctx=ctx,
        weight_post=(lp["norm_attn_post"][0]
                     if "norm_attn_post" in lp else None))
    h2 = h2_flat.reshape(b, s, d)

    if kind.is_moe:
        f_part, aux = X.moe_forward(lp["moe"], h2, cfg, tp_axis=ctx.tp_axis,
                                    ep_axis=pcfg.moe_ep_axis)
    else:
        f_part = M.mlp_forward(lp["mlp"], h2, tp_axis=ctx.tp_axis,
                               act=cfg.act)

    h3_flat, res = fc.comm_norm(
        f_part.reshape(b * s, d), res, lp["norm_ffn"][0], ctx=ctx,
        weight_post=(lp["norm_ffn_post"][0]
                     if "norm_ffn_post" in lp else None))
    return h3_flat.reshape(b, s, d), res, kv_out, aux


def _weave_layer(lp, state, cache_layer, *, kind, cfg, pcfg, ctx, lay,
                 decode: bool, block_tables=None, window_kv: bool = False):
    """Run one layer over one or two splits in paper-Fig.8 order.

    state: dict with lists h[i], res[i], positions[i], mrope[i].
    Returns (state, kv_out or new_cache_layer, aux); with ``window_kv``
    the decode kv_out is the window's (k, v, pos), batch splits joined.
    """
    n = len(state["h"])
    kv_outs, auxes = [], []
    new_h, new_res = list(state["h"]), list(state["res"])

    if state.get("pslots") is not None:
        # packed mixed-segment step: the splits run over the SAME cache in
        # sequence — the suffix split's attention reads the prefix split's
        # freshly scattered KV (a straddling segment's later tokens need
        # its earlier ones), the same §3.1 chunked-attention dependency the
        # prefill weave already carries, so the Fig.8 overlap is preserved.
        cl = cache_layer
        for i in range(n):
            h, res, cl, aux = _layer_split(
                lp, state["h"][i], state["res"][i],
                positions=state["positions"][i],
                mrope_positions=state["mrope"][i], kind=kind, cfg=cfg,
                pcfg=pcfg, ctx=ctx, lay=lay, kv_prefix=None, cache_layer=cl,
                decode=False, block_tables=block_tables,
                packed_slots=state["pslots"][i])
            new_h[i], new_res[i] = h, res
            auxes.append(aux)
        return dict(state, h=new_h, res=new_res), cl, sum(auxes)

    if decode and block_tables is not None:
        # paged decode runs unsplit (forward forces split=None): a batch
        # split would fork the shared block pool into two divergent copies
        assert n == 1, "paged decode cannot weave-split the shared pool"
        h, res, new_cache, aux = _layer_split(
            lp, state["h"][0], state["res"][0],
            positions=state["positions"][0], mrope_positions=state["mrope"][0],
            kind=kind, cfg=cfg, pcfg=pcfg, ctx=ctx, lay=lay, kv_prefix=None,
            cache_layer=cache_layer, decode=True, block_tables=block_tables)
        return dict(state, h=[h], res=[res]), new_cache, aux

    if decode:
        sizes = [h.shape[0] for h in state["h"]]
        offs = [0]
        for s_ in sizes[:-1]:
            offs.append(offs[-1] + s_)
        for i in range(n):
            cl = jax.tree.map(
                lambda c, o=offs[i], s_=sizes[i]:
                    lax.dynamic_slice_in_dim(c, o, s_, axis=0), cache_layer)
            h, res, kv, aux = _layer_split(
                lp, state["h"][i], state["res"][i],
                positions=state["positions"][i],
                mrope_positions=state["mrope"][i], kind=kind, cfg=cfg,
                pcfg=pcfg, ctx=ctx, lay=lay, kv_prefix=None, cache_layer=cl,
                decode=True, window_kv=window_kv)
            new_h[i], new_res[i] = h, res
            kv_outs.append(kv)
            auxes.append(aux)
        new_cache = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *kv_outs)
        return dict(state, h=new_h, res=new_res), new_cache, sum(auxes)

    kv_prev = _cache_prefix(cache_layer)
    for i in range(n):
        h, res, kv, aux = _layer_split(
            lp, state["h"][i], state["res"][i],
            positions=state["positions"][i],
            mrope_positions=state["mrope"][i], kind=kind, cfg=cfg, pcfg=pcfg,
            ctx=ctx, lay=lay, kv_prefix=kv_prev, cache_layer=None,
            decode=False)
        new_h[i], new_res[i] = h, res
        kv_outs.append(kv)
        auxes.append(aux)
        # later splits attend to cache-prefix + all earlier splits' kv
        kv_prev = kv if kv_prev is None else tuple(
            jnp.concatenate([a, b], axis=1) for a, b in zip(kv_prev, kv))
    kv_new = kv_outs[0] if n == 1 else tuple(
        jnp.concatenate([a, b], axis=1) for a, b in zip(*kv_outs))
    return dict(state, h=new_h, res=new_res), kv_new, sum(auxes)


def _cache_prefix(cache_layer):
    if cache_layer is None:
        return None
    return (cache_layer["k"], cache_layer["v"], cache_layer["pos"])


# --------------------------------------------------------------------------
# full forward
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WeaveInfo:
    """Full weave decision for one forward dispatch: the split (in the
    dispatch's native axis units), WHY it was or wasn't taken, and the
    parameters the decision saw — the host-side record the observability
    layer attaches to every forward span (DESIGN.md §12)."""
    weave: bool
    split: Optional[Tuple[int, int]]
    reason: str   # split | weave_disabled | paged_pool_unsplit |
    #               below_min_tokens | below_wave_floor
    axis: str     # packed | batch | seq
    threshold: int  # configured tokenweave_min_tokens (tokens)
    unit: int       # effective wave quantum the decision used
    site: str       # dispatch kind: prefill | decode | verify | packed


def weave_decision_info(b: int, s: int, *, tp: int, pcfg: ParallelConfig,
                        decode: bool = False, packed: bool = False,
                        paged_pool: bool = False) -> WeaveInfo:
    """The weave rule (DESIGN.md §14): the host-side mirror of the
    trace-time split decision (pure int math), with the refusal reason
    attached.

    One ``split_decision`` per dispatch kind — prefill: split along the
    sequence dim (all rows cut at the same position — rectangular
    shapes), unit ``split_unit_for(tp)``, floor ``tokenweave_min_tokens``;
    decode: split along the batch dim, unit ``max(tp, 8)`` rows, floor two
    units; verify: decode with s = gamma+1 tokens per row, the token floor
    converted to rows; packed: split along the flat packed token axis
    (b == 1), so the threshold sees the true combined iteration size
    (DESIGN.md §6).
    ``paged_pool`` marks a non-packed paged decode/verify dispatch, which
    always runs unsplit (a batch split would fork the shared pool,
    DESIGN.md §7); packed paged steps thread the pool sequentially
    through the splits and CAN weave.
    """
    thr = pcfg.tokenweave_min_tokens
    site = ("packed" if packed else "prefill" if not decode else
            "decode" if s == 1 else "verify")
    axis = "packed" if packed else ("batch" if decode else "seq")
    if not pcfg.tokenweave:
        return WeaveInfo(False, None, "weave_disabled", axis, thr, 0, site)
    if paged_pool and not packed:
        return WeaveInfo(False, None, "paged_pool_unsplit", axis, thr, 0,
                         site)
    if packed:
        d = split_decision(b * s, unit=pcfg.split_unit_for(tp),
                           min_tokens=thr)
        split = d.split
    elif decode:
        unit = max(tp, 8)
        # speculative verify: every batch row carries s = gamma+1 tokens,
        # so the paper's token threshold converts to rows — this is
        # exactly how spec decoding pushes decode iterations across
        # tokenweave_min_tokens (DESIGN.md §8)
        min_rows = max(2 * unit, -(-thr // s)) if s > 1 else 2 * unit
        d = split_decision(b, unit=unit, min_tokens=min_rows)
        split = d.split
    else:
        d = split_decision(b * s, unit=pcfg.split_unit_for(tp),
                           min_tokens=thr, row_multiple=b)
        split = None if d.split is None else (d.split[0] // b,
                                              d.split[1] // b)
    return WeaveInfo(split is not None, split, d.reason, axis, thr, d.unit,
                     site)


def _decide_split(b: int, s: int, *, tp: int, pcfg: ParallelConfig,
                  decode: bool, packed: bool = False
                  ) -> Optional[Tuple[int, int]]:
    """Static (trace-time) TokenWeave split decision (per-dim sizes or
    None) — thin view over ``weave_decision_info``."""
    return weave_decision_info(b, s, tp=tp, pcfg=pcfg, decode=decode,
                               packed=packed).split


def weave_decision(b: int, s: int, *, tp: int, pcfg: ParallelConfig,
                   decode: bool = False, packed: bool = False,
                   paged_pool: bool = False) -> bool:
    """Boolean view of ``weave_decision_info`` (the engine's legacy
    weave-activation predicate)."""
    return weave_decision_info(b, s, tp=tp, pcfg=pcfg, decode=decode,
                               packed=packed, paged_pool=paged_pool).weave


def comm_mode_for(pcfg: ParallelConfig, t_local: int, tp: int) -> str:
    """The comm mode a forward over ``t_local`` tokens runs at every
    ``comm_norm`` site (DESIGN.md §14): ``pcfg.comm_mode``, except that
    the token-sharded (fused/reordered/ring) layouts need t_local
    divisible by tp and fall back to vanilla otherwise (the paper's
    fallback for small decode batches)."""
    mode = pcfg.comm_mode
    if (mode in ("fused", "reordered", "ring")
            and (t_local % tp != 0 or t_local < tp)):
        return "vanilla"
    return mode


def _comm_ctx(pcfg: ParallelConfig, cfg: ModelConfig, t_local: int,
              tp: int) -> CommCtx:
    """The forward's ``CommCtx``, in the mode ``comm_mode_for`` picks."""
    return CommCtx(tp_axis=pcfg.tp_axis, dp_axes=pcfg.dp_axes,
                   mode=comm_mode_for(pcfg, t_local, tp),
                   eps=cfg.norm_eps, use_pallas=pcfg.use_pallas_norm,
                   bf16_wire=pcfg.bf16_wire)


def _entry_norm(emb, w_first, ctx):
    """Split-local embedding -> residual birth + first input norm."""
    b, s, d = emb.shape
    res0 = fc.fresh_residual(b * s, d, emb.dtype, ctx=ctx)
    h_flat, res = fc.comm_norm(emb.reshape(b * s, d), res0, w_first, ctx=ctx)
    return h_flat.reshape(b, s, d), res


def forward(params, tokens, *, cfg: ModelConfig, pcfg: ParallelConfig,
            positions=None, mrope_positions=None, extra_embeds=None,
            cache=None, decode: bool = False, return_kv: bool = True,
            block_tables=None, packed_slots=None):
    """Shared forward. Returns (hidden_normed (B,S,d), kv_or_cache, aux).

    train: cache=None, decode=False (kv output suppressed via return_kv).
    prefill chunk: cache = existing KV cache (attended as prefix); the
        chunk's new kv is returned for the engine to insert.
    decode: cache required; S == 1, or S == gamma+1 for the speculative
        verify window (multi-token causal decode attention); returns the
        updated cache.  On a stacked slot cache under the layer scan the
        layers attend the cache read-only and return only the window's KV,
        which is written into the cache once, after the scan
        (``KC.insert_window``).
    block_tables: (B, max_blocks) int32 — switches decode to the paged
        block-pool cache layout (runtime/paging.py); prefill is unaffected
        (the engine pre-gathers the paged prefix into rectangular rows).
    packed_slots: (T,) int32 — switches to the packed mixed-segment mode
        (DESIGN.md §6): tokens is (1, T) with per-token cache-row /
        block-table-row owners (-1 = padding); cache is the FULL slot
        cache (or the paged pool with block_tables) and the updated cache
        is returned.  The weave split runs over the flat packed token
        axis, so the threshold sees the true combined iteration size.
    """
    tp = lax.axis_size(pcfg.tp_axis)
    b = tokens.shape[0]
    s_total = tokens.shape[1] + (extra_embeds.shape[1]
                                 if extra_embeds is not None else 0)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(s_total, dtype=jnp.int32)[None], (b, s_total))

    packed = packed_slots is not None
    winfo = weave_decision_info(
        b, s_total, tp=tp, pcfg=pcfg, decode=decode, packed=packed,
        paged_pool=(decode and block_tables is not None and not packed))
    ctx = _comm_ctx(pcfg, cfg, b * s_total, tp)
    emb = E.embed_tokens(params["embedding"], tokens, tp_axis=ctx.tp_axis,
                         scale=cfg.embed_scale)
    if extra_embeds is not None:
        # VLM stub frontend: patch embeddings are complete values; divide so
        # the TP reduction reconstructs them alongside the partial text rows
        img = (extra_embeds / tp).astype(emb.dtype)
        emb = jnp.concatenate([img, emb], axis=1)
    d = cfg.d_model
    w_first = params["norm_first"][0]

    split = winfo.split
    if decode and block_tables is not None and not packed:
        split = None  # shared pool cannot be forked across a batch split
        #               (weave_decision_info already refused via paged_pool)
    pslots = None
    if split is not None and packed:
        s1, _ = split          # cut along the flat packed token axis
        embs = [emb[:, :s1], emb[:, s1:]]
        poss = [positions[:, :s1], positions[:, s1:]]
        pslots = [packed_slots[:s1], packed_slots[s1:]]
        mrs = [None, None]
    elif split is not None and not decode:
        s1, _ = split
        embs = [emb[:, :s1], emb[:, s1:]]
        poss = [positions[:, :s1], positions[:, s1:]]
        mrs = _split_mrope(mrope_positions, s1)
    elif split is not None and decode:
        b1, _ = split
        embs = [emb[:b1], emb[b1:]]
        poss = [positions[:b1], positions[b1:]]
        mrs = _split_mrope_batch(mrope_positions, b1)
    else:
        embs, poss, mrs = [emb], [positions], [mrope_positions]
        if packed:
            pslots = [packed_slots]

    hs, ress = [], []
    for e in embs:
        h_i, r_i = _entry_norm(e, w_first, ctx)
        hs.append(h_i)
        ress.append(r_i)
    state = {"h": hs, "res": ress, "positions": poss, "mrope": mrs,
             "pslots": pslots}

    kinds = layer_kinds(cfg)
    lay = A.attention_layout(tp, cfg.num_heads, cfg.num_kv_heads,
                             cfg.head_dim)
    aux_total = jnp.zeros((), jnp.float32)
    scan_mode = use_scan(cfg, pcfg) and "layer_0" not in params["layers"]

    if scan_mode:
        kind = kinds[0]
        # slot decode/verify (context-parallel decode excepted: it scatters
        # into its shard of the cache before it attends)
        window_kv = (decode and cache is not None and block_tables is None
                     and not packed
                     and not (s_total == 1 and pcfg.seq_shard_kv
                              and kind.window == 0))

        def body(carry, xs):
            st, aux = carry
            lp, cache_layer = xs
            st, kv_new, aux_l = _weave_layer(
                lp, st, cache_layer, kind=kind, cfg=cfg, pcfg=pcfg, ctx=ctx,
                lay=lay, decode=decode, block_tables=block_tables,
                window_kv=window_kv)
            ys = kv_new if (return_kv or decode) else None
            return (st, aux + aux_l), ys

        if pcfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        if cache is None:
            def body_nocache(carry, lp):
                return body(carry, (lp, None))
            bodyfn, scan_xs = body_nocache, params["layers"]
        else:
            bodyfn, scan_xs = body, (params["layers"], cache)
        (state, aux_total), kv_all = lax.scan(
            bodyfn, (state, aux_total), scan_xs)
        if window_kv:
            kv_all = KC.insert_window(cache, kv_all)
    else:
        kv_list = []
        for i, kind in enumerate(kinds):
            lp = params["layers"][f"layer_{i}"]
            cache_layer = None if cache is None else cache[f"layer_{i}"]
            fn = functools.partial(
                _weave_layer, kind=kind, cfg=cfg, pcfg=pcfg, ctx=ctx,
                lay=lay, decode=decode, block_tables=block_tables)
            if pcfg.remat:
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies.nothing_saveable)
            state, kv_new, aux_l = fn(lp, state, cache_layer)
            aux_total = aux_total + aux_l
            if return_kv or decode:
                kv_list.append(kv_new)
        kv_all = ({f"layer_{i}": kv for i, kv in enumerate(kv_list)}
                  if kv_list else None)

    if len(state["h"]) == 2:
        axis = 0 if decode else 1
        h_out = jnp.concatenate(state["h"], axis=axis)
    else:
        h_out = state["h"][0]
    return h_out, kv_all, aux_total


def _split_mrope(mrope, s1):
    if mrope is None:
        return [None, None]
    return [mrope[:, :, :s1], mrope[:, :, s1:]]


def _split_mrope_batch(mrope, b1):
    if mrope is None:
        return [None, None]
    return [mrope[:b1], mrope[b1:]]


# --------------------------------------------------------------------------
# task heads
# --------------------------------------------------------------------------

def train_loss(params, batch, *, cfg: ModelConfig, pcfg: ParallelConfig,
               aux_weight: float = 0.01):
    """batch: {tokens (B,S), labels (B,S)} -> (loss_sum, denom, aux)."""
    h, _, aux = forward(params, batch["tokens"], cfg=cfg, pcfg=pcfg,
                        mrope_positions=batch.get("mrope_positions"),
                        extra_embeds=batch.get("extra_embeds"),
                        return_kv=False)
    labels = batch["labels"]
    if h.shape[1] != labels.shape[1]:      # VLM: image positions carry no loss
        h = h[:, h.shape[1] - labels.shape[1]:]
    logits = E.lm_head_logits(params["embedding"], h)
    loss_sum, denom = E.sharded_softmax_xent(
        logits, labels, vocab_size=cfg.vocab_size, tp_axis=pcfg.tp_axis)
    return loss_sum, denom, aux * aux_weight


def prefill(params, tokens, cache, *, cfg, pcfg, positions,
            mrope_positions=None, extra_embeds=None, last_idx=None):
    """One (chunked-)prefill step. Returns (last-pos logits local shard,
    chunk kv to insert, aux). ``last_idx``: per-request index of the last
    valid (unpadded) token in the chunk."""
    h, kv, aux = forward(params, tokens, cfg=cfg, pcfg=pcfg,
                         positions=positions, mrope_positions=mrope_positions,
                         extra_embeds=extra_embeds, cache=cache,
                         return_kv=True)
    if last_idx is None:
        h_last = h[:, -1:]
    else:
        h_last = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)
    logits = E.lm_head_logits(params["embedding"], h_last)
    return logits, kv, aux


def decode_step(params, tokens, cache, *, cfg, pcfg, positions,
                mrope_positions=None, block_tables=None):
    """Single-token decode. Returns (logits local shard (B,1,V_loc),
    updated cache). ``block_tables`` selects the paged block-pool layout
    (cache = pool from runtime/paging.init_paged_cache)."""
    h, new_cache, _ = forward(params, tokens, cfg=cfg, pcfg=pcfg,
                              positions=positions,
                              mrope_positions=mrope_positions, cache=cache,
                              decode=True, block_tables=block_tables)
    logits = E.lm_head_logits(params["embedding"], h)
    return logits, new_cache


def verify_step(params, tokens, cache, *, cfg, pcfg, positions,
                mrope_positions=None, block_tables=None):
    """Speculative multi-token verify: tokens (B, gamma+1) = the pending
    decode input followed by the draft proposal, positions -1 where a row
    has no (or a short) draft.  Returns (logits local shard
    (B, gamma+1, V_loc) — one target distribution per window position —
    and the updated cache with the whole window's KV written; the engine
    rolls back rejected positions host-side, DESIGN.md §8)."""
    h, new_cache, _ = forward(params, tokens, cfg=cfg, pcfg=pcfg,
                              positions=positions,
                              mrope_positions=mrope_positions, cache=cache,
                              decode=True, block_tables=block_tables)
    logits = E.lm_head_logits(params["embedding"], h)
    return logits, new_cache


def packed_step(params, tokens, cache, *, cfg, pcfg, positions, seg_slots,
                sample_idx, block_tables=None):
    """One packed hybrid forward (DESIGN.md §6): tokens (1, T) carries
    prefill-chunk segments, single-token decode slots, and speculative
    verify windows concatenated along one token axis; ``seg_slots`` (T,)
    maps each token to its owning cache row (legacy) or block-table row
    (paged), -1 = padding.  ``sample_idx`` (Nseg, W) indexes each
    segment's sampling window into the packed axis (row 0 = the position
    whose logits a plain sample would use; rows 1..γ the verify window;
    -1 = unused, clamped — the engine masks host-side).  Returns (logits
    local shard (Nseg, W, V_loc), updated cache)."""
    h, new_cache, _ = forward(params, tokens, cfg=cfg, pcfg=pcfg,
                              positions=positions, cache=cache,
                              return_kv=True, block_tables=block_tables,
                              packed_slots=seg_slots)
    h_sel = h[0][jnp.maximum(sample_idx, 0)]          # (Nseg, W, d)
    logits = E.lm_head_logits(params["embedding"], h_sel)
    return logits, new_cache


# --------------------------------------------------------------------------
# cache factory (dense / moe / vlm families)
# --------------------------------------------------------------------------

def init_cache(batch: int, max_len: int, cfg: ModelConfig, tp: int,
               pcfg: ParallelConfig | None = None):
    kinds = layer_kinds(cfg)
    scan = (pcfg is None or pcfg.scan_layers) and uniform_kinds(cfg)
    if scan:
        return A.init_kv_cache(batch, max_len, cfg, tp,
                               window=kinds[0].window)
    return {f"layer_{i}": A.init_kv_cache(batch, max_len, cfg, tp,
                                          window=k.window, layers=0)
            for i, k in enumerate(kinds)}


def cache_specs(cfg: ModelConfig, pcfg: ParallelConfig,
                batch1: bool = False):
    """KV-cache PartitionSpecs. ``batch1``: global batch of 1 cannot shard
    the batch axis (long_500k cell) — context-parallel seq sharding
    (pcfg.seq_shard_kv) carries the distribution instead; sliding-window
    ring caches stay replicated (they are tiny and their decode path is
    shard-local)."""
    from jax.sharding import PartitionSpec as P
    dp = tuple(pcfg.dp_axes)
    b = None if batch1 else dp

    def kv_spec(window: int):
        if pcfg.seq_shard_kv and window == 0:
            return {"k": P(None, None, dp, "model", None),
                    "v": P(None, None, dp, "model", None),
                    "pos": P(None, None, dp)}
        return {"k": P(None, b, None, "model", None),
                "v": P(None, b, None, "model", None),
                "pos": P(None, b, None)}

    kinds = layer_kinds(cfg)
    if use_scan(cfg, pcfg):
        return kv_spec(kinds[0].window)
    return {f"layer_{i}": {k: P(*s[1:]) for k, s in
                           kv_spec(kind.window).items()}
            for i, kind in enumerate(kinds)}
