"""The paper's core op (DESIGN.md §2): AllReduce + residual-add + RMSNorm,
five ways.

All variants run inside ``jax.shard_map`` with manual collectives so the
collective schedule is explicit (the paper's point). Shapes (per dp shard):

    x          (T, d)      row-parallel matmul output; *partial sums* over TP
    residual   vanilla/nocomm: (T, d)  full
               reordered/fused: (T // tp, d)  this shard's token slice only
               (paper Listing 1: each GPU only ever touches its 1/N residual
               slice -> the residual stream lives permanently token-sharded)
    weight     (d,)        RMSNorm gain, replicated
    returns    (normed_full (T, d), new_residual (layout per mode))

Modes:
    vanilla   : psum -> +residual -> RMSNorm on all T tokens on every shard
                (the vLLM default the paper measures 5-9% overhead for)
    reordered : psum_scatter -> +res -> RMSNorm (1/N tokens) -> all_gather,
                with the *unfused* two-pass add+norm (paper Fig. 4 middle bar:
                reordering alone, overheads eat the gains)
    fused     : psum_scatter -> single-pass fused add+norm kernel ->
                all_gather (paper's fused AllReduce-RMSNorm, composed from
                XLA collectives)
    ring      : the REAL single-kernel path — kernels/ring_ar_rmsnorm.py
                does reduce-scatter + fused add/norm + all-gather in ONE
                Pallas kernel (the paper's multimem kernel, TPU ring
                analogue), reached only through
                ``ParallelConfig.comm_mode="ring"``. Falls down a ladder
                to the ``fused``
                composition when the backend can't run it (see
                ``_ring_supported``); numerics pinned either way by
                tests/test_fused_path.py.
    nocomm    : collectives skipped entirely (perf counterfactual; wrong math,
                correct shapes - mirrors vllm-nocomm)
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.distributed.context import CommCtx, token_shard_slice
from repro.kernels.ops import fused_residual_rmsnorm
from repro.kernels.ring_ar_rmsnorm import ring_fused_ar_rmsnorm
from repro.layers.norms import residual_rmsnorm_unfused, rms_norm


# comm slots the ring kernel cycles through; the kernel clamps them to
# [1, tp - 1], so every ring dispatch runs min(8, tp - 1)
RING_CHANNELS = 8


def _ring_supported(ctx: CommCtx, reduce_input: bool, weight_post) -> bool:
    """Fallback ladder for mode="ring" (DESIGN.md §2): the one-kernel ring
    path needs (a) a genuine reduction to fold in (``reduce_input``), (b)
    no sandwich post-norm (the kernel fuses exactly add+norm), and (c)
    Pallas enabled."""
    return reduce_input and weight_post is None and ctx.use_pallas


def comm_norm(x, residual, weight, *, ctx: CommCtx, reduce_input: bool = True,
              weight_post=None):
    """The fused AllReduce-RMSNorm slot at the end of attention / FFN.

    ``reduce_input=False`` means x is already complete per token (e.g. the
    MoE ep2d combine returned full values): the reduction is skipped but the
    token-sharded norm + AG structure is preserved.

    ``weight_post``: optional gemma-style post-norm applied to the *reduced
    block output* before the residual add (sandwich norm); it rides the same
    scattered shard so the redundancy elimination still applies.
    """
    mode = ctx.mode
    if mode in ("nocomm", "vanilla"):
        if mode == "vanilla" and reduce_input:
            x = lax.psum(x, ctx.tp_axis)
            if ctx.bf16_wire:
                x = lax.optimization_barrier(x)
        if weight_post is not None:
            x = rms_norm(x, weight_post, ctx.eps)
        out, new_res = residual_rmsnorm_unfused(x, residual, weight, ctx.eps)
        return out, new_res

    if mode not in ("reordered", "fused", "ring"):
        raise ValueError(f"unknown comm mode {mode!r}")

    if mode == "ring":
        if _ring_supported(ctx, reduce_input, weight_post):
            return ring_fused_ar_rmsnorm(
                x, residual, weight, axis_name=ctx.tp_axis,
                n_dev=ctx.tp_size(), eps=ctx.eps, interpret=ctx.interpret,
                channels=RING_CHANNELS)
        mode = "fused"  # rung 2 of the ladder: the composed RS/fused/AG path

    # --- TokenWeave path: RS -> (+res, norm on 1/N tokens) -> AG -----------
    if reduce_input:
        local = lax.psum_scatter(x, ctx.tp_axis, scatter_dimension=0, tiled=True)
        if ctx.bf16_wire:
            # stop XLA's excess-precision pass from hoisting the fp32 norm
            # cast above the reduce-scatter (f32 wire = 2x bytes)
            local = lax.optimization_barrier(local)
    else:
        local = token_shard_slice(x, ctx)

    if weight_post is not None:
        local = rms_norm(local, weight_post, ctx.eps)

    if mode == "fused" and weight_post is None:
        normed_shard, new_res = fused_residual_rmsnorm(
            local, residual, weight, eps=ctx.eps,
            use_pallas=ctx.use_pallas, interpret=ctx.interpret)
    else:
        normed_shard, new_res = residual_rmsnorm_unfused(
            local, residual, weight, ctx.eps)

    full = lax.all_gather(normed_shard, ctx.tp_axis, axis=0, tiled=True)
    return full, new_res


def final_norm(residual, weight, *, ctx: CommCtx):
    """Final pre-LM-head RMSNorm on the residual stream (no add)."""
    if ctx.sharded_residual:
        normed_shard = rms_norm(residual, weight, ctx.eps)
        return lax.all_gather(normed_shard, ctx.tp_axis, axis=0, tiled=True)
    return rms_norm(residual, weight, ctx.eps)


def fresh_residual(t_tokens: int, d: int, dtype, *, ctx: CommCtx):
    """Zero residual in the layout the configured mode expects."""
    if ctx.sharded_residual:
        tp = ctx.tp_size()
        return jnp.zeros((t_tokens // tp, d), dtype=dtype)
    return jnp.zeros((t_tokens, d), dtype=dtype)


def gather_residual(residual, *, ctx: CommCtx):
    """Materialize the full residual stream (checkpointing / logits paths)."""
    if ctx.sharded_residual:
        return lax.all_gather(residual, ctx.tp_axis, axis=0, tiled=True)
    return residual
