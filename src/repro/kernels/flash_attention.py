"""Pallas TPU flash attention (forward) for the overlapped compute path.

Blockwise softmax attention with GQA grouping, causal + sliding-window
masking via position arrays (so chunked prefill / TokenWeave suffix splits
with arbitrary offsets work unchanged). The kernel keeps the running
(m, l, acc) statistics in VMEM scratch across the kv-block grid dimension —
the logits tile never touches HBM, which is exactly the traffic the pure-jnp
chunked path pays (see EXPERIMENTS.md §Perf iteration on the memory term).

Grid: (num_q_blocks, num_kv_blocks), kv minor (sequential on TPU, so the
scratch carries across kv steps for a fixed q block). Batch and KV-head
dims are vmapped over the kernel.

Validated against kernels/ref.flash_attention_ref in interpret mode across
shape/dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, causal, window, sm_scale,
                  num_kv_blocks):
    kv_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)        # (bq, g, dh)
    k = k_ref[...].astype(jnp.float32)        # (bk, dh)
    v = v_ref[...].astype(jnp.float32)        # (bk, dh)
    qp = qpos_ref[...]                        # (bq, 1)
    kp = kpos_ref[...]                        # (1, bk)

    logits = jnp.einsum("qgd,kd->qgk", q, k) * sm_scale
    mask = kp >= 0
    if causal:
        mask &= qp >= kp
    if window > 0:
        mask &= (qp - kp) < window
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)

    m_prev = m_ref[...]                       # (bq, g)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "qgk,kd->qgd", p, v)
    m_ref[...] = m_new

    @pl.when(kv_idx == num_kv_blocks - 1)
    def _finish():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)[..., None]
                      ).astype(o_ref.dtype)


def _flash_single(q, k, v, qpos, kpos, *, causal, window, sm_scale,
                  block_q, block_kv, interpret):
    """q: (Sq, G, dh); k/v: (Sk, dh); qpos (Sq,), kpos (Sk,)."""
    sq, g, dh = q.shape
    sk = k.shape[0]
    bq = min(block_q, sq)
    bk = min(block_kv, sk)
    # the f32 (bq, g, bk) logits tile and its exp live in VMEM: with a wide
    # GQA group, halve the kv block until the tile is at most 2^20 entries
    # (the v5e compiler refuses bq=512, g=5, bk=1024 at its 16 MiB scoped
    # VMEM default)
    while bq * g * bk > 2 ** 20 and bk > 128:
        bk //= 2
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, pq), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, (0, pq), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, pk), (0, 0)))
        v = jnp.pad(v, ((0, pk), (0, 0)))
        kpos = jnp.pad(kpos, (0, pk), constant_values=-1)
    nq, nk = (sq + pq) // bq, (sk + pk) // bk

    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               sm_scale=sm_scale, num_kv_blocks=nk)
    # positions ride as a (Sq, 1) column and a (1, Sk) row: 2-D blocks
    # whose tiling matches XLA's layout, broadcasting to the (bq, bk) mask
    out = pl.pallas_call(
        kernel,
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
            pl.BlockSpec((bq, g, dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bk, dh), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, dh), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, g, dh), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((sq + pq, g, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, g), jnp.float32),       # running max
            pltpu.VMEM((bq, g), jnp.float32),       # running denom
            pltpu.VMEM((bq, g, dh), jnp.float32),   # accumulator
        ],
        interpret=interpret,
    )(qpos[:, None], kpos[None, :], q, k, v)
    return out[:sq]


def flash_attention(q, k, v, qpos, kpos, *, causal: bool, window: int = 0,
                    sm_scale: float | None = None, block_q: int = 512,
                    block_kv: int = 1024, interpret: bool = False):
    """q: (B, Sq, KVH, G, dh); k/v: (B, Sk, KVH, dh); positions (B, S*)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    fn = functools.partial(_flash_single, causal=causal, window=window,
                           sm_scale=sm_scale, block_q=block_q,
                           block_kv=block_kv, interpret=interpret)
    fn_h = jax.vmap(fn, in_axes=(0, 0, 0, None, None))   # over KV heads
    fn_b = jax.vmap(fn_h, in_axes=(0, 0, 0, 0, 0))       # over batch
    qr = jnp.moveaxis(q, 2, 1)      # (B, KVH, Sq, G, dh)
    kr = jnp.moveaxis(k, 2, 1)      # (B, KVH, Sk, dh)
    vr = jnp.moveaxis(v, 2, 1)
    out = fn_b(qr, kr, vr, qpos, kpos)   # (B, KVH, Sq, G, dh)
    return jnp.moveaxis(out, 1, 2)       # (B, Sq, KVH, G, dh)
