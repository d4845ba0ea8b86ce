"""TPU-native ring fused AllReduce-RMSNorm kernel (paper Listing 1 analogue).

The paper's H100 kernel rides NVSwitch multimem: ld_reduce pulls the
reduced value, the norm happens in registers, multimem.st broadcasts the
result — one kernel, minimal HBM traffic, 2-8 SMs. TPU has no switch
multicast; the native analogue is a *ring* schedule on ICI driven by async
remote DMAs, which likewise leaves the compute units almost entirely free:

  phase 1  ring reduce-scatter: N-1 hops; the hop arriving at its owner is
           accumulated IN VMEM and never round-trips to HBM
  phase 2  fused residual-add + RMSNorm on the owned 1/N token chunk,
           still in VMEM (the paper's lines 23-37)
  phase 3  ring all-gather of the normed chunks

Chunk ownership matches `lax.psum_scatter(..., tiled=True)`: device r ends
up owning rows [r*C, (r+1)*C), so this kernel is a drop-in for the
psum_scatter/all_gather pair in core.fused_collectives — and IS dispatched
there on the serving hot path (``comm_norm`` mode="ring", DESIGN.md §2)
whenever the backend supports it, falling back to that composition
otherwise.

``channels`` sizes the comm slots the ring cycles through (HBM staging
slots + their semaphores). Every DMA the kernel starts is waited on
before the next step, so more slots add no concurrency; ``comm_norm``
passes a fixed count.

Numerics are pinned against kernels/ref.ring_ar_rmsnorm_ref and the
unfused vanilla composition by tests/test_fused_path.py (in-process and
subprocess-distributed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_hbm, res_ref, w_ref, out_hbm, res_out_ref, comm_hbm,
            acc_vmem, send_vmem, chunk_vmem, send_sem, recv_sem, free_sem,
            *, n_dev: int, chunk: int, eps: float, axis_name: str,
            channels: int):
    me = jax.lax.axis_index(axis_name)
    # neighbours named by their coordinate on the ring's axis alone, so on
    # a multi-axis mesh every other coordinate stays this device's own
    right = {axis_name: jax.lax.rem(me + 1, n_dev)}
    left = {axis_name: jax.lax.rem(me - 1 + n_dev, n_dev)}
    if n_dev > 1:
        # both neighbours must be inside this kernel before a remote DMA or
        # semaphore signal of ours can reach them
        barrier = pltpu.get_barrier_semaphore()
        for nb in (left, right):
            pltpu.semaphore_signal(barrier, 1, device_id=nb,
                                   device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 2)

    def dma_in(idx, dst):
        """x_hbm[idx*chunk : (idx+1)*chunk] -> dst (VMEM)."""
        cp = pltpu.make_async_copy(x_hbm.at[pl.ds(idx * chunk, chunk)], dst,
                                   send_sem.at[2])
        cp.start()
        cp.wait()

    # ---- phase 1: ring reduce-scatter -----------------------------------
    # chunk c starts at device (c+1)%N and travels right, ending at c.
    first = jax.lax.rem(me - 1 + n_dev, n_dev)
    dma_in(first, send_vmem)
    for s in range(n_dev - 1):
        slot = s % channels
        # wait until the receiver freed this comm slot (steps >= channels)
        if s >= channels:
            pltpu.semaphore_wait(free_sem.at[slot], 1)
        rcp = pltpu.make_async_remote_copy(
            src_ref=send_vmem,
            dst_ref=comm_hbm.at[slot],
            send_sem=send_sem.at[0], recv_sem=recv_sem.at[slot],
            device_id=right, device_id_type=pltpu.DeviceIdType.MESH)
        rcp.start()
        rcp.wait()
        # arrival of chunk (me - s - 2) from the left neighbor
        cp = pltpu.make_async_copy(comm_hbm.at[slot], acc_vmem,
                                   send_sem.at[1])
        cp.start()
        cp.wait()
        # slot consumed: free it for the left neighbor (phase-1 tail signals
        # are drained by phase-3's first two sends — see pairing note below)
        pltpu.semaphore_signal(free_sem.at[slot], 1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.MESH)
        idx = jax.lax.rem(me - s - 2 + 2 * n_dev, n_dev)
        dma_in(idx, chunk_vmem)
        if s < n_dev - 2:
            send_vmem[...] = acc_vmem[...] + chunk_vmem[...]
    # after the loop: acc + own contribution = fully reduced chunk `me`
    t = (acc_vmem[...] + chunk_vmem[...]).astype(jnp.float32) \
        if n_dev > 1 else 0.0

    # ---- phase 2: fused residual add + RMSNorm (VMEM, paper lines 23-37) -
    if n_dev == 1:
        dma_in(0, chunk_vmem)
        t = chunk_vmem[...].astype(jnp.float32)
    t = t + res_ref[...].astype(jnp.float32)
    var = jnp.mean(t * t, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    res_out_ref[...] = t.astype(res_out_ref.dtype)
    normed = (t * inv * w_ref[...].astype(jnp.float32)[None, :])
    send_vmem[...] = normed.astype(send_vmem.dtype)

    # write own chunk to the output
    wcp = pltpu.make_async_copy(send_vmem, out_hbm.at[pl.ds(me * chunk,
                                                            chunk)],
                                send_sem.at[2])
    wcp.start()
    wcp.wait()

    # ---- phase 3: ring all-gather of normed chunks ----------------------
    # semaphore pairing (k = channels): each device emits N-1 phase-1 free
    # signals; N-1-k are consumed by phase-1 sends (s >= k) and the final k
    # by phase-3's first k sends, which guarantees the receiver has drained
    # its phase-1 slots before phase-3 data lands (no cross-phase race).
    # Phase-3 emits its own signals only while a later sender still waits
    # (s + k < N-1), so all semaphores end at zero for ANY k in [1, N-1].
    for s in range(n_dev - 1):
        slot = s % channels
        pltpu.semaphore_wait(free_sem.at[slot], 1)
        rcp = pltpu.make_async_remote_copy(
            src_ref=send_vmem,
            dst_ref=comm_hbm.at[slot],
            send_sem=send_sem.at[0], recv_sem=recv_sem.at[slot],
            device_id=right, device_id_type=pltpu.DeviceIdType.MESH)
        rcp.start()
        rcp.wait()
        cp = pltpu.make_async_copy(comm_hbm.at[slot], chunk_vmem,
                                   send_sem.at[1])
        cp.start()
        cp.wait()
        if s + channels < n_dev - 1:
            pltpu.semaphore_signal(free_sem.at[slot], 1, device_id=left,
                                   device_id_type=pltpu.DeviceIdType.MESH)
        idx = jax.lax.rem(me - s - 1 + 2 * n_dev, n_dev)
        ocp = pltpu.make_async_copy(chunk_vmem,
                                    out_hbm.at[pl.ds(idx * chunk, chunk)],
                                    send_sem.at[2])
        ocp.start()
        ocp.wait()
        send_vmem[...] = chunk_vmem[...]


def ring_fused_ar_rmsnorm(x, residual, weight, *, axis_name: str,
                          n_dev: int, eps: float = 1e-6,
                          interpret: bool = False, channels: int = 2):
    """Inside shard_map over `axis_name` (size n_dev).

    x: (T, d) per-device partial sums; residual: (T//n_dev, d) own token
    slice; weight: (d,). Returns (normed_full (T, d), new_residual).

    ``channels`` = comm slots the ring cycles through (see module
    docstring). Clamped to [1, n_dev-1] — more slots than ring hops buy
    nothing.
    """
    t_tokens, d = x.shape
    assert t_tokens % n_dev == 0
    chunk = t_tokens // n_dev
    channels = max(1, min(int(channels), max(n_dev - 1, 1)))
    kernel = functools.partial(_kernel, n_dev=n_dev, chunk=chunk, eps=eps,
                               axis_name=axis_name, channels=channels)
    out, new_res, _ = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),           # x (HBM)
            pl.BlockSpec((chunk, d), lambda: (0, 0)),    # residual (VMEM)
            pl.BlockSpec((d,), lambda: (0,)),            # weight
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),           # normed out (HBM)
            pl.BlockSpec((chunk, d), lambda: (0, 0)),    # new residual
            pl.BlockSpec(memory_space=pl.ANY),           # comm buffer (HBM)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_tokens, d), x.dtype),
            jax.ShapeDtypeStruct((chunk, d), residual.dtype),
            jax.ShapeDtypeStruct((channels, chunk, d), x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk, d), x.dtype),             # acc
            pltpu.VMEM((chunk, d), x.dtype),             # send
            pltpu.VMEM((chunk, d), x.dtype),             # chunk in
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SemaphoreType.DMA((channels,)),
            pltpu.SemaphoreType.REGULAR((channels,)),
        ],
        # the barrier semaphore is keyed by collective_id; a lone device
        # takes no barrier and may name none
        compiler_params=pltpu.CompilerParams(
            collective_id=7 if n_dev > 1 else None),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x, residual, weight)
    return out, new_res
