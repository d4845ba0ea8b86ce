"""Axis helpers used inside ``jax.shard_map`` bodies.

All model code runs fully-manual inside shard_map; these helpers make the
axis arithmetic uniform (and degrade to identities on 1-sized axes, which is
how single-device CPU tests exercise the exact same code path).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
from jax import lax


def axis_size(name) -> int:
    return lax.axis_size(name)


def axis_index(name):
    return lax.axis_index(name)


@dataclasses.dataclass(frozen=True)
class CommCtx:
    """Everything the fused collective ops need to know about the layout."""
    tp_axis: str = "model"
    dp_axes: Tuple[str, ...] = ("data",)
    mode: str = "fused"            # vanilla | reordered | fused | ring | nocomm
    eps: float = 1e-6
    use_pallas: bool = False
    interpret: bool = False        # pallas interpret mode (CPU validation)
    bf16_wire: bool = False        # pin collective dtype (see ParallelConfig)

    @property
    def sharded_residual(self) -> bool:
        """fused/reordered/ring keep the residual stream token-sharded
        over TP."""
        return self.mode in ("fused", "reordered", "ring")

    def tp_size(self) -> int:
        return lax.axis_size(self.tp_axis)

    def tp_index(self):
        return lax.axis_index(self.tp_axis)


def token_shard_slice(x: jnp.ndarray, ctx: CommCtx) -> jnp.ndarray:
    """Slice this TP shard's token range out of a token-replicated array."""
    tp = ctx.tp_size()
    if tp == 1:
        return x
    shard = x.shape[0] // tp
    return lax.dynamic_slice_in_dim(x, ctx.tp_index() * shard, shard, axis=0)


def psum_dp(x, ctx: CommCtx):
    """All-reduce over every data-parallel axis (grad sync)."""
    for ax in ctx.dp_axes:
        x = lax.psum(x, ax)
    return x
