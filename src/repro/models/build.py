"""Uniform model API over the four family implementations."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import encdec, hybrid, mamba_model, transformer


_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": mamba_model,
    "hybrid": hybrid,
    "encdec": encdec,
}


@dataclasses.dataclass(frozen=True)
class ModelApi:
    """Bound functional API; every method is meant to run inside shard_map
    (except init/specs helpers which are pure host-side)."""
    cfg: ModelConfig
    pcfg: ParallelConfig
    mod: Any
    tp: int
    ep: int = 1

    # ---- host-side ------------------------------------------------------
    def init(self, key, mesh=None):
        """Parameters from ``key``.  With a ``mesh`` they are generated
        under jit straight into their ``specs()`` sharding, so each device
        makes only its own shard and no device ever holds the whole
        model."""
        if mesh is None:
            return self.mod.init_params(key, self.cfg, self.pcfg, self.tp,
                                        self.ep)
        return jax.jit(self.init, out_shardings=named_shardings(
            mesh, self.specs()))(key)

    def specs(self):
        return self.mod.param_specs(self.cfg, self.pcfg)

    def init_cache(self, batch: int, max_len: int, **kw):
        if self.mod is transformer:
            return transformer.init_cache(batch, max_len, self.cfg, self.tp,
                                          self.pcfg)
        return self.mod.init_cache(batch, max_len, self.cfg, self.tp, **kw)

    def cache_specs(self, batch1: bool = False):
        if self.mod is transformer:
            return transformer.cache_specs(self.cfg, self.pcfg, batch1)
        return self.mod.cache_specs(self.cfg, self.pcfg, batch1)

    # ---- inside shard_map -----------------------------------------------
    def train_loss(self, params, batch):
        return self.mod.train_loss(params, batch, cfg=self.cfg,
                                   pcfg=self.pcfg)

    def prefill(self, params, batch, cache, **kw):
        if self.mod is encdec:
            return encdec.prefill(params, batch, cache, cfg=self.cfg,
                                  pcfg=self.pcfg, **kw)
        return self.mod.prefill(params, batch["tokens"], cache, cfg=self.cfg,
                                pcfg=self.pcfg,
                                positions=batch.get("positions"),
                                **({k: v for k, v in batch.items()
                                    if k in ("mrope_positions",
                                             "extra_embeds")}
                                   if self.mod is transformer else {}), **kw)

    def decode_step(self, params, tokens, cache, positions, **kw):
        return self.mod.decode_step(params, tokens, cache, cfg=self.cfg,
                                    pcfg=self.pcfg, positions=positions, **kw)

    def verify_step(self, params, tokens, cache, positions, **kw):
        """Speculative multi-token verify (transformer families only)."""
        return self.mod.verify_step(params, tokens, cache, cfg=self.cfg,
                                    pcfg=self.pcfg, positions=positions, **kw)

    def packed_step(self, params, tokens, cache, positions, **kw):
        """Packed mixed-segment hybrid step (transformer families only,
        DESIGN.md §6)."""
        return self.mod.packed_step(params, tokens, cache, cfg=self.cfg,
                                    pcfg=self.pcfg, positions=positions, **kw)


def named_shardings(mesh, specs):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def build_model(cfg: ModelConfig, pcfg: ParallelConfig, tp: int,
                ep: int = 1) -> ModelApi:
    if cfg.family not in _FAMILY:
        raise KeyError(f"unknown family {cfg.family!r}")
    return ModelApi(cfg=cfg, pcfg=pcfg, mod=_FAMILY[cfg.family], tp=tp, ep=ep)
