"""Per-assigned-architecture smoke tests (assignment requirement f):
instantiate the REDUCED same-family config and run one forward/train step on
CPU, asserting output shapes and finiteness. Full configs are exercised only
via the dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED, PAPER_MODELS, get_config
from repro.configs.base import ParallelConfig

PCFG = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                      split_unit=16, tokenweave_min_tokens=32)
B, S = 2, 64


def _batch(cfg, key=0):
    tok = jax.random.randint(jax.random.PRNGKey(key), (B, S), 0,
                             cfg.vocab_size)
    lab = jax.random.randint(jax.random.PRNGKey(key + 1), (B, S), 0,
                             cfg.vocab_size)
    batch = {"tokens": tok, "labels": lab}
    if cfg.family == "vlm":
        batch["extra_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, 16, cfg.d_model)) * 0.02
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S + 16)[None, None], (B, 3, S + 16)).astype(jnp.int32)
        batch["labels"] = jnp.pad(lab, ((0, 0), (16, 0)))[:, :S]
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, 32, cfg.d_model)) * 0.02
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_reduced_train_step(arch, mesh11):
    from repro.models.build import build_model
    cfg = get_config(arch).reduced()
    api = build_model(cfg, PCFG, tp=1)
    params = api.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(params, batch):
        ls, dn, aux = api.train_loss(params, batch)
        return ls / jnp.maximum(dn, 1)

    f = jax.jit(jax.shard_map(loss_fn, mesh=mesh11,
                              in_specs=(api.specs(), P()), out_specs=P(),
                              check_vma=False))
    loss = float(f(params, batch))
    assert np.isfinite(loss)
    # random init, uniform-ish prediction: loss near log(vocab)
    assert 0.5 * np.log(cfg.vocab_size) < loss < 2.5 * np.log(cfg.vocab_size)

    # gradient step sanity: grads exist and are finite
    g = jax.jit(jax.grad(lambda p: jax.shard_map(
        loss_fn, mesh=mesh11, in_specs=(api.specs(), P()), out_specs=P(),
        check_vma=False)(p, batch)))(params)
    flat = jax.tree.leaves(g)
    assert all(np.isfinite(np.asarray(x, np.float32)).all() for x in flat)


@pytest.mark.parametrize("arch", PAPER_MODELS)
def test_paper_model_reduced_forward(arch, mesh11):
    from repro.models.build import build_model
    cfg = get_config(arch).reduced()
    api = build_model(cfg, PCFG, tp=1)
    params = api.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(params, batch):
        ls, dn, _ = api.train_loss(params, batch)
        return ls / jnp.maximum(dn, 1)

    f = jax.jit(jax.shard_map(loss_fn, mesh=mesh11,
                              in_specs=(api.specs(), P()), out_specs=P(),
                              check_vma=False))
    assert np.isfinite(float(f(params, batch)))


def test_gemma3_local_global_pattern():
    from repro.models.transformer import layer_kinds, uniform_kinds
    cfg = get_config("gemma3-1b")
    kinds = layer_kinds(cfg)
    assert len(kinds) == 26
    assert not uniform_kinds(cfg)
    globals_ = [i for i, k in enumerate(kinds) if k.window == 0]
    assert globals_ == [5, 11, 17, 23]            # 5:1 local:global
    assert all(kinds[i].window == 512 for i in range(5))
    assert kinds[5].theta == 1_000_000.0
    assert kinds[0].theta == 10_000.0


def test_tokenweave_equivalence_dense(tiny_cfg, mesh11):
    """Two-split weave == unsplit forward, exactly (same params/batch)."""
    import dataclasses
    from repro.models import transformer as T
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
    outs = {}
    for weave in (False, True):
        pcfg = dataclasses.replace(PCFG, tokenweave=weave)
        params = T.init_params(jax.random.PRNGKey(0), tiny_cfg, pcfg, 1)

        def f(params):
            h, _, _ = T.forward(params, tok, cfg=tiny_cfg, pcfg=pcfg,
                                return_kv=False)
            return h
        outs[weave] = jax.jit(jax.shard_map(
            f, mesh=mesh11, in_specs=(T.param_specs(tiny_cfg, pcfg),),
            out_specs=P(), check_vma=False))(params)
    np.testing.assert_allclose(outs[False], outs[True], rtol=2e-5,
                               atol=2e-5)
