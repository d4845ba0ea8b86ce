"""Train-step builders.

``make_train_step`` (default): gradients via jax.grad OUTSIDE shard_map —
the shard_map transpose (VMA-tracked) inserts exactly the right psums for
replicated params, including the subtle token-sharded-norm-weight case; the
optimizer runs as plain jit under GSPMD with ZeRO-1 state sharding.

``make_manual_sync_train_step``: full-manual variant where gradients are
synced explicitly inside shard_map — VMA-aware psum over `model` (only the
grads that actually vary, e.g. token-sharded norm weights), psum over
`data` (fast ICI), then an int8+error-feedback *compressed* psum over `pod`
(the slow DCN hop). tests/test_training.py pins manual == automatic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.build import ModelApi
from repro.training import compression as C
from repro.training.optimizer import (AdamWConfig, adamw_update,
                                      init_opt_state, opt_state_specs)


def _batch_specs(batch_like, dp_axes):
    dp = tuple(dp_axes) if len(dp_axes) > 1 else dp_axes[0]
    return jax.tree.map(lambda _: P(dp), batch_like)


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


def make_loss_fn(api: ModelApi, mesh, batch_like):
    """shard_map-wrapped global-mean loss (dp psums inside)."""
    pspec = api.specs()
    bspec = _batch_specs(batch_like, api.pcfg.dp_axes)

    def local_loss(params, batch):
        ls, dn, aux = api.train_loss(params, batch)
        for ax in api.pcfg.dp_axes:
            ls = lax.psum(ls, ax)
            dn = lax.psum(dn, ax)
            aux = lax.pmean(aux, ax)
        return ls / jnp.maximum(dn, 1.0) + aux

    # check_vma=False: the VMA-checked transpose of scan+checkpoint bodies
    # trips a jax error-formatting bug; the unchecked transpose inserts the
    # conservative (correct) psums — tests pin fused==vanilla gradients.
    return jax.shard_map(local_loss, mesh=mesh, in_specs=(pspec, bspec),
                         out_specs=P(), check_vma=False), pspec, bspec


def make_train_step(api: ModelApi, mesh, batch_like, ocfg: AdamWConfig,
                    dp_size: int):
    """Returns (jitted step, init_fn). step(params, opt, batch) ->
    (params, opt, metrics)."""
    loss_sm, pspec, bspec = make_loss_fn(api, mesh, batch_like)

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_sm)(params, batch)
        new_params, new_opt, gnorm = adamw_update(params, grads, opt, ocfg)
        return new_params, new_opt, {"loss": loss, "grad_norm": gnorm}

    params_like = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    ospec = opt_state_specs(params_like, pspec, api.pcfg.dp_axes, dp_size)
    jstep = jax.jit(step,
                    in_shardings=(_ns(mesh, pspec), _ns(mesh, ospec),
                                  _ns(mesh, bspec)),
                    out_shardings=(_ns(mesh, pspec), _ns(mesh, ospec), None),
                    donate_argnums=(0, 1))

    def init_fn(key):
        params = jax.jit(api.init, out_shardings=_ns(mesh, pspec))(key)
        opt = jax.jit(init_opt_state, out_shardings=_ns(mesh, ospec))(params)
        return params, opt

    return jstep, init_fn


# --------------------------------------------------------------------------
# manual-sync variant (explicit collectives + cross-pod grad compression)
# --------------------------------------------------------------------------

def _vma_psum(g, axis):
    """psum over `axis` iff the value actually varies over it."""
    if axis in jax.typeof(g).vma:
        return lax.psum(g, axis)
    return g


def _spec_has_axis(spec, axis) -> bool:
    return any(e == axis or (isinstance(e, tuple) and axis in e)
               for e in spec)


def _sync_model_axis(grads, pspec, tp_axis):
    """Replicated params used token-/head-sharded (norm weights etc.) need
    their grads psum'd over the model axis; tp-SHARDED param grads are
    per-slice values that must NOT be summed. Spec + VMA decide exactly."""
    def leaf(g, s):
        if _spec_has_axis(s, tp_axis):
            return g
        return _vma_psum(g, tp_axis)
    return jax.tree.map(leaf, grads, pspec)


def _manual_global_norm(grads, pspec, tp_axis):
    """Global grad L2 norm inside shard_map: sharded-leaf sums-of-squares
    psum over model; replicated leaves counted once."""
    ss_sharded = jnp.zeros((), jnp.float32)
    ss_repl = jnp.zeros((), jnp.float32)
    flat_g = jax.tree.leaves(grads)
    flat_s = jax.tree.leaves(pspec, is_leaf=lambda s: isinstance(s, P))
    for g, s in zip(flat_g, flat_s):
        ss = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if _spec_has_axis(s, tp_axis):
            ss_sharded = ss_sharded + ss
        else:
            ss_repl = ss_repl + ss
    # ss_repl is numerically identical on every model shard but formally
    # varying (post-all_gather values); pmean restores VMA invariance
    ss_repl = lax.psum(ss_repl, tp_axis) / lax.axis_size(tp_axis)
    return jnp.sqrt(lax.psum(ss_sharded, tp_axis) + ss_repl)


def make_manual_sync_train_step(api: ModelApi, mesh, batch_like,
                                ocfg: AdamWConfig, *,
                                compress_pod: bool | None = None):
    pspec = api.specs()
    bspec = _batch_specs(batch_like, api.pcfg.dp_axes)
    pcfg = api.pcfg
    has_pod = "pod" in mesh.axis_names
    if compress_pod is None:
        compress_pod = pcfg.grad_compression == "int8" and has_pod
    ospec = {"m": pspec, "v": pspec, "step": P()}
    # error-feedback residuals are PER-POD state: leading pod axis
    efspec = jax.tree.map(lambda s: P("pod", *s) if has_pod else s, pspec,
                          is_leaf=lambda s: isinstance(s, P)) \
        if compress_pod else None

    def step_body(params, opt, ef, batch):
        def body_loss(p):
            ls, dn, aux = api.train_loss(p, batch)
            loss = ls / jnp.maximum(dn, 1.0) + aux
            # The per-shard loss is numerically identical across the model
            # axis but formally *varying* (it flows through all_gather'd
            # activations). Under VMA semantics jax.grad seeds a cotangent
            # on every shard's copy, scaling grads by tp; pmean over the
            # model axis expresses the loss once and fixes the seed.
            return lax.pmean(loss, pcfg.tp_axis)

        loss, grads = jax.value_and_grad(body_loss)(params)
        for ax in pcfg.dp_axes:
            loss = lax.pmean(loss, ax)
        # 1. model axis: only replicated params whose grads vary
        #    (token-sharded norm-weight use); sharded slices stay local
        grads = _sync_model_axis(grads, pspec, pcfg.tp_axis)
        # 2. fast intra-pod data reduce
        grads = jax.tree.map(lambda g: _vma_psum(g, "data"), grads)
        # 3. slow cross-pod hop, optionally int8-compressed w/ error feedback
        if has_pod:
            if compress_pod:
                ef_in = jax.tree.map(lambda e: jnp.squeeze(e, 0), ef)
                grads, ef_out = C.compress_grads(grads, "pod", ef_in)
                ef = jax.tree.map(lambda e: e[None], ef_out)
            else:
                grads = jax.tree.map(lambda g: _vma_psum(g, "pod"), grads)
        # grads divide by the global token denominator already (body_loss is
        # a per-shard mean); rescale to the global mean: each dp shard's
        # loss averaged its own tokens, so the psum'd grad is dp_size times
        # the global-mean grad
        n_dp = 1
        for ax in pcfg.dp_axes:
            n_dp *= lax.axis_size(ax)
        grads = jax.tree.map(lambda g: g / n_dp, grads)
        gnorm = _manual_global_norm(grads, pspec, pcfg.tp_axis)
        new_params, new_opt, _ = adamw_update(params, grads, opt, ocfg,
                                              gnorm=gnorm)
        out = (new_params, new_opt, {"loss": loss, "grad_norm": gnorm})
        if compress_pod:
            return out + (ef,)
        return out

    in_specs = [pspec, ospec, efspec, bspec] if compress_pod else \
        [pspec, ospec, None, bspec]
    out_specs = (pspec, ospec, P())
    if compress_pod:
        out_specs = out_specs + (efspec,)

    if compress_pod:
        sm = jax.shard_map(step_body, mesh=mesh,
                           in_specs=(pspec, ospec, efspec, bspec),
                           out_specs=out_specs)
        jstep = jax.jit(sm, donate_argnums=(0, 1, 2))
    else:
        def step_noef(params, opt, batch):
            return step_body(params, opt, None, batch)
        sm = jax.shard_map(step_noef, mesh=mesh,
                           in_specs=(pspec, ospec, bspec),
                           out_specs=out_specs)
        jstep = jax.jit(sm, donate_argnums=(0, 1))

    def init_fn(key):
        params = jax.jit(api.init, out_shardings=_ns(mesh, pspec))(key)
        opt = jax.jit(init_opt_state, out_shardings=_ns(mesh, ospec))(params)
        if compress_pod:
            pod = mesh.shape["pod"]
            ef = jax.jit(
                lambda p: jax.tree.map(
                    lambda x: jnp.zeros((pod,) + x.shape, jnp.float32), p),
                out_shardings=_ns(mesh, efspec))(params)
            return params, opt, ef
        return params, opt

    return jstep, init_fn
