"""Compile-only checks against the TPU compiler for a described v5e:2x2
(no chip needed): the Pallas kernels and one engine decode step at real
widths.  Nothing runs; each test asserts that the chip's compiler accepts
the program and that it fits a v5e chip's 16 GB.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so describing it while
test files are collected would break multi-worker runs."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

V5E_HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache, so keep it out of the cache while these tests run
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_1x4(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_count(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("t,d", [(1024, 2560), (256, 5120)])
def test_fused_rmsnorm_compiles(t, d, one_chip):
    """qwen1.5-4b width (a 1024-token chunk at tp=1) and qwen3-14b width
    (that chunk's 1/4 token shard at tp=4)."""
    from repro.kernels.fused_rmsnorm import fused_residual_rmsnorm_pallas
    c = jax.jit(fused_residual_rmsnorm_pallas).lower(
        _sds((t, d), one_chip), _sds((t, d), one_chip),
        _sds((d,), one_chip)).compile()
    assert _kernel_count(c) == 1


def test_flash_attention_compiles_head_dim_128(one_chip):
    """qwen1.5-4b's attention shard (20 KV heads, group 1, head_dim 128) at
    the default 512 x 1024 blocks: a 512-token chunk over 1024 keys."""
    from repro.kernels.flash_attention import flash_attention
    b, sq, sk, kvh, g, dh = 1, 512, 1024, 20, 1, 128

    def f(q, k, v, qp, kp):
        return flash_attention(q, k, v, qp, kp, causal=True)
    c = jax.jit(f).lower(
        _sds((b, sq, kvh, g, dh), one_chip), _sds((b, sk, kvh, dh), one_chip),
        _sds((b, sk, kvh, dh), one_chip),
        _sds((b, sq), one_chip, jnp.int32),
        _sds((b, sk), one_chip, jnp.int32)).compile()
    assert _kernel_count(c) >= 1


def test_ring_ar_rmsnorm_compiles_on_1x4_mesh(mesh_1x4):
    """The ring AllReduce-RMSNorm kernel at qwen3-14b width (d=5120) on
    the (1, 4) serving mesh.  1024 tokens (a 256-row chunk per device) is
    the largest count that compiles: the kernel keeps whole (chunk, d)
    buffers in VMEM, and at 2048 tokens they need 30.22 MB against the
    16 MB scoped limit (ROADMAP queue 1 item 7)."""
    from repro.kernels.ring_ar_rmsnorm import ring_fused_ar_rmsnorm
    t, d, tp = 1024, 5120, 4

    def f(x, r, w):
        return ring_fused_ar_rmsnorm(x[0], r, w, axis_name="model",
                                     n_dev=tp, channels=2)
    sm = jax.shard_map(f, mesh=mesh_1x4,
                       in_specs=(P("model"), P("model"), P()),
                       out_specs=(P(), P("model")), check_vma=False)
    shard = NamedSharding(mesh_1x4, P("model"))
    c = jax.jit(sm).lower(
        _sds((tp, t, d), shard), _sds((t, d), shard),
        _sds((d,), NamedSharding(mesh_1x4, P()))).compile()
    assert _kernel_count(c) == 1


def test_engine_decode_step_compiles_full_width(topo):
    """The engine's own jitted decode step for qwen1.5-4b at full width
    and depth (40 layers, scanned, so compile time does not grow with
    depth) over the chip smoke's slot cache (4 x 2048): params, cache and
    temporaries fit one 16 GB chip."""
    from repro.configs import get_config
    from repro.configs.base import ParallelConfig
    from repro.models.build import build_model, named_shardings
    from repro.runtime.engine import Engine
    from repro.runtime.scheduler import SchedulerConfig

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    api = build_model(get_config("qwen1.5-4b"),
                      ParallelConfig(comm_mode="fused", tokenweave=True,
                                     remat=False), tp=1)
    scfg = SchedulerConfig(max_batch=4, max_len=2048)
    # _decode_fn needs the engine's layout, not its device arrays
    # (a described device cannot hold any): an Engine shell without them
    eng = object.__new__(Engine)
    eng.api, eng.mesh, eng.scfg = api, mesh, scfg
    eng._pspec, eng._cspec = api.specs(), api.cache_specs()
    eng._is_ssm, eng.paged, eng._jit_cache = False, False, {}
    eng.temperature, eng.top_k, eng.top_p = 0.0, 0, 1.0

    def abstract(tree, specs):
        return jax.tree.map(
            lambda a, s: _sds(a.shape, s, a.dtype), tree,
            named_shardings(mesh, specs))
    params = abstract(jax.eval_shape(api.init, jax.random.PRNGKey(0)),
                      api.specs())
    cache = abstract(jax.eval_shape(lambda: api.init_cache(4, 2048)),
                     api.cache_specs())
    rep = NamedSharding(mesh, P())
    c = eng._decode_fn().lower(
        params, cache, _sds((4, 1), rep, jnp.int32),
        _sds((4, 1), rep, jnp.int32), _sds((2,), rep, jnp.uint32)).compile()
    ma = c.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert ma.argument_size_in_bytes > 11e9     # the whole model + cache
    assert total < V5E_HBM, total
