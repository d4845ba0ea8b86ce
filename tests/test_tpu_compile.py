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


def _engine_shell(model, tp, mesh, scfg):
    """An engine's step-program builder for ``model`` at ``tp`` over
    ``mesh``, with its parameters and slot cache as shapes: the step
    programs need the engine's layout, not its device arrays (a described
    device cannot hold any)."""
    from repro.configs import get_config
    from repro.configs.base import ParallelConfig
    from repro.models.build import build_model, named_shardings
    from repro.runtime.engine import Engine

    api = build_model(get_config(model),
                      ParallelConfig(comm_mode="fused", tokenweave=True,
                                     remat=False), tp=tp)
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
    cache = abstract(jax.eval_shape(
        lambda: api.init_cache(scfg.max_batch, scfg.max_len)),
        api.cache_specs())
    return eng, params, cache


def _bytes_needed(compiled) -> float:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_engine_decode_step_compiles_full_width(topo):
    """The engine's own jitted decode step for qwen1.5-4b at full width
    and depth (40 layers, scanned, so compile time does not grow with
    depth) over the chip smoke's slot cache (4 x 2048): params, cache and
    temporaries fit one 16 GB chip, and the step updates the donated
    cache in place: its temporaries hold no copy of the cache, nor of one
    layer's slice of it."""
    from repro.runtime.scheduler import SchedulerConfig

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    eng, params, cache = _engine_shell(
        "qwen1.5-4b", 1, mesh, SchedulerConfig(max_batch=4, max_len=2048))
    api = eng.api
    rep = NamedSharding(mesh, P())
    c = eng._decode_fn().lower(
        params, cache, _sds((4, 1), rep, jnp.int32),
        _sds((4, 1), rep, jnp.int32), _sds((2,), rep, jnp.uint32)).compile()
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes > 11e9     # the whole model + cache
    total = _bytes_needed(c)
    assert total < V5E_HBM, total
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    layer_kv = cache_bytes / api.cfg.num_layers / 2    # one layer's K
    assert ma.temp_size_in_bytes < layer_kv, (ma.temp_size_in_bytes,
                                               layer_kv)


@pytest.mark.parametrize("program", ["decode", "prefill_b4_s512"])
def test_qwen3_14b_tp4_step_programs_fit_at_32_slots(program, mesh_1x4):
    """qwen3-14b at full width and depth on the (1, 4) serving mesh of a
    v5e 2x2 over the benchmark's slot cache (32 slots x 2048): the decode
    step, and the prefill program with the most temporaries of those the
    benchmark's traffic emits (4 rows of 512 tokens: 10.08 GB of weights
    and cache plus 5.43 GB of temporaries a chip), fit one 16 GB chip.
    Both run the token-sharded ``fused`` comm_norm: 32 decode rows and
    2048 prefill tokens divide over the 4 chips, so the programs gather
    the normed token shards back (the compiler keeps prefill's
    reduce-scatters and turns decode's small ones into all-reduces)."""
    from repro.runtime.scheduler import SchedulerConfig

    eng, params, cache = _engine_shell(
        "qwen3-14b", 4, mesh_1x4,
        SchedulerConfig(max_batch=32, max_len=2048, chunk_tokens=2048,
                        prefill_bucket=256))
    rep = NamedSharding(mesh_1x4, P())
    key = _sds((2,), rep, jnp.uint32)
    if program == "decode":
        ids = _sds((32, 1), rep, jnp.int32)
        c = eng._decode_fn().lower(params, cache, ids, ids, key).compile()
    else:
        ids = _sds((4, 512), rep, jnp.int32)
        rows = _sds((4,), rep, jnp.int32)
        c = eng._prefill_fn(4, 512).lower(params, cache, ids, ids, rows,
                                          rows, rows, key).compile()
    assert c.memory_analysis().argument_size_in_bytes > 10e9
    total = _bytes_needed(c)
    assert total < V5E_HBM, total
    text = c.as_text()
    assert "all-gather" in text
    assert ("reduce-scatter" in text) == (program != "decode")


def _cache_copies(text: str, leaf_shape) -> list:
    """Instructions of a compiled program that copy a whole cache leaf:
    a ``copy`` (or a fusion the compiler names after one) whose result has
    the leaf's per-device shape."""
    shape = "bf16[%s]" % ",".join(map(str, leaf_shape))
    return [ln.strip()[:120] for ln in text.splitlines()
            if f"= {shape}" in ln
            and (" copy(" in ln or ln.lstrip().startswith("%copy"))]


@pytest.mark.parametrize("model,tp,slots,rows,chunk", [
    ("qwen1.5-4b", 1, 3, 1, 1024), ("qwen3-14b", 4, 32, 1, 2048)])
def test_prefill_writes_the_chunk_in_place(model, tp, slots, rows, chunk,
                                           topo, mesh_1x4):
    """The benchmark's largest-chunk prefill programs (qwen1.5-4b on one
    chip, qwen3-14b on the (1, 4) mesh) write the chunk's KV into the
    donated cache in place: no instruction copies a whole cache leaf, and
    the temporaries are the rows the chunk attends, the chunk's own KV of
    every layer, and under 0.25 GB of activations.  The scatter this
    replaced ran on a transposed copy of the cache (1.90 and 2.88 GB of
    temporaries)."""
    from repro.runtime.scheduler import SchedulerConfig

    mesh = mesh_1x4 if tp == 4 else Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    eng, params, cache = _engine_shell(
        model, tp, mesh, SchedulerConfig(max_batch=slots, max_len=2048,
                                         chunk_tokens=chunk))
    rep = NamedSharding(mesh, P())
    ids = _sds((rows, chunk), rep, jnp.int32)
    row_ids = _sds((rows,), rep, jnp.int32)
    c = eng._prefill_fn(rows, chunk).lower(
        params, cache, ids, ids, row_ids, row_ids, row_ids,
        _sds((2,), rep, jnp.uint32)).compile()
    k = cache["k"].sharding.shard_shape(cache["k"].shape)
    assert not _cache_copies(c.as_text(), k)
    assert _bytes_needed(c) < V5E_HBM
    kv_bytes = 2 * 2 * np.prod(k) / slots              # one row's K and V
    chunk_kv = kv_bytes * rows * chunk / k[2]
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < kv_bytes * rows + chunk_kv + 0.25e9, temp


# every step program qwen1.5-4b's ``code`` traffic emits at 3 slots: a
# 1024-token budget in 256-token buckets, so rows x chunk <= 1024
CODE_PROGRAMS_3_SLOTS = ["decode_b3"] + [
    f"prefill_b{rows}_s{chunk}" for chunk in (256, 512, 768, 1024)
    for rows in range(1, min(3, 1024 // chunk) + 1)]


@pytest.mark.parametrize("program", CODE_PROGRAMS_3_SLOTS)
def test_qwen15_4b_code_programs_fit_at_3_slots(program, topo):
    """qwen1.5-4b on one chip at the benchmark's ``code`` shapes (3 slots
    of 2048 positions, 1024-token budget, bucket 256): each step program
    the traffic emits fits one 16 GB chip.  The one with the most
    temporaries is ``prefill_b3_s256``, which gathers the 3 rows it
    attends (0.84 GB each)."""
    from repro.runtime.scheduler import SchedulerConfig

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    eng, params, cache = _engine_shell(
        "qwen1.5-4b", 1, mesh, SchedulerConfig(
            max_batch=3, max_len=2048, chunk_tokens=1024,
            prefill_bucket=256))
    rep = NamedSharding(mesh, P())
    key = _sds((2,), rep, jnp.uint32)
    if program == "decode_b3":
        ids = _sds((3, 1), rep, jnp.int32)
        c = eng._decode_fn().lower(params, cache, ids, ids, key).compile()
    else:
        rows, chunk = map(int, program[len("prefill_b"):].split("_s"))
        ids = _sds((rows, chunk), rep, jnp.int32)
        row_ids = _sds((rows,), rep, jnp.int32)
        c = eng._prefill_fn(rows, chunk).lower(
            params, cache, ids, ids, row_ids, row_ids, row_ids,
            key).compile()
    assert _bytes_needed(c) < V5E_HBM
