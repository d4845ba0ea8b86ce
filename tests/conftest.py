"""Shared fixtures. Tests in this process see ONE CPU device; multi-device
semantics are exercised via subprocess helpers (run_distributed) so the
512-device dry-run flag never leaks into the main test process."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_distributed(code: str, n_devices: int = 8, timeout: int = 420):
    """Run a python snippet in a subprocess with n fake CPU devices.
    The snippet should raise/assert on failure and print 'PASS' on success.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"distributed snippet failed\nSTDOUT:\n{proc.stdout[-3000:]}\n"
        f"STDERR:\n{proc.stderr[-3000:]}")
    assert "PASS" in proc.stdout, proc.stdout[-2000:]
    return proc.stdout


@pytest.fixture(scope="session")
def mesh11():
    import jax
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="session")
def tiny_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=128, dtype="float32")


@pytest.fixture(scope="session")
def tiny_pcfg():
    from repro.configs.base import ParallelConfig
    return ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=32)


@pytest.fixture(scope="session")
def model_builder(tiny_pcfg):
    """Session-memoized ``(cfg[, pcfg, tp]) -> (api, params)``: the tiny
    models test modules used to rebuild per test are built ONCE and shared
    (params are never mutated — engines only read them)."""
    import jax
    from repro.models.build import build_model

    cache = {}

    def build(cfg, pcfg=None, tp=1):
        key = (repr(cfg), repr(pcfg), tp)
        if key not in cache:
            api = build_model(cfg, pcfg if pcfg is not None else tiny_pcfg,
                              tp=tp)
            cache[key] = (api, api.init(jax.random.PRNGKey(0)))
        return cache[key]

    return build


@pytest.fixture(scope="session")
def tiny_model(mesh11, tiny_cfg, model_builder):
    """The standard tiny dense transformer: ``(api, mesh, params)`` —
    the shape every engine test wants."""
    api, params = model_builder(tiny_cfg)
    return api, mesh11, params


@pytest.fixture(scope="session")
def tiny_engine_builder(tiny_model):
    """Factory for tiny engines over the shared model.  Engines with the
    same scheduler/sampling signature share a jit cache, so replaying many
    short traces (the differential harness, lifecycle tests) compiles each
    step shape once per configuration instead of once per engine."""
    import dataclasses

    from repro.runtime.engine import Engine
    from repro.runtime.scheduler import SchedulerConfig

    jit_caches = {}

    def build(*, draft=None, seed=0, temperature=0.0, top_k=0, top_p=1.0,
              obs=None, obs_track="engine", profiler=None, pcfg=None,
              **scfg_kw):
        api, mesh, params = tiny_model
        if pcfg is not None:
            # same weights, another ParallelConfig (comm mode, weave)
            api = dataclasses.replace(api, pcfg=pcfg)
        scfg_kw.setdefault("max_batch", 4)
        scfg_kw.setdefault("chunk_tokens", 48)
        scfg_kw.setdefault("max_len", 128)
        scfg_kw.setdefault("prefill_bucket", 16)
        # obs/profiler are deliberately NOT in the jit-cache key: tracing
        # and measured-time profiling must not change compilation (on/off
        # identity, DESIGN.md §12/§13)
        key = tuple(sorted(scfg_kw.items())) + (temperature, top_k, top_p,
                                                repr(pcfg))
        cache = jit_caches.setdefault(key, {})
        return Engine(api, mesh, params, SchedulerConfig(**scfg_kw),
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      draft=draft, seed=seed, jit_cache=cache,
                      obs=obs, obs_track=obs_track, profiler=profiler)

    return build
