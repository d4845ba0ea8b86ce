"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
serve-and-check phases work end to end at a tiny size on CPU devices (the
full-width run needs the chip).  Also the compile-cache placement every
entry point shares."""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import REPO, run_distributed

sys.path.insert(0, REPO)
import chip_smoke as cs  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ParallelConfig  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models.build import build_model  # noqa: E402
from repro.runtime.scheduler import SchedulerConfig  # noqa: E402


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def tiny_served(mesh11):
    """The smoke's request mix through a tiny bf16 model on one CPU
    device, plus the full-context reference it is checked against."""
    cfg = dataclasses.replace(get_config("qwen1.5-4b").reduced(),
                              dtype="bfloat16")
    api = build_model(cfg, ParallelConfig(comm_mode="fused",
                                          tokenweave=True, remat=False),
                      tp=1)
    params = api.init(jax.random.PRNGKey(0), mesh11)
    scfg = SchedulerConfig(max_batch=4, max_len=2048, chunk_tokens=1024,
                           prefill_bucket=1024)
    reqs = cs.make_requests(cfg.vocab_size, seed=0)
    outputs, line = cs.serve(api, mesh11, params, scfg,
                             cs.make_requests(cfg.vocab_size, seed=0))
    n_idx = max(cs.NEW_TOKENS)
    ref = cs.reference_fn(cfg, 1, mesh11, 2048, n_idx)
    return dict(cfg=cfg, params=params, reqs=reqs, outputs=outputs,
                line=line, ref=ref, n_idx=n_idx)


def test_serve_and_check_tiny(tiny_served):
    s = tiny_served
    assert [len(s["outputs"][r.rid]) for r in s["reqs"]] == list(
        cs.NEW_TOKENS)
    # 1024-token prefill chunks cross the 512-token weave floor
    assert "(weave 0)" not in s["line"]
    short = cs.check_tokens(
        s["ref"], s["params"], s["reqs"], s["outputs"],
        vocab=s["cfg"].vocab_size, seq_len=2048, n_idx=s["n_idx"])
    assert [len(short[r.rid]) for r in s["reqs"]] == list(cs.NEW_TOKENS)
    assert all(0 <= v.min() and v.max() <= cs.TOL for v in short.values())


def test_check_rejects_wrong_tokens(tiny_served):
    """The recompute check discriminates: shifting one request's tokens
    off the model's choice fails it."""
    s = tiny_served
    vocab = s["cfg"].vocab_size
    bad = dict(s["outputs"])
    bad[0] = [(t + vocab // 2) % vocab for t in bad[0]]
    with pytest.raises(RuntimeError, match="below the top"):
        cs.check_tokens(s["ref"], s["params"], s["reqs"], bad, vocab=vocab,
                        seq_len=2048, n_idx=s["n_idx"])


@pytest.mark.parametrize("gap,ok", [(0.0625, True), (0.5, False)])
def test_compare_runs_allows_only_near_ties(gap, ok):
    """Two runs may part only where the recompute sees their two choices
    as a near-tie; identical requests are counted, not checked."""
    a = {0: [5, 6, 7], 1: [1, 2, 3]}
    b = {0: [5, 6, 7], 1: [1, 9, 3]}
    short_a = {0: np.zeros(3), 1: np.zeros(3)}
    short_b = {0: np.zeros(3), 1: np.array([0.0, gap, 0.0])}
    if ok:
        assert cs.compare_runs(a, b, short_a, short_b) == (1, {1: (1, gap)})
    else:
        with pytest.raises(RuntimeError, match="apart in the recompute"):
            cs.compare_runs(a, b, short_a, short_b)


@pytest.mark.slow
def test_tp4_fused_vs_vanilla_tiny():
    """The --chips 4 phase on 4 virtual CPU devices at a tiny width:
    engine at tp=4 under fused and vanilla, each checked against the
    full-context recompute, then compared with each other; and a decode
    batch tp does not divide."""
    run_distributed(f"""
import dataclasses, sys
import jax
sys.path.insert(0, {REPO!r})
import chip_smoke as cs
from repro.configs import get_config
cfg = dataclasses.replace(get_config("qwen3-14b").reduced(), dtype="bfloat16")
out = cs.run(cfg, jax.devices()[:4], ("fused", "vanilla"), seed=0,
             max_batch=4, max_len=2048, chunk=1024)
assert set(out) == {{"fused", "vanilla"}}
# 3 decode rows do not split over tp=4: the engine falls back to the
# vanilla reduction for those steps and still passes the check
cs.run(cfg, jax.devices()[:4], ("fused",), seed=0, max_batch=3,
       max_len=2048, chunk=1024)
print("PASS")
""", n_devices=4)


def test_compile_cache_placement(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads it

    monkeypatch.delenv(compile_cache.ENV_VAR)
    try:
        assert compile_cache.use_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
