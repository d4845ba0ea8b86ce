"""Benchmark harness: one entry per paper table/figure.

CPU container => wall-clock TPU numbers are impossible; each figure is
reproduced through the calibrated two-stream simulator (sim/overlap_sim,
fed by the same v5e roofline constants the dry-run uses) plus CPU
micro-benchmarks where a kernel can be timed for real (interpret mode /
pure-jnp ops). Prints ``name,us_per_call,derived`` CSV rows; derived
carries the figure-level ratio the paper reports.

``--json PATH`` additionally writes the DETERMINISTIC serving metrics
(weave-activation rate, tokens/forward, prefix hit rate, spec acceptance
— counters, never wall clock) for the CI regression gate
(`scripts/check_bench.py` vs `benchmarks/baseline.json`).  Every gated
metric is sourced from a metrics-registry ``snapshot()`` (DESIGN.md §12)
and the JSON carries a ``__provenance__`` map recording where each value
came from — check_bench fails any baseline key it cannot trace back to
the registry.  The serve benchmarks additionally run with a
``TraceRecorder`` attached and assert the trace-derived weave counts
equal ``EngineStats`` EXACTLY; ``--trace PATH`` exports the merged
Chrome-trace/Perfetto JSON (inspect with scripts/trace_view.py).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only a,b] \
        [--strict] [--json BENCH_serve.json] [--trace BENCH_trace.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import use_compile_cache

# deterministic metrics collected during the run for --json (the CI
# regression gate compares them against benchmarks/baseline.json), the
# per-metric provenance map written alongside them, and the serve
# benchmarks' trace recorders (merged by --trace)
_METRICS: dict = {}
_PROVENANCE: dict = {}
_RECORDERS: list = []


def _metric(name, value, source="adhoc"):
    _METRICS[name] = round(float(value), 6)
    _PROVENANCE[name] = source


def _reg(name, snap, key):
    """Gated metric copied verbatim from a registry snapshot key."""
    _metric(name, snap[key], source=f"registry:{key}")


def _recorder(ns):
    """New TraceRecorder registered for the --trace export.  ``ns``
    namespaces request ids so merged traces keep one lifecycle thread
    per (benchmark, engine, rid)."""
    from repro.obs import TraceRecorder
    rec = TraceRecorder(request_ns=f"{ns}/")
    _RECORDERS.append(rec)
    return rec


def _assert_trace_matches(rec, stats, what, track=None):
    """The hard §12 invariant: weave counts recomputed from the trace's
    per-forward attribution spans equal the engine counters EXACTLY."""
    from repro.obs import weave_counts_from_trace
    w, n = weave_counts_from_trace(rec, track=track)
    assert (w, n) == (stats.weave_forwards, stats.forwards), (
        f"{what}: trace-derived weave counts ({w}/{n}) != EngineStats "
        f"({stats.weave_forwards}/{stats.forwards})")


def _row(name, us, derived=""):
    print(f"{name},{us if us == '' else f'{us:.2f}'},{derived}")


def _time_call(fn, *args, reps=5):
    fn(*args)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


# ---------------------------------------------------------------------------
def fig1_comm_overhead(quick=False):
    """Paper Fig.1: AllReduce overhead vs sequence length (sim, v5e)."""
    from repro.configs import get_config
    from repro.sim.overlap_sim import e2e_latency
    models = ["llama3.3-70b", "qwen2.5-72b", "mixtral-8x22b"]
    seqs = [1024, 4096, 16384] if quick else [1024, 2048, 4096, 8192, 16384]
    for m in models:
        cfg = get_config(m)
        for s in seqs:
            v = e2e_latency(cfg, "vanilla", s, tp=16)
            n = e2e_latency(cfg, "nocomm", s, tp=16)
            _row(f"fig1/{m}/seq{s}", v * 1e6,
                 f"comm_overhead={100*(v/n-1):.1f}%")


def fig4_fused_kernel(quick=False):
    """Paper Fig.4: AR+RMSNorm 3 ways (sim) + real CPU micro of the fused
    single-pass kernel vs the unfused reference."""
    from repro.sim.overlap_sim import HW, t_allreduce, t_norm, t_rs_or_ag
    hw = HW()
    d, n = 8192, 16
    for toks in ([1024, 8192] if quick else [1024, 2048, 4096, 8192, 16384]):
        vanilla = t_allreduce(toks, d, n, hw) + t_norm(toks, d, hw,
                                                       fused=False)
        reorder = (2 * t_rs_or_ag(toks, d, n, hw)
                   + t_norm(toks // n, d, hw, fused=False))
        fused = (2 * t_rs_or_ag(toks, d, n, hw)
                 + t_norm(toks // n, d, hw, fused=True))
        _row(f"fig4/sim/seq{toks}", vanilla * 1e6,
             f"reordered={reorder*1e6:.1f}us fused={fused*1e6:.1f}us "
             f"speedup={vanilla/fused:.2f}x")

    # CPU-real: fused single-pass vs unfused two-pass (jnp, jitted)
    from repro.kernels.ref import fused_residual_rmsnorm_ref
    from repro.layers.norms import residual_rmsnorm_unfused
    x = jnp.ones((2048, 1024), jnp.float32)
    r = jnp.ones((2048, 1024), jnp.float32)
    w = jnp.ones((1024,), jnp.float32)
    fused_us = _time_call(jax.jit(fused_residual_rmsnorm_ref), x, r, w)
    unfused_us = _time_call(jax.jit(residual_rmsnorm_unfused), x, r, w)
    _row("fig4/cpu_micro/fused_rmsnorm", fused_us,
         f"unfused={unfused_us:.1f}us ratio={unfused_us/fused_us:.2f}x")


def fig9_smart_split(quick=False):
    """Paper Fig.9: FFN latency — no-split vs equal vs smart split."""
    from repro.configs import get_config
    from repro.core.splitting import naive_split, smart_split, wave_count
    from repro.sim.overlap_sim import HW, t_ffn_layer
    cfg = get_config("llama3.3-70b")
    hw = HW()
    for toks in ([512, 1024, 4096] if quick else
                 [512, 768, 1024, 2048, 4096, 8192]):
        full = t_ffn_layer(cfg, toks, 16, hw)
        e0, e1 = naive_split(toks)
        equal = t_ffn_layer(cfg, e0, 16, hw) + t_ffn_layer(cfg, e1, 16, hw)
        sm = smart_split(toks, hw.tile)
        if sm:
            s0, s1 = sm
            smart = t_ffn_layer(cfg, s0, 16, hw) + t_ffn_layer(cfg, s1, 16,
                                                               hw)
        else:
            smart = full
        _row(f"fig9/seq{toks}", full * 1e6,
             f"equal_split={equal/full:.3f}x smart_split={smart/full:.3f}x "
             f"waves={wave_count(toks, hw.tile)}")


def fig11_latency(quick=False):
    """Paper Fig.11: prefill latency across models / seq / schemes."""
    from repro.configs import get_config
    from repro.sim.overlap_sim import e2e_latency
    models = ["llama3.3-70b"] if quick else \
        ["llama3.3-70b", "qwen2.5-72b", "mixtral-8x22b"]
    for m in models:
        cfg = get_config(m)
        for s in ([1024, 8192] if quick else [1024, 2048, 4096, 8192, 16384]):
            r = {md: e2e_latency(cfg, md, s, tp=16)
                 for md in ("vanilla", "fuseonly", "tokenweave", "nocomm")}
            _row(f"fig11/{m}/seq{s}", r["tokenweave"] * 1e6,
                 f"speedup_vs_vanilla={r['vanilla']/r['tokenweave']:.3f}x "
                 f"vs_nocomm={r['nocomm']/r['tokenweave']:.3f}x "
                 f"fuseonly={r['vanilla']/r['fuseonly']:.3f}x")


def fig12_throughput(quick=False):
    """Paper Fig.12/13: chunked-prefill throughput (sim; chunk sweep)."""
    from repro.configs import get_config
    from repro.sim.overlap_sim import e2e_latency
    cfg = get_config("llama3.3-70b")
    for chunk in ([2048] if quick else [1024, 2048, 4096, 8192]):
        tw = e2e_latency(cfg, "tokenweave", chunk, tp=16)
        va = e2e_latency(cfg, "vanilla", chunk, tp=16)
        _row(f"fig13/chunk{chunk}", tw * 1e6,
             f"tokens_per_s_tw={chunk/tw:,.0f} "
             f"throughput_gain={va/tw:.3f}x")


def fig12_engine_cpu(quick=False):
    """CPU-real end-to-end: tiny model through the continuous-batching
    engine, TokenWeave on vs off (correct outputs, measured steps/s)."""
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.models.build import build_model
    from repro.runtime.engine import Engine
    from repro.runtime.requests import fixed_trace
    from repro.runtime.scheduler import SchedulerConfig

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    n_req = 4 if quick else 8
    results = {}
    for weave in (False, True):
        pcfg = ParallelConfig(tokenweave=weave, comm_mode="fused",
                              remat=False, split_unit=16,
                              tokenweave_min_tokens=32)
        api = build_model(cfg, pcfg, tp=1)
        params = api.init(jax.random.PRNGKey(0))
        eng = Engine(api, mesh, params,
                     SchedulerConfig(max_batch=4, chunk_tokens=64,
                                     max_len=256, prefill_bucket=32))
        for r in fixed_trace(n_req, 48, 8, vocab=128):
            eng.add_request(r)
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        toks = eng.stats.prefill_tokens + eng.stats.decode_tokens
        results[weave] = (toks / dt, [r.output for r in done])
    assert results[True][1] == results[False][1], \
        "tokenweave changed outputs!"
    _row("fig12/cpu_engine", 1e6 / results[True][0],
         f"tokens_per_s={results[True][0]:.0f} outputs_identical=True")


def serve_prefix_cache(quick=False):
    """Paged-KV serving: multi-turn shared-system-prompt workload through
    the paged engine vs. the same trace cold (prefix cache off).  CPU-real;
    reports prefix-hit rate, preemptions, and prefill tokens saved —
    outputs are pinned token-identical between the two runs."""
    import sys as _sys
    import os as _os
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), ".."))
    from examples.shared_prefix_serve import conversation_trace, run_trace
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.models.build import build_model

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))
    users, turns = (2, 2) if quick else (4, 3)

    runs = {}
    for cached in (False, True):
        trace = conversation_trace(users, turns, vocab=cfg.vocab_size)
        eng, done, dt = run_trace(api, mesh, params, trace,
                                  prefix_caching=cached, paged=True,
                                  chunk=64, max_batch=2)
        runs[cached] = (eng, {r.rid: r.output for r in done}, dt)
    assert runs[True][1] == runs[False][1], "prefix cache changed outputs!"
    eng, _, dt = runs[True]
    cold_prefill = runs[False][0].stats.prefill_tokens
    st = eng.block_mgr.stats
    _row("serve/prefix_cache", dt * 1e6 / max(eng.stats.steps, 1),
         f"hit_rate={st.hit_rate:.2f} "
         f"prefill_saved={cold_prefill - eng.stats.prefill_tokens} "
         f"preemptions={st.preemptions} evictions={st.evictions} "
         f"outputs_identical=True")
    snap = eng.metrics_snapshot()
    cold_snap = runs[False][0].metrics_snapshot()
    _reg("serve/prefix_cache/hit_rate", snap, "paging/hit_rate")
    _metric("serve/prefix_cache/prefill_saved",
            cold_snap["engine/prefill_tokens"]
            - snap["engine/prefill_tokens"],
            source="derived:engine/prefill_tokens(cold-warm)")
    _reg("serve/prefix_cache/preemptions", snap, "paging/preemptions")


def serve_spec_decode(quick=False):
    """Speculative decoding through the paged engine (CPU-real): greedy
    n-gram-draft and model-self-draft runs vs. plain greedy decode on the
    same trace — outputs pinned token-identical; reports acceptance rate,
    committed tokens/step, and end-to-end speedup (engine iterations and
    wall clock) — plus the analytic weave-crossover row from the sim's
    spec mode."""
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.models.build import build_model
    from repro.runtime.engine import Engine
    from repro.runtime.requests import repetitive_trace
    from repro.runtime.scheduler import SchedulerConfig
    from repro.runtime.spec import ModelDraft

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))
    n_req, n_new = (3, 16) if quick else (6, 32)
    gamma = 4

    def trace():
        # repeated-motif prompts: the prompt-lookup-friendly structure
        return repetitive_trace(n_req, motif_len=12, repeats=3,
                                output_len=n_new, vocab=cfg.vocab_size,
                                seed=7)

    def run(gamma_, draft=None):
        eng = Engine(api, mesh, params,
                     SchedulerConfig(max_batch=4, chunk_tokens=96,
                                     max_len=256, prefill_bucket=32,
                                     paged=True, spec_gamma=gamma_),
                     draft=draft)
        # pass 1 warms every jit cache; pass 2 is the timed, steady-state
        # run (its prompts also hit the prefix cache, so decode dominates —
        # the regime speculative decoding targets)
        for r in trace():
            eng.add_request(r)
        eng.run()
        s0 = eng.stats.steps
        for r in trace():
            eng.add_request(r)
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        return eng, eng.stats.steps - s0, {r.rid: r.output for r in done}, dt

    eng0, steps0, ref, dt0 = run(0)
    runs = {"ngram": run(gamma),
            "model_draft": run(gamma, ModelDraft(api, mesh, params,
                                                 gamma=gamma, max_batch=4))}
    for name, (eng, steps, outs, dt) in runs.items():
        assert outs == ref, f"speculative ({name}) changed outputs!"
        st = eng.stats.spec
        assert st.acceptance_rate > 0, f"{name}: no draft token accepted"
        assert st.tokens_per_step > 1, f"{name}: spec not committing >1/step"
        _row(f"serve/spec_decode/{name}", dt * 1e6 / max(steps, 1),
             f"accept_rate={st.acceptance_rate:.2f} "
             f"tokens_per_step={st.tokens_per_step:.2f} "
             f"speedup_steps={steps0 / max(steps, 1):.2f}x "
             f"speedup_wall={dt0 / dt:.2f}x outputs_identical=True")
        snap = eng.metrics_snapshot()
        _reg(f"serve/spec_decode/{name}/accept_rate", snap,
             "spec/acceptance_rate")
        _reg(f"serve/spec_decode/{name}/tokens_per_step", snap,
             "spec/tokens_per_step")

    # analytic (sim spec mode): sub-wave decode batches commit E[tokens]
    # per step almost for free; large verify batches cross the weave
    # threshold so tokenweave beats the unsplit fused kernel
    from repro.configs import get_config
    from repro.sim.overlap_sim import spec_decode_summary
    big = get_config("llama3.3-70b")
    s32 = spec_decode_summary(big, batch=32, gamma=4, alpha=0.7, tp=16)
    _row("serve/spec_decode/sim_b32_g4", s32["spec/tokenweave"] * 1e6,
         f"spec_speedup={s32['plain/fuseonly']/s32['spec/tokenweave']:.2f}x "
         f"tokens_per_step={s32['tokens_per_step']:.2f}")
    s256 = spec_decode_summary(big, batch=256, gamma=4, alpha=0.7, tp=16)
    _row("serve/spec_decode/sim_b256_g4", s256["spec/tokenweave"] * 1e6,
         f"weave_gain_on_verify="
         f"{s256['spec/fuseonly']/s256['spec/tokenweave']:.3f}x "
         f"verify_tokens={s256['verify_tokens']:.0f} "
         f"tokens_per_step={s256['tokens_per_step']:.2f}")


def serve_packed(quick=False):
    """Packed hybrid batching (DESIGN.md §6, CPU-real): the same mixed
    prefill+decode trace through the two-dispatch engine and the packed
    engine — outputs pinned token-identical; reports weave-activation rate
    and tokens/forward for both (packed must weave strictly more often:
    mixed iterations whose decode and prefill halves are each below
    ``tokenweave_min_tokens`` jointly cross it), plus the sim's analytic
    packed crossover row."""
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.models.build import build_model
    from repro.runtime.engine import Engine
    from repro.runtime.requests import repetitive_trace
    from repro.runtime.scheduler import SchedulerConfig

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    # genuine-crossover sizing (REAL tokens, not shape padding): four γ=3
    # verify windows carry 16 real tokens and the ragged prefill take adds
    # up to 16 more, so mixed packed iterations hit exactly the 32-token
    # threshold (asserted via max_forward_tokens below); the two-dispatch
    # engine judges the same halves apart — verify (4, 4) is far under the
    # row floor and its prefill chunk is capped at 32-16=16 tokens — and
    # only weaves on the rare pure-prefill iteration
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))
    n_req = 6 if quick else 10

    def run(packed):
        tag = "packed" if packed else "two_dispatch"
        rec = _recorder(f"packed:{tag}")
        eng = Engine(api, mesh, params,
                     SchedulerConfig(max_batch=4, chunk_tokens=32,
                                     max_len=256, prefill_bucket=16,
                                     paged=True, spec_gamma=3,
                                     packed=packed),
                     obs=rec, obs_track=f"packed/{tag}")
        for r in repetitive_trace(n_req, motif_len=12, repeats=3,
                                  output_len=10, vocab=cfg.vocab_size,
                                  seed=7):
            eng.add_request(r)
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        return eng, {r.rid: r.output for r in done}, dt, rec

    two, ref, _, rec2 = run(False)
    pk, got, dt, recp = run(True)
    assert got == ref, "packed batching changed outputs!"
    _assert_trace_matches(rec2, two.stats, "serve/packed two_dispatch")
    _assert_trace_matches(recp, pk.stats, "serve/packed packed")
    assert pk.stats.weave_rate > two.stats.weave_rate, (
        f"packed weave rate {pk.stats.weave_rate:.2f} not above "
        f"two-dispatch {two.stats.weave_rate:.2f}")
    assert pk.stats.max_forward_tokens >= pcfg.tokenweave_min_tokens, (
        "packed crossover must be carried by real tokens, not padding")
    _row("serve/packed", dt * 1e6 / max(pk.stats.steps, 1),
         f"weave_rate={pk.stats.weave_rate:.2f} "
         f"weave_rate_two_dispatch={two.stats.weave_rate:.2f} "
         f"tokens_per_forward={pk.stats.tokens_per_forward:.1f} "
         f"vs_two_dispatch={two.stats.tokens_per_forward:.1f} "
         f"forwards={pk.stats.forwards} vs {two.stats.forwards} "
         f"max_real_tokens={pk.stats.max_forward_tokens} "
         f"outputs_identical=True")
    snap_pk, snap_two = pk.metrics_snapshot(), two.metrics_snapshot()
    _reg("serve/packed/weave_rate", snap_pk, "engine/weave_rate")
    _reg("serve/packed/weave_rate_two_dispatch", snap_two,
         "engine/weave_rate")
    _reg("serve/packed/tokens_per_forward", snap_pk,
         "engine/tokens_per_forward")
    _reg("serve/packed/tokens_per_forward_two_dispatch", snap_two,
         "engine/tokens_per_forward")
    _reg("serve/packed/max_forward_tokens", snap_pk,
         "engine/max_forward_tokens")

    # analytic (sim packed mode): the crossover cell — decode batch and
    # prefill chunk each under the wave/threshold floor (no split), the
    # packed iteration over it (splits, overlaps)
    from repro.configs import get_config
    from repro.sim.overlap_sim import packed_summary
    big = get_config("llama3.3-70b")
    s = packed_summary(big, decode_tokens=256, chunk_tokens=384, tp=16)
    _row("serve/packed/sim_d256_c384", s["packed/tokenweave"] * 1e6,
         f"packed_gain={s['packed/fuseonly']/s['packed/tokenweave']:.3f}x "
         f"two_dispatch_gain={s['two/fuseonly']/s['two/tokenweave']:.3f}x "
         f"halves_weave={s['halves_weave']:.0f} "
         f"packed_weaves={s['packed_weaves']:.0f}")


def serve_online(quick=False):
    """Online serving frontend (runtime/server.py, DESIGN.md §10,
    CPU-real): a seeded Poisson-arrival ShareGPT-like trace through the
    OnlineServer on BOTH dispatch schemes — emitted tokens pinned
    identical to the OFFLINE engine on the same trace (the continuous-
    batching guarantee transfers to arrival dynamics) — reporting virtual-
    time TTFT/TPOT percentiles, goodput under tight deadlines, and the
    load-dependent weave rate; plus the sim's analytic crossover row
    (offered-load window where only the packed iteration weaves)."""
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.models.build import build_model
    from repro.runtime.engine import Engine
    from repro.runtime.requests import (poisson_arrivals,
                                        sharegpt_like_trace)
    from repro.runtime.scheduler import SchedulerConfig
    from repro.runtime.server import OnlineServer, ServerConfig, StepCost

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))
    n_req = 8 if quick else 16

    def trace():
        t = sharegpt_like_trace(n_req, vocab=cfg.vocab_size, seed=11,
                                max_in=48, max_out=8)
        for r in t:
            r.max_new_tokens = max(2, min(r.max_new_tokens, 8))
        return poisson_arrivals(t, rate=0.25, seed=5)

    def scfg(packed):
        return SchedulerConfig(max_batch=4, chunk_tokens=48, max_len=128,
                               prefill_bucket=16, paged=True, packed=packed)

    jit_caches = {False: {}, True: {}}

    def offline(packed):
        eng = Engine(api, mesh, params, scfg(packed),
                     jit_cache=jit_caches[packed])
        for r in trace():
            eng.add_request(r)
        done = eng.run()
        return eng, {r.rid: r.output for r in done}

    def online(packed, tag, deadline=None):
        rec = _recorder(f"online:{tag}")
        eng = Engine(api, mesh, params, scfg(packed),
                     jit_cache=jit_caches[packed],
                     obs=rec, obs_track=f"online/{tag}")
        srv = OnlineServer(eng, ServerConfig(
            step_cost=StepCost(base=1.0, per_token=0.05),
            expire_on_deadline=deadline is not None))
        for r in trace():
            if deadline is not None:
                r.deadline = r.arrival_time + deadline
            srv.submit(r)
        done = srv.run()
        return eng, srv, {r.rid: r.output for r in done}, rec

    # the offline reference engines run UNTRACED: got == ref below is the
    # §12 on/off identity check riding along with the dispatch-scheme one
    _, ref = offline(False)
    _, ref_pk = offline(True)
    assert ref_pk == ref, "offline packed diverged from two-dispatch!"
    eng2, _, got2, rec2 = online(False, "two_dispatch")
    engp, srvp, gotp, recp = online(True, "packed")
    assert got2 == ref, "online two-dispatch changed emitted tokens!"
    assert gotp == ref, "online packed changed emitted tokens!"
    _assert_trace_matches(rec2, eng2.stats, "serve/online two_dispatch")
    _assert_trace_matches(recp, engp.stats, "serve/online packed")
    lat = engp.stats.latency.summary()
    _row("serve/online", srvp.clock * 1e6 / max(engp.stats.steps, 1),
         f"goodput={lat['goodput']:.2f} ttft_p50={lat['ttft_p50']:.2f} "
         f"tpot_p50={lat['tpot_p50']:.2f} e2e_p99={lat['e2e_p99']:.2f} "
         f"weave_rate={engp.stats.weave_rate:.2f} "
         f"weave_rate_two_dispatch={eng2.stats.weave_rate:.2f} "
         f"outputs_identical=True")
    snapp, snap2 = engp.metrics_snapshot(), eng2.metrics_snapshot()
    _reg("serve/online/goodput", snapp, "latency/goodput")
    _reg("serve/online/ttft_p50", snapp, "latency/ttft/p50")
    _reg("serve/online/tpot_p50", snapp, "latency/tpot/p50")
    _reg("serve/online/e2e_p99", snapp, "latency/e2e/p99")
    _reg("serve/online/weave_rate", snapp, "engine/weave_rate")
    _reg("serve/online/weave_rate_two_dispatch", snap2,
         "engine/weave_rate")

    # tight e2e deadlines under the same load: some requests expire (their
    # blocks/prefix refs released mid-flight), goodput drops below 1 —
    # deterministic virtual-time counters, gated like the rest
    engd, srvd, _, recd = online(True, "slo", deadline=14.0)
    _assert_trace_matches(recd, engd.stats, "serve/online slo")
    latd = engd.stats.latency.summary()
    _row("serve/online/slo", srvd.clock * 1e6 / max(engd.stats.steps, 1),
         f"goodput={latd['goodput']:.2f} expired={engd.stats.expired} "
         f"completed={engd.stats.completed}")
    snapd = engd.metrics_snapshot()
    _reg("serve/online/slo_goodput", snapd, "latency/goodput")
    _reg("serve/online/slo_expired", snapd, "engine/expired")

    # analytic (sim online mode): the offered-load window where the packed
    # iteration crosses the split floor but the two-dispatch halves don't
    from repro.configs import get_config
    from repro.sim.overlap_sim import online_crossover_rate, online_summary
    big = get_config("llama3.3-70b")
    rates = [5.0, 15.0, 25.0, 30.0, 40.0]
    summ = online_summary(big, rates, tp=16)
    cross = online_crossover_rate(big, rates, tp=16)
    x = summ[cross] if cross is not None else summ[rates[-1]]
    _row("serve/online/sim_load_sweep",
         x["t_iter_packed"] * 1e6,
         f"crossover_rate={cross} decode_tokens={x['decode_tokens']:.0f} "
         f"chunk_tokens={x['chunk_tokens']:.0f} "
         f"packed_gain={x['packed_gain']:.3f} "
         f"halves_weave={x['halves_weave']:.0f}")


def serve_cluster(quick=False):
    """Cluster serving layer (runtime/cluster.py, DESIGN.md §11,
    CPU-real): N independent engine replicas behind a pluggable router.

    Part 1 — routing: a grouped shared-prefix trace through a 3-replica
    mixed fleet under every router (round_robin, least_loaded,
    prefix_affinity); outputs pinned token-identical to a SINGLE engine on
    the same seeded trace for each (greedy outputs are batch-composition-
    invariant, so where a request lands never changes what it generates);
    prefix_affinity must actually find hot blocks (affinity hit rate > 0).

    Part 2 — disaggregation: the same offered load through (a) a
    monolithic fleet of 3 mixed replicas and (b) 2 prefill + 1 decode
    replica with KV handoff.  Outputs pinned identical to the single
    engine again, every request migrates exactly once, and the decode
    fleet's merged batches must weave STRICTLY more often than the
    monolithic fleet's (the §11 payoff: concentrated decode traffic
    crosses ``tokenweave_min_tokens`` at loads where a monolithic
    engine's share sits below it) — plus the sim's analytic crossover
    row."""
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.models.build import build_model
    from repro.runtime.cluster import ClusterConfig, ClusterServer, Replica
    from repro.runtime.engine import Engine
    from repro.runtime.requests import (grouped_prefix_trace,
                                        poisson_arrivals,
                                        sharegpt_like_trace)
    from repro.runtime.scheduler import SchedulerConfig

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=48)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))

    jit_cache = {}

    def engine(max_batch=16, chunk=64, obs=None):
        return Engine(api, mesh, params,
                      SchedulerConfig(max_batch=max_batch,
                                      chunk_tokens=chunk, max_len=96,
                                      prefill_bucket=16, paged=True,
                                      block_size=8, packed=True),
                      jit_cache=jit_cache, obs=obs)

    def single_ref(trace):
        eng = engine()
        for r in trace():
            eng.add_request(r)
        return {r.rid: r.output for r in eng.run()}

    # ---- part 1: routers on a shared-prefix workload ------------------
    per_group = 3 if quick else 4

    def affinity_trace():
        t = grouped_prefix_trace(3, per_group, prefix_len=24, tail_len=6,
                                 output_len=6, vocab=cfg.vocab_size, seed=3)
        return poisson_arrivals(t, rate=0.5, seed=5)

    ref = single_ref(affinity_trace)
    summaries, cs_aff = {}, None
    for router in ("round_robin", "least_loaded", "prefix_affinity"):
        reps = [Replica(f"r{i}", engine()) for i in range(3)]
        cs = ClusterServer(reps, ClusterConfig(router=router))
        for r in affinity_trace():
            cs.submit(r)
        got = {r.rid: r.output for r in cs.run()}
        assert got == ref, f"cluster ({router}) changed outputs!"
        cs.check_quiescent()
        summaries[router] = cs.summary()
        if router == "prefix_affinity":
            cs_aff = cs
    aff = summaries["prefix_affinity"]["affinity_hit_rate"]
    assert aff > 0, "prefix_affinity never found a hot block"

    # ---- part 2: disaggregated prefill/decode vs monolithic fleet -----
    n_req, rate = (36, 8.0) if quick else (48, 8.0)

    def load_trace():
        t = sharegpt_like_trace(n_req, vocab=cfg.vocab_size, seed=11,
                                max_in=32, max_out=32)
        for r in t:
            r.max_new_tokens = max(24, min(r.max_new_tokens, 32))
        return poisson_arrivals(t, rate=rate, seed=5)

    ref2 = single_ref(load_trace)

    rec_m = _recorder("cluster:mono")
    mono = [Replica(f"m{i}", engine(obs=rec_m)) for i in range(3)]
    cs_m = ClusterServer(mono, ClusterConfig(router="round_robin"))
    for r in load_trace():
        cs_m.submit(r)
    assert {r.rid: r.output for r in cs_m.run()} == ref2, \
        "monolithic fleet changed outputs!"
    cs_m.check_quiescent()
    mono_fwd = sum(r.engine.stats.forwards for r in mono)
    mono_wv = sum(r.engine.stats.weave_forwards for r in mono)
    mono_weave = mono_wv / max(mono_fwd, 1)
    from repro.obs import weave_counts_from_trace
    wm, nm = weave_counts_from_trace(rec_m)
    assert (wm, nm) == (mono_wv, mono_fwd), (
        f"serve/cluster mono fleet: trace weave counts ({wm}/{nm}) != "
        f"fleet counters ({mono_wv}/{mono_fwd})")

    rec_d = _recorder("cluster:disagg")
    disagg = [Replica("p0", engine(obs=rec_d), role="prefill"),
              Replica("p1", engine(obs=rec_d), role="prefill"),
              Replica("d0", engine(max_batch=48, obs=rec_d),
                      role="decode")]
    t0 = time.perf_counter()
    cs_d = ClusterServer(disagg, ClusterConfig(router="round_robin"))
    for r in load_trace():
        cs_d.submit(r)
    assert {r.rid: r.output for r in cs_d.run()} == ref2, \
        "disaggregated cluster changed outputs!"
    dt = time.perf_counter() - t0
    cs_d.check_quiescent()
    sd = cs_d.summary()
    d0 = disagg[2].engine.stats
    _assert_trace_matches(rec_d, d0, "serve/cluster d0", track="d0")
    wd, nd = weave_counts_from_trace(rec_d)
    dis_fwd = sum(r.engine.stats.forwards for r in disagg)
    dis_wv = sum(r.engine.stats.weave_forwards for r in disagg)
    assert (wd, nd) == (dis_wv, dis_fwd), (
        f"serve/cluster disagg fleet: trace weave counts ({wd}/{nd}) != "
        f"fleet counters ({dis_wv}/{dis_fwd})")
    assert sd["migrations"] == n_req, \
        f"expected {n_req} migrations, got {sd['migrations']}"
    assert sd["decode_fleet/weave_rate"] > mono_weave, (
        f"decode-fleet weave rate {sd['decode_fleet/weave_rate']:.2f} not "
        f"above the monolithic fleet's {mono_weave:.2f}")
    assert d0.max_forward_tokens >= pcfg.tokenweave_min_tokens - 16, (
        "decode-fleet crossover must be carried by merged real decode "
        "batches")
    steps = sum(r.engine.stats.steps for r in disagg)
    _row("serve/cluster", dt * 1e6 / max(steps, 1),
         f"affinity_hit_rate={aff:.2f} migrations={int(sd['migrations'])} "
         f"decode_fleet_weave={sd['decode_fleet/weave_rate']:.2f} "
         f"mono_fleet_weave={mono_weave:.2f} "
         f"d0_tokens_per_forward={d0.tokens_per_forward:.1f} "
         f"import_shared_blocks="
         f"{disagg[2].engine.block_mgr.stats.import_shared_blocks} "
         f"outputs_identical=True")
    snap_aff = cs_aff.metrics_snapshot()
    snap_d = cs_d.metrics_snapshot()
    _reg("serve/cluster/affinity_hit_rate", snap_aff,
         "summary/affinity_hit_rate")
    _reg("serve/cluster/migrations", snap_d, "summary/migrations")
    _metric("serve/cluster/mono_fleet_weave_rate", mono_weave,
            source="derived:engine/weave_forwards over engine/forwards "
                   "(mono fleet aggregate)")
    _reg("serve/cluster/decode_fleet_weave_rate", snap_d,
         "summary/decode_fleet/weave_rate")
    _reg("serve/cluster/p0_weave_rate", snap_d, "summary/p0/weave_rate")
    _reg("serve/cluster/p1_weave_rate", snap_d, "summary/p1/weave_rate")
    _reg("serve/cluster/d0_tokens_per_forward", snap_d,
         "summary/d0/tokens_per_forward")

    # analytic (sim cluster mode): the total-offered-load window where the
    # disaggregated decode fleet's merged batches weave while a monolithic
    # engine's 1/N share of the same traffic sits under the split floor
    from repro.configs import get_config
    from repro.sim.overlap_sim import cluster_crossover_rate, cluster_summary
    big = get_config("llama3.3-70b")
    rates = [10.0, 20.0, 30.0, 40.0, 60.0, 80.0]
    summ = cluster_summary(big, rates, n_replicas=4, tp=16)
    cross = cluster_crossover_rate(big, rates, 4, tp=16)
    x = summ[cross] if cross is not None else summ[rates[-1]]
    _row("serve/cluster/sim_fleet4", x["t_iter_decode_fleet"] * 1e6,
         f"crossover_rate={cross} "
         f"decode_fleet_tokens={x['decode_fleet_tokens']:.0f} "
         f"mono_iter_tokens={x['mono_iter_tokens']:.0f} "
         f"decode_fleet_gain={x['decode_fleet_gain']:.3f} "
         f"mono_weaves={x['mono_weaves']:.0f}")


def serve_cluster_wire(quick=False):
    """Wire transport + failure handling on the serving hot path
    (runtime/transport.py + runtime/cluster.py, DESIGN.md §15).

    A disaggregated fleet served over the LOOPBACK WIRE: every submit
    envelope and KV-migration payload crosses the versioned frame codec
    (the same bytes a socket would carry), with per-byte wire latency
    charged into migration delay.  Mid-trace one decode replica is
    KILLED; the heartbeat detector requeues everything it owned onto the
    survivors with recompute semantics.  Hard assertions: outputs stay
    token-identical to a never-failed single engine, the death/requeue
    counters fire, and the block-pool quiescence sweep passes afterwards.
    Gated metrics: wire frame/byte counts and the frame-size p50 straight
    from the ``cluster/wire/*`` registry instruments, plus the
    ``cluster/replica_deaths`` / ``cluster/requeued`` fault counters."""
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.models.build import build_model
    from repro.runtime.cluster import ClusterConfig, ClusterServer, Replica
    from repro.runtime.engine import Engine
    from repro.runtime.requests import poisson_arrivals, sharegpt_like_trace
    from repro.runtime.scheduler import SchedulerConfig

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=48)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))

    jit_cache = {}

    def engine():
        return Engine(api, mesh, params,
                      SchedulerConfig(max_batch=8, chunk_tokens=64,
                                      max_len=96, prefill_bucket=16,
                                      paged=True, block_size=8),
                      jit_cache=jit_cache)

    n_req = 10 if quick else 16

    def trace():
        t = sharegpt_like_trace(n_req, vocab=cfg.vocab_size, seed=13,
                                max_in=32, max_out=24)
        for r in t:
            r.max_new_tokens = max(12, min(r.max_new_tokens, 24))
        return poisson_arrivals(t, rate=2.0, seed=7)

    ref_eng = engine()
    for r in trace():
        ref_eng.add_request(r)
    ref = {r.rid: r.output for r in ref_eng.run()}

    reps = [Replica("p0", engine(), role="prefill"),
            Replica("d0", engine(), role="decode"),
            Replica("d1", engine(), role="decode")]
    cs = ClusterServer(reps, ClusterConfig(
        router="round_robin", wire="loopback", wire_per_byte=1e-6,
        heartbeat_timeout=2.0))
    for r in trace():
        cs.submit(r)
    cs.kill_replica("d0", at=3.0)          # mid-trace decode-replica crash
    t0 = time.perf_counter()
    done = cs.run()
    dt = time.perf_counter() - t0
    assert {r.rid: r.output for r in done} == ref, \
        "wire cluster with replica kill changed outputs!"
    assert cs.stats.replica_deaths == 1, "the kill never landed"
    assert cs.stats.requeued >= 1, \
        "d0 died holding no work — the recovery path went unexercised"
    cs.check_quiescent()

    snap = cs.metrics_snapshot()
    steps = sum(r.engine.stats.steps for r in reps)
    _row("serve/cluster_wire", dt * 1e6 / max(steps, 1),
         f"frames={int(snap['cluster/wire/frames'])} "
         f"bytes={int(snap['cluster/wire/bytes'])} "
         f"frame_bytes_p50={snap['cluster/wire/frame_bytes/p50']:.0f} "
         f"replica_deaths={int(snap['cluster/replica_deaths'])} "
         f"requeued={int(snap['cluster/requeued'])} "
         f"outputs_identical=True")
    _reg("serve/cluster_wire/frames", snap, "cluster/wire/frames")
    _reg("serve/cluster_wire/bytes", snap, "cluster/wire/bytes")
    _reg("serve/cluster_wire/frame_bytes_p50", snap,
         "cluster/wire/frame_bytes/p50")
    _reg("serve/cluster_wire/replica_deaths", snap,
         "cluster/replica_deaths")
    _reg("serve/cluster_wire/requeued", snap, "cluster/requeued")


def fig14_overlap_comparison(quick=False):
    """Paper Fig.14 analogue: TokenWeave vs a TileLink-style GEMM-fused
    overlap (which can only hide comm inside GEMMs and pays split RS/AG)."""
    from repro.configs import get_config
    from repro.sim.overlap_sim import (HW, e2e_latency, t_attn_layer,
                                       t_ffn_layer, t_rs_or_ag)
    cfg = get_config("llama3.3-70b")
    hw = HW()
    tp = 16
    for toks in ([1024, 8192] if quick else [1024, 2048, 4096, 8192, 16384]):
        tw = e2e_latency(cfg, "tokenweave", toks, tp=tp)
        va = e2e_latency(cfg, "vanilla", toks, tp=tp)
        # TileLink-style: RS overlapped with producer GEMM (capped by GEMM
        # time), AG overlapped with next GEMM; norms unfused; per-CTA
        # streaming adds ~15% GEMM overhead (paper Fig.14 shows occupancy
        # loss); attention comm not overlappable.
        attn = t_attn_layer(cfg, toks, toks, tp, hw) * 1.15
        ffn = t_ffn_layer(cfg, toks, tp, hw) * 1.15
        rs = t_rs_or_ag(toks, cfg.d_model, tp, hw)
        from repro.sim.overlap_sim import t_norm
        norm = t_norm(toks, cfg.d_model, hw, fused=False)
        per_layer = (attn + max(rs - ffn, 0) + rs + norm
                     + ffn + max(rs - attn, 0) + rs + norm)
        tl = per_layer * cfg.num_layers
        _row(f"fig14/seq{toks}", tw * 1e6,
             f"tokenweave={va/tw:.3f}x tilelink_style={va/tl:.3f}x")


def fig16_ablation(quick=False):
    """Paper Fig.16: vllm-multimem vs fuseonly vs full TokenWeave."""
    from repro.configs import get_config
    from repro.sim.overlap_sim import e2e_latency
    for m in (["llama3.3-70b"] if quick else
              ["llama3.3-70b", "qwen2.5-72b", "mixtral-8x22b"]):
        cfg = get_config(m)
        for s in ([2048, 8192] if quick else [1024, 2048, 4096, 8192]):
            base = e2e_latency(cfg, "vanilla", s, tp=16)
            fo = e2e_latency(cfg, "fuseonly", s, tp=16)
            tw = e2e_latency(cfg, "tokenweave", s, tp=16)
            _row(f"fig16/{m}/seq{s}", tw * 1e6,
                 f"fuseonly={base/fo:.3f}x full={base/tw:.3f}x")


def kernels_micro(quick=False):
    """Interpret-mode kernel micro-latency (correctness-bearing, CPU)."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.fused_rmsnorm import fused_residual_rmsnorm_pallas
    x = jnp.ones((256, 512), jnp.float32)
    r = jnp.ones((256, 512), jnp.float32)
    w = jnp.ones((512,), jnp.float32)
    us = _time_call(
        jax.jit(lambda a, b, c: fused_residual_rmsnorm_pallas(
            a, b, c, interpret=True, block_tokens=64)), x, r, w, reps=2)
    _row("kernels/fused_rmsnorm_interpret", us, "pallas_interpret")
    q = jnp.ones((1, 64, 2, 2, 32))
    k = jnp.ones((1, 64, 2, 32))
    qp = jnp.broadcast_to(jnp.arange(64)[None], (1, 64))
    us = _time_call(
        jax.jit(lambda q_, k_: flash_attention(
            q_, k_, k_, qp, qp, causal=True, block_q=32, block_kv=32,
            interpret=True)), q, k, reps=2)
    _row("kernels/flash_attention_interpret", us, "pallas_interpret")


def profile_calibration(quick=False, report_path=None):
    """Measured-time profile smoke + cost-model calibration (§13, opt-in
    via --profile): run the tiny CPU model through the engine with a
    ``WallClockProfiler`` attached, assert the profiled run is token-
    identical to an unprofiled reference, fit the ``HW`` cost model from
    the steady samples, and publish everything under the ``measured:``
    provenance namespace — informational (machine-dependent), exempt
    from the ±15% determinism gate, but drift-gated in CI through
    scripts/check_calibration.py on the report this writes."""
    from repro.analysis.calibration import fit_calibration
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.models.build import build_model
    from repro.obs import WallClockProfiler
    from repro.runtime.engine import Engine
    from repro.runtime.requests import sharegpt_like_trace
    from repro.runtime.scheduler import SchedulerConfig

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, dtype="float32")
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=16, tokenweave_min_tokens=32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))
    n_req = 8 if quick else 16
    jit_cache = {}

    def run(profiler, rec=None):
        eng = Engine(api, mesh, params,
                     SchedulerConfig(max_batch=4, chunk_tokens=64,
                                     max_len=256, prefill_bucket=16,
                                     paged=True),
                     jit_cache=jit_cache, profiler=profiler,
                     obs=rec, obs_track="profile")
        # varied prompt lengths -> several prefill buckets -> several
        # (method, tokens) calibration buckets
        for r in sharegpt_like_trace(n_req, vocab=cfg.vocab_size, seed=7,
                                     max_in=56, max_out=8):
            r.max_new_tokens = max(2, min(r.max_new_tokens, 8))
            eng.add_request(r)
        done = eng.run()
        return eng, {r.rid: tuple(r.output) for r in done}

    _, ref = run(None)                        # also pre-compiles the cache
    prof = WallClockProfiler()
    # the recorder gives the measured spans a home in the merged --trace
    # export (virtual spans on "profile", wall time on "profile [measured]")
    eng, got = run(prof, rec=_recorder("profile"))
    assert got == ref, "profiling changed tokens!"

    steady = prof.steady_samples()
    rep = fit_calibration(api.cfg, steady, tp=1,
                          tile=pcfg.split_unit_for(1))
    rep.export_to(eng.metrics)
    snap = eng.metrics.snapshot()
    for key in sorted(snap):
        if key.startswith("profile/"):
            _reg(f"measured:{key}", snap, key)
    _row("profile/calibration", rep.overhead * 1e6,
         f"n_steady={len(steady)} mfu_cap={rep.mfu_cap:.3g} "
         f"ici_gbps={rep.ici / 1e9:.3g} worst_rel_err={rep.worst_rel_err:.3f} "
         f"outputs_identical=True")
    for mode in sorted(rep.per_mode_rel_err):
        _row(f"profile/predicted_vs_measured/{mode}",
             rep.per_mode_rel_err[mode] * 1e6,
             f"rel_err={rep.per_mode_rel_err[mode]:.3f}")
    if report_path:
        rep.save(report_path)
        print(f"wrote calibration report to {report_path}", file=sys.stderr)


FIGS = [fig1_comm_overhead, fig4_fused_kernel, fig9_smart_split,
        fig11_latency, fig12_throughput, fig12_engine_cpu,
        serve_prefix_cache, serve_spec_decode, serve_packed, serve_online,
        serve_cluster, serve_cluster_wire,
        fig14_overlap_comparison,
        fig16_ablation, kernels_micro]


def _select_figs(only: str | None):
    """Resolve ``--only`` (comma-separated section names, substring match
    per entry) to a figure list.  An entry matching NOTHING is an error —
    a typo'd filter used to silently run zero figures, which would make
    the CI gate vacuously green."""
    if not only:
        return list(FIGS)
    valid = [f.__name__ for f in FIGS]
    selected, seen = [], set()
    for entry in only.split(","):
        entry = entry.strip()
        matches = [f for f in FIGS if entry and entry in f.__name__]
        if not matches:
            raise SystemExit(
                f"--only entry {entry!r} matches no benchmark section; "
                f"valid names: {', '.join(valid)}")
        for f in matches:
            if f.__name__ not in seen:
                seen.add(f.__name__)
                selected.append(f)
    return selected


def main() -> None:
    p = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="Scenario-by-scenario docs and the semantics of every "
               "gated metric: benchmarks/README.md.  Baseline update "
               "workflow: README.md (top level).")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--only", default=None,
                   help="comma-separated section names (substring match); "
                        "unknown names error with the valid choices")
    p.add_argument("--strict", action="store_true",
                   help="exit non-zero if any figure errors (CI gate; the "
                        "default keeps the full local sweep robust)")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write the deterministic serving metrics as JSON "
                        "(compared against benchmarks/baseline.json by "
                        "scripts/check_bench.py), with a __provenance__ "
                        "map recording each metric's registry source")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="export the serve benchmarks' merged Chrome-trace/"
                        "Perfetto JSON (inspect or --validate it with "
                        "scripts/trace_view.py; load at "
                        "https://ui.perfetto.dev)")
    p.add_argument("--profile", action="store_true",
                   help="run the measured-time profile smoke + calibration "
                        "fit (DESIGN.md §13); wall-clock results land in "
                        "the JSON under the measured: namespace "
                        "(provenance-required, tolerance-exempt)")
    p.add_argument("--calibration-out", default=None, metavar="PATH",
                   help="write the CalibrationReport JSON (implies "
                        "--profile; gate it with "
                        "scripts/check_calibration.py)")
    args = p.parse_args()
    use_compile_cache()
    figs = _select_figs(args.only)
    if args.profile or args.calibration_out:
        def _profile(quick=False):
            profile_calibration(quick=quick,
                                report_path=args.calibration_out)
        _profile.__name__ = "profile_calibration"
        figs.append(_profile)
    print("name,us_per_call,derived")
    errors = 0
    for fig in figs:
        try:
            fig(quick=args.quick)
        except Exception as e:  # keep the harness robust
            errors += 1
            _row(f"{fig.__name__}/ERROR", 0.0, f"{type(e).__name__}: {e}")
            import traceback
            traceback.print_exc(file=sys.stderr)
    if args.json:
        payload = dict(_METRICS)
        payload["__provenance__"] = dict(_PROVENANCE)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(_METRICS)} metrics to {args.json}",
              file=sys.stderr)
    if args.trace:
        if _RECORDERS:
            from repro.obs import export_chrome_trace
            doc = export_chrome_trace(_RECORDERS, path=args.trace)
            print(f"wrote trace ({len(doc['traceEvents'])} events, "
                  f"{len(_RECORDERS)} recorders) to {args.trace}",
                  file=sys.stderr)
        else:
            print("no trace recorded (no serve benchmark ran)",
                  file=sys.stderr)
    if args.strict and errors:
        sys.exit(1)


if __name__ == "__main__":
    main()
