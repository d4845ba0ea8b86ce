"""End-to-end serving driver (the paper is an inference paper — this is the
required e2e example): a ShareGPT-like trace through the continuous-batching
engine with Sarathi-style chunked prefill, TokenWeave on, reporting
throughput and per-request latency stats.

    PYTHONPATH=src python examples/serve_e2e.py [--requests 8] [--weave-off]
"""
import argparse
import time

import jax

from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models.build import build_model
from repro.runtime.engine import Engine
from repro.runtime.requests import sharegpt_like_trace
from repro.runtime.scheduler import SchedulerConfig


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--arch", default="qwen1.5-4b")
    p.add_argument("--weave-off", action="store_true")
    p.add_argument("--chunk", type=int, default=128)
    args = p.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch).reduced()
    pcfg = ParallelConfig(tokenweave=not args.weave_off, comm_mode="fused",
                          remat=False, split_unit=32,
                          tokenweave_min_tokens=64)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)
    params = api.init(jax.random.PRNGKey(0))

    eng = Engine(api, mesh, params,
                 SchedulerConfig(max_batch=4, chunk_tokens=args.chunk,
                                 max_len=1024, prefill_bucket=64))
    trace = sharegpt_like_trace(args.requests, vocab=cfg.vocab_size,
                                seed=0, max_in=512, max_out=32)
    for r in trace:
        r.max_new_tokens = min(r.max_new_tokens, 16)   # CPU demo budget
        eng.add_request(r)

    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    toks = eng.stats.prefill_tokens + eng.stats.decode_tokens
    print(f"arch={cfg.name} tokenweave={'off' if args.weave_off else 'on'}")
    print(f"requests completed : {len(done)}/{args.requests}")
    print(f"engine iterations  : {eng.stats.steps}")
    print(f"prefill tokens     : {eng.stats.prefill_tokens}")
    print(f"decode tokens      : {eng.stats.decode_tokens}")
    print(f"throughput (CPU!)  : {toks/dt:,.0f} tok/s over {dt:.1f}s")
    ttfts = [r.first_token_step - r.arrival_step for r in done]
    print(f"TTFT (steps)       : mean {sum(ttfts)/len(ttfts):.1f} "
          f"max {max(ttfts)}")


if __name__ == "__main__":
    main()
