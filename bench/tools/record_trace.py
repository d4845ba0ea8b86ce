#!/usr/bin/env python3
"""Record a small profiler trace on the chip for the trace-reduction test
(bench/tests/data): a tiny dense model at tensor parallelism over every
chip present, a few engine steps of prefill and decode, with the
benchmark's own host spans.

    python bench/tools/record_trace.py --out <dir>
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

TINY = {"name": "tiny", "kind": "dense_transformer", "hidden_size": 512,
        "intermediate_size": 1024, "num_hidden_layers": 2,
        "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 64,
        "vocab_size": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "torch_dtype": "bfloat16", "qkv_bias": False, "qk_norm": True}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    a = p.parse_args()
    import jax
    from bench.harness import cell as C
    from bench.harness import trace as TR
    from bench.harness.spec import load_module
    devs = jax.devices()
    tp = len(devs)
    ad = load_module(os.path.join(ROOT, "bench", "adapters",
                                  "dense_transformer.py"), "ad")
    from repro.models.build import build_model
    from repro.runtime.engine import Engine
    from repro.runtime.scheduler import SchedulerConfig
    from repro.configs.base import ParallelConfig
    mesh = jax.make_mesh((1, tp), ("data", "model"), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(ad.model_config(TINY),
                      ParallelConfig(remat=False, tokenweave_min_tokens=128),
                      tp=tp)
    params = ad.build_params(C.weights_key(1), TINY, api, mesh)
    eng = Engine(api, mesh, params,
                 SchedulerConfig(max_batch=4, max_len=512, chunk_tokens=256,
                                 prefill_bucket=128))
    drv = C.Clients(eng, annotate=True)
    for n in (256, 100):               # warm every shape used below
        drv.admit([3] * n, 4, 0.0, "warm")
    while not eng.sched.all_done():
        drv.step()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/traced"):
        with drv.ann("bench/admit"):
            drv.admit([5] * 256, 4, 0.0, "t")
            drv.admit([7] * 100, 4, 0.0, "t")
        while not eng.sched.all_done():
            drv.step()
        with drv.ann("bench/wait"):
            import time
            time.sleep(0.002)
    jax.profiler.stop_trace()
    os.makedirs(a.out, exist_ok=True)
    xp = TR.find_xplane(d)
    shutil.copy(xp, os.path.join(a.out, f"tiny_tp{tp}.xplane.pb"))
    with open(os.path.join(a.out, f"tiny_tp{tp}.summary.txt"), "w") as f:
        f.write(TR.summary(xp, n=40))
    with open(os.path.join(a.out, f"tiny_tp{tp}.steps.json"), "w") as f:
        json.dump(drv.steps, f)
    print("steps", drv.steps[-5:])
    print("bytes", os.path.getsize(xp))


if __name__ == "__main__":
    main()
