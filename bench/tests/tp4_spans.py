"""The ``engine/dispatch`` spans' ``weave`` and ``comm`` stats against the
engine's own counters, at tensor parallelism over every device JAX has
(four virtual CPU devices in the test), on the tiny Qwen3-shaped
configuration: a 128-token chunk (over the 64-token weave floor: it
weaves), a 32-token chunk (under it: one part) and a 2-row decode step
(2 tokens, which 4 devices do not divide: ``comm_norm`` falls back to
vanilla).

    python bench/tests/tp4_spans.py      # prints one JSON object
"""
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def run(config="tiny-qwen3-tp4"):
    import jax
    from bench.harness import engine_spans as ES
    from bench.harness import trace as TR
    from bench.harness.cell import weights_key
    from bench.harness.spec import load_module
    from repro.models.build import build_model
    from repro.runtime.engine import Engine
    from repro.runtime.requests import Request

    with open(os.path.join(HERE, "data", "configs", config + ".json")) as f:
        cfg = dict(json.load(f), name=config)
    ad = load_module(os.path.join(ROOT, "bench", "adapters",
                                  "dense_transformer.py"), "ad")
    devs = jax.devices()
    tp = len(devs)
    sv = dict(cfg["serving"], tp=tp)
    mesh = jax.make_mesh((1, tp), ("data", "model"), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(ad.model_config(cfg), ad.parallel_config(sv), tp=tp)
    params = ad.build_params(weights_key(7), cfg, api, mesh)
    eng = Engine(api, mesh, params, ad.scheduler_config(sv))
    # step 1: the 128-token prompt's one chunk; step 2: the 20-token
    # prompt's chunk (bucket 32) and one decode step for the first request,
    # which then has its 2 tokens; the second is done at its prefill
    reqs = [Request(rid=0, prompt=list(range(3, 131)), max_new_tokens=2),
            Request(rid=1, prompt=list(range(200, 220)), max_new_tokens=1)]
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        for r in reqs:
            eng.add_request(r)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    spans = [e.stats for e in ES.spans(TR.find_xplane(d))
             if e.name == "engine/dispatch"]
    snap = eng.metrics_snapshot()
    return {"spans": [[s["program"], int(s["weave"]), s["comm"]]
                      for s in spans],
            "forwards": eng.stats.forwards,
            "weave_forwards": eng.stats.weave_forwards,
            "weave_rate": eng.stats.weave_rate,
            "comm_vanilla": {k: v for k, v in snap.items()
                             if k.startswith("engine/comm_vanilla")},
            "outputs": [list(r.output) for r in reqs]}


if __name__ == "__main__":
    print(json.dumps(run(*sys.argv[1:2])))
