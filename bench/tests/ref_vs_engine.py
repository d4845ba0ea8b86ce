"""The plain reference's logits against the program's prefill-then-decode
logits through the slot cache, at a tiny width: a 128-token chunk (which
weaves), a 32-token chunk (which does not), then three decode steps, on as
many devices as JAX has (tensor parallel over all of them).

    python bench/tests/ref_vs_engine.py      # prints the largest |diff|
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def max_diff(config="tiny-f32"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from bench.harness.cell import weights_key
    from bench.harness.spec import load_module
    from repro.models.build import build_model, named_shardings
    from repro.runtime import kv_cache as KC

    with open(os.path.join(HERE, "data", "configs", config + ".json")) as f:
        cfg = dict(json.load(f), name=config)
    bench = os.path.join(ROOT, "bench")
    ad = load_module(os.path.join(bench, "adapters", "dense_transformer.py"),
                     "ad")
    ref = load_module(os.path.join(bench, "reference",
                                   "dense_transformer.py"), "ref")
    devs = jax.devices()
    tp = len(devs)
    mesh = jax.make_mesh((1, tp), ("data", "model"), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sv = dict(cfg["serving"], tp=tp)
    api = build_model(ad.model_config(cfg), ad.parallel_config(sv), tp=tp)
    key = weights_key(3)
    params = ad.build_params(key, cfg, api, mesh)
    cache = jax.jit(lambda: api.init_cache(2, 256), out_shardings=(
        named_shardings(mesh, api.cache_specs())))()
    ps, cs = api.specs(), api.cache_specs()
    vs = P(None, None, "model")

    def pf(params, cache, tokens, positions, offsets, last_idx):
        slots = jnp.zeros(1, jnp.int32)
        rows = KC.gather_slots(cache, slots)
        lg, kv, _ = api.mod.prefill(params, tokens, rows, cfg=api.cfg,
                                    pcfg=api.pcfg, positions=positions,
                                    last_idx=last_idx)
        return lg, KC.insert_chunk(cache, kv, offsets, slots)

    def dc(params, cache, tokens, positions):
        return api.mod.decode_step(params, tokens, cache, cfg=api.cfg,
                                   pcfg=api.pcfg, positions=positions)

    pf = jax.jit(jax.shard_map(pf, mesh=mesh, in_specs=(ps, cs) + (P(),) * 4,
                               out_specs=(vs, cs), check_vma=False))
    dc = jax.jit(jax.shard_map(dc, mesh=mesh, in_specs=(ps, cs, P(), P()),
                               out_specs=(vs, cs), check_vma=False))
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg["vocab_size"], 163).tolist()
    got, pos = [], []
    at = 0
    for n in (128, 32):
        toks = np.asarray([seq[at:at + n]], np.int32)
        p = np.arange(at, at + n, dtype=np.int32)[None]
        lg, cache = pf(params, cache, toks, p, np.asarray([at], np.int32),
                       np.asarray([n - 1], np.int32))
        got.append(np.asarray(lg)[0, 0])
        at += n
        pos.append(at - 1)
    for _ in range(3):
        toks = np.asarray([[seq[at]], [0]], np.int32)
        p = np.asarray([[at], [-1]], np.int32)
        lg, cache = dc(params, cache, toks, p)
        got.append(np.asarray(lg)[0, 0])
        pos.append(at)
        at += 1
    plain = jax.jit(lambda k: ref.model_weights(k, cfg))(key)
    want = np.asarray(ref.forward_logits(plain, seq[:at], pos, cfg))
    got = np.stack(got)[:, :cfg["vocab_size"]]
    return float(np.abs(got - want).max()), float(np.abs(want).max())


if __name__ == "__main__":
    print(json.dumps(max_diff(*sys.argv[1:2])))
