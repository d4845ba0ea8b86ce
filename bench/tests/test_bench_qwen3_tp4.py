"""The Qwen3 block at tensor parallelism 4, at a tiny width
(``data/configs/tiny-qwen3-tp4.json``: no q/k/v bias, per-head q/k norm,
untied head, 20 query heads over 4 KV heads, so each of the 4 shards
holds a GQA group of 5 as qwen3-14b does on a v5e 2x2), on 4 virtual CPU
devices:

* the program's prefill (a 128-token chunk that weaves, a 32-token chunk
  under the 64-token weave floor) then decode through the slot cache (2
  rows, which 4 devices do not divide: ``comm_norm`` runs vanilla) gives
  the plain float32 reference's logits;
* the fp8 control, the reference with fp8 matmuls, is outside the same
  tolerance;
* the ``engine/dispatch`` spans' ``weave`` and ``comm`` stats agree with
  ``EngineStats.weave_rate`` and the ``engine/comm_vanilla`` counter.
"""
import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "tiny-qwen3-tp4"
# float32 throughout: the program and the reference differ only in the
# order of their sums (the shards' partial sums meet in comm_norm's
# reduce-scatter), a few float32 ulps of the largest logit
TOL = 1e-4


def _four_devices(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, script), *args],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _config():
    with open(os.path.join(HERE, "data", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_config_is_the_qwen3_block_with_a_gqa_group_of_5_per_shard():
    cfg = _config()
    tp = cfg["serving"]["tp"]
    assert not cfg["qkv_bias"] and cfg["qk_norm"]
    assert not cfg["tie_word_embeddings"]
    assert cfg["num_key_value_heads"] % tp == 0
    per_shard = (cfg["num_attention_heads"] // tp,
                 cfg["num_key_value_heads"] // tp)
    assert per_shard == (5, 1)


def test_engine_matches_reference_on_four_devices():
    diff, scale = _four_devices("ref_vs_engine.py", CONFIG)
    assert scale > 1.0
    assert diff < TOL * scale


def test_fp8_control_fails_the_tolerance():
    """Rounding every matmul's operands to fp8 moves the logits far past
    the tolerance the program meets, so the comparison would catch a
    program computing in a lower precision than the configuration's."""
    import jax
    from bench.harness.cell import weights_key
    from bench.harness.spec import load_module
    ref = load_module(os.path.join(os.path.dirname(HERE), "reference",
                                   "dense_transformer.py"), "ref")
    cfg = _config()
    plain = jax.jit(lambda k: ref.model_weights(k, cfg))(weights_key(3))
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg["vocab_size"], 163).tolist()
    pos = [127, 159, 160, 161, 162]
    want = np.asarray(ref.forward_logits(plain, seq, pos, cfg))
    fp8 = np.asarray(ref.forward_logits(plain, seq, pos, cfg,
                                        quant=ref.fp8_quant))
    scale = np.abs(want).max()
    assert np.abs(fp8 - want).max() > 100 * TOL * scale


def test_dispatch_span_stats_agree_with_the_counters():
    got = _four_devices("tp4_spans.py", CONFIG)
    spans = got["spans"]
    assert spans == [["prefill_b1_s128", 2, "fused"],    # weaves
                     ["prefill_b1_s32", 1, "fused"],     # under the floor
                     ["decode_b2", 1, "vanilla"]]        # 2 rows, tp 4
    assert got["forwards"] == len(spans)
    woven = sum(1 for _, w, _ in spans if w == 2)
    assert got["weave_forwards"] == woven
    assert got["weave_rate"] == woven / len(spans)
    vanilla = sum(1 for _, _, c in spans if c == "vanilla")
    assert got["comm_vanilla"] == {"engine/comm_vanilla{site=decode}":
                                   float(vanilla)}
    assert [len(o) for o in got["outputs"]] == [2, 1]
