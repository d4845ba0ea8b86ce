"""The plain float32 reference agrees with the program's prefill (a chunk
that weaves and one that does not) then decode through the slot cache, at
a tiny width, on one CPU device and on four virtual ones (tensor
parallel)."""
import json
import os
import subprocess
import sys

from bench.tests import ref_vs_engine

HERE = os.path.dirname(os.path.abspath(__file__))


def test_one_device():
    diff, scale = ref_vs_engine.max_diff()
    assert scale > 1.0
    assert diff < 1e-4 * scale


def test_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable,
                        os.path.join(HERE, "ref_vs_engine.py")], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    diff, scale = json.loads(p.stdout.strip().splitlines()[-1])
    assert scale > 1.0
    assert diff < 1e-4 * scale


def test_layer_at_a_time_weights_match_the_whole_model():
    import jax
    import numpy as np
    from bench.harness.spec import load_module
    ref = load_module(os.path.join(os.path.dirname(HERE), "reference",
                                   "dense_transformer.py"), "ref")
    with open(os.path.join(HERE, "data", "configs", "tiny-f32.json")) as f:
        cfg = json.load(f)
    cfg["torch_dtype"] = "bfloat16"
    key = jax.random.PRNGKey(9)
    whole = jax.jit(lambda k: ref.model_weights(k, cfg))(key)
    for layer in range(cfg["num_hidden_layers"]):
        one = jax.jit(lambda k: ref.layer_weights(k, cfg, layer))(key)
        for name, a in one.items():
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(whole["layers"][name]
                                                     [layer], np.float32))
