"""Quickstart: build a tiny TokenWeave model, train it for a handful of
steps on synthetic data, then greedily generate through the serving engine.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models.build import build_model
from repro.runtime.engine import Engine
from repro.runtime.requests import Request
from repro.runtime.scheduler import SchedulerConfig
from repro.training.data import SyntheticLM
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import make_train_step


def main():
    use_compile_cache()
    cfg = ModelConfig(name="quickstart", family="dense", num_layers=2,
                      d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                      d_ff=256, vocab_size=256, dtype="float32")
    # TokenWeave on: fused AllReduce-RMSNorm + two-split weave
    pcfg = ParallelConfig(tokenweave=True, comm_mode="fused", remat=False,
                          split_unit=32, tokenweave_min_tokens=64)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(cfg, pcfg, tp=1)

    data = SyntheticLM(vocab=cfg.vocab_size, seq_len=128, global_batch=4)
    batch0 = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    step, init = make_train_step(api, mesh, batch0,
                                 AdamWConfig(lr=3e-3, warmup_steps=10),
                                 dp_size=1)
    params, opt = init(jax.random.PRNGKey(0))
    print("training a 2-layer model on synthetic Markov data...")
    for i in range(30):
        b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        params, opt, m = step(params, opt, b)
        if i % 10 == 0 or i == 29:
            print(f"  step {i:3d}  loss {float(m['loss']):.4f}")

    print("serving with continuous batching + chunked prefill...")
    eng = Engine(api, mesh, params,
                 SchedulerConfig(max_batch=2, chunk_tokens=64, max_len=256,
                                 prefill_bucket=32))
    prompt = data.batch(999)["tokens"][0, :40].tolist()
    eng.add_request(Request(rid=0, prompt=prompt, max_new_tokens=16))
    done = eng.run()
    print(f"  prompt tail: {prompt[-8:]}")
    print(f"  generated : {done[0].output}")
    print("done — same schedule that runs on the 512-chip mesh "
          "(see launch/dryrun.py).")


if __name__ == "__main__":
    main()
