"""Drive one whole benchmark run at a tiny size on the CPU, past the
harness's look for a chip, optionally with a fault planted in the timed
path underneath:

``token``     one sampled token altered where the step produces it
``state``     prefill returns the KV cache unchanged (no chunk written)
``half``      decode leaves half of the batch's rows out
``exchange``  comm_norm skips the reduction across the chips

    python bench/tests/tiny_run.py <config> <traffic> <fault|none>

prints the run's result line.  ``run(...)`` does the same in-process.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = os.path.join(HERE, "data")


def bench(config: str, traffic: str) -> dict:
    w = f"{config}.{traffic}"
    return {"workloads": [{"name": w, "config": config, "traffic": traffic,
                           "chips": 1}],
            "end_to_end": [
                {"name": "ttft_p90_ms", "unit": "ms"},
                {"name": "itl_p95_ms", "unit": "ms"},
                {"name": "output_tok_s", "unit": "tokens/s"},
                {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def plant(fault: str):
    """Break the program underneath the harness; returns an undo."""
    import jax.numpy as jnp
    from repro.core import fused_collectives as fc
    from repro.models import transformer as T
    from repro.runtime import engine as E
    from repro.runtime import kv_cache as KC
    saved = []

    def patch(mod, name, new):
        saved.append((mod, name, getattr(mod, name)))
        setattr(mod, name, new)

    if fault == "token":
        orig = E.sample

        def sample(*a, **k):
            tok = orig(*a, **k)
            return tok.at[0].set((tok[0] + 1) % 7)
        patch(E, "sample", sample)
    elif fault == "state":
        patch(KC, "insert_chunk", lambda cache, *a, **k: cache)
    elif fault == "half":
        orig = T.decode_step

        def decode_step(params, tokens, cache, *, positions, **k):
            b = positions.shape[0]
            keep = (jnp.arange(b) % 2 == 0)[:, None]
            return orig(params, tokens, cache,
                        positions=jnp.where(keep, positions, -1), **k)
        patch(T, "decode_step", decode_step)
    elif fault == "exchange":
        orig = fc.comm_norm

        def comm_norm(x, residual, weight, *, ctx, reduce_input=True,
                      weight_post=None):
            return orig(x, residual, weight, ctx=ctx, reduce_input=False,
                        weight_post=weight_post)
        patch(fc, "comm_norm", comm_norm)
    elif fault != "none":
        raise ValueError(fault)

    def undo():
        for mod, name, old in reversed(saved):
            setattr(mod, name, old)
    return undo


def run(config: str, traffic: str, fault: str = "none", seed: int = 5,
        seconds: float = 1.5) -> dict:
    import jax
    from bench.harness.cell import run_cell
    from bench.harness.spec import Cell
    t0 = time.perf_counter()
    cell = Cell(bench(config, traffic), f"{config}.{traffic}",
                data_dir=DATA)
    undo = plant(fault)
    try:
        return run_cell(cell, seed, seconds, False, jax.devices(), t0,
                        log=lambda m: None)
    finally:
        undo()


if __name__ == "__main__":
    print(json.dumps(run(*sys.argv[1:4])))
