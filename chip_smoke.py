#!/usr/bin/env python3
"""Chip smoke: the serving engine's main path on a TPU, at full published
width and depth, with random weights from a seed.

    python chip_smoke.py             # qwen1.5-4b on one chip, (1, 1) mesh
    python chip_smoke.py --chips 4   # qwen3-14b at tp=4 on a (1, 4) mesh,
                                     # comm_mode fused, then vanilla

Each run builds the parameters and the slot KV cache sharded on the mesh,
serves a handful of greedy requests through ``Engine`` under the TokenWeave
comm path (prompts of 64..1536 tokens, so 1024-token prefill chunks cross
the 512-token weave floor), and checks every generated token against the
model's own full-context recompute of that request (``check_tokens``).
With ``--chips 4`` the fused and vanilla runs are also compared with each
other.  The lines it prints are smoke output: compile seconds, wall time
and peak memory of one run, not benchmark results.

It needs a TPU: without one it exits non-zero before doing anything else,
and it never falls back to the CPU.  The last line of standard output is
one JSON object naming the device, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig, ParallelConfig  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.layers import embedding as E  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.build import build_model  # noqa: E402
from repro.runtime.engine import Engine  # noqa: E402
from repro.runtime.requests import Request  # noqa: E402
from repro.runtime.scheduler import SchedulerConfig  # noqa: E402

# A generated token passes when its logit in the full-context recompute is
# within TOL of that recompute's largest logit.  The engine (chunked
# prefill through the KV cache, weave split, bf16 cache) and the recompute
# (one unsplit forward over the whole sequence) round differently in bf16,
# so exact greedy identity is unsteady at near-ties.  0.25 is about 8 bf16
# ulps at the |logit| of 4..8 the top entries have here (random weights put
# logits near N(0, 1) and the top one near 4.5), while a token read from a
# wrong cache position or a broken kernel lands near an ordinary vocabulary
# entry, several units below the top.
TOL = 0.25
# fused and vanilla differ only in how the tensor-parallel sums are ordered
# and rounded, so where their greedy tokens part, the two choices must be a
# near-tie in the recompute: their logits within 4 bf16 ulps at 4..8.
COMPARE_TOL = 0.125

PROMPT_LENS = (64, 320, 640, 1024, 1280, 1536)
NEW_TOKENS = (16, 20, 24, 28, 32, 16)


def require_tpu(n_chips: int):
    """The first ``n_chips`` TPU devices; exits non-zero without them."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform is "
                         f"{devs[0].platform!r}); this smoke runs only on "
                         f"a TPU")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, found "
                         f"{len(devs)}")
    return devs[:n_chips]


class CompileLog:
    """Backend compile seconds per jitted function, each marked with what
    the persistent compilation cache did for it (hit, written, or not
    consulted), from ``jax.monitoring`` events."""

    def __init__(self):
        self.records = []      # (function name, seconds, cache status)
        self._status = "uncached"
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._status = "cache hit"
        elif event == "/jax/compilation_cache/cache_misses":
            self._status = "cache miss, written"

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.records.append((kw.get("fun_name", "?"), secs,
                                 self._status))
            self._status = "uncached"

    def report(self, phase: str) -> None:
        """Print and clear the compiles since the last report: each one
        that took half a second or touched the persistent cache on its own
        line, the other small eager-op compiles summed on one."""
        small = []
        for name, secs, status in self.records:
            if secs >= 0.5 or status != "uncached":
                print(f"[{phase}] compile {name}: {secs:.2f} s ({status})")
            else:
                small.append(secs)
        if small:
            print(f"[{phase}] compile {len(small)} small programs: "
                  f"{sum(small):.2f} s")
        self.records = []


def make_requests(vocab: int, seed: int):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=[int(t) for t in
                                   rng.randint(0, vocab, size=n)],
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(PROMPT_LENS, NEW_TOKENS))]


def serve(api, mesh, params, scfg: SchedulerConfig, requests):
    """Serve ``requests`` through a fresh Engine; returns {rid: output}
    and the engine stats line.  The engine (and its cache) is released on
    return."""
    eng = Engine(api, mesh, params, scfg)
    for r in requests:
        eng.add_request(r)
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    st = eng.stats
    if len(done) != len(requests):
        raise RuntimeError(f"{len(done)}/{len(requests)} requests finished")
    for r in done:
        if len(r.output) != r.max_new_tokens:
            raise RuntimeError(f"rid={r.rid}: {len(r.output)} of "
                               f"{r.max_new_tokens} tokens")
        if not all(0 <= t < api.cfg.vocab_size for t in r.output):
            raise RuntimeError(f"rid={r.rid}: token id out of range")
    line = (f"requests {len(done)}, prefill tokens {st.prefill_tokens}, "
            f"decode tokens {st.decode_tokens}, forwards {st.forwards} "
            f"(weave {st.weave_forwards}), steps {st.steps}, wall "
            f"{dt:.2f} s including compiles")
    return {r.rid: list(r.output) for r in done}, line


def reference_fn(cfg: ModelConfig, tp: int, mesh, seq_len: int, n_idx: int):
    """Jitted full-context recompute: the plain composition (vanilla
    AllReduce, no weave) over one (1, seq_len) sequence, returning the
    logits at ``n_idx`` positions, gathered over the vocab shards."""
    api = build_model(cfg, ParallelConfig(comm_mode="vanilla",
                                          tokenweave=False, remat=False),
                      tp=tp)

    def reference_logits(params, tokens, idx):
        h, _, _ = T.forward(params, tokens, cfg=api.cfg, pcfg=api.pcfg,
                            return_kv=False)
        return E.lm_head_logits(params["embedding"], h[:, idx])

    g = jax.jit(jax.shard_map(
        reference_logits, mesh=mesh, in_specs=(api.specs(), P(), P()),
        out_specs=P(None, None, "model"), check_vma=False))
    return lambda params, toks, idx: g(params, jnp.asarray(toks),
                                       jnp.asarray(idx))


def check_tokens(ref, params, requests, outputs, *, vocab: int,
                 seq_len: int, n_idx: int, tol: float = TOL):
    """Teacher-forced check of every generated token: recompute each
    request's prompt + output[:-1] in one forward and require that every
    output token is within ``tol`` of that position's top logit, and that
    all recomputed logits are finite.  Returns {rid: each token's shortfall
    below its position's top logit}."""
    shortfalls = {}
    for r in requests:
        out = outputs[r.rid]
        seq = list(r.prompt) + out[:-1]
        toks = np.zeros((1, seq_len), np.int32)
        toks[0, :len(seq)] = seq
        pos = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(out))
        idx = np.zeros(n_idx, np.int32)
        idx[:len(pos)] = pos
        lg = np.asarray(ref(params, toks, idx), np.float32)[0, :len(out),
                                                            :vocab]
        if not np.isfinite(lg).all():
            raise RuntimeError(f"rid={r.rid}: non-finite recomputed logits")
        short = lg.max(axis=-1) - lg[np.arange(len(out)), out]
        if short.max() > tol:
            j = int(short.argmax())
            raise RuntimeError(
                f"rid={r.rid} token {j}: engine chose {out[j]} whose "
                f"recomputed logit is {short[j]:.3f} below the top "
                f"(tolerance {tol})")
        shortfalls[r.rid] = short
    return shortfalls


def compare_runs(a, b, short_a, short_b, tol: float = COMPARE_TOL):
    """Greedy tokens of two runs of the same requests: equal up to each
    request's first divergence, where the two choices must lie within
    ``tol`` of each other in the recompute.  Both runs share the prefix up
    to that token, so their shortfalls there are read off the same
    logits.  Returns (token-identical requests, {rid: (first divergence,
    logit gap)})."""
    gaps = {}
    for rid in a:
        j = next((i for i, (x, y) in enumerate(zip(a[rid], b[rid]))
                  if x != y), None)
        if j is None:
            continue
        gaps[rid] = (j, float(abs(short_a[rid][j] - short_b[rid][j])))
        if gaps[rid][1] > tol:
            raise RuntimeError(
                f"rid={rid} token {j}: the runs chose {a[rid][j]} and "
                f"{b[rid][j]}, {gaps[rid][1]:.3f} apart in the recompute "
                f"(tolerance {tol})")
    return len(a) - len(gaps), gaps


def print_peak_bytes(devs) -> None:
    for d in devs:
        ms = d.memory_stats() or {}
        print(f"device {d.id} peak_bytes_in_use "
              f"{ms.get('peak_bytes_in_use')} bytes_limit "
              f"{ms.get('bytes_limit')}")


def run(cfg: ModelConfig, devs, modes, *, seed: int, max_batch: int,
        max_len: int, chunk: int, log: CompileLog | None = None):
    """Build, serve under each comm mode in ``modes`` and check; returns
    {mode: outputs}."""
    tp = len(devs)
    mesh = jax.make_mesh((1, tp), ("data", "model"), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads} kv, "
          f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}")
    print(f"mesh {dict(mesh.shape)} on {[d.id for d in devs]}")
    scfg = SchedulerConfig(max_batch=max_batch, max_len=max_len,
                           chunk_tokens=chunk, prefill_bucket=chunk,
                           paged=False, packed=False)
    print(f"slot KV cache: max_batch {max_batch}, max_len {max_len}; "
          f"prefill chunk {chunk}")

    def apis(mode):
        return build_model(cfg, ParallelConfig(comm_mode=mode,
                                               tokenweave=True,
                                               remat=False), tp=tp)

    t0 = time.perf_counter()
    params = apis(modes[0]).init(jax.random.PRNGKey(seed), mesh)
    jax.block_until_ready(params)
    leaves = jax.tree.leaves(params)
    print(f"params {sum(x.size for x in leaves)} "
          f"({sum(x.nbytes for x in leaves) / 1e9:.2f} GB) built sharded "
          f"in {time.perf_counter() - t0:.2f} s")
    if log is not None:
        log.report("init")

    reqs = make_requests(cfg.vocab_size, seed)
    outputs = {}
    for mode in modes:
        outputs[mode], line = serve(apis(mode), mesh, params, scfg,
                                    make_requests(cfg.vocab_size, seed))
        print(f"[serve {mode}] {line}")
        if log is not None:
            log.report(f"serve {mode}")

    n_idx = max(NEW_TOKENS)
    ref = reference_fn(cfg, tp, mesh, max_len, n_idx)
    shortfalls = {}
    for mode in modes:
        shortfalls[mode] = short = check_tokens(
            ref, params, reqs, outputs[mode], vocab=cfg.vocab_size,
            seq_len=max_len, n_idx=n_idx)
        s = np.concatenate(list(short.values()))
        print(f"[check {mode}] {s.size} tokens within {TOL} of the "
              f"recompute's top logit; {int((s == 0).sum())} at the top; "
              f"largest shortfall {s.max():.4f}")
    if log is not None:
        log.report("reference")
    if len(modes) == 2:
        same, gaps = compare_runs(*(outputs[m] for m in modes),
                                  *(shortfalls[m] for m in modes))
        parted = "; ".join(f"rid {rid} at token {j}, logit gap {g:.4f}"
                           for rid, (j, g) in gaps.items())
        print(f"[compare {modes[0]} vs {modes[1]}] {same}/{len(reqs)} "
              f"requests token-identical; the others part at a near-tie "
              f"within {COMPARE_TOL}: {parted or 'none'}")
    print_peak_bytes(devs)
    return outputs


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    devs = require_tpu(args.chips)
    cache_dir = use_compile_cache()
    print(f"compile cache {cache_dir}")
    log = CompileLog()
    name, modes = (("qwen1.5-4b", ("fused",)) if args.chips == 1
                   else ("qwen3-14b", ("fused", "vanilla")))
    run(get_config(name), devs, modes, seed=args.seed, max_batch=4,
        max_len=2048, chunk=1024, log=log)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
