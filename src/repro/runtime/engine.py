"""Continuous-batching inference engine (single-host execution; the
multi-pod serve path is exercised via launch/dryrun.py's serve_step).

Supports the transformer families (dense/moe/vlm) and ssm; hybrid and encdec
are served via direct serve-step calls (see launch/dryrun.py) — documented
in DESIGN.md §6.

Per iteration: one decode step over ALL cache slots (inactive slots are
masked via position -1) and/or one rectangular prefill chunk for a group of
admitted requests (Sarathi-style chunked prefill, lengths bucketed to bound
recompilation). TokenWeave activates inside the model whenever the batch
crosses ``tokenweave_min_tokens``.

Two KV-cache backends (SchedulerConfig.paged):

* legacy slots — fixed (L, max_batch, max_len) rows per request; slots are
  invalidated on finish so stale positions never leak into a reused slot.
* paged (runtime/paging.py) — block pool + per-request block tables with
  prefix-cache sharing, LRU eviction, copy-on-write, and recompute
  preemption (DECODE -> WAITING) when the pool runs dry.  Admission and
  chunk accounting charge only prefix-MISS tokens, so the TokenWeave
  min-token threshold sees true compute size.  Transformer families only
  (ssm state is not paged), single host (the shared pool cannot shard over
  the data axis) — DESIGN.md §7.

Speculative decoding (``SchedulerConfig.spec_gamma > 0``, runtime/spec.py):
decode iterations become gamma+1-token verify batches — a pluggable draft
proposes, ONE multi-token forward scores the window, rejection sampling
commits the longest accepted prefix + 1 token, and rejected KV is rolled
back by block-table truncation (paged) or hidden by the legacy slots'
visibility rule (old-cache keys only below each row's first window
position) — DESIGN.md §8.  Greedy
spec output is token-identical to plain greedy decoding.

Packed hybrid batching (``SchedulerConfig.packed``, DESIGN.md §6): the
two dispatches above collapse into ONE forward per iteration — prefill
segments, decode slots, and verify windows ride a single packed token
axis through ``ModelApi.packed_step``, so the TokenWeave threshold sees
the true combined iteration size (mixed iterations whose halves are each
sub-threshold now weave).  Token-identical to the two-dispatch engine
under greedy sampling; transformer families only, and sliding-window
models need the paged backend (mask-enforced windows).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.splitting import pad_to_multiple
from repro.models import transformer as TRX
from repro.models.build import ModelApi, named_shardings
from repro.obs.attribution import Attributor, WeaveAttribution
from repro.obs.metrics import MetricsRegistry, percentile as _percentile
from repro.obs.profiler import WallClockProfiler
from repro.obs.spans import span
from repro.obs.trace import TraceRecorder
from repro.runtime import kv_cache as KC
from repro.runtime import paging as PG
from repro.runtime import spec as SP
from repro.runtime.paging import BlockManager
from repro.runtime.requests import Request, State, reset_for_requeue
from repro.runtime.sampler import sample
from repro.runtime.scheduler import (PackedPlan, Scheduler, SchedulerConfig)


class LatencyStats:
    """Per-request serving latencies in VIRTUAL time (runtime/server.py's
    deterministic clock, DESIGN.md §10) plus SLO attainment.

    ``slo_total`` counts every request whose outcome the service is
    accountable for: completions and deadline expiries.  User-initiated
    cancellations are excluded — the client walked away, the server did
    not fail it.  ``goodput`` is the SLO-attainment fraction the paper's
    serving sections report (requests served within their deadline /
    accountable requests).

    Thin view over ``latency/*`` instruments in a MetricsRegistry
    (DESIGN.md §12): every mutation lands in the registry, so a
    ``snapshot()`` and this object can never disagree.  The list/int
    attributes the old dataclass exposed are preserved as live views."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = (registry if registry is not None
                         else MetricsRegistry())
        self._ttft = self.registry.histogram("latency/ttft")
        self._tpot = self.registry.histogram("latency/tpot")
        self._e2e = self.registry.histogram("latency/e2e")
        self._slo_total = self.registry.counter("latency/slo_total")
        self._slo_met = self.registry.counter("latency/slo_met")

    @property
    def ttft(self) -> List[float]:
        return self._ttft.values

    @property
    def tpot(self) -> List[float]:
        return self._tpot.values

    @property
    def e2e(self) -> List[float]:
        return self._e2e.values

    @property
    def slo_total(self) -> int:
        return self._slo_total.value

    @property
    def slo_met(self) -> int:
        return self._slo_met.value

    def record(self, r) -> None:
        if r.finish_reason != "cancelled":
            self._slo_total.inc()
            self._slo_met.inc(int(r.slo_ok))
        if r.ttft is not None:
            self._ttft.observe(r.ttft)
        if r.tpot is not None:
            self._tpot.observe(r.tpot)
        if r.e2e_latency is not None:
            self._e2e.observe(r.e2e_latency)

    @property
    def goodput(self) -> float:
        return self.slo_met / self.slo_total if self.slo_total else 0.0

    def percentile(self, metric: str, q: float) -> float:
        return _percentile(getattr(self, metric), q)

    def summary(self) -> Dict[str, float]:
        out: Dict[str, float] = {"goodput": self.goodput,
                                 "requests": float(self.slo_total)}
        for m in ("ttft", "tpot", "e2e"):
            for q in (0.5, 0.9, 0.99):
                out[f"{m}_p{int(q * 100)}"] = self.percentile(m, q)
        return out


class SpecStatsView:
    """Registry view with the ``SpecStats`` API (runtime/spec.py,
    DESIGN.md §8) over ``spec/*`` counters."""

    def __init__(self, registry: MetricsRegistry):
        self._verify_steps = registry.counter("spec/verify_steps")
        self._draft_proposed = registry.counter("spec/draft_proposed")
        self._draft_accepted = registry.counter("spec/draft_accepted")
        self._emitted = registry.counter("spec/emitted")

    @property
    def verify_steps(self) -> int:
        return self._verify_steps.value

    @property
    def draft_proposed(self) -> int:
        return self._draft_proposed.value

    @property
    def draft_accepted(self) -> int:
        return self._draft_accepted.value

    @property
    def emitted(self) -> int:
        return self._emitted.value

    @property
    def acceptance_rate(self) -> float:
        return (self.draft_accepted / self.draft_proposed
                if self.draft_proposed else 0.0)

    @property
    def tokens_per_step(self) -> float:
        """Mean committed tokens per verified sequence per engine step
        (plain decode == 1.0 by definition)."""
        seqs = self.emitted - self.draft_accepted
        return self.emitted / seqs if seqs else 0.0


@dataclasses.dataclass
class Handoff:
    """A request parked for disaggregated prefill->decode migration
    (runtime/cluster.py, DESIGN.md §11): the request object plus the KV it
    computed — ``n_tokens`` committed context positions whose block payload
    was extracted before the exporter released its references."""
    req: Request
    n_tokens: int
    payload: dict


class EngineStats:
    """Thin read view over the engine's MetricsRegistry (DESIGN.md §12).

    Every counter the old dataclass carried is now an ``engine/*``
    instrument mutated by the engine through the registry; the attribute
    names here are unchanged, read-only, and always equal to what
    ``Engine.metrics_snapshot()`` exports."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = (registry if registry is not None
                         else MetricsRegistry())
        r = self.registry
        self._steps = r.counter("engine/steps")
        self._prefill_tokens = r.counter("engine/prefill_tokens")
        self._decode_tokens = r.counter("engine/decode_tokens")
        self._completed = r.counter("engine/completed")
        self._cancelled = r.counter("engine/cancelled")
        self._expired = r.counter("engine/expired")
        self._forwards = r.counter("engine/forwards")
        self._weave_forwards = r.counter("engine/weave_forwards")
        self._forward_tokens = r.counter("engine/forward_tokens")
        self._max_forward_tokens = r.gauge("engine/max_forward_tokens")
        self.spec = SpecStatsView(r)
        self.latency = LatencyStats(r)

    @property
    def steps(self) -> int:
        return self._steps.value

    @property
    def prefill_tokens(self) -> int:
        return self._prefill_tokens.value

    @property
    def decode_tokens(self) -> int:
        return self._decode_tokens.value

    @property
    def completed(self) -> int:
        return self._completed.value

    @property
    def cancelled(self) -> int:
        """User-initiated aborts (online serving)."""
        return self._cancelled.value

    @property
    def expired(self) -> int:
        """Deadline-expiry aborts (online serving)."""
        return self._expired.value

    @property
    def forwards(self) -> int:
        """Model dispatches (2/iter two-dispatch peak)."""
        return self._forwards.value

    @property
    def weave_forwards(self) -> int:
        """Dispatches whose static shape fires the weave."""
        return self._weave_forwards.value

    @property
    def forward_tokens(self) -> int:
        """Real (non-padding) tokens across dispatches."""
        return self._forward_tokens.value

    @property
    def max_forward_tokens(self) -> int:
        """Largest REAL token count in one dispatch."""
        return int(self._max_forward_tokens.value)

    @property
    def weave_rate(self) -> float:
        """Fraction of model dispatches that ran the TokenWeave split —
        the §6 packed-batching payoff metric: mixed iterations that
        two-dispatch judges as two sub-threshold halves count as weave
        misses there and (usually) one weave hit when packed."""
        return self.weave_forwards / self.forwards if self.forwards else 0.0

    @property
    def tokens_per_forward(self) -> float:
        return self.forward_tokens / self.forwards if self.forwards else 0.0


class Engine:
    def __init__(self, api: ModelApi, mesh, params, scfg: SchedulerConfig,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 draft: SP.DraftProposer | None = None, seed: int = 0,
                 jit_cache: Dict | None = None,
                 obs: TraceRecorder | None = None,
                 obs_track: str = "engine",
                 profiler: "WallClockProfiler | None" = None):
        if scfg.plan_path:
            # tuned overlap-plan cache (core/policy.py, DESIGN.md §14):
            # install the policy on the model's ParallelConfig BEFORE any
            # jit cache or attributor is built, so every consumer —
            # forward dispatch, packed planner, attribution — sees it
            from repro.core.policy import load_policy
            api = dataclasses.replace(
                api, pcfg=dataclasses.replace(
                    api.pcfg, overlap_policy=load_policy(scfg.plan_path)))
        self.api = api
        self.mesh = mesh
        self.params = params
        self.scfg = scfg
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.metrics = MetricsRegistry()
        self.stats = EngineStats(self.metrics)
        self.metrics.gauge("engine/plan_id").set(
            getattr(api.pcfg.overlap_policy, "plan_id", 0)
            if api.pcfg.overlap_policy is not None else 0)
        # tracing (DESIGN.md §12): obs is None by default — every obs code
        # path is behind an ``is not None`` guard, so tracing off costs
        # nothing and (invariant) tracing on changes no tokens or steps
        self.obs = obs
        self.obs_track = obs_track
        # measured time (DESIGN.md §13): like obs, the profiler is None by
        # default and every hook is behind an ``is not None`` guard; when
        # set it only observes (each dispatch's time from the same two
        # stamps as the engine/dispatch and engine/sync spans), so profiled
        # runs are token- and step-identical to unprofiled ones
        self.profiler = profiler
        self._dispatch_s = 0.0      # the last dispatch + sync, wall seconds
        if profiler is not None:
            profiler.attach(self.metrics, trace=obs, track=obs_track)
        self._attributor = (Attributor(api.cfg, api.pcfg, api.tp)
                            if obs is not None or profiler is not None
                            else None)
        self._step_forwards: List[WeaveAttribution] = []
        self._step_count = 0
        # jit_cache may be SHARED across engines built with the same
        # (api, mesh, scfg shapes, sampling params) — e.g. the differential
        # harness replaying many short traces — to skip recompilation
        self._jit_cache: Dict = {} if jit_cache is None else jit_cache
        self._programs_run: set = set()   # step programs dispatched here
        self._pspec = api.specs()
        self._is_ssm = api.cfg.family == "ssm"
        self.paged = bool(scfg.paged)

        self.packed = bool(scfg.packed)
        if self.packed:
            if self._is_ssm:
                raise ValueError("packed hybrid batching scatters per-token "
                                 "KV; ssm state has no token axis — use the "
                                 "two-dispatch path")
            if not hasattr(api.mod, "packed_step"):
                raise ValueError(
                    f"packed batching needs a packed hybrid step; family "
                    f"{api.cfg.family!r} has none")
            if api.pcfg.seq_shard_kv:
                raise ValueError("packed steps gather full cache rows "
                                 "locally; disable seq_shard_kv")
            if not scfg.paged and api.cfg.sliding_window:
                raise ValueError(
                    "packed scatter into a sliding-window ring buffer could "
                    "evict keys earlier packed queries still need; use the "
                    "paged backend (full-length storage, mask-enforced "
                    "windows)")

        self.spec_gamma = int(scfg.spec_gamma)
        self.draft = None
        if self.spec_gamma:
            if self._is_ssm:
                raise ValueError("speculative decoding rolls back KV "
                                 "positions; ssm state has no token axis")
            if not hasattr(api.mod, "verify_step"):
                raise ValueError(
                    f"speculative decoding needs a multi-token verify path; "
                    f"family {api.cfg.family!r} has none")
            if api.pcfg.seq_shard_kv:
                raise ValueError("speculative verify writes full KV rows "
                                 "locally; disable seq_shard_kv")
            if not self.paged and api.cfg.sliding_window:
                raise ValueError(
                    "legacy-slot sliding-window ring buffers cannot hold a "
                    "multi-token verify window (a later write could evict a "
                    "key an earlier query needs); use the paged backend")
            self.draft = draft if draft is not None else SP.make_draft(
                "ngram", self.spec_gamma, ngram=scfg.spec_ngram)
            if self.draft.gamma < self.spec_gamma:
                raise ValueError(
                    f"draft gamma {self.draft.gamma} < scheduler "
                    f"spec_gamma {self.spec_gamma}")
        self._rng_key = jax.random.PRNGKey(seed)

        if self.paged:
            if self._is_ssm:
                raise ValueError("paged KV cache requires attention layers; "
                                 "ssm state caches stay on the slot path")
            self.block_mgr = BlockManager(
                scfg.effective_num_blocks, scfg.block_size,
                scfg.max_blocks_per_req,
                prefix_caching=scfg.prefix_caching)

            def make_cache():
                return PG.init_paged_cache(scfg.effective_num_blocks,
                                           scfg.block_size, api.cfg, api.tp,
                                           api.pcfg)
            cspec = PG.paged_cache_specs(api.cfg, api.pcfg)
        else:
            self.block_mgr = None

            def make_cache():
                return api.init_cache(scfg.max_batch, scfg.max_len)
            cspec = api.cache_specs()
        self.sched = Scheduler(
            scfg, block_mgr=self.block_mgr,
            on_admit=self._obs_admit if obs is not None else None,
            # the packed planner consumes the SAME per-site overlap plan
            # as the forward dispatch (DESIGN.md §14): a late-binding
            # closure over self.api, so install_overlap_policy() swaps
            # the planner's view too
            overlap_hint=self._overlap_hint if self.packed else None)
        # disaggregated serving (DESIGN.md §11): requests parked by
        # ``_park_for_handoff`` wait here for the cluster to migrate them
        self.handoff_ready: List[Handoff] = []
        # built on the mesh in its final sharding: no device ever holds
        # the whole cache on its way to being placed
        self.cache = jax.jit(make_cache, out_shardings=named_shardings(
            mesh, cspec))()
        self._cspec = cspec

    def _next_key(self):
        """Per-dispatch PRNG key: one deterministic stream (seeded at
        construction) feeds prefill, decode, and verify sampling alike, so
        stochastic runs are reproducible for a fixed request order."""
        with span("engine/rng"):
            self._rng_key, k = jax.random.split(self._rng_key)
        return k

    def _inputs(self, *host) -> list:
        """Device copies of a dispatch's host inputs, then its PRNG key."""
        return [jnp.asarray(a) for a in host] + [self._next_key()]

    def _dispatch(self, fn, args, info: TRX.WeaveInfo, comm: str) -> list:
        """Run one jitted step on (params, cache, *args) and fetch its
        outputs to the host; the returned cache replaces ``self.cache``.
        ``engine/dispatch`` is the call until it returns (a compile shows
        there, on the program's first dispatch), ``engine/sync`` the host's
        wait for the outputs.  The profiler's time for the dispatch runs
        from the first span's start to the second's end.  The dispatch span
        carries the token parts the program runs in (``info``, its weave
        decision) and its ``comm_norm`` mode (``comm``)."""
        name = fn.__name__
        first = name not in self._programs_run
        self._programs_run.add(name)
        t0 = time.perf_counter()
        with span("engine/dispatch", program=name, first=int(first),
                  weave=2 if info.weave else 1, comm=comm):
            *outs, self.cache = fn(self.params, self.cache, *args)
            args.clear()        # free the device inputs inside the span
        with span("engine/sync"):
            outs = [np.asarray(o) for o in outs]
        self._dispatch_s = time.perf_counter() - t0
        return outs

    # ------------------------------------------------------------------
    # per-site overlap policy (core/policy.py, DESIGN.md §14)
    # ------------------------------------------------------------------
    def _overlap_hint(self, tokens: int) -> TRX.WeaveInfo:
        """The packed planner's view of the active overlap policy: the
        same ``weave_decision_info`` the packed forward dispatch will run
        for ``tokens``, stamped on ``PackedPlan.overlap`` — one plan
        format everywhere."""
        return TRX.weave_decision_info(
            1, tokens, tp=self.api.tp, pcfg=self.api.pcfg, packed=True,
            family=self.api.cfg.family)

    def install_overlap_policy(self, policy) -> None:
        """Swap the active ``OverlapPolicy`` (e.g. a freshly loaded tuned
        plan).  The policy lives on the model's ``ParallelConfig``, which
        is baked into jitted step closures and the attributor — so both
        are rebuilt; in-flight requests and caches are untouched (the
        policy only picks split points, never shapes semantics)."""
        self.api = dataclasses.replace(
            self.api, pcfg=dataclasses.replace(self.api.pcfg,
                                               overlap_policy=policy))
        self._jit_cache = {}
        self._programs_run = set()
        if self._attributor is not None:
            self._attributor = Attributor(self.api.cfg, self.api.pcfg,
                                          self.api.tp)
        self.metrics.gauge("engine/plan_id").set(
            getattr(policy, "plan_id", 0) if policy is not None else 0)

    # ------------------------------------------------------------------
    # jitted step functions
    # ------------------------------------------------------------------
    def _jit_step(self, fn, name: str, n_plain: int, n_out: int):
        """shard_map + jit one step body: params and cache in their specs,
        ``n_plain`` replicated host inputs, ``n_out`` replicated outputs
        followed by the (donated) cache.  ``name`` is the step's stable name
        in compile logs and device traces."""
        fn.__name__ = name
        sm = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(self._pspec, self._cspec) + (P(),) * n_plain,
            out_specs=(P(),) * n_out + (self._cspec,), check_vma=False)
        return jax.jit(sm, donate_argnums=(1,))

    def _prefill_fn(self, b_sel: int, chunk: int):
        key = ("prefill", b_sel, chunk)
        if key in self._jit_cache:
            return self._jit_cache[key]
        api = self.api

        def fn(params, cache, tokens, positions, slot_ids, offsets,
               last_idx, rng):
            if self._is_ssm:
                rows = jax.tree.map(lambda c: c[:, slot_ids], cache)
                # fresh requests (offset 0) start from zero state
                fresh = offsets == 0

                def zero_fresh(c):
                    m = fresh.reshape((1, -1) + (1,) * (c.ndim - 2))
                    return jnp.where(m, jnp.zeros_like(c), c)
                rows = jax.tree.map(zero_fresh, rows)
                logits, new_rows, _ = api.mod.prefill(
                    params, tokens, rows, cfg=api.cfg, pcfg=api.pcfg,
                    positions=positions, last_idx=last_idx)
                new_cache = jax.tree.map(
                    lambda c, r: c.at[:, slot_ids].set(r), cache, new_rows)
                # SSM: logits of last *valid* token need a re-run on unpadded
                # length; we instead require ssm chunks to be unpadded
                tok = sample(logits, vocab_size=api.cfg.vocab_size,
                             tp_axis=api.pcfg.tp_axis,
                             temperature=self.temperature,
                             top_k=self.top_k, top_p=self.top_p, key=rng)
                return tok, new_cache
            rows = KC.gather_slots(cache, slot_ids)
            logits, kv, _ = api.mod.prefill(
                params, tokens, rows, cfg=api.cfg, pcfg=api.pcfg,
                positions=positions, last_idx=last_idx)
            new_cache = KC.insert_chunk(cache, kv, offsets, slot_ids,
                                        max_len=self.scfg.max_len)
            tok = sample(logits, vocab_size=api.cfg.vocab_size,
                         tp_axis=api.pcfg.tp_axis,
                         temperature=self.temperature,
                         top_k=self.top_k, top_p=self.top_p, key=rng)
            return tok, new_cache

        jfn = self._jit_step(fn, f"prefill_b{b_sel}_s{chunk}", 6, 1)
        self._jit_cache[key] = jfn
        return jfn

    def _paged_prefill_fn(self, b_sel: int, chunk: int):
        key = ("pprefill", b_sel, chunk)
        if key in self._jit_cache:
            return self._jit_cache[key]
        api = self.api

        def fn(params, pool, tokens, positions, block_tables, last_idx,
               rng):
            # rectangular context view through the block-table indirection;
            # the model's prefill path is backend-agnostic (rows look
            # exactly like gathered slot rows)
            rows = PG.gather_block_rows(pool, block_tables)
            logits, kv, _ = api.mod.prefill(
                params, tokens, rows, cfg=api.cfg, pcfg=api.pcfg,
                positions=positions, last_idx=last_idx)
            new_pool = PG.insert_chunk_paged(pool, kv, block_tables)
            tok = sample(logits, vocab_size=api.cfg.vocab_size,
                         tp_axis=api.pcfg.tp_axis,
                         temperature=self.temperature,
                         top_k=self.top_k, top_p=self.top_p, key=rng)
            return tok, new_pool

        jfn = self._jit_step(fn, f"paged_prefill_b{b_sel}_s{chunk}", 5, 1)
        self._jit_cache[key] = jfn
        return jfn

    def _decode_fn(self):
        key = ("decode",)
        if key in self._jit_cache:
            return self._jit_cache[key]
        api = self.api

        def fn(params, cache, tokens, positions, rng):
            logits, new_cache = api.mod.decode_step(
                params, tokens, cache, cfg=api.cfg, pcfg=api.pcfg,
                positions=positions)
            tok = sample(logits, vocab_size=api.cfg.vocab_size,
                         tp_axis=api.pcfg.tp_axis,
                         temperature=self.temperature,
                         top_k=self.top_k, top_p=self.top_p, key=rng)
            return tok, new_cache

        jfn = self._jit_step(fn, f"decode_b{self.scfg.max_batch}", 3, 1)
        self._jit_cache[key] = jfn
        return jfn

    def _paged_decode_fn(self):
        key = ("pdecode",)
        if key in self._jit_cache:
            return self._jit_cache[key]
        api = self.api

        def fn(params, pool, tokens, positions, block_tables, rng):
            logits, new_pool = api.mod.decode_step(
                params, tokens, pool, cfg=api.cfg, pcfg=api.pcfg,
                positions=positions, block_tables=block_tables)
            tok = sample(logits, vocab_size=api.cfg.vocab_size,
                         tp_axis=api.pcfg.tp_axis,
                         temperature=self.temperature,
                         top_k=self.top_k, top_p=self.top_p, key=rng)
            return tok, new_pool

        jfn = self._jit_step(fn, f"paged_decode_b{self.scfg.max_batch}", 4,
                             1)
        self._jit_cache[key] = jfn
        return jfn

    def _verify_fn(self, s_v: int):
        """Jitted speculative verify over the legacy slot cache: one
        multi-token decode forward + on-device rejection sampling."""
        key = ("verify", s_v)
        if key in self._jit_cache:
            return self._jit_cache[key]
        api = self.api

        def fn(params, cache, tokens, positions, draft, rng):
            logits, new_cache = api.verify_step(params, tokens, cache,
                                                positions)
            n_acc, emit = SP.verify_tokens(
                logits, draft, rng, vocab_size=api.cfg.vocab_size,
                tp_axis=api.pcfg.tp_axis, temperature=self.temperature,
                top_k=self.top_k, top_p=self.top_p)
            return n_acc, emit, new_cache

        jfn = self._jit_step(fn, f"verify_s{s_v}", 4, 2)
        self._jit_cache[key] = jfn
        return jfn

    def _paged_verify_fn(self, s_v: int):
        key = ("pverify", s_v)
        if key in self._jit_cache:
            return self._jit_cache[key]
        api = self.api

        def fn(params, pool, tokens, positions, block_tables, draft, rng):
            logits, new_pool = api.verify_step(params, tokens, pool,
                                               positions,
                                               block_tables=block_tables)
            n_acc, emit = SP.verify_tokens(
                logits, draft, rng, vocab_size=api.cfg.vocab_size,
                tp_axis=api.pcfg.tp_axis, temperature=self.temperature,
                top_k=self.top_k, top_p=self.top_p)
            return n_acc, emit, new_pool

        jfn = self._jit_step(fn, f"paged_verify_s{s_v}", 5, 2)
        self._jit_cache[key] = jfn
        return jfn

    def _packed_fn(self, t: int, w: int):
        """Jitted packed hybrid step (DESIGN.md §6): ONE forward over the
        (1, t) packed token axis, then unified sampling over per-segment
        windows — ``w == 1`` plain sampling at each segment's last valid
        token, ``w == gamma+1`` speculative rejection sampling (segments
        without a draft have all-(-1) draft rows, for which verification
        degenerates to exactly the plain sample of window row 0)."""
        key = ("packed", t, w)
        if key in self._jit_cache:
            return self._jit_cache[key]
        api = self.api
        paged = self.paged

        def fn(params, cache, tokens, positions, seg_slots, sample_idx,
               *rest):
            rest = list(rest)
            bt = rest.pop(0) if paged else None
            draft = rest.pop(0) if w > 1 else None
            rng = rest.pop(0)
            logits, new_cache = api.packed_step(
                params, tokens, cache, positions, seg_slots=seg_slots,
                sample_idx=sample_idx, block_tables=bt)
            if w > 1:
                n_acc, emit = SP.verify_tokens(
                    logits, draft, rng, vocab_size=api.cfg.vocab_size,
                    tp_axis=api.pcfg.tp_axis, temperature=self.temperature,
                    top_k=self.top_k, top_p=self.top_p)
            else:
                n_acc = jnp.zeros(logits.shape[0], jnp.int32)
                emit = sample(logits, vocab_size=api.cfg.vocab_size,
                              tp_axis=api.pcfg.tp_axis,
                              temperature=self.temperature,
                              top_k=self.top_k, top_p=self.top_p, key=rng)
            return n_acc, emit, new_cache

        n_plain = 5 + (1 if paged else 0) + (1 if w > 1 else 0)
        jfn = self._jit_step(fn, f"packed_t{t}_w{w}", n_plain, 2)
        self._jit_cache[key] = jfn
        return jfn

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def add_request(self, req: Request):
        if len(req.prompt) + 1 > self.scfg.max_len:
            # the prompt plus at least one decode slot must fit the cache;
            # legacy slots would silently ring-wrap, paged tables would
            # overflow — reject loudly instead
            raise ValueError(
                f"prompt length {len(req.prompt)} + 1 exceeds max_len "
                f"{self.scfg.max_len} (rid={req.rid})")
        if self.paged:
            need = self.block_mgr.blocks_needed(len(req.prompt)) + 1
            if need > self.scfg.effective_num_blocks:
                # even an otherwise-empty pool could never admit it
                raise ValueError(
                    f"prompt needs {need} blocks but the pool has only "
                    f"{self.scfg.effective_num_blocks} (rid={req.rid})")
        req.arrival_step = self._step_count
        if req.enqueued_s is None:          # first enqueue, host clock
            req.enqueued_s = time.perf_counter()
        self.sched.add(req)
        if self.obs is not None:
            self.obs.request_event(req.rid, "queued")

    def _obs_admit(self, req: Request) -> None:
        """Scheduler admission hook (only wired when tracing is on)."""
        self.obs.request_event(req.rid, "admit", args={"slot": req.slot})

    def metrics_snapshot(self) -> Dict[str, float]:
        """Sync derived gauges, then flatten the registry — the
        provenance-checked source for gated benchmark metrics
        (benchmarks/run.py, scripts/check_bench.py; DESIGN.md §12)."""
        m = self.metrics
        st = self.stats
        m.gauge("engine/weave_rate").set(st.weave_rate)
        m.gauge("engine/tokens_per_forward").set(st.tokens_per_forward)
        # per-site weave rates (overlap policy attribution, DESIGN.md §14)
        for (name, lk), inst in list(m._instruments.items()):
            if name != "engine/site_forwards" or not inst.value:
                continue
            labels = dict(lk)
            w = m.get("engine/site_weave", **labels)
            m.gauge("engine/site_weave_rate", **labels).set(
                (w.value if w is not None else 0) / inst.value)
            fz = m.get("engine/site_fused", **labels)
            m.gauge("engine/site_fused_rate", **labels).set(
                (fz.value if fz is not None else 0) / inst.value)
        m.gauge("spec/acceptance_rate").set(st.spec.acceptance_rate)
        m.gauge("spec/tokens_per_step").set(st.spec.tokens_per_step)
        m.gauge("latency/goodput").set(st.latency.goodput)
        if self.block_mgr is not None:
            bs = self.block_mgr.stats
            m.gauge("paging/hit_rate").set(bs.hit_rate)
            m.gauge("paging/preemptions").set(bs.preemptions)
            m.gauge("paging/evictions").set(bs.evictions)
        return m.snapshot()

    def abort(self, req: Request, reason: str = "cancelled") -> bool:
        """Cancel a live request at ANY lifecycle point (waiting, mid-
        prefill, mid-decode/verify), releasing every resource it holds:
        paged blocks (including prefix-cache shared refs), the legacy cache
        slot (stale-position reset), and its scheduler entry.  Safe only
        BETWEEN engine steps (steps are atomic).  Returns False when the
        request was already finished."""
        if req.state == State.DONE:
            return False
        req.finish_reason = reason
        if reason == "expired":
            self.stats._expired.inc()
        else:
            self.stats._cancelled.inc()
        if self.obs is not None:
            self.obs.request_event(
                req.rid, "expire" if reason == "expired" else "cancel",
                args={"reason": reason})
        if req.state == State.WAITING:
            # not admitted: no slot, and (paged) no blocks — allocation
            # happens at admission; a preempted request already freed its
            self.sched.remove_waiting(req)
            req.state = State.DONE
            return True
        if req.slot is None:
            # parked for handoff (DESIGN.md §11): the exporter already
            # released blocks and slot; just drop it from the handoff queue
            self.handoff_ready = [h for h in self.handoff_ready
                                  if h.req is not req]
            req.state = State.DONE
            return True
        if self.paged:
            # drops private AND prefix-shared refs; cached blocks park in
            # the LRU (still hittable), so cancelling never poisons the
            # prefix cache — only releases this request's references
            self.block_mgr.free_request(req.rid)
        elif not self._is_ssm:
            self.cache = KC.reset_slots(self.cache, np.asarray([req.slot]))
        self.sched.active[req.slot] = None
        req.slot = None
        req.state = State.DONE
        return True

    # ------------------------------------------------------------------
    # disaggregated prefill/decode KV handoff (runtime/cluster.py,
    # DESIGN.md §11)
    # ------------------------------------------------------------------
    def _park_for_handoff(self, r: Request):
        """Export the request's KV and detach it from this engine: the
        payload is pulled off the device (the migration's network copy),
        then every exporter-side reference is released — shared prefix
        blocks keep their other readers, private ones recycle — and the
        slot frees for the next prefill.  The request (state DECODE, its
        first token already committed) waits in ``handoff_ready`` for the
        cluster to adopt it on a decode replica."""
        if not self.paged:
            raise ValueError("KV handoff requires the paged backend "
                             "(legacy slot rows cannot be exported)")
        n_tokens = r.prefill_pos          # committed context now in cache
        blocks = self.block_mgr.export_blocks(r.rid, n_tokens)
        payload = jax.device_get(PG.extract_blocks(self.cache, blocks))
        self.block_mgr.free_request(r.rid)
        self.sched.active[r.slot] = None
        r.slot = None
        self.handoff_ready.append(Handoff(req=r, n_tokens=n_tokens,
                                          payload=payload))
        if self.obs is not None:
            self.obs.request_event(r.rid, "handoff_export",
                                   args={"n_tokens": n_tokens})

    def take_handoffs(self) -> List[Handoff]:
        out, self.handoff_ready = self.handoff_ready, []
        return out

    def evacuate(self) -> List[Request]:
        """Dead-replica recovery (runtime/cluster.py, DESIGN.md §15):
        release every resource of every live request this engine owns —
        paged blocks (prefix-shared refs included), legacy slots, parked
        handoffs, scheduler entries — and return the requests reset for
        re-admission elsewhere (WAITING, recompute semantics like
        preemption).  In a real deployment the dead machine's memory is
        simply gone; the deterministic twin models that by sweeping the
        pool back to empty, which is exactly what makes a requeue that
        SKIPS the release visible to ``ClusterServer.check_quiescent``
        (the fault-injection tests monkeypatch this to leak)."""
        out: List[Request] = []
        for h in self.take_handoffs():
            # exporter-side refs were already released at park
            out.append(reset_for_requeue(h.req))
        for r in list(self.sched.waiting):
            self.sched.remove_waiting(r)
            out.append(reset_for_requeue(r))
        for slot, r in enumerate(self.sched.active):
            if r is None:
                continue
            if self.paged:
                self.block_mgr.free_request(r.rid)
            elif not self._is_ssm:
                self.cache = KC.reset_slots(self.cache,
                                            np.asarray([r.slot]))
            self.sched.active[slot] = None
            out.append(reset_for_requeue(r))
        return out

    def adopt_request(self, req: Request, n_tokens: int, payload) -> bool:
        """Adopt a migrated DECODE request: rebuild its block table on this
        engine (sharing importer-side prefix-cache hits, implanting the
        payload into the rest), re-register its prefix-cache entries, and
        place it straight into a free slot — no re-prefill, decode resumes
        from the migrated KV.  Returns False (no state changed, retry
        later) when no slot is free or the pool cannot cover it."""
        if not self.paged:
            raise ValueError("adopt_request requires the paged backend")
        if req.state != State.DECODE:
            raise ValueError(f"rid={req.rid} is {req.state}, not DECODE")
        free = [i for i, r in enumerate(self.sched.active) if r is None]
        if not free:
            return False
        ctx = req.prompt + req.output[:-1]
        assert n_tokens <= len(ctx), (req.rid, n_tokens, len(ctx))
        imported = self.block_mgr.import_blocks(req.rid, ctx[:n_tokens],
                                                n_tokens)
        if imported is None:
            return False
        table, copy_idx = imported
        # drain queued pool maintenance first: a freshly allocated table
        # entry may still carry a pending pos reset from its previous
        # owner, which would clobber the implant if applied after it
        self._apply_fixups()
        if copy_idx:
            self.cache = PG.implant_blocks(
                self.cache, PG.select_payload(payload, copy_idx),
                [table[i] for i in copy_idx])
        self.block_mgr.register_filled(req.rid, ctx, n_tokens)
        req.handoff_after_prefill = False
        req.migrations += 1
        req.slot = free[0]
        req.arrival_step = self._step_count
        self.sched.active[req.slot] = req
        if self.obs is not None:
            self.obs.request_event(req.rid, "handoff_adopt",
                                   args={"slot": req.slot})
        return True

    def step(self) -> bool:
        """Run one engine iteration. Returns False when idle.

        Its host phases are profiler-clock spans (obs/spans.py):
        ``engine/step`` holds ``engine/schedule`` and, for each dispatch,
        ``engine/prepare`` -> ``engine/dispatch`` -> ``engine/sync`` ->
        ``engine/commit``."""
        obs = self.obs
        with span("engine/step", i=self._step_count) as sp:
            if obs is not None:
                # offline engines self-clock one tick per step; a no-op
                # once an external owner (server/replica) has synced.
                # Stamped BEFORE next_step() so admission events land at
                # step time.
                obs.auto(float(self._step_count))
                self._step_forwards = []
            with span("engine/schedule"):
                plan = self.sched.next_step()
            if plan is None:
                return False
            self._step_count += 1
            self.stats._steps.inc()

            if isinstance(plan, PackedPlan):
                sp.set_metadata(kind="packed", rows=len(plan.segments),
                                chunk=plan.total_tokens)
                self._run_packed(plan)
                if obs is not None:
                    self._obs_emit_step(packed=True)
                return True
            group, chunk = plan.prefill or ((), 0)
            dec = plan.decode_slots
            kind = "verify" if self.spec_gamma else "decode"
            if not dec or group:
                kind = "mixed" if dec else "prefill"
            sp.set_metadata(kind=kind, rows=len(group) + len(dec),
                            chunk=chunk)
            if group:
                self._run_prefill(group, chunk)
            if dec:
                if self.spec_gamma:
                    self._run_verify()
                else:
                    self._run_decode()
            if obs is not None:
                self._obs_emit_step(packed=False)
            return True

    def _obs_emit_step(self, packed: bool) -> None:
        """Emit this iteration's step span plus one nested forward span
        per model dispatch, carrying the weave attribution record
        (DESIGN.md §12).  All spans start at the step's clock stamp with
        §9 sim-roofline durations; the step span covers its longest
        forward, so nesting holds however far the owner clock advances."""
        obs = self.obs
        fwds = self._step_forwards
        t0 = obs.now
        durs = [max(a.est_makespan, 1e-9) for a in fwds]
        obs.complete(self.obs_track,
                     "step/packed" if packed else "step/two_dispatch",
                     t0, max(durs, default=1e-9), cat="step",
                     args={"step": self._step_count, "forwards": len(fwds)})
        for a, d in zip(fwds, durs):
            args = a.args()
            args["step"] = self._step_count
            obs.complete(self.obs_track, f"forward/{a.kind}", t0, d,
                         cat="forward", args=args)
        self._step_forwards = []

    def _weave_info(self, b: int, s: int, *, decode: bool = False,
                    packed: bool = False) -> Tuple[TRX.WeaveInfo, str]:
        """The weave decision of a dispatch of static shape (b, s) (the
        host-side mirror of the trace-time split decision) and the mode its
        ``comm_norm`` sites run (the forward's own rule), taken once before
        the dispatch and shared by its span and its counters."""
        info = TRX.weave_decision_info(b, s, tp=self.api.tp,
                                       pcfg=self.api.pcfg, decode=decode,
                                       packed=packed,
                                       paged_pool=self.paged and decode,
                                       family=self.api.cfg.family)
        return info, TRX.comm_mode_for(self.api.pcfg, b * s, self.api.tp,
                                       info.comm_mode or None)

    def _note_forward(self, info: TRX.WeaveInfo, comm: str, b: int, s: int,
                      n_real: int, *, kind: str = "prefill"):
        """Record one model dispatch: its weave decision and comm mode (the
        ones its ``engine/dispatch`` span carried) feed the counters, its
        real token count tokens/forward.  The SAME decision
        object feeds the counter and (when tracing) the trace attribution
        record, so trace-derived weave rates match ``EngineStats.weave_rate``
        exactly (DESIGN.md §12)."""
        st = self.stats
        st._forwards.inc()
        st._forward_tokens.inc(n_real)
        st._max_forward_tokens.set_max(n_real)
        # per-site weave attribution (DESIGN.md §14): which policy site
        # decided, and whether the weave fired there
        site = info.site or kind
        self.metrics.counter("engine/site_forwards", site=site).inc()
        if info.weave:
            st._weave_forwards.inc()
            self.metrics.counter("engine/site_weave", site=site).inc()
        if info.comm_mode == "ring":
            # a tuned plan routed this site onto the REAL fused ring
            # AllReduce-RMSNorm kernel (method fused / fused-unsplit)
            self.metrics.counter("engine/site_fused", site=site).inc()
        if comm == "vanilla":
            # every comm_norm site of the dispatch ran the plain psum: the
            # configured mode, or the fallback where tp does not divide
            # the dispatch's tokens
            self.metrics.counter("engine/comm_vanilla", site=site).inc()
        if self._attributor is not None:
            att = self._attributor.attribute(info, b=b, s=s, n_real=n_real,
                                             kind=kind)
            if self.obs is not None:
                self._step_forwards.append(att)
            if self.profiler is not None:
                # join this very dispatch's wall time (``_dispatch``) to
                # its attribution record (DESIGN.md §13)
                self.profiler.commit(att, self._dispatch_s)

    def run(self, max_steps: int = 100000) -> List[Request]:
        while not self.sched.all_done() and max_steps > 0:
            max_steps -= 1
            if not self.step():
                if self.sched.waiting:
                    # nothing active and the queue head cannot be admitted:
                    # permanently stuck (e.g. a preempted request whose
                    # regrown context outgrew the pool) — surface it rather
                    # than silently dropping the request
                    rids = [r.rid for r in self.sched.waiting]
                    raise RuntimeError(
                        f"engine idle with unservable waiting request(s) "
                        f"{rids}: block pool too small for their context")
                break
        return self.sched.finished

    # ------------------------------------------------------------------
    # paged-cache plumbing
    # ------------------------------------------------------------------
    def _apply_fixups(self):
        """Drain queued device-side pool maintenance: pos resets of
        recycled blocks FIRST (a reset target may since have been handed
        out again — its new owner writes later, and a COW destination is
        overwritten entirely by its copy), then copy-on-write copies."""
        resets = self.block_mgr.take_pending_resets()
        copies = self.block_mgr.take_pending_copies()
        if resets:
            self.cache = PG.reset_blocks(self.cache, resets)
        if copies:
            self.cache = PG.copy_blocks(self.cache, copies)

    def _preempt(self, victim: Request):
        self.block_mgr.free_request(victim.rid)
        self.block_mgr.stats.preemptions += 1
        self.sched.preempt(victim)
        if self.obs is not None:
            self.obs.request_event(victim.rid, "preempt")

    def _ensure_decode_blocks(self) -> List[Request]:
        """Grow/COW the write-target block of every DECODE request; on
        pool exhaustion preempt the youngest DECODE request (recompute
        mode) and retry.  Returns the surviving decode batch."""
        def decoding():
            return [r for r in self.sched.active
                    if r is not None and r.state == State.DECODE]
        for r in decoding():
            if r.length - 1 >= self.scfg.max_len:
                # context hit the cache ceiling: stop generating early
                # (truncated output) rather than overflow the block table
                self._finish(r)
        for r in sorted(decoding(), key=lambda r: (r.arrival_step, r.rid)):
            while r.state == State.DECODE:
                if self.block_mgr.ensure_writable(r.rid, r.length - 1):
                    break
                victims = decoding()
                victim = max(victims, key=lambda v: (v.arrival_step, v.rid))
                self._preempt(victim)   # may be r itself -> loop exits
        return decoding()

    # ------------------------------------------------------------------
    # per-request commit helpers — ONE implementation shared by the
    # two-dispatch and packed paths, so cache-invalidation / registration
    # fixes can never diverge between them
    # ------------------------------------------------------------------
    def _commit_prefill(self, r: Request, tok: int):
        """After a prefill chunk advanced ``r.prefill_pos``: register the
        filled blocks and, when the context completed, commit the first
        sampled token (dropped for recompute-readmissions, whose pending
        decode input was already emitted) and move to DECODE."""
        if self.paged:
            self.block_mgr.register_filled(r.rid, r.context_tokens,
                                           r.prefill_pos)
        if r.prefill_done:
            if r.resumed:
                r.resumed = False
            else:
                r.output.append(tok)
                r.first_token_step = self._step_count
            r.state = State.DECODE
            if self.obs is not None:
                self.obs.request_event(r.rid, "prefill_done",
                                       args={"tokens": r.prefill_pos})
            self._maybe_finish(r)
            if r.state != State.DONE and r.handoff_after_prefill:
                self._park_for_handoff(r)

    def _commit_decode(self, r: Request, tok: int):
        n_written = r.length  # positions [0, length-1] now in cache
        r.output.append(tok)
        self.stats._decode_tokens.inc()
        if self.paged and n_written % self.scfg.block_size == 0:
            # a block just filled: make it hittable for future prompts
            self.block_mgr.register_filled(
                r.rid, r.prompt + r.output[:-1], n_written)
        self._maybe_finish(r)

    def _commit_verify(self, r: Request, prop: List[int], n_acc: int,
                       emit: int):
        """Commit the longest accepted draft prefix + the corrected/bonus
        token and roll back rejected KV (paged: block-table truncation;
        legacy slots need none: a step sees old-cache keys only below its
        window's first position, DESIGN.md §8)."""
        n = min(n_acc, len(prop))
        base_len = r.length          # L: window wrote L-1 .. L-1+|prop|
        r.output.extend(prop[:n] + [emit])
        st = self.stats.spec
        st._draft_proposed.inc(len(prop))
        st._draft_accepted.inc(n)
        st._emitted.inc(n + 1)
        self.stats._decode_tokens.inc(n + 1)
        if self.paged:
            # rollback: keep exactly the blocks covering the committed
            # context (positions 0 .. L-1+n); rejected draft KV beyond
            # them is dropped with the tail blocks, never copied
            self.block_mgr.truncate(r.rid, base_len + n)
            self.block_mgr.register_filled(
                r.rid, r.prompt + r.output[:-1], base_len + n)
        self._maybe_finish(r)

    # ------------------------------------------------------------------
    def _run_prefill(self, group: List[Request], chunk: int):
        with span("engine/prepare"):
            b_sel = len(group)
            if self._is_ssm:
                # ssm chunks must be exact (no pads): shrink to min remainder
                chunk = min(min(len(r.context_tokens) - r.prefill_pos
                                for r in group), chunk)
            tokens = np.zeros((b_sel, chunk), np.int32)
            positions = np.full((b_sel, chunk), -1, np.int32)
            offsets = np.zeros(b_sel, np.int32)
            last_idx = np.zeros(b_sel, np.int32)
            for i, r in enumerate(group):
                ctx = r.context_tokens
                take = min(chunk, len(ctx) - r.prefill_pos)
                tokens[i, :take] = ctx[r.prefill_pos:r.prefill_pos + take]
                positions[i, :take] = np.arange(r.prefill_pos,
                                                r.prefill_pos + take)
                offsets[i] = r.prefill_pos
                last_idx[i] = take - 1
                r.prefill_pos += take

            if self.paged:
                self._apply_fixups()
                bt = np.stack([self.block_mgr.table_array(r.rid)
                               for r in group])
                fn = self._paged_prefill_fn(b_sel, chunk)
                args = self._inputs(tokens, positions, bt, last_idx)
            else:
                slot_ids = np.array([r.slot for r in group], np.int32)
                fn = self._prefill_fn(b_sel, chunk)
                args = self._inputs(tokens, positions, slot_ids, offsets,
                                    last_idx)
            info, comm = self._weave_info(b_sel, chunk)
        tok, = self._dispatch(fn, args, info, comm)
        with span("engine/commit"):
            n_real = int((positions >= 0).sum())
            self.stats._prefill_tokens.inc(n_real)
            self._note_forward(info, comm, b_sel, chunk, n_real,
                               kind="prefill")
            if not (self.paged or self._is_ssm) and "k" in self.cache:
                # the chunk went into the stacked slot cache in place
                self.metrics.counter("engine/prefill_write",
                                     form="inplace").inc()
            for i, r in enumerate(group):
                self._commit_prefill(r, int(tok[i]))

    def _run_decode(self):
        with span("engine/prepare"):
            if self.paged:
                reqs = self._ensure_decode_blocks()
                if not reqs:
                    return
                self._apply_fixups()
            else:
                reqs = [r for r in self.sched.active
                        if r is not None and r.state == State.DECODE]
            bmax = self.scfg.max_batch
            tokens = np.zeros((bmax, 1), np.int32)
            positions = np.full((bmax, 1), -1, np.int32)
            for r in reqs:
                tokens[r.slot, 0] = r.output[-1]
                positions[r.slot, 0] = r.length - 1

            if self.paged:
                bt = np.full((bmax, self.scfg.max_blocks_per_req), -1,
                             np.int32)
                for r in reqs:
                    bt[r.slot] = self.block_mgr.table_array(r.rid)
                fn = self._paged_decode_fn()
                args = self._inputs(tokens, positions, bt)
            else:
                fn = self._decode_fn()
                args = self._inputs(tokens, positions)
            info, comm = self._weave_info(bmax, 1, decode=True)
        tok, = self._dispatch(fn, args, info, comm)
        with span("engine/commit"):
            self._note_forward(info, comm, bmax, 1, len(reqs),
                               kind="decode")
            for r in list(reqs):
                self._commit_decode(r, int(tok[r.slot]))

    # ------------------------------------------------------------------
    # speculative decoding (runtime/spec.py, DESIGN.md §8)
    # ------------------------------------------------------------------
    def _grow_for_draft(self, r: Request, want: int) -> int:
        """Best-effort paged-block growth for the draft positions
        ``length .. length-1+want``; on allocation failure the draft is
        SHRUNK (draft tokens are optional) instead of preempting a peer.
        Returns the number of draft tokens whose KV cell is writable."""
        for j in range(1, want + 1):
            if not self.block_mgr.ensure_writable(r.rid, r.length - 1 + j):
                return j - 1
        return want

    def _capped_drafts(self, reqs: List[Request]) -> Dict[int, List[int]]:
        """Draft proposals for the given DECODE requests, capped so the
        verify window never overshoots max_new_tokens (the verify always
        commits >= 1 extra token) or the cache ceiling, and shrunk — never
        preempting a peer — to the paged blocks that can actually grow.
        ONE implementation shared by the two-dispatch and packed paths."""
        gamma = self.spec_gamma
        props = self.draft.propose([r.prompt + r.output for r in reqs])
        capped: Dict[int, List[int]] = {}
        for r, prop in zip(reqs, props):
            cap = min(gamma, r.max_new_tokens - len(r.output) - 1,
                      self.scfg.max_len - r.length)
            prop = list(prop[:max(cap, 0)])
            if self.paged and prop:
                prop = prop[:self._grow_for_draft(r, len(prop))]
            capped[r.rid] = prop
        return capped

    def _run_verify(self):
        """One speculative iteration over every DECODE request: draft
        gamma tokens, run ONE gamma+1-token verify forward, commit the
        longest accepted prefix + 1 corrected/bonus token, and roll back
        the rejected suffix (paged: block-table truncation)."""
        gamma = self.spec_gamma
        with span("engine/prepare"):
            if self.paged:
                reqs = self._ensure_decode_blocks()  # input cell: mandatory
            else:
                reqs = [r for r in self.sched.active
                        if r is not None and r.state == State.DECODE]
            if not reqs:
                return
            capped = self._capped_drafts(reqs)
            drafted = any(capped.values())
            if drafted:
                if self.paged:
                    self._apply_fixups()
                bmax = self.scfg.max_batch
                s_v = gamma + 1
                tokens = np.zeros((bmax, s_v), np.int32)
                positions = np.full((bmax, s_v), -1, np.int32)
                draft = np.full((bmax, gamma), -1, np.int32)
                for r in reqs:
                    prop = capped[r.rid]
                    tokens[r.slot, 0] = r.output[-1]
                    positions[r.slot, 0] = r.length - 1
                    for j, d in enumerate(prop):
                        tokens[r.slot, 1 + j] = d
                        positions[r.slot, 1 + j] = r.length + j
                        draft[r.slot, j] = d
                if self.paged:
                    bt = np.full((bmax, self.scfg.max_blocks_per_req), -1,
                                 np.int32)
                    for r in reqs:
                        bt[r.slot] = self.block_mgr.table_array(r.rid)
                    fn = self._paged_verify_fn(s_v)
                    args = self._inputs(tokens, positions, bt, draft)
                else:
                    fn = self._verify_fn(s_v)
                    args = self._inputs(tokens, positions, draft)
        if not drafted:
            # nothing drafted anywhere: a gamma+1-wide verify would pay
            # (gamma+1)x decode compute to commit one token per request —
            # take the plain single-token decode step instead
            self._run_decode()
            return
        info, comm = self._weave_info(bmax, s_v, decode=True)
        n_acc, emit = self._dispatch(fn, args, info, comm)
        with span("engine/commit"):
            self._note_forward(info, comm, bmax, s_v,
                               sum(1 + len(capped[r.rid]) for r in reqs),
                               kind="verify")
            self.stats.spec._verify_steps.inc()
            for r in list(reqs):
                self._commit_verify(r, capped[r.rid], int(n_acc[r.slot]),
                                    int(emit[r.slot]))

    # ------------------------------------------------------------------
    # packed hybrid batching (DESIGN.md §6)
    # ------------------------------------------------------------------
    def _run_packed(self, plan: PackedPlan):
        """ONE forward for the whole iteration: prefill-chunk segments,
        single-token decode slots, and speculative verify windows are
        concatenated along a single (1, T) token axis (T padded to a
        recompilation bucket) and dispatched through
        ``ModelApi.packed_step``; unified sampling/verification then
        commits every segment kind from the same (n_acc, emit) pair."""
        with span("engine/prepare"):
            gamma = self.spec_gamma
            w = gamma + 1 if gamma else 1
            if self.paged:
                # grow/COW the decode write cells first; this can preempt or
                # ceiling-finish DECODE requests, so re-filter the plan
                self._ensure_decode_blocks()
            segs = [s for s in plan.segments
                    if s.req.state == (State.PREFILL if s.kind == "prefill"
                                       else State.DECODE)]
            if not segs:
                return

            props: Dict[int, List[int]] = {}
            if gamma:
                vreqs = [s.req for s in segs if s.kind == "verify"]
                if vreqs:
                    props = self._capped_drafts(vreqs)
            if self.paged:
                self._apply_fixups()

            def seg_len(s):
                if s.kind == "prefill":
                    return s.n_tokens
                if s.kind == "verify":
                    return 1 + len(props.get(s.req.rid, []))
                return 1

            t_real = sum(seg_len(s) for s in segs)
            pad_mult = math.lcm(self.scfg.prefill_bucket, self.api.tp)
            t = pad_to_multiple(t_real, pad_mult)
            bmax = self.scfg.max_batch
            tokens = np.zeros((1, t), np.int32)
            positions = np.full((1, t), -1, np.int32)
            seg_slots = np.full(t, -1, np.int32)
            sample_idx = np.full((bmax, w), -1, np.int32)
            draft = np.full((bmax, gamma), -1, np.int32) if gamma else None
            bt = (np.full((bmax, self.scfg.max_blocks_per_req), -1, np.int32)
                  if self.paged else None)

            cur = 0
            for s in segs:
                r = s.req
                m = r.slot
                if self.paged:
                    bt[m] = self.block_mgr.table_array(r.rid)
                if s.kind == "prefill":
                    ctx = r.context_tokens
                    take = s.n_tokens
                    tokens[0, cur:cur + take] = \
                        ctx[r.prefill_pos:r.prefill_pos + take]
                    positions[0, cur:cur + take] = np.arange(
                        r.prefill_pos, r.prefill_pos + take)
                    seg_slots[cur:cur + take] = m
                    sample_idx[m, 0] = cur + take - 1
                    r.prefill_pos += take
                    cur += take
                else:
                    prop = props.get(r.rid, []) if s.kind == "verify" else []
                    tokens[0, cur] = r.output[-1]
                    positions[0, cur] = r.length - 1
                    seg_slots[cur:cur + 1 + len(prop)] = m
                    sample_idx[m, 0] = cur
                    for j, d in enumerate(prop):
                        tokens[0, cur + 1 + j] = d
                        positions[0, cur + 1 + j] = r.length + j
                        draft[m, j] = d
                        sample_idx[m, 1 + j] = cur + 1 + j
                    cur += 1 + len(prop)

            host = [tokens, positions, seg_slots, sample_idx]
            if self.paged:
                host.append(bt)
            if w > 1:
                host.append(draft)
            fn = self._packed_fn(t, w)
            args = self._inputs(*host)
            info, comm = self._weave_info(1, t, packed=True)
        n_acc, emit = self._dispatch(fn, args, info, comm)
        with span("engine/commit"):
            self._note_forward(info, comm, 1, t, t_real, kind="packed")
            if any(s.kind == "verify" for s in segs):
                self.stats.spec._verify_steps.inc()
            for s in segs:
                r = s.req
                m = r.slot
                if s.kind == "prefill":
                    self.stats._prefill_tokens.inc(s.n_tokens)
                    self._commit_prefill(r, int(emit[m]))
                elif s.kind == "decode":
                    self._commit_decode(r, int(emit[m]))
                else:
                    self._commit_verify(r, props.get(r.rid, []),
                                        int(n_acc[m]), int(emit[m]))

    def _maybe_finish(self, r: Request):
        if len(r.output) >= r.max_new_tokens:
            self._finish(r)

    def _finish(self, r: Request):
        if self.paged:
            # final registration, then drop refs: cached blocks park in
            # the LRU (still prefix-hittable), private ones recycle
            self.block_mgr.register_filled(
                r.rid, r.prompt + r.output[:-1], r.length - 1)
            self.block_mgr.free_request(r.rid)
        elif not self._is_ssm:
            # release slot state: stale ring-buffer positions from a
            # finished request must not leak into the slot's next owner
            self.cache = KC.reset_slots(self.cache, np.asarray([r.slot]))
        r.finish_reason = r.finish_reason or "stop"
        self.sched.finish(r, self._step_count)
        self.stats._completed.inc()
        if self.obs is not None:
            self.obs.request_event(r.rid, "finish",
                                   args={"reason": r.finish_reason,
                                         "tokens": len(r.output)})
