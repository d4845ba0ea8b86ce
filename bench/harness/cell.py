"""One run of one cell: build the system from the seed, warm up the shapes
the cell's traffic can emit, drive ``Engine.add_request`` / ``Engine.step``
on the host wall clock for the window, check what it served against the
plain reference, and, with ``trace``, reduce a profiler trace of a steady
stretch of the window to the cell's per-layer metrics.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import random
import shutil
import tempfile
import time
from typing import Dict, List

import numpy as np

from bench.harness import trace as TR
from bench.harness import traffic as TF
from bench.harness.peaks import peaks as peaks_of

CLOCK = time.perf_counter
DRAIN_CAP_S = 60.0          # wait for a window request's first token
TAIL_S = 30.0               # open-loop traffic offered while draining
SAMPLE_MIN_REQUESTS = 8     # correctness sample: at least this many ...
SAMPLE_MIN_TOKENS = 256     # ... and this many served tokens,
SAMPLE_MAX_REQUESTS = 24    # ... and no more requests than this
PER_CLIENT = 40             # closed loop: requests drawn per client


def set_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (cache hits included) from
    ``jax.monitoring``; ``window`` marks the ones inside the window."""

    def __init__(self):
        import jax
        self.total = 0
        self.window = 0
        self.in_window = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += 1
            if self.in_window:
                self.window += 1


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


class Record:
    __slots__ = ("req", "due", "phase", "first", "last", "n", "done",
                 "client")

    def __init__(self, req, due, phase, client=None):
        self.req, self.due, self.phase, self.client = req, due, phase, client
        self.first = self.last = None
        self.n = 0
        self.done = False


class Clients:
    """Feeds the engine and stamps every output token on the host clock
    after the step that produced it (what a client streaming tokens per
    iteration sees)."""

    def __init__(self, eng, annotate: bool):
        import jax
        self.eng = eng
        self.ann = (jax.profiler.TraceAnnotation if annotate
                    else (lambda *a, **k: contextlib.nullcontext()))
        self.recs: Dict[int, Record] = {}
        self.prev: Dict[int, tuple] = {}
        self.steps: List[dict] = []
        self.n_finished = len(eng.sched.finished)
        self.w0 = self.w1 = math.inf
        self.gaps: List[float] = []       # token gaps stamped in the window
        self.win_tokens = 0               # tokens stamped in the window
        self.on_finish = None
        self._rid = 0

    def admit(self, prompt, max_new, due, phase, client=None):
        from repro.runtime.requests import Request
        r = Request(rid=self._rid, prompt=prompt, max_new_tokens=max_new)
        self._rid += 1
        self.recs[r.rid] = Record(r, due, phase, client)
        self.eng.add_request(r)
        return r

    def step(self):
        i = len(self.steps)
        with self.ann("bench/step", i=i):
            self.eng.step()
        t = CLOCK()
        with self.ann("bench/stamp"):
            self._stamp(t, i)

    def _stamp(self, t, i):
        sched = self.eng.sched
        fin = sched.finished[self.n_finished:]
        self.n_finished = len(sched.finished)
        pre, dec = [], []
        done = []
        for r in [a for a in sched.active if a is not None] + fin:
            pp0, n0 = self.prev.get(r.rid, (0, 0))
            pp, n = r.prefill_pos, len(r.output)
            self.prev[r.rid] = (pp, n)
            if pp > pp0:
                pre.append((pp0, pp - pp0, 1))
            new = n - n0
            if new <= 0:
                continue
            if pp0 < len(r.prompt) <= pp:
                new_dec = new - 1           # the prefill's first token
            else:
                new_dec = new
            if new_dec > 0:
                dec.append((len(r.prompt) + n - 2, 1, 1))
            rec = self.recs[r.rid]
            in_win = self.w0 <= t < self.w1
            for _ in range(new):
                if rec.n == 0:
                    rec.first = t
                elif in_win:
                    self.gaps.append(t - rec.last)
                rec.last = t
                rec.n += 1
            if in_win:
                self.win_tokens += new
            if r.state.value == "done" and not rec.done:
                rec.done = True
                done.append(rec)
        self.steps.append({"i": i, "prefill": pre, "decode": dec})
        for rec in done:
            self.prev.pop(rec.req.rid, None)
            if self.on_finish is not None:
                self.on_finish(rec, t)


# --------------------------------------------------------------------------
# building and warming up
# --------------------------------------------------------------------------

def build(cell, seed: int, devices):
    import jax
    from repro.models.build import build_model
    from repro.runtime.engine import Engine
    ad = cell.module("adapters")
    sv = cell.config["serving"]
    tp = sv["tp"]
    if len(devices) < tp:
        raise SystemExit(f"config needs tp={tp} devices, have {len(devices)}")
    mesh = jax.make_mesh((1, tp), ("data", "model"), devices=devices[:tp],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api = build_model(ad.model_config(cell.config),
                      ad.parallel_config(sv), tp=tp)
    params = ad.build_params(weights_key(seed), cell.config, api, mesh)
    jax.block_until_ready(params)
    eng = Engine(api, mesh, params, ad.scheduler_config(sv))
    return eng, params


def weights_key(seed: int):
    import jax
    # any integer seed: fold its two low 32-bit words into the key
    s = seed & (2 ** 64 - 1)
    k = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(s >> 32))


def prefill_shapes(cell) -> List[tuple]:
    """Every (rows, chunk) prefill shape the scheduler can emit for this
    cell's prompt lengths."""
    sv = cell.config["serving"]
    b, budget = sv["prefill_bucket"], sv["chunk_tokens"]
    top = min(budget, -(-cell.mix["prompt"]["max"] // b) * b)
    shapes = []
    for c in range(b, top + 1, b):
        for rows in range(1, min(sv["max_batch"], max(1, budget // c)) + 1):
            shapes.append((rows, c))
    return shapes


def warm_up(eng, cell, drv: Clients):
    """Compile (or load from the cache) and run once every step program the
    cell's traffic can emit, then serve one short request end to end, so
    the host path's small programs are built too."""
    import jax
    import jax.numpy as jnp
    for rows, c in prefill_shapes(cell):
        fn = eng._prefill_fn(rows, c)
        tok, eng.cache = fn(
            eng.params, eng.cache, jnp.asarray(np.zeros((rows, c), np.int32)),
            jnp.asarray(np.full((rows, c), -1, np.int32)),
            jnp.asarray(np.arange(rows, dtype=np.int32)),
            jnp.asarray(np.zeros(rows, np.int32)),
            jnp.asarray(np.zeros(rows, np.int32)), eng._next_key())
        np.asarray(tok)
    b = eng.scfg.max_batch
    tok, eng.cache = eng._decode_fn()(
        eng.params, eng.cache, jnp.asarray(np.zeros((b, 1), np.int32)),
        jnp.asarray(np.full((b, 1), -1, np.int32)), eng._next_key())
    np.asarray(tok)
    drv.admit([1] * eng.scfg.prefill_bucket, 3, CLOCK(), "warmup")
    while not eng.sched.all_done():
        drv.step()
    jax.block_until_ready(eng.cache)
    return len(prefill_shapes(cell)) + 1


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

class Tracer:
    """Profiler trace of a stretch of the window, in a directory of its own
    under TMPDIR."""

    def __init__(self, start: float, length: float):
        self.start, self.length = start, length
        self.dir = None
        self.state = "idle"
        self.span = None

    def poll(self, now: float):
        import jax
        if self.state == "idle" and now >= self.start:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans: TraceMe only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench/traced")
            self.span.__enter__()
            self.t_on = CLOCK()
            self.state = "on"
        elif self.state == "on" and now >= self.t_on + self.length:
            self.stop()

    def stop(self):
        import jax
        if self.state == "on":
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


def drive_open(drv: Clients, cell, seed, seconds, tracer):
    mix = cell.mix
    vocab = cell.config["vocab_size"]
    phases = TF.open_loop(mix, seed, seconds, vocab, TAIL_S)
    t0 = CLOCK()
    drv.w0 = t0 + mix["preroll_s"]
    drv.w1 = drv.w0 + seconds
    pending = []
    for name, ph in phases.items():
        for p, o, d in zip(ph.prompts, ph.outputs, ph.due):
            pending.append((t0 + d, name, p, o))
    pending.sort(key=lambda x: x[0])
    k = 0
    eng = drv.eng
    window = []
    while True:
        now = CLOCK()
        if k < len(pending) and pending[k][0] <= now:
            with drv.ann("bench/admit"):
                while k < len(pending) and pending[k][0] <= now:
                    due, name, p, o = pending[k]
                    r = drv.admit(p, o, due, name)
                    if name == "window":
                        window.append(drv.recs[r.rid])
                    k += 1
        if tracer is not None:
            tracer.poll(now)
        if now >= drv.w1:
            if all(rec.first is not None for rec in window):
                break
            if now >= drv.w1 + DRAIN_CAP_S:
                break
        if not eng.sched.all_done():
            drv.step()
        elif k < len(pending):
            with drv.ann("bench/wait"):
                time.sleep(max(0.0, pending[k][0] - CLOCK()))
        else:
            break
    if tracer is not None:
        tracer.stop()
    return window


def drive_closed(drv: Clients, cell, seed, seconds, tracer):
    mix = cell.mix
    slots = cell.config["serving"]["max_batch"]
    n_cl = int(mix["clients_per_slot"] * slots)
    streams = TF.closed_loop(mix, seed, n_cl, PER_CLIENT,
                             cell.config["vocab_size"])
    nxt = [0] * n_cl
    window = []

    def submit(c, t):
        # a client that has sent all its requests starts over
        p, o = streams[c][nxt[c] % len(streams[c])]
        nxt[c] += 1
        phase = "window" if drv.w0 <= t < drv.w1 else (
            "preroll" if t < drv.w0 else "tail")
        r = drv.admit(p, o, t, phase, client=c)
        if phase == "window":
            window.append(drv.recs[r.rid])

    def on_finish(rec, t):
        if t < drv.w1:
            with drv.ann("bench/admit"):
                submit(rec.client, t)

    t0 = CLOCK()
    drv.w0 = t0 + mix["preroll_s"]
    drv.w1 = drv.w0 + seconds
    drv.on_finish = on_finish
    with drv.ann("bench/admit"):
        for c in range(n_cl):
            submit(c, t0)
    while True:
        now = CLOCK()
        if tracer is not None:
            tracer.poll(now)
        if now >= drv.w1:
            # no new requests; wait for the window's first tokens
            if all(rec.first is not None for rec in window) or \
                    now >= drv.w1 + DRAIN_CAP_S:
                break
        drv.step()
    if tracer is not None:
        tracer.stop()
    return window


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def pick_sample(drv: Clients, seed: int) -> List[Record]:
    """Finished requests, drawn from the seed, with the longest served
    output first."""
    done = [r for r in drv.recs.values()
            if r.done and r.phase != "warmup" and r.req.output]
    if not done:
        return []
    done.sort(key=lambda r: r.req.rid)
    longest = max(done, key=lambda r: (len(r.req.output), -r.req.rid))
    rest = [r for r in done if r is not longest]
    random.Random(f"{seed}/sample").shuffle(rest)
    out, toks = [longest], len(longest.req.output)
    for r in rest:
        if (len(out) >= SAMPLE_MIN_REQUESTS and toks >= SAMPLE_MIN_TOKENS) \
                or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(r)
        toks += len(r.req.output)
    return out


def served_gaps(cell, seed: int, sample: List[Record], devices,
                control: bool = False):
    """For each sampled request, the gap by which each served token's
    reference logit lies below the reference's best at its position; with
    ``control``, also the gap of the token the fp8 control (the reference
    with fp8 matmuls) puts first at the same positions."""
    ref = cell.module("reference")
    seqs = [list(r.req.prompt) + list(r.req.output[:-1]) for r in sample]
    idxs = [list(range(len(r.req.prompt) - 1,
                       len(r.req.prompt) - 1 + len(r.req.output)))
            for r in sample]
    key = weights_key(seed)
    lg = ref.blocked_logits(key, cell.config, seqs, idxs, devices=devices)
    gaps, finite = [], True
    for r, l in zip(sample, lg):
        finite &= bool(np.isfinite(l).all())
        out = np.asarray(r.req.output)
        gaps.append(l.max(-1) - l[np.arange(len(out)), out])
    res = {"gaps": gaps, "finite": finite}
    if control:
        lq = ref.blocked_logits(key, cell.config, seqs, idxs,
                                devices=devices, quant=ref.fp8_quant)
        res["control_gaps"] = [l.max(-1) - l[np.arange(len(q)),
                                             q.argmax(-1)]
                               for l, q in zip(lg, lq)]
    return res


# --------------------------------------------------------------------------
# trace -> per-layer metrics
# --------------------------------------------------------------------------

class TraceContext:
    """What a per-layer metric reader sees (bench/metrics/<name>.py)."""

    def __init__(self, tr: TR.Trace, drv: Clients, cell, peak: dict,
                 chips: int):
        spans = [e for e in tr.host if e.name == "bench/traced"]
        if not spans:
            raise RuntimeError("the trace holds no bench/traced span")
        self.lo, self.hi = spans[0].start, spans[0].end
        self.trace = tr
        self.cell = cell
        self.cfg = cell.config
        self.tp = cell.config["serving"]["tp"]
        self.chips = chips
        self.peak = peak
        self.cost = cell.module("costs")
        self.devices = sorted(tr.ops)
        self.dev0 = self.devices[0] if self.devices else None
        # steps whose host span lies inside the traced stretch, each with
        # the device-0 programs that started inside it
        mods = tr.modules.get(self.dev0, [])
        self.steps = []
        j = 0
        for e in tr.host:
            if e.name != "bench/step" or e.start < self.lo or e.end > self.hi:
                continue
            i = int(e.stats.get("i", -1))
            if not 0 <= i < len(drv.steps):
                continue
            while j < len(mods) and mods[j].start < e.start:
                j += 1
            k = j
            progs = []
            while k < len(mods) and mods[k].start < e.end:
                progs.append(mods[k])
                k += 1
            self.steps.append(dict(drv.steps[i], t0=e.start, t1=e.end,
                                   programs=progs))

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def ops(self, dev=None) -> List[TR.Event]:
        """``XLA Ops`` events of a device that overlap the stretch."""
        dev = self.dev0 if dev is None else dev
        return [e for e in self.trace.ops.get(dev, [])
                if e.end > self.lo and e.start < self.hi]

    def leaf_ops(self, dev=None) -> List[TR.Event]:
        """The ops that hold no other op (a loop's body, not the loop)."""
        return TR.nesting(self.ops(dev))[0]

    def busy(self, dev=None) -> List[TR.Interval]:
        return TR.union(TR.clip(self.ops(dev), self.lo, self.hi))

    def roofline_s(self, rows) -> float:
        """Least time one chip could take for a dispatch over ``rows``."""
        f = self.cost.per_chip(self.cost.flops(self.cfg, rows), self.tp)
        b = self.cost.per_chip(self.cost.bytes_moved(self.cfg, rows),
                               self.tp)
        return max(f / self.peak["bf16_flops"], b / self.peak["hbm_bytes_s"])

    def program_share(self, prefix: str, kind: str):
        """Roofline-minimum time of the ``kind`` dispatches (``prefill`` or
        ``decode``) of the traced steps over the device time of the
        programs named ``prefix*`` in those steps; None when there are
        none."""
        need = dev = 0.0
        for st in self.steps:
            progs = [p for p in st["programs"]
                     if _prog_name(p.name).startswith(prefix)]
            if not progs or not st[kind]:
                continue
            need += self.roofline_s(st[kind])
            dev += sum(p.end - p.start for p in progs) * 1e-9
        return None if dev <= 0 else 100.0 * need / dev


def _prog_name(name: str) -> str:
    n = name.split("(")[0]
    return n[4:] if n.startswith("jit_") else n


def breakdown(ctx: TraceContext, top: int = 10) -> dict:
    """Device-0 self time by program and operation, and idle time by what
    the host was doing, over the traced stretch."""
    mods = ctx.trace.modules.get(ctx.dev0, [])
    agg: Dict[str, float] = {}
    j = 0
    evs = ctx.ops()
    _, own = TR.nesting(evs)
    for e, t in zip(evs, own):
        while j + 1 < len(mods) and mods[j + 1].start <= e.start:
            j += 1
        prog = (_prog_name(mods[j].name)
                if mods and mods[j].start <= e.start < mods[j].end else "-")
        k = f"{prog}: {TR.short_name(e.name)}"
        agg[k] = agg.get(k, 0.0) + t * 1e-9
    ops = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    host = [e for e in ctx.trace.host if e.name != "bench/traced"]
    idle: Dict[str, List[float]] = {}
    for a, b in TR.gaps(ctx.busy(), ctx.lo, ctx.hi):
        mid = 0.5 * (a + b)
        label = "no bench span"
        for e in host:
            if e.start <= mid < e.end:
                label = e.name          # innermost: latest start wins
            elif e.start > mid:
                break
        idle.setdefault(label, []).append((b - a) * 1e-9)
    gaps = sorted(((f"{k} ({len(v)} gaps, longest {max(v) * 1e3:.3f} ms)",
                    sum(v)) for k, v in idle.items()), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, log=print, control: bool = False) -> dict:
    """One run of ``cell``.  With ``control`` the fp8 control's tokens take
    the served tokens' place in the correctness check, so the run has to
    come out not correct; the program's own reading is kept beside it."""
    import jax
    limit = cell.config["check"]["max_logit_gap"]
    if limit is None:
        raise SystemExit(f"{cell.config['name']}: check.max_logit_gap is "
                         f"not set; read it on the chip first "
                         f"(bench/tools/readings.py)")
    kind = devices[0].device_kind
    peak = peaks_of(kind) if trace else None
    counter = CompileCounter()
    t_build = CLOCK()
    eng, params = build(cell, seed, devices)
    t_engine = CLOCK()
    drv = Clients(eng, annotate=trace)
    n_shapes = warm_up(eng, cell, drv)
    t_warm = CLOCK()
    setup_s = t_warm - t_start
    log(f"setup: {setup_s:.3f} s = start and imports "
        f"{t_build - t_start:.3f} + weights and engine "
        f"{t_engine - t_build:.3f} + warm-up {t_warm - t_engine:.3f}; "
        f"{n_shapes} step programs warmed, {counter.total} compiles or "
        f"cache loads")

    tracer = None
    if trace:
        length = min(3.0, seconds / 3)
        tracer = Tracer(0.0, length)
    closed = cell.mix["loop"] == "closed"
    counter.in_window = True
    if tracer is not None:
        # start the trace a second into the window
        t_now = CLOCK()
        tracer.start = t_now + cell.mix["preroll_s"] + min(1.0, seconds / 4)
    window = (drive_closed if closed else drive_open)(drv, cell, seed,
                                                      seconds, tracer)
    counter.in_window = False
    log(f"compiles in window: {counter.window}")

    used = devices[:cell.config["serving"]["tp"]]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in used)
    n_steps = len(drv.steps)
    eng.cache = eng.params = None
    del eng, params
    drv.eng = None
    gc.collect()

    metrics = {}
    unfinished = sum(1 for r in window if r.first is None)
    if not trace:
        for m in cell.end_to_end:
            n = m["name"]
            if n == "setup_s":
                v = setup_s
            elif n.startswith("ttft_p"):
                v = 1e3 * percentile([r.first - r.due for r in window
                                      if r.first is not None],
                                     float(n[6:].split("_")[0]))
            elif n.startswith("itl_p"):
                v = 1e3 * percentile(drv.gaps, float(n[5:].split("_")[0]))
            elif n == "output_tok_s":
                v = drv.win_tokens / seconds
            else:
                raise KeyError(f"no measurement for end-to-end metric {n}")
            metrics[n] = {"value": v, "unit": m["unit"]}

    sample = pick_sample(drv, seed)
    res = (served_gaps(cell, seed, sample, used, control=control)
           if sample else None)
    checked = res and res["control_gaps" if control else "gaps"]
    widest = float(max(g.max() for g in checked)) if res else math.inf
    checks = {
        "max_logit_gap": {"value": widest, "limit": limit},
        "nonfinite_logits": {"value": 0 if res and res["finite"] else 1,
                             "limit": 0},
        "window_requests_without_answer": {"value": unfinished,
                                           "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"window: {len(window)} requests, {len(drv.gaps)} token gaps, "
        f"{n_steps} steps; sample {len(sample)} requests, "
        f"{sum(len(r.req.output) for r in sample)} served tokens")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    out = {"correct": correct, "attempted": len(window),
           "failed": unfinished, "metrics": metrics, "device": device}
    if trace:
        tr = TR.load(TR.find_xplane(tracer.dir))
        shutil.rmtree(tracer.dir, ignore_errors=True)
        ctx = TraceContext(tr, drv, cell, peak, len(used))
        busy = [TR.length(ctx.busy(d)) for d in ctx.devices
                if d < len(used)]
        device["busy_s"] = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0
        device["window_s"] = ctx.window_ns * 1e-9
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["breakdown"] = breakdown(ctx)
        log(f"trace: {len(ctx.steps)} steps in {device['window_s']:.3f} s")
    if control and res:
        # the program's own reading beside the control's (readings.py)
        out["program_max_logit_gap"] = float(
            max(g.max() for g in res["gaps"]))
        out["served_tokens"] = int(sum(len(r.req.output) for r in sample))
    out["checks"] = checks
    jax.clear_caches()
    return out
