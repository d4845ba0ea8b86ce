"""Plain float32 reference of a dense decoder-only transformer (the Qwen1.5
and Qwen3 families), and the seeded weights it and the system under test
both run on.

Architecture, as in the Hugging Face ``Qwen2ForCausalLM`` and
``Qwen3ForCausalLM`` code: token embedding; per layer a pre-norm (RMSNorm)
attention block with rotary positions (rotate-half, theta from the config),
optional q/k/v bias (Qwen1.5) and optional per-head RMSNorm of q and k
before the rotation (Qwen3), grouped-query attention with causal softmax at
scale head_dim**-0.5, an output projection and a residual add; then a
pre-norm SwiGLU feed-forward (silu(x Wg) * (x Wu)) Wd with a residual add;
a final RMSNorm and an untied LM head.  Every matmul runs in float32 at
``highest`` precision.

Weights are random from the seed, in the type they are served in
(``torch_dtype``, bfloat16 for the benchmark's configurations), made on the
device.  ``layer_weights(key, cfg, l)`` gives layer
``l`` alone, so the reference can regenerate one layer at a time after the
system under test has freed its copy; ``model_weights`` makes all of them
in one call, stacked on a leading layer axis, with the same values.

Nothing here imports the system under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def wdtype(cfg: dict):
    """The type the weights are served in."""
    return jnp.dtype(cfg.get("torch_dtype", "bfloat16"))


def _normal(key, shape, scale, dt):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)


def _gain(key, shape, dt):
    # RMSNorm gains near 1, so a norm applied with the wrong gain shows
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dt)


def layer_weights(key, cfg: dict, layer):
    """Plain weights of layer ``layer`` (a Python or traced int)."""
    m = dims(cfg)
    d, h, kv, dh, f = m["d"], m["h"], m["kv"], m["dh"], m["f"]
    k = jax.random.split(jax.random.fold_in(key, layer), 14)
    dt = wdtype(cfg)
    w = {
        "norm_in": _gain(k[0], (d,), dt),
        "wq": _normal(k[1], (d, h * dh), d ** -0.5, dt),
        "wk": _normal(k[2], (d, kv * dh), d ** -0.5, dt),
        "wv": _normal(k[3], (d, kv * dh), d ** -0.5, dt),
        "wo": _normal(k[4], (h * dh, d), (h * dh) ** -0.5, dt),
        "norm_post": _gain(k[5], (d,), dt),
        "w_gate": _normal(k[6], (d, f), d ** -0.5, dt),
        "w_up": _normal(k[7], (d, f), d ** -0.5, dt),
        "w_down": _normal(k[8], (f, d), f ** -0.5, dt),
    }
    if cfg.get("qkv_bias"):
        w["bq"] = _normal(k[9], (h * dh,), 0.5, dt)
        w["bk"] = _normal(k[10], (kv * dh,), 0.5, dt)
        w["bv"] = _normal(k[11], (kv * dh,), 0.5, dt)
    if cfg.get("qk_norm"):
        w["q_norm"] = _gain(k[12], (dh,), dt)
        w["k_norm"] = _gain(k[13], (dh,), dt)
    return w


def top_weights(key, cfg: dict):
    """Embedding, final norm and LM head."""
    m = dims(cfg)
    k = jax.random.split(jax.random.fold_in(key, 2 ** 31), 3)
    dt = wdtype(cfg)
    return {"embed": _normal(k[0], (m["v"], m["d"]), 1.0, dt),
            "norm_final": _gain(k[1], (m["d"],), dt),
            "lm_head": _normal(k[2], (m["d"], m["v"]), m["d"] ** -0.5, dt)}


def model_weights(key, cfg: dict):
    """All weights; layer leaves stacked on axis 0 (same values as
    ``layer_weights`` layer by layer)."""
    layers = jax.vmap(lambda l: layer_weights(key, cfg, l))(
        jnp.arange(cfg["num_hidden_layers"]))
    return {"top": top_weights(key, cfg), "layers": layers}


# --------------------------------------------------------------------------
# forward, float32
# --------------------------------------------------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (S, n, dh), rotate-half convention."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _mm(a, w, quant):
    w = w.astype(jnp.float32)
    if quant is not None:
        a, w = quant(a, 1), quant(w, 0)
    return a @ w


def layer_forward(w, x, cfg: dict, quant=None):
    """One layer over one sequence x (S, d) float32, causal from position 0
    (padding may follow the real tokens: it never feeds a real one)."""
    m = dims(cfg)
    h, kv, dh = m["h"], m["kv"], m["dh"]
    eps = cfg["rms_norm_eps"]
    f32 = {n: a.astype(jnp.float32) for n, a in w.items()
           if not n.startswith("w")}
    s = x.shape[0]
    pos = jnp.arange(s)
    a = _rms(x, f32["norm_in"], eps)
    q = _mm(a, w["wq"], quant)
    k = _mm(a, w["wk"], quant)
    v = _mm(a, w["wv"], quant)
    if "bq" in w:
        q, k, v = q + f32["bq"], k + f32["bk"], v + f32["bv"]
    q = q.reshape(s, h, dh)
    k = k.reshape(s, kv, dh)
    v = v.reshape(s, kv, dh)
    if "q_norm" in w:
        q = _rms(q, f32["q_norm"], eps)
        k = _rms(k, f32["k_norm"], eps)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    g = h // kv
    k = jnp.repeat(k, g, axis=1)          # query head i reads kv head i // g
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -0.5
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, h * dh)
    x = x + _mm(o, w["wo"], quant)
    a = _rms(x, f32["norm_post"], eps)
    ff = jax.nn.silu(_mm(a, w["w_gate"], quant)) * _mm(a, w["w_up"], quant)
    return x + _mm(ff, w["w_down"], quant)


def embed(top, ids):
    return top["embed"][ids].astype(jnp.float32)


def head_logits(top, x, cfg: dict, quant=None):
    """Logits (n, V) at the rows of x (n, d)."""
    return _mm(_rms(x, top["norm_final"].astype(jnp.float32),
                    cfg["rms_norm_eps"]), top["lm_head"], quant)


def fp8_quant(x, axis):
    """Round to float8 e4m3 with one scale per row (axis=1: per token of an
    activation) or per column (axis=0: per output channel of a weight),
    then back to float32: the lower-precision control."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def forward_logits(weights, ids, idx, cfg: dict, quant=None):
    """Whole-model logits of one sequence at positions ``idx``, unblocked
    (for tests at small sizes)."""
    with jax.default_matmul_precision("highest"):
        x = embed(weights["top"], jnp.asarray(ids))
        for l in range(cfg["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[l], weights["layers"])
            x = layer_forward(w, x, cfg, quant)
        return head_logits(weights["top"], x[jnp.asarray(idx)], cfg, quant)


def blocked_logits(key, cfg: dict, seqs, idxs, *, devices, quant=None):
    """Reference logits of many sequences, layer by layer: the weights of
    one layer at a time are regenerated from ``key``, and the sequences are
    spread over ``devices`` (each device takes whole sequences).

    seqs: list of token lists; idxs: list of position lists.  Returns a
    list of float32 numpy arrays (len(idx), V)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    nd = len(devices)
    mesh = Mesh(np.asarray(devices), ("x",))
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("x"))
    n = len(seqs)
    n_pad = -(-n // nd) * nd
    s_pad = -(-max(len(s) for s in seqs) // 256) * 256
    ids = np.zeros((n_pad, s_pad), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    w_idx = max(len(i) for i in idxs)
    pick = np.zeros((n_pad, w_idx), np.int32)
    for i, ix in enumerate(idxs):
        pick[i, :len(ix)] = ix

    top = jax.jit(lambda k: top_weights(k, cfg), out_shardings=rep)(key)
    gen = jax.jit(lambda k, l: layer_weights(k, cfg, l), out_shardings=rep)

    def per_device(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P(), P("x")),
                                     out_specs=P("x"), check_vma=False))

    with jax.default_matmul_precision("highest"):
        emb = per_device(lambda t, i: jax.vmap(lambda r: embed(t, r))(i))
        lay = per_device(lambda w, x: jax.lax.map(
            lambda r: layer_forward(w, r, cfg, quant), x))
        head = per_device(lambda t, xp: jax.lax.map(
            lambda r: head_logits(t, r, cfg, quant), xp))
        x = emb(top, jax.device_put(ids, row))
        for l in range(cfg["num_hidden_layers"]):
            x = lay(gen(key, l), x)
        xp = jnp.take_along_axis(x, jax.device_put(pick, row)[..., None],
                                 axis=1)
        lg = np.asarray(head(top, xp))
    return [lg[i, :len(ix)] for i, ix in enumerate(idxs)]
