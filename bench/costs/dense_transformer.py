"""Operations and bytes a dense transformer step needs, from shapes alone.

A dispatch is a list of rows; a row is ``(start, n, sampled)``: ``n`` new
tokens at positions ``start .. start+n-1`` of one sequence, attending to
every earlier position of that sequence and to themselves, and ``sampled``
LM-head positions (1 for a prefill chunk that ends a prompt or a decode
row; a chunk that ends mid-prompt still samples one token in this engine).

Counted: the weight matmuls (2 per weight per token), attention scores and
values over each token's real context (2 * 2 * heads * head_dim per key),
and the LM head at the sampled positions.  Not counted: norms, rotary,
softmax and other elementwise work, so a share of peak built on these is a
lower bound on the chip's work.  Bytes: every weight the step reads once
(the embedding only at the rows looked up), the KV of each row's real
context read once and its new KV written once.  Activations are left out:
they are not needed by a minimal implementation that keeps them on chip.

All counts are of the whole model; ``per_chip`` divides by the
tensor-parallel degree, which splits heads, the feed-forward width and the
vocabulary evenly (the configurations checked here split exactly).
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    return (d, h, cfg["num_key_value_heads"], dh, cfg["intermediate_size"],
            cfg["vocab_size"], cfg["num_hidden_layers"])


def layer_matmul_params(cfg: dict) -> int:
    d, h, kv, dh, f, _, _ = _dims(cfg)
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def nonembed_params(cfg: dict) -> int:
    """Parameters outside the embedding and LM head (norms and biases
    included)."""
    d, h, kv, dh, f, _, L = _dims(cfg)
    per = layer_matmul_params(cfg) + 2 * d
    if cfg.get("qkv_bias"):
        per += (h + 2 * kv) * dh
    if cfg.get("qk_norm"):
        per += 2 * dh
    return L * per + d


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    _, _, kv, dh, _, _, L = _dims(cfg)
    return L * 2 * kv * dh * itemsize


def flops(cfg: dict, rows) -> float:
    """Model FLOPs of one dispatch over ``rows`` (whole model)."""
    d, h, _, dh, _, v, L = _dims(cfg)
    lin = 2 * L * layer_matmul_params(cfg)
    total = 0.0
    for start, n, sampled in rows:
        # sum of context lengths start+1 .. start+n
        ctx = n * start + n * (n + 1) // 2
        total += lin * n + 4 * L * h * dh * ctx + 2 * d * v * sampled
    return total


def bytes_moved(cfg: dict, rows, itemsize: int = 2) -> float:
    """Bytes of one dispatch over ``rows`` (whole model): weights once,
    the real context's KV read and the new KV written."""
    d, _, _, _, _, v, _ = _dims(cfg)
    weights = (nonembed_params(cfg) + d * v) * itemsize
    kvt = kv_bytes_per_token(cfg, itemsize)
    toks = sum(n for _, n, _ in rows)
    ctx = sum(start + n for start, n, _ in rows)
    return weights + toks * d * itemsize + kvt * ctx


def per_chip(x: float, tp: int) -> float:
    return x / tp
