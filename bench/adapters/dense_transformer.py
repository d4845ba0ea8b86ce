"""The system under test for a dense transformer configuration: its
``ModelConfig``, its parallel and scheduler settings, and the seeded plain
weights of ``reference/dense_transformer.py`` laid out as its parameter
tree (per-shard leading axis, layers stacked for the scan)."""
from __future__ import annotations

import importlib.util
import os

import jax

_REF = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "reference", "dense_transformer.py")
_spec = importlib.util.spec_from_file_location("bench_ref_dense", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    m = ref.dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=m["L"],
        d_model=m["d"], num_heads=m["h"], num_kv_heads=m["kv"],
        head_dim=m["dh"], d_ff=m["f"], vocab_size=m["v"],
        qkv_bias=bool(cfg.get("qkv_bias")), qk_norm=bool(cfg.get("qk_norm")),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings")),
        dtype=cfg.get("torch_dtype", "bfloat16"))


def parallel_config(serving: dict):
    from repro.configs.base import ParallelConfig
    extra = {k: serving[k] for k in ("tokenweave_min_tokens", "split_unit")
             if k in serving}
    return ParallelConfig(comm_mode=serving["comm_mode"],
                          tokenweave=serving["tokenweave"],
                          remat=serving["remat"], **extra)


def scheduler_config(serving: dict):
    from repro.runtime.scheduler import SchedulerConfig
    return SchedulerConfig(max_batch=serving["max_batch"],
                           max_len=serving["max_len"],
                           chunk_tokens=serving["chunk_tokens"],
                           prefill_bucket=serving["prefill_bucket"],
                           paged=serving["paged"], packed=serving["packed"],
                           plan_path=serving["plan_path"])


def _cols(w, tp):
    """(..., a, b) column-split into tp shards -> (..., tp, a, b/tp)."""
    *lead, a, b = w.shape
    w = w.reshape(*lead, a, tp, b // tp)
    return jax.numpy.moveaxis(w, -2, -3)


def _rows(w, tp):
    """(..., a, b) row-split -> (..., tp, a/tp, b)."""
    *lead, a, b = w.shape
    return w.reshape(*lead, tp, a // tp, b)


def program_params(plain: dict, cfg: dict, tp: int):
    """Plain weights -> the program's parameter tree for ``tp`` shards."""
    from repro.layers.attention import attention_layout
    m = ref.dims(cfg)
    lay = attention_layout(tp, m["h"], m["kv"], m["dh"])
    if lay.replicas != 1 or lay.kv_store * tp != m["kv"]:
        raise ValueError(f"heads {m['h']}/{m['kv']} do not split evenly "
                         f"over tp={tp}")
    if m["v"] % tp:
        raise ValueError(f"vocab {m['v']} does not split over tp={tp}")
    L = plain["layers"]
    top = plain["top"]

    def vec(b):                      # (L, n) -> (L, tp, n/tp)
        return b.reshape(b.shape[0], tp, b.shape[1] // tp)

    attn = {"wq": _cols(L["wq"], tp), "wk": _cols(L["wk"], tp),
            "wv": _cols(L["wv"], tp), "wo": _rows(L["wo"], tp)}
    if "bq" in L:
        attn.update(bq=vec(L["bq"]), bk=vec(L["bk"]), bv=vec(L["bv"]))
    if "q_norm" in L:
        attn.update(q_norm=L["q_norm"][:, None], k_norm=L["k_norm"][:, None])
    # the program applies layer i+1's input norm at the end of layer i
    # (and the final norm at the end of the last layer)
    nxt = jax.numpy.concatenate([L["norm_in"][1:], top["norm_final"][None]])
    layers = {"attn": attn, "norm_attn": L["norm_post"][:, None],
              "norm_ffn": nxt[:, None],
              "mlp": {"w_gate": _cols(L["w_gate"], tp),
                      "w_up": _cols(L["w_up"], tp),
                      "w_down": _rows(L["w_down"], tp)}}
    return {"embedding": {"embed": _rows(top["embed"], tp),
                          "lm_head": _cols(top["lm_head"], tp)},
            "norm_first": L["norm_in"][0][None],
            "layers": layers}


def build_params(key, cfg: dict, api, mesh):
    """The program's parameters from ``key``, made on the device in one
    jitted call straight into the program's sharding."""
    from repro.models.build import named_shardings
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not laid out here")
    return jax.jit(
        lambda k: program_params(ref.model_weights(k, cfg), cfg, api.tp),
        out_shardings=named_shardings(mesh, api.specs()))(key)
