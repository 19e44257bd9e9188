"""Least work of one program call of a dense decoder, from its shapes.

The count is what any correct implementation must do, so no kernel can
read above 100% of the roofline it sets:

* every weight is read once per call (the embedding only at the rows the
  call's tokens select);
* keys and values are read at the positions already written (``past``)
  and written at the new ones -- never at the reserved ``cache_len``;
* attention counts each query against the keys at or before it;
* the output head runs only for tokens whose logits are needed.

A call is a list of rows ``(q, past, logits)``: ``q`` new tokens at
positions ``past .. past + q - 1``, of which ``logits`` need logits.
Configuration keys are the published ``config.json`` names.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return {"d": d, "heads": heads,
            "kv_heads": cfg.get("num_key_value_heads", heads),
            "head_dim": cfg.get("head_dim", d // heads),
            "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"],
            "wb": BYTES[cfg["param_dtype"]],
            "cb": BYTES[cfg["compute_dtype"]]}


def attn_params(cfg: dict) -> int:
    """Weights of one attention block (q, k, v, o projections)."""
    m = dims(cfg)
    q = m["heads"] * m["head_dim"]
    kv = m["kv_heads"] * m["head_dim"]
    return m["d"] * (q + 2 * kv) + q * m["d"]


def norm_params(cfg: dict) -> int:
    per = 2 if cfg.get("norm", "rmsnorm") == "layernorm" else 1
    return per * cfg["hidden_size"]


def kv_bytes_per_token(cfg: dict) -> int:
    """Cache bytes one token adds over all layers (keys and values)."""
    m = dims(cfg)
    return m["layers"] * 2 * m["kv_heads"] * m["head_dim"] * m["cb"]


def attention_flops(cfg: dict, q: int, past: int) -> float:
    """Scores and weighted values of q queries at past .. past + q - 1,
    each against the keys at or before it, over all layers."""
    m = dims(cfg)
    keys = q * past + q * (q + 1) // 2
    return 4.0 * m["layers"] * m["heads"] * m["head_dim"] * keys


def stack_work(cfg: dict, rows, layer_params: int,
               layer_flops_per_token: float,
               layer_bytes: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one call given the per-layer weights that every
    token uses (``layer_params``), the per-token per-layer FLOPs besides
    them, and the per-layer weight bytes the call must read."""
    m = dims(cfg)
    tokens = sum(q for q, _, _ in rows)
    logit_rows = sum(lg for _, _, lg in rows)
    flops = tokens * m["layers"] * (2.0 * layer_params
                                   + layer_flops_per_token)
    flops += sum(attention_flops(cfg, q, past) for q, past, _ in rows)
    flops += 2.0 * m["d"] * m["vocab"] * logit_rows
    head = m["d"] * m["vocab"] + norm_params(cfg)
    nbytes = m["layers"] * layer_bytes + head * m["wb"]
    nbytes += tokens * m["d"] * m["wb"]                 # embedding rows
    kvb = kv_bytes_per_token(cfg)
    nbytes += sum((past + q) * kvb for q, past, _ in rows)
    return flops, nbytes


def call_work(cfg: dict, rows) -> tuple[float, float]:
    """(FLOPs, HBM bytes) that one call over ``rows`` cannot go under."""
    m = dims(cfg)
    per_layer = attn_params(cfg) + 3 * m["d"] * cfg["intermediate_size"]
    return stack_work(cfg, rows, per_layer, 0.0,
                      (per_layer + 2 * norm_params(cfg)) * m["wb"])

