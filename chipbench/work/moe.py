"""Least work of one program call of a mixture-of-experts decoder.

As ``dense``, with the MLP replaced by routed experts: each token runs
its ``num_experts_per_tok`` experts and the router, never the others,
and a call reads at least the weights of ``num_experts_per_tok`` experts
in every layer -- the fewest that any routing of at least one token can
touch.  The router is held in float32.
"""
from __future__ import annotations

from . import dense


def call_work(cfg: dict, rows) -> tuple[float, float]:
    """(FLOPs, HBM bytes) that one call over ``rows`` cannot go under."""
    m = dense.dims(cfg)
    n_exp = cfg["num_local_experts"]
    top_k = cfg["num_experts_per_tok"]
    expert = 3 * m["d"] * cfg["intermediate_size"]
    attn = dense.attn_params(cfg)
    router = m["d"] * n_exp
    per_token = 2.0 * (top_k * expert + router)
    layer_bytes = ((attn + 2 * dense.norm_params(cfg)) * m["wb"]
                   + router * 4 + top_k * expert * m["wb"])
    return dense.stack_work(cfg, rows, attn, per_token, layer_bytes)

