"""Plain float32 reference of a dense decoder: pre-norm attention and a
gated SiLU MLP, scanned over the layers (see ``common``)."""
from __future__ import annotations

import jax

from . import common


def mlp(num: common.Numerics, h, lp):
    p = lp["mlp"]
    g = num.linear(h, p["wg"]["w"])
    u = num.linear(h, p["wu"]["w"])
    return num.linear(jax.nn.silu(g) * u, p["wd"]["w"])


def gap_fn(cfg: dict, precision: str = "float32"):
    return common.gap_fn(cfg, precision, mlp)
