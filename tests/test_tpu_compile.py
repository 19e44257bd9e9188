"""Compile the Pallas kernels for a described TPU v5e, with no chip.

Interpret mode runs a kernel's body but checks none of Mosaic's rules
(block tiling, VMEM limits, lowerable primitives); these tests hand the
real kernels at real model widths to the TPU compiler instead.  Nothing
runs: a pass means the chip's compiler accepts the kernel.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import dataclasses
import functools
import itertools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tpu_mapping import (TpuTilePlan, plan_fused_mlp,
                                    plan_gemm_tiling)
from repro.kernels.decode_attention import decode_attention
from repro.kernels.goma_gemm import goma_matmul
from repro.kernels.mamba2_ssd import ssd_pallas
from repro.kernels.ops import fused_mlp, fused_mlp_composition
from repro.kernels.wkv6 import wkv6_pallas

# stablelm-1.6b (d_model 2048, d_ff 5632, vocab 100352): its projections'
# (N, K), at the rows chip_smoke.py dispatches — decode slots and chunks
# (8 and 32 rows pad to the same 128-row plans) and a 2 x 128 prefill
STABLELM_NK = [(2048, 2048), (5632, 2048), (2048, 5632), (100352, 2048)]
GEMM_SHAPES = [(m, n, k) for m in (8, 256) for (n, k) in STABLELM_NK]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Compile for the described chip; assert a Mosaic kernel is in it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _copies(text):
    """Result shapes of the copy ops in a compiled program's text."""
    return [line.split("=", 1)[1].split("{", 1)[0].strip()
            for line in text.splitlines() if " copy(" in line]


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", GEMM_SHAPES,
                         ids=[f"{m}x{n}x{k}" for m, n, k in GEMM_SHAPES])
def test_goma_gemm_plan_compiles(one_chip, shape):
    plan = plan_gemm_tiling(*shape, dtype_bytes=2)
    pm, pn, pk = plan.padded
    _compile(lambda a, b: goma_matmul(a, b, plan),
             _sds(one_chip, (pm, pk)), _sds(one_chip, (pk, pn)))


@pytest.mark.parametrize("order", list(itertools.permutations("mnk")),
                         ids="".join)
def test_goma_gemm_grid_order_compiles(one_chip, order):
    """A single-step reduction may put any grid axis innermost."""
    M, N, K = 512, 768, 1024
    plan = TpuTilePlan(M=M, N=N, K=K, padded=(M, N, K),
                       block=(128, 256, K), grid_order=order,
                       walk={"m": "x", "n": "y", "k": "z"}[order[-1]],
                       objective=0.0, solve_time_s=0.0)
    _compile(lambda a, b: goma_matmul(a, b, plan),
             _sds(one_chip, (M, K)), _sds(one_chip, (K, N)))


@pytest.mark.parametrize("bk", [128, 512], ids=["multi_k", "single_k"])
def test_fused_chain_compiles(one_chip, bk):
    """The fused kernel and its composition oracle (goma_gemm x3 plus the
    combine kernel) at a chain the VMEM budget holds."""
    M, FF, K = 256, 1024, 512
    plan = plan_fused_mlp(M, FF, K, dtype_bytes=2)
    assert plan.fused
    plan = dataclasses.replace(plan, bk=bk)
    args = (_sds(one_chip, (M, K)), _sds(one_chip, (K, FF)),
            _sds(one_chip, (K, FF)), _sds(one_chip, (FF, K)))
    _compile(lambda a, g, u, d: fused_mlp(a, g, u, d, plan=plan,
                                          interpret=False), *args)
    _compile(lambda a, g, u, d: fused_mlp_composition(
        a, g, u, d, plan, interpret=False), *args)


def test_wkv6_compiles_rwkv6_7b_heads(one_chip):
    B, S, H, P = 1, 256, 64, 64          # rwkv6-7b: 64 heads of 64
    x = _sds(one_chip, (B, S, H, P), jnp.float32)
    _compile(lambda r, k, v, w, u: wkv6_pallas(r, k, v, w, u, chunk=64),
             x, x, x, x, _sds(one_chip, (H, P), jnp.float32))


def test_ssd_compiles_zamba2_heads(one_chip):
    B, S, H, P, N = 1, 256, 80, 64, 64   # zamba2-2.7b: 80 heads, state 64
    f32 = jnp.float32
    _compile(lambda *t: ssd_pallas(*t, chunk=64),
             _sds(one_chip, (B, S, H, P), f32),
             _sds(one_chip, (B, S, H), f32), _sds(one_chip, (H,), f32),
             _sds(one_chip, (B, S, N), f32), _sds(one_chip, (B, S, N), f32))


# (name, slots B, cache T, heads H, KV heads, head dim): the benchmark's
# decode rows — stablelm-1.6b's 24 slots x 1024 and granite-moe's 32 x
# 1280 (grouped heads, G = 2)
DECODE_SHAPES = [("stablelm", 24, 1024, 32, 32, 64),
                 ("granite", 32, 1280, 16, 8, 64)]


@pytest.mark.parametrize("shape", DECODE_SHAPES,
                         ids=[s[0] for s in DECODE_SHAPES])
def test_decode_attention_compiles(one_chip, shape):
    """The kernel reads the cache in the layout XLA stores it: nothing
    copies it in front of the custom call."""
    _, B, T, H, KV, hd = shape
    text = _compile(
        lambda q, k, v, n: decode_attention(q, k, v, kv_len=n,
                                            q_positions=n - 1),
        _sds(one_chip, (B, 1, H, hd)), _sds(one_chip, (B, T, KV, hd)),
        _sds(one_chip, (B, T, KV, hd)), _sds(one_chip, (B,), jnp.int32))
    assert _copies(text) == []


# (config, slots, cache, runs the kernel): stablelm's head_dim 64 cache
# is stored T-minor and read in place; llama3-8b's head_dim 128 cache is
# stored row-major and keeps the flash scan
DECODE_STEPS = [("stablelm-1.6b", 24, 1024, True),
                ("llama3-8b", 8, 2048, False)]


def _decode_program(one_chip, arch, B, T, jit):
    """The compiled text of ``jit(model.decode_step)`` for ``arch`` at
    full width with two layers, B slots of T positions, one write
    position a row; and the number of leaves of its parameters."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config(arch).replace(
        param_dtype="bfloat16", compute_dtype="bfloat16", layers=2)
    model = build_model(cfg)

    def placed(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = placed(jax.eval_shape(model.init_params,
                                   jax.random.PRNGKey(0)))
    text = jit(model.decode_step).lower(
        params, placed(jax.eval_shape(lambda: model.init_cache(B, T))),
        _sds(one_chip, (B, 1), jnp.int32),
        _sds(one_chip, (B,), jnp.int32)).compile().as_text()
    return cfg, text, len(jax.tree.leaves(params))


@pytest.mark.parametrize("step", DECODE_STEPS,
                         ids=[s[0] for s in DECODE_STEPS])
def test_decode_step_keeps_the_cache_in_place(one_chip, monkeypatch, step):
    """A per-slot decode step (two layers, full width): the row write,
    the attention and the layer scan leave the cache in its stored
    layout — no copy of a layer's cache anywhere in the program.  The
    kernel's step carries the stacked cache through the scan, so it is
    compiled as the engine runs it, the cache donated (else the carry
    starts from one copy of the argument); llama3-8b's keeps the
    sliced scan and is compiled as it always was."""
    import repro.models.layers as layers
    monkeypatch.setattr(layers, "interpret_default", lambda: False)
    arch, B, T, kernel = step
    jit = (functools.partial(jax.jit, donate_argnums=1) if kernel
           else jax.jit)
    cfg, text, _ = _decode_program(one_chip, arch, B, T, jit)
    assert ("tpu_custom_call" in text) == kernel
    kv_hd = [cfg.kv_heads, cfg.head_dim]

    def layer_cache(copy):          # a layer's K or V, in any view
        dims = [int(d) for d in copy[copy.index("[") + 1:-1].split(",")
                if d]
        return (math.prod(dims) >= B * T * math.prod(kv_hd)
                and (dims[-2:] == kv_hd or T in dims))
    assert [c for c in _copies(text) if layer_cache(c)] == []


def _instructions(text):
    """(name, result dims, opcode, operand names) of every array-valued
    instruction of a compiled module, fused computations included."""
    pat = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                     r"([\w-]+)\(([^)]*)\)")
    out = []
    for line in text.splitlines():
        m = pat.match(line)
        if m:
            name, dims, op, args = m.groups()
            out.append((name, [int(d) for d in dims.split(",") if d], op,
                        re.findall(r"%([\w.-]+)", args)))
    return out


def test_donated_decode_slots_updates_the_cache_in_place(one_chip,
                                                         monkeypatch):
    """The engine's ``decode_slots`` program for stablelm-1.6b at full
    width (two layers, 24 slots x 1024), its cache donated: the cache
    leaves are aliased from argument to result, and nothing in it —
    copy, slice or update, fused or not — moves a block the size of a
    layer's cache or more: the scan carries the stacked cache, writes
    each layer's rows into it and the kernel reads the layer in place."""
    import repro.models.layers as layers
    from repro.serving.engine import _named_jit
    monkeypatch.setattr(layers, "interpret_default", lambda: False)
    B, T = 24, 1024
    cfg, text, n_params = _decode_program(
        one_chip, "stablelm-1.6b", B, T,
        lambda f: _named_jit(f, "decode_slots", donate_argnums=1))
    assert text.startswith("HloModule jit_decode_slots")
    assert "tpu_custom_call" in text
    alias = re.search(r"input_output_alias=\{(.*?) \}", text).group(1)
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}", alias)
    assert sorted(map(int, aliased)) == [n_params, n_params + 1]  # K, V
    layer = B * T * cfg.kv_heads * cfg.head_dim
    ins = _instructions(text)
    dims = {name: d for name, d, _, _ in ins}
    moved = []
    for name, d, op, args in ins:
        if op in ("copy", "dynamic-slice"):
            moved.append((name, d))
        elif op == "dynamic-update-slice":     # the update, not the buffer
            moved.append((name, dims[args[1]]))
    assert any(op == "dynamic-update-slice" for _, _, op, _ in ins)
    assert [(n, d) for n, d in moved if math.prod(d) >= layer] == []


def test_sharded_decode_step_keeps_the_scan(topo, monkeypatch):
    """The TP-sharded engine's decode step (four chips, heads over the
    model axis) spans a mesh, which a Mosaic kernel cannot be split
    over: it keeps the flash scan and compiles."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro.models.layers as layers
    from repro.configs import get_config
    from repro.models import build_model
    from repro.sharding.rules import param_shardings
    monkeypatch.setattr(layers, "interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    cfg = get_config("stablelm-1.6b").replace(
        param_dtype="bfloat16", compute_dtype="bfloat16", layers=2)
    model = build_model(cfg)
    B, T = 4, 128
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, param_shardings(shapes, mesh, mode="tp"))
    rep = NamedSharding(mesh, P())
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: model.init_cache(B, T)))
    text = jax.jit(model.decode_step).lower(
        params, cache, _sds(rep, (B, 1), jnp.int32),
        _sds(rep, (B,), jnp.int32)).compile().as_text()
    assert "all-reduce" in text and "tpu_custom_call" not in text
