"""GOMA-chain-tiled fused gated-MLP Pallas kernel.

Executes the two-link chain ``out = act(A@Wg, A@Wu) @ Wd`` in one
``pallas_call``: the intermediate strip ``(bm, FF)`` lives in VMEM
scratch, never touching HBM — the execution the chain solver's residency
credit prices (core/fusion.py).  The (bm, bk) tiling is not hand-tuned:
it comes from ``core.tpu_mapping.plan_fused_mlp`` (the exact chain solve
on the TPU-v5e-like hierarchy).

Bit-identity contract: the kernel is token-identical to the unfused
composition under the plan's compatibility tiles
(``FusedTilePlan.producer_plan`` / ``consumer_plan``) — same bk-ordered
fp32 accumulation of both producers, same cast to the I/O dtype before
the elementwise combine, the combine itself as a kernel
(``goma_combine``: XLA and Mosaic may round a bf16 elementwise chain
differently), and a single full-K fp32 dot for the consumer (the
composition's nk == 1 fast path).  Enforced by tests/test_kernels.py,
the bench_fusion smoke gate and, compiled, by chip_smoke.py.

Grid semantics: m strips are independent ("parallel"); k carries the
strip accumulators and is sequential ("arbitrary"), innermost — the
chain solver's z-walk realized, as in goma_gemm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.tpu_mapping import VMEM_LIMIT_BYTES, FusedTilePlan

# Elementwise combines (chain.elementwise -> jnp op on (gate, up)).
# Both kernels apply them to producer outputs already cast down to the
# I/O dtype (``_combine``), as the unfused composition does.
ACTIVATIONS = {
    "silu_mul": lambda g, u: jax.nn.silu(g) * u,
    "gelu_mul": lambda g, u: jax.nn.gelu(g) * u,
    "sqrelu_mul": lambda g, u: jnp.square(jax.nn.relu(g)) * u,
    "identity": lambda g, u: g * u,
}


def _fused_kernel(a_ref, wg_ref, wu_ref, wd_ref, o_ref, hg_ref, hu_ref, *,
                  nk: int, activation: str, io_dtype, interpret: bool):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        hg_ref[...] = jnp.zeros_like(hg_ref)
        hu_ref[...] = jnp.zeros_like(hu_ref)

    hg_ref[...] += jnp.dot(a_ref[...], wg_ref[...],
                           preferred_element_type=jnp.float32)
    hu_ref[...] += jnp.dot(a_ref[...], wu_ref[...],
                           preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _consume():
        g = _rounded(hg_ref[...].astype(io_dtype), interpret)
        u = _rounded(hu_ref[...].astype(io_dtype), interpret)
        act = _rounded(_combine(g, u, activation), interpret)
        o_ref[...] = jnp.dot(act, wd_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _combine(g, u, activation: str):
    """The elementwise combine of two rounded producer strips, computed
    in f32 and rounded once to their dtype (Mosaic lowers no bf16
    logistic)."""
    f32 = jnp.float32
    return ACTIVATIONS[activation](g.astype(f32), u.astype(f32)
                                   ).astype(g.dtype)


def _rounded(x, interpret: bool):
    """Force the value to materialize in its stated dtype.

    The unfused composition rounds the intermediate to the I/O dtype at
    every pallas_call boundary.  Mosaic rounds at the ``astype`` itself
    (and lowers no optimization barrier); XLA, which runs the body in
    interpret mode, would otherwise fuse the cast/elementwise into the
    consumer dot and keep extra precision — bit-breaking the composition
    contract for bf16."""
    return jax.lax.optimization_barrier(x) if interpret else x


def _fused_kernel_single_k(a_ref, wg_ref, wu_ref, wd_ref, o_ref, *,
                           activation: str, io_dtype, interpret: bool):
    # nk == 1: each producer dot is the whole reduction — no strip
    # accumulators, no init branch (mirrors goma_gemm's fast path)
    g = _rounded(jnp.dot(a_ref[...], wg_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(io_dtype), interpret)
    u = _rounded(jnp.dot(a_ref[...], wu_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(io_dtype), interpret)
    act = _rounded(_combine(g, u, activation), interpret)
    o_ref[...] = jnp.dot(act, wd_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def goma_fused_matmul(a: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                      wd: jnp.ndarray, plan: FusedTilePlan, *,
                      activation: str = "silu_mul", out_dtype=None,
                      interpret: bool = False) -> jnp.ndarray:
    """out = act(A@Wg, A@Wu) @ Wd on padded shapes.

    A: (pm, pk); Wg/Wu: (pk, pff); Wd: (pff, pn2).  The ``(bm, pff)``
    intermediate strips live in VMEM scratch per the plan."""
    pm, pff, pk, pn2 = plan.padded
    assert a.shape == (pm, pk), (a.shape, plan)
    assert wg.shape == (pk, pff) and wu.shape == (pk, pff), (wg.shape,
                                                            wu.shape, plan)
    assert wd.shape == (pff, pn2), (wd.shape, plan)
    assert plan.fused and plan.bm > 0, ("unfused plan dispatched to the "
                                        "fused kernel", plan)
    bm, bk = plan.bm, plan.bk
    out_dtype = out_dtype or a.dtype
    io_dtype = a.dtype
    nm, nk = plan.grid

    if nk == 1:
        kernel = functools.partial(_fused_kernel_single_k,
                                   activation=activation, io_dtype=io_dtype,
                                   interpret=interpret)
        scratch = []
    else:
        kernel = functools.partial(_fused_kernel, nk=nk,
                                   activation=activation, io_dtype=io_dtype,
                                   interpret=interpret)
        scratch = [pltpu.VMEM((bm, pff), jnp.float32),
                   pltpu.VMEM((bm, pff), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(nm, nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda m, k: (m, k)),
                  pl.BlockSpec((bk, pff), lambda m, k: (k, 0)),
                  pl.BlockSpec((bk, pff), lambda m, k: (k, 0)),
                  pl.BlockSpec((pff, pn2), lambda m, k: (0, 0))],
        out_specs=pl.BlockSpec((bm, pn2), lambda m, k: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((pm, pn2), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(a, wg, wu, wd)


def _combine_kernel(g_ref, u_ref, o_ref, *, activation: str):
    o_ref[...] = _combine(g_ref[...], u_ref[...], activation)


def goma_combine(g: jnp.ndarray, u: jnp.ndarray, plan: FusedTilePlan, *,
                 activation: str = "silu_mul",
                 interpret: bool = False) -> jnp.ndarray:
    """``act(g, u)`` over the plan's (bm, pff) strips: the unfused
    composition's elementwise step, lowered as the fused kernel's own
    combine is."""
    pm, pff = g.shape
    spec = pl.BlockSpec((plan.bm, pff), lambda m: (m, 0))
    return pl.pallas_call(
        functools.partial(_combine_kernel, activation=activation),
        grid=(pm // plan.bm,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(g, u)
