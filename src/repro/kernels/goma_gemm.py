"""GOMA-tiled Pallas TPU GEMM kernel.

The BlockSpec tiling (bm, bn, bk) and the grid iteration order are not
hand-tuned: they come from the GOMA exact solver instantiated with the
TPU-v5e-like hierarchy (core/tpu_mapping.py).  GOMA's walking axis is the
innermost grid dimension — the axis whose operand projection stays
VMEM-resident between consecutive grid steps; its z-walk is the classic
accumulate-in-VMEM schedule, derived here from the paper's geometry
instead of folklore.

The plan's structure also drives the Mosaic compiler hints: m/n grid
dimensions touch disjoint output blocks and are declared "parallel"
(Mosaic may reorder/parallelize them), while k carries the accumulator
and is "arbitrary" (sequential), in the plan's grid order.  When the
plan has no k tiling (nk == 1) each block's dot is complete, so the
VMEM accumulator scratch and the flush epilogue are skipped entirely
and the dot is written straight to the output block.

VMEM: the kernel declares ``VMEM_LIMIT_BYTES`` to Mosaic; the planner
sizes its blocks so that the double-buffered blocks and the accumulator
(``TpuTilePlan.vmem_bytes``) fit the budget within it (core/tpu_mapping).

Validated against ref.matmul_ref in interpret mode (CPU) over a
shape/dtype sweep; tests/test_tpu_compile.py compiles the plans of real
model widths for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.tpu_mapping import VMEM_LIMIT_BYTES, TpuTilePlan


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_axis: int | None,
                   nk: int):
    k = pl.program_id(k_axis) if k_axis is not None else 0

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _matmul_kernel_single_k(a_ref, b_ref, o_ref):
    # nk == 1: the block dot is the whole reduction — no accumulator
    # scratch, no init/flush branches
    o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def goma_matmul(a: jnp.ndarray, b: jnp.ndarray, plan: TpuTilePlan,
                *, out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """C = A @ B on padded shapes; A: (pm, pk), B: (pk, pn)."""
    pm, pn, pk = plan.padded
    bm, bn, bk = plan.block
    assert a.shape == (pm, pk) and b.shape == (pk, pn), (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    order = plan.grid_order
    pos = {g: i for i, g in enumerate(order)}
    grid = plan.grid
    nk = pk // bk
    # the accumulator carries one output block across consecutive grid
    # steps only: a split reduction must be the innermost walk
    assert nk == 1 or order[-1] == "k", plan
    k_axis = pos["k"] if nk > 1 else None

    def a_map(*idx):
        return (idx[pos["m"]], idx[pos["k"]])

    def b_map(*idx):
        return (idx[pos["k"]], idx[pos["n"]])

    def o_map(*idx):
        return (idx[pos["m"]], idx[pos["n"]])

    # m/n blocks are independent (parallel); k is the sequential
    # reduction walk — ordered per the plan's grid order
    params = pltpu.CompilerParams(
        dimension_semantics=tuple(
            "arbitrary" if g == "k" else "parallel" for g in order),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)
    if nk == 1:
        kernel = _matmul_kernel_single_k
        scratch = []
    else:
        kernel = functools.partial(_matmul_kernel, k_axis=k_axis, nk=nk)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), a_map),
                  pl.BlockSpec((bk, bn), b_map)],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(a, b)
