"""Jit'd public wrappers around the Pallas kernels.

``gemm`` is the user-facing entry: it pads to the GOMA plan's MXU-aligned
shape, dispatches the Pallas kernel, and slices the result back.  On the
CPU backend it runs the kernel in interpret mode (the correctness path)
unless ``force_xla=True`` picks the plain XLA dot instead; on any other
backend the kernel compiles, or fails to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.tpu_mapping import plan_fused_mlp, plan_gemm_tiling
from .goma_fused import ACTIVATIONS, goma_combine, goma_fused_matmul
from .goma_gemm import goma_matmul
from .ref import matmul_ref


def interpret_default() -> bool:
    """Interpret Pallas kernels on the CPU only, where Mosaic cannot
    compile them; an accelerator never falls back to the interpreter."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit,
                   static_argnames=("interpret", "force_xla", "plan"))
@jax.named_scope("goma_gemm")
def gemm(a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool | None = None,
         force_xla: bool = False, plan=None) -> jnp.ndarray:
    """C[M,N] = A[M,K] @ B[K,N] through the GOMA-planned Pallas kernel.

    ``plan``: an explicit TpuTilePlan (e.g. rehydrated from a plan store
    or ModelMappingManifest via ``planner.tile_plan_from_store``) — skips
    the in-process planner entirely.  Default: ``plan_gemm_tiling``,
    which itself reads through the plan database when one is installed.

    Its ops carry the ``goma_gemm`` scope in their HLO ``op_name``, so a
    profile finds the kernel's device time under that name.
    """
    if force_xla:
        return matmul_ref(a, b)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    if plan is None:
        plan = plan_gemm_tiling(M, N, K,
                                dtype_bytes=jnp.dtype(a.dtype).itemsize)
    assert (plan.M, plan.N, plan.K) == (M, N, K), (plan, (M, N, K))
    pm, pn, pk = plan.padded
    a_p = jnp.pad(a, ((0, pm - M), (0, pk - K)))
    b_p = jnp.pad(b, ((0, pk - K), (0, pn - N)))
    itp = interpret_default() if interpret is None else interpret
    out = goma_matmul(a_p, b_p, plan, interpret=itp)
    return out[:M, :N]


def gemm_plan_info(M: int, N: int, K: int, dtype_bytes: int = 2):
    """Expose the GOMA plan (for logging / EXPERIMENTS.md §Perf)."""
    return plan_gemm_tiling(M, N, K, dtype_bytes=dtype_bytes)


def _pad2(x, rows, cols):
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


@functools.partial(jax.jit, static_argnames=("activation", "interpret",
                                             "plan"))
def fused_mlp_composition(a: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                          wd: jnp.ndarray, plan, *,
                          activation: str = "silu_mul",
                          interpret: bool | None = None) -> jnp.ndarray:
    """The *unfused* composition (producer ``goma_matmul`` x2, the
    ``goma_combine`` kernel, consumer ``goma_matmul``) under the fused
    plan's compatibility tiles — the bit-identity oracle the fused
    kernel must match token-for-token."""
    M, K = a.shape
    _, N2 = wd.shape
    pm, pff, pk, pn2 = plan.padded
    itp = interpret_default() if interpret is None else interpret
    a_p = _pad2(a, pm, pk)
    hg = goma_matmul(a_p, _pad2(wg, pk, pff), plan.producer_plan(),
                     interpret=itp)
    hu = goma_matmul(a_p, _pad2(wu, pk, pff), plan.producer_plan(),
                     interpret=itp)
    act = goma_combine(hg, hu, plan, activation=activation, interpret=itp)
    out = goma_matmul(act, _pad2(wd, pff, pn2), plan.consumer_plan(),
                      interpret=itp)
    return out[:M, :N2]


@functools.partial(jax.jit, static_argnames=("activation", "interpret",
                                             "force_xla", "plan"))
@jax.named_scope("goma_fused_mlp")
def fused_mlp(a: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
              wd: jnp.ndarray, *, activation: str = "silu_mul",
              interpret: bool | None = None, force_xla: bool = False,
              plan=None) -> jnp.ndarray:
    """``out[M,N2] = act(A@Wg, A@Wu) @ Wd`` through the GOMA-chain-planned
    fused Pallas kernel (intermediate strips in VMEM scratch, zero HBM
    round-trips).

    ``plan``: an explicit ``FusedTilePlan`` (e.g. prewarmed through the
    plan store's fused section).  Default: ``plan_fused_mlp``, which
    reads through the plan database when one is installed.  When the
    chain solver kept the unfused pair (residency infeasible),
    dispatches the ordinary per-GEMM ``gemm`` composition instead.

    Its ops carry the ``goma_fused_mlp`` scope in their HLO ``op_name``
    (see ``gemm``).
    """
    M, K = a.shape
    K2, FF = wg.shape
    FF2, N2 = wd.shape
    assert K == K2 and wu.shape == (K, FF) and FF2 == FF, (
        a.shape, wg.shape, wu.shape, wd.shape)
    if force_xla:
        act = ACTIVATIONS[activation](matmul_ref(a, wg), matmul_ref(a, wu))
        return matmul_ref(act, wd)
    if plan is None:
        plan = plan_fused_mlp(M, FF, K, N2,
                              dtype_bytes=jnp.dtype(a.dtype).itemsize)
    assert (plan.M, plan.FF, plan.K, plan.N2) == (M, FF, K, N2), (
        plan, (M, FF, K, N2))
    itp = interpret_default() if interpret is None else interpret
    if not plan.fused:
        act = ACTIVATIONS[activation](gemm(a, wg, interpret=interpret),
                                      gemm(a, wu, interpret=interpret))
        return gemm(act, wd, interpret=interpret)
    pm, pff, pk, pn2 = plan.padded
    out = goma_fused_matmul(_pad2(a, pm, pk), _pad2(wg, pk, pff),
                            _pad2(wu, pk, pff), _pad2(wd, pff, pn2),
                            plan, activation=activation, interpret=itp)
    return out[:M, :N2]
