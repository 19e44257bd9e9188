"""Pallas TPU kernels for the framework's compute hot-spots.

  goma_gemm   — GEMM whose BlockSpec tiling + grid walk order come from
                the GOMA exact solver on the HBM->VMEM->MXU hierarchy
                (the paper's technique as a kernel planner).
  goma_fused  — fused gated-MLP chain (gate/up -> silu* -> down) with
                the intermediate strip in VMEM scratch, tiled by the
                GOMA chain solver (core/fusion.py); bit-identical to
                the unfused two-goma_matmul composition.
  decode_attention — one query token per row against its KV cache,
                reading only each row's valid blocks in the cache's
                stored layout (the decode step of every cached model).
  wkv6        — RWKV-6 chunked recurrence (rwkv6-7b's scan hot-spot).
  mamba2_ssd  — Mamba2 SSD chunked scan (zamba2-2.7b's hot-spot).

ops.py holds the jit'd public wrappers; ref.py the pure-jnp oracles every
kernel is validated against (interpret mode on CPU, compiled on TPU).
"""
from .decode_attention import decode_attention
from .goma_fused import goma_fused_matmul
from .goma_gemm import goma_matmul
from .mamba2_ssd import ssd_pallas
from .ops import fused_mlp, fused_mlp_composition, gemm, gemm_plan_info
from .ref import decode_attention_ref, matmul_ref, ssd_ref, wkv6_ref
from .wkv6 import wkv6_pallas

__all__ = ["decode_attention", "decode_attention_ref", "fused_mlp",
           "fused_mlp_composition", "gemm", "gemm_plan_info",
           "goma_fused_matmul", "goma_matmul", "matmul_ref",
           "ssd_pallas", "ssd_ref", "wkv6_pallas", "wkv6_ref"]
