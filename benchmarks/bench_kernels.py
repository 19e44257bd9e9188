"""Pallas goma_gemm kernel: correctness vs the jnp oracle + GOMA plans.

On CPU the kernel runs in interpret mode (Python-executed kernel body),
so wall-clock is NOT a TPU number — the derived columns report the GOMA
plan (block shapes / grid / walk axis), the modeled pJ/MAC, and the
max error vs the oracle; per-shape VMEM working sets are asserted
against the v5e budget.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from common import Timer, emit

from repro.core.tpu_mapping import plan_gemm_tiling, tpu_spec
from repro.kernels.ops import gemm
from repro.kernels.ref import matmul_ref

SHAPES = [(512, 512, 512), (1024, 4096, 1024), (4096, 4096, 4096),
          (300, 200, 100)]


def run() -> None:
    hw = tpu_spec(4)
    for (M, N, K) in SHAPES:
        plan = plan_gemm_tiling(M, N, K, dtype_bytes=4)
        bm, bn, bk = plan.block
        vmem = plan.vmem_bytes(4)
        assert bm * bk + bk * bn + bm * bn <= hw.sram_words
        a = (jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
             * 0.05)
        b = (jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
             * 0.05)
        with Timer() as t:
            out = gemm(a, b, interpret=True)
            out.block_until_ready()
        err = float(jnp.max(jnp.abs(out - matmul_ref(a, b))))
        emit(f"goma_gemm[{M}x{N}x{K}]", t.dt * 1e6,
             f"block={plan.block} grid={plan.grid} walk={plan.walk} "
             f"vmem={vmem / 2**20:.1f}MiB obj={plan.objective:.4f}pJ/MAC "
             f"maxerr={err:.2e} solve={plan.solve_time_s:.2f}s")


if __name__ == "__main__":
    run()
