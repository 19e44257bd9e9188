"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunked form).

The WKV scan is rwkv6-7b's non-GEMM hot spot (the arch with the best
roofline fraction in §Roofline).  TPU adaptation of the CUDA chunked
kernels: one (batch, head) stream per grid row, chunk index innermost so
the (P x P) state lives in VMEM scratch across consecutive grid steps;
intra-chunk pairwise decays are computed as exp of *non-positive* log
differences (numerically safe — no separate exp(+cum) factors), giving
a (C,C) score matrix built row by row.

The kernel works on a head-major (B, H, S, P) layout so each block's last
two dims are (chunk, P): Mosaic tiles the last two dims of a block in
(8, 128) units, which a block of one head against the full H axis cannot
meet.  The in-chunk prefix sums are matmuls against a triangular mask
(Mosaic has no cumsum), at fp32 contraction precision.

Math (see models/rwkv.py): S_t = diag(w_t) S_{t-1} + k_t v_t^T,
y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # x @ y.T
_TN = (((0,), (0,)), ((), ()))      # x.T @ y


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, so_ref,
                 state_ref, cum_tm1_ref, scores_ref, *, chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0, 0].astype(jnp.float32)              # (C, P)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)            # log decay < 0
    u = u_ref[0].astype(jnp.float32)                 # (1, P)
    state = state_ref[...]                           # (P, P)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cum = jnp.dot((col <= row).astype(jnp.float32), lw,
                  precision=_HI)                     # inclusive cumsum
    cum_tm1 = cum - lw                               # exclusive cumsum
    total = cum[chunk - 1:chunk]                     # (1, P)
    cum_tm1_ref[...] = cum_tm1

    # intra-chunk scores, one row t at a time (Mosaic cannot broadcast a
    # (C, P) value along a new middle axis):
    # scores[t, s] = sum_p r[t,p] exp(cum_tm1[t,p] - cum[s,p]) k[s,p], s < t
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def score_row(t, carry):
        ct = cum_tm1_ref[pl.ds(t, 1), :]             # (1, P)
        rt = r_ref[0, 0, pl.ds(t, 1), :].astype(jnp.float32)
        # cum is non-increasing, so ct - cum <= 0 exactly where s < t;
        # the clamp keeps the masked s >= t entries finite
        e = jnp.exp(jnp.minimum(ct - cum, 0.0)) * k  # (C, P)
        srow = jax.lax.dot_general(rt, e, _NT, precision=_HI)
        scores_ref[pl.ds(t, 1), :] = jnp.where(s_idx < t, srow, 0.0)
        return carry

    jax.lax.fori_loop(0, chunk, score_row, 0)
    y = jnp.dot(scores_ref[...], v, precision=_HI)   # (C, P)
    # bonus diagonal
    y = y + jnp.sum(r * u * k, axis=1, keepdims=True) * v
    # inter-chunk: y[t] += (r_t . exp(cum_tm1[t])) @ state
    y = y + jnp.dot(r * jnp.exp(cum_tm1), state, precision=_HI)

    # state update: S <- diag(exp(total)) S + (k . exp(total - cum))^T v
    prow = jax.lax.broadcasted_iota(jnp.int32, state.shape, 0)
    pcol = jax.lax.broadcasted_iota(jnp.int32, state.shape, 1)
    diag = jnp.where(prow == pcol, jnp.exp(total), 0.0)
    new_state = (jnp.dot(diag, state, precision=_HI)
                 + jax.lax.dot_general(k * jnp.exp(total - cum), v, _TN,
                                       precision=_HI))
    state_ref[...] = new_state
    so_ref[0, 0] = new_state          # final chunk's write survives
    o_ref[0, 0] = y.astype(o_ref.dtype)


def wkv6_pallas(r, k, v, logw, u, *, chunk: int = 64,
                interpret: bool = False):
    """r/k/v/logw: (B, S, H, P); u: (H, P).
    Returns (y: (B, S, H, P), final_state: (B, H, P, P))."""
    B, S, H, P = r.shape
    assert S % chunk == 0, "pad sequence to the chunk size first"
    assert chunk % 8 == 0, "chunk is a block's sublane dim: a multiple of 8"
    grid = (B, H, S // chunk)
    r, k, v, logw = (t.transpose(0, 2, 1, 3) for t in (r, k, v, logw))

    spec = pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0))
    u_spec = pl.BlockSpec((1, 1, P), lambda b, h, c: (h, 0, 0))
    s_spec = pl.BlockSpec((1, 1, P, P), lambda b, h, c: (b, h, 0, 0))
    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec, u_spec],
        out_specs=(spec, s_spec),
        out_shape=(jax.ShapeDtypeStruct((B, H, S, P), r.dtype),
                   jax.ShapeDtypeStruct((B, H, P, P), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((P, P), jnp.float32),
                        pltpu.VMEM((chunk, P), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(H, 1, P))
    return y.transpose(0, 2, 1, 3), state
