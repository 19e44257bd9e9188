"""Pallas kernel validation: shape/dtype sweep vs the pure-jnp oracles in
interpret mode (assignment requirement), plus the chunked-scan kernels'
algorithmic cores vs their sequential references."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tpu_mapping import (MXU, VMEM_BUDGET_BYTES, FusedTilePlan,
                                    TpuTilePlan, plan_fused_mlp,
                                    plan_gemm_tiling, tpu_spec)
from repro.kernels.goma_gemm import goma_matmul
from repro.kernels.ops import fused_mlp, fused_mlp_composition, gemm
from repro.kernels.decode_attention import BLOCK, decode_attention
from repro.kernels.ref import (decode_attention_ref, matmul_ref, ssd_ref,
                               wkv6_ref)
from repro.models.layers import flash_attention

SHAPES = [(128, 128, 128), (256, 512, 128), (300, 200, 100),
          (512, 384, 1024), (1024, 256, 2048), (64, 4096, 512)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.1).astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_goma_gemm_vs_ref(shape, dtype):
    M, N, K = shape
    a = (jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
         * 0.1).astype(dtype)
    b = (jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
         * 0.1).astype(dtype)
    out = gemm(a, b, interpret=True)
    ref = matmul_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# --- kernel numerics matrix: goma_matmul + fused kernel -------------------
# odd / non-divisor-rich shapes alongside MXU-friendly ones; the fused
# matrix also pins the nk == 1 fast path and the multi-k scratch path
# via handcrafted plans (deterministic, VMEM-size-independent).

MATRIX_SHAPES = [(128, 128, 128), (300, 200, 100), (129, 257, 65),
                 (100, 50, 1), (256, 384, 512)]


@pytest.mark.parametrize("shape", MATRIX_SHAPES,
                         ids=[f"{m}x{n}x{k}" for m, n, k in MATRIX_SHAPES])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_goma_gemm_matrix(shape, dtype):
    M, N, K = shape
    a = _rand(jax.random.PRNGKey(0), (M, K), dtype)
    b = _rand(jax.random.PRNGKey(1), (K, N), dtype)
    out = gemm(a, b, interpret=True)
    ref = matmul_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", MATRIX_SHAPES,
                         ids=[f"{m}x{n}x{k}" for m, n, k in MATRIX_SHAPES])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_fused_mlp_matrix(shape, dtype):
    """Fused kernel vs jnp reference AND bit-identical to the unfused
    two-goma_matmul composition under the plan's compatibility tiles."""
    M, FF, K = shape
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    a = _rand(ks[0], (M, K), dtype)
    wg = _rand(ks[1], (K, FF), dtype)
    wu = _rand(ks[2], (K, FF), dtype)
    wd = _rand(ks[3], (FF, K), dtype)
    out = fused_mlp(a, wg, wu, wd, interpret=True)
    ref = fused_mlp(a, wg, wu, wd, force_xla=True)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    plan = plan_fused_mlp(M, FF, K,
                          dtype_bytes=jnp.dtype(dtype).itemsize)
    if plan.fused:
        comp = fused_mlp_composition(a, wg, wu, wd, plan, interpret=True)
        assert np.array_equal(np.asarray(out), np.asarray(comp)), (
            shape, dtype)


def _manual_fused_plan(M, FF, K, bm, bk):
    return FusedTilePlan(M=M, FF=FF, K=K, N2=K, padded=(M, FF, K, K),
                         fused=True, bm=bm, bk=bk, objective=0.0,
                         unfused_objective=0.0, solve_time_s=0.0)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("bm,bk,label", [
    (128, 128, "single_k"),        # nk == 1 fast path (no scratch)
    (128, 64, "multi_k"),          # VMEM scratch accumulation path
    (64, 32, "multi_m_multi_k"),   # both grid dims > 1
])
def test_fused_kernel_grid_paths(dtype, bm, bk, label):
    """The fused kernel's nk==1 fast path and scratch-accumulation path
    are bit-identical to the composition built from the same tiles."""
    M, FF, K = 128, 256, 128
    plan = _manual_fused_plan(M, FF, K, bm, bk)
    nm, nk = plan.grid
    assert (nk == 1) == (label == "single_k")
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    a = _rand(ks[0], (M, K), dtype)
    wg = _rand(ks[1], (K, FF), dtype)
    wu = _rand(ks[2], (K, FF), dtype)
    wd = _rand(ks[3], (FF, K), dtype)
    out = fused_mlp(a, wg, wu, wd, plan=plan, interpret=True)
    comp = fused_mlp_composition(a, wg, wu, wd, plan, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(comp)), label
    ref = fused_mlp(a, wg, wu, wd, force_xla=True)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bk,expect_single", [(128, True), (64, False)])
def test_goma_gemm_nk1_fast_path(bk, expect_single):
    """goma_matmul's nk==1 path (direct block write, no accumulator
    scratch) computes the same result as the accumulated path."""
    M = N = K = 128
    plan = TpuTilePlan(M=M, N=N, K=K, padded=(M, N, K),
                       block=(128, 128, bk), grid_order=("m", "n", "k"),
                       walk="z", objective=0.0, solve_time_s=0.0)
    nk = K // bk
    assert (nk == 1) == expect_single
    a = _rand(jax.random.PRNGKey(4), (M, K), jnp.float32)
    b = _rand(jax.random.PRNGKey(5), (K, N), jnp.float32)
    out = goma_matmul(a, b, plan, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("activation", ["silu_mul", "gelu_mul",
                                        "sqrelu_mul"])
def test_fused_mlp_activations(activation):
    M, FF, K = 128, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    a = _rand(ks[0], (M, K), jnp.float32)
    wg = _rand(ks[1], (K, FF), jnp.float32)
    wu = _rand(ks[2], (K, FF), jnp.float32)
    wd = _rand(ks[3], (FF, K), jnp.float32)
    out = fused_mlp(a, wg, wu, wd, activation=activation, interpret=True)
    ref = fused_mlp(a, wg, wu, wd, activation=activation, force_xla=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_plan_respects_hardware_constraints():
    for dtype_bytes in (2, 4):
        hw = tpu_spec(dtype_bytes)
        for (M, N, K) in [(4096, 4096, 4096), (8192, 1024, 8192),
                          (128, 256000, 4608), (300, 200, 100),
                          (256, 2048, 5632), (8, 100352, 2048)]:
            plan = plan_gemm_tiling(M, N, K, dtype_bytes=dtype_bytes)
            bm, bn, bk = plan.block
            pm, pn, pk = plan.padded
            assert pm % MXU == 0 and pn % MXU == 0
            assert pm % bm == 0 and pn % bn == 0 and pk % bk == 0
            # VMEM capacity (the GOMA SRAM constraint, in dtype words)
            assert bm * bk + bk * bn + bm * bn <= hw.sram_words
            # ...which bounds what the kernel allocates, double buffers
            # and accumulator included
            assert plan.vmem_bytes(dtype_bytes) <= VMEM_BUDGET_BYTES
            # MXU alignment of the VMEM tile; K tiles too, unless whole
            assert bm % MXU == 0 and bn % MXU == 0
            assert bk % MXU == 0 or bk == pk
            # realizability: z-walk or full reduction per block
            assert plan.walk == "z" or bk == pk
            # grid order puts the walking axis innermost
            assert plan.grid_order[-1] == {"x": "m", "y": "n",
                                           "z": "k"}[plan.walk]


def test_fused_plan_counts_the_resident_down_projection():
    """The fused kernel holds Wd's whole (FF, N2) block in VMEM: a chain
    whose Wd exceeds the budget is planned unfused, whatever the chain
    solver's strips allow; one that fits stays fused within budget."""
    big = plan_fused_mlp(256, 5632, 2048, 2048, dtype_bytes=2)
    assert 2 * 5632 * 2048 * 2 > VMEM_BUDGET_BYTES
    assert not big.fused and big.objective == big.unfused_objective
    small = plan_fused_mlp(256, 1024, 512, 512, dtype_bytes=2)
    assert small.fused
    assert small.vmem_bytes(2) <= VMEM_BUDGET_BYTES
    assert small.bk % MXU == 0 or small.bk == small.padded[2]


def test_plan_grid_covers_problem():
    plan = plan_gemm_tiling(1000, 3000, 500, dtype_bytes=4)
    sizes = dict(zip(plan.grid_order, plan.grid))
    pm, pn, pk = plan.padded
    bm, bn, bk = plan.block
    assert sizes["m"] * bm == pm
    assert sizes["n"] * bn == pn
    assert sizes["k"] * bk == pk


def test_wkv6_chunked_vs_sequential():
    from repro.models.rwkv import wkv_chunked
    B, S, H, P = 2, 24, 3, 8
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, P)) * 0.5
               for i in range(3))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, P)) - 2.0)
    u = jax.random.normal(ks[4], (H, P)) * 0.3
    y_c, s_c = wkv_chunked(r, k, v, logw, u, chunk=8)
    y_r = wkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunked_vs_sequential():
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N = 2, 24, 3, 8, 4
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    xh = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a_log = jax.random.normal(ks[2], (H,)) * 0.2
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    D = jnp.ones((H,)) * 0.1
    y_c, s_c = ssd_chunked(xh, dt, a_log, Bm, Cm, D, chunk=8)
    y_r = ssd_ref(xh, dt, a_log, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)


def test_gemm_plan_deterministic_and_cached():
    p1 = plan_gemm_tiling(512, 512, 512, dtype_bytes=2)
    p2 = plan_gemm_tiling(512, 512, 512, dtype_bytes=2)
    assert p1 is p2  # lru_cache


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_ssd_pallas_vs_ref(chunk):
    from repro.kernels.mamba2_ssd import ssd_pallas
    B, S, H, P, N = 2, 128, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    xh = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a_log = jax.random.normal(ks[2], (H,)) * 0.2
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y, st = ssd_pallas(xh, dt, a_log, Bm, Cm, chunk=chunk, interpret=True)
    from repro.models.ssm import ssd_chunked
    _, st_ref = ssd_chunked(xh, dt, a_log, Bm, Cm, jnp.zeros((H,)),
                            chunk=chunk)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-3, atol=1e-3)
    ref = ssd_ref(xh, dt, a_log, Bm, Cm, jnp.zeros((H,)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("chunk,dtype", [(32, jnp.float32),
                                         (64, jnp.float32),
                                         (32, jnp.bfloat16)])
def test_wkv6_pallas_vs_ref(chunk, dtype):
    from repro.kernels.wkv6 import wkv6_pallas
    B, S, H, P = 2, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r, k, v = ((jax.random.normal(ks[i], (B, S, H, P)) * 0.5).astype(dtype)
               for i in range(3))
    logw = (-jnp.exp(jax.random.normal(ks[3], (B, S, H, P)) - 2.0)
            ).astype(dtype)
    u = jax.random.normal(ks[4], (H, P)) * 0.3
    y, st = wkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=True)
    # final state must match the chunked JAX implementation's
    from repro.models.rwkv import wkv_chunked
    _, st_ref = wkv_chunked(r.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32),
                            logw.astype(jnp.float32), u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=2e-3, atol=2e-3)
    ref = wkv6_ref(r.astype(jnp.float32), k.astype(jnp.float32),
                   v.astype(jnp.float32), logw.astype(jnp.float32), u)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)


# --- decode attention: one query token per row against its cache --------
# tk = BLOCK; lengths 1, tk-1, tk, tk+1 and T sit in one batch,
# so the clamped block walk, the partial last block (T = 300) and the
# per-row finish are all exercised.

DECODE_CASES = {
    "g1_T300": dict(T=300, G=1),
    "g2_T256": dict(T=256, G=2),
    "g4_T300": dict(T=300, G=4),
    "g2_window": dict(T=300, G=2, window=100, window_active=True),
    "g4_window_switched_off": dict(T=300, G=4, window=100,
                                   window_active=False),
    "g2_softcap": dict(T=300, G=2, softcap=20.0),
    "g2_scalar_index": dict(T=300, G=2, scalar=True),
    "g2_bf16": dict(T=300, G=2, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(DECODE_CASES), ids=str)
def test_decode_attention_vs_references(case):
    c = DECODE_CASES[case]
    T, G, KV, hd, tk = c["T"], c["G"], 2, 64, BLOCK
    dtype = c.get("dtype", jnp.float32)
    lens = ([200] * 5 if c.get("scalar")
            else [1, tk - 1, tk, tk + 1, T])
    B = len(lens)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (B, 1, KV * G, hd), dtype) * 10
    k = _rand(ks[1], (B, T, KV, hd), dtype) * 10
    v = _rand(ks[2], (B, T, KV, hd), dtype) * 10
    kv_len = jnp.asarray(lens, jnp.int32)
    window, softcap = c.get("window"), c.get("softcap")
    active = jnp.asarray(c.get("window_active", True))

    @jax.jit
    def kernel(q, k, v, kv_len, active):
        if c.get("scalar"):      # one shared write position, broadcast
            kv_len = kv_len[0]
        return decode_attention(q, k, v, kv_len=kv_len,
                                q_positions=kv_len - 1, window=window,
                                window_active=active, softcap=softcap,
                                interpret=True)

    out = np.asarray(kernel(q, k, v, kv_len, active), np.float32)
    flash = flash_attention(q, k, v, q_positions=(kv_len - 1)[:, None],
                            kv_positions=jnp.arange(T), window=window,
                            window_active=active, kv_len=kv_len,
                            softcap=softcap)
    ref = decode_attention_ref(q, k, v, kv_len=kv_len,
                               q_positions=kv_len - 1, window=window,
                               window_active=active, softcap=softcap)
    # f32 accumulation either way; a bf16 output rounds once
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, np.asarray(flash, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(out, np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("head_dim", [16, 128])
def test_decode_kernel_runs_only_in_single_token_decode(head_dim):
    """The dispatch reads the shapes: per-slot decode (S = 1) runs the
    Pallas kernel, a 32-wide prefill chunk keeps the flash scan, and so
    does a cache whose head_dim fills whole lane tiles (stored
    row-major, so the kernel could not read it in place)."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Engine, ServeConfig
    cfg = get_config("llama3-8b", smoke=True).replace(head_dim=head_dim)
    model = build_model(cfg)
    eng = Engine(model, model.init_params(jax.random.PRNGKey(0)),
                 ServeConfig(cache_len=64))

    def jaxpr(fn, batch, width, index):
        return str(jax.make_jaxpr(fn)(
            eng.new_cache(batch), jnp.zeros((batch, width), jnp.int32),
            index))

    decode = jaxpr(eng.decode_slots, 3, 1, jnp.array([0, 5, 9], jnp.int32))
    chunk = jaxpr(eng.prefill_chunk, 1, 32, jnp.int32(0))
    in_place = head_dim % 128 != 0
    assert ("pallas_call" in decode) == in_place
    assert ("decode_attention" in decode) == in_place
    assert "pallas_call" not in chunk
