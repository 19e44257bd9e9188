"""Program capture: jaxpr harvest, PlanProgram IR, plan pass.

The headline guarantee is the differential oracle: capturing the
LlmSpec reference programs (capture.reference) reproduces the
hand-enumerated GEMM multiset of ``core.workloads`` *exactly* —
weights, chains and all — on every ``paper_cases()`` spec.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.capture import (PlanProgram, capture, capture_model_decode,
                           capture_model_prefill, capture_spec_decode,
                           capture_spec_prefill, captured_program,
                           diff_programs, plan_program, programs_equal)
from repro.core import TEMPLATES
from repro.core.solver import reset_solver_stats, solver_stats
from repro.core.workloads import (EDGE_MODELS, CENTER_MODELS, LlmSpec,
                                  decode_program, paper_cases,
                                  prefill_program, scenario_gemms,
                                  scenario_program)

SPECS = {s.name: s for s in EDGE_MODELS + CENTER_MODELS}
TINY = LlmSpec("tiny", layers=2, d_model=64, n_heads=4, kv_heads=2,
               head_dim=16, d_ff=128, vocab=512)
TINY_MOE = LlmSpec("tiny-moe", layers=2, d_model=64, n_heads=4,
                   kv_heads=2, head_dim=16, d_ff=128, vocab=512,
                   n_experts=4, top_k=2, shared_experts=1)


# ------------------------------------------------ differential oracle

def _distinct_cases():
    return sorted({(spec.name, seq) for _, spec, seq, _ in paper_cases()})


@pytest.mark.parametrize("name,seq", _distinct_cases())
def test_capture_matches_enumeration_prefill(name, seq):
    spec = SPECS[name]
    cap = capture_spec_prefill(spec, seq)
    hand = prefill_program(spec, seq)
    assert programs_equal(cap, hand), diff_programs(cap, hand)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_capture_matches_enumeration_decode(name):
    spec = SPECS[name]
    cap = capture_spec_decode(spec, 8, 4096)
    hand = decode_program(spec, 8, 4096)
    assert programs_equal(cap, hand), diff_programs(cap, hand)


@pytest.mark.parametrize("spec", [TINY, TINY_MOE],
                         ids=lambda s: s.name)
def test_capture_matches_enumeration_tiny_scenario(spec):
    from repro.capture import capture_spec_scenario
    kw = dict(prefill_seqs=(64, 128), decode_batches=(4,), cache_len=256)
    cap = capture_spec_scenario(spec, **kw)
    hand = scenario_program(spec, **kw)
    assert programs_equal(cap, hand), diff_programs(cap, hand)


# ------------------------------------------------ jaxpr walk mechanics

def test_scan_trip_counts_multiply_weights():
    w = jnp.zeros((8, 8))

    def inner(x):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    def outer(x):
        def body(c, _):
            return inner(c), None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    prog = captured_program(outer, jax.ShapeDtypeStruct((4, 8),
                                                        jnp.float32))
    assert prog.gemm_multiset() == {(4, 8, 8): 15}      # 5 x 3


def test_vmap_batch_dims_flatten_into_weight():
    w = jnp.zeros((8, 16))
    fn = jax.vmap(jax.vmap(lambda x: x @ w))
    prog = captured_program(fn, jax.ShapeDtypeStruct((3, 5, 4, 8),
                                                     jnp.float32))
    # vmap adds lhs-only free dims -> they flatten into m, not weight
    # (a batched-lhs GEMM is one bigger GEMM; only dims shared by BOTH
    # operands are execution repeats)
    assert prog.gemm_multiset() == {(60, 16, 8): 1}


def test_shared_batch_dims_flatten_into_weight():
    fn = lambda a, b: jnp.einsum("bhsd,bhtd->bhst", a, b)
    prog = captured_program(
        fn, jax.ShapeDtypeStruct((2, 4, 8, 16), jnp.float32),
        jax.ShapeDtypeStruct((2, 4, 8, 16), jnp.float32))
    assert prog.gemm_multiset() == {(8, 8, 16): 8}      # B*H repeats


def test_cond_branches_harvested_once_each():
    w = jnp.zeros((8, 8))

    def fn(pred, x):
        return jax.lax.cond(pred, lambda v: v @ w,
                            lambda v: (v @ w) @ w, x)

    prog = captured_program(fn, jax.ShapeDtypeStruct((), jnp.bool_),
                            jax.ShapeDtypeStruct((4, 8), jnp.float32))
    assert prog.gemm_multiset() == {(4, 8, 8): 3}


# ------------------------------------------------ chain detection

def _mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def test_chain_detected_silu_mul():
    args = (jax.ShapeDtypeStruct((32, 16), jnp.float32),
            jax.ShapeDtypeStruct((16, 64), jnp.float32),
            jax.ShapeDtypeStruct((16, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 16), jnp.float32))
    prog = captured_program(_mlp, *args)
    assert prog.chain_multiset() == {
        ((32, 64, 16), (32, 16, 64), 2, "silu_mul"): 1}


def test_chain_detected_gelu_and_identity():
    def gelu_mlp(x, wg, wu, wd):
        return (jax.nn.gelu(x @ wg) * (x @ wu)) @ wd

    def plain(x, w1, w2):
        return (x @ w1) @ w2

    sds = jax.ShapeDtypeStruct
    p1 = captured_program(gelu_mlp, sds((8, 4), jnp.float32),
                          sds((4, 16), jnp.float32),
                          sds((4, 16), jnp.float32),
                          sds((16, 4), jnp.float32))
    assert [c.chain.elementwise for c in p1.chains] == ["gelu_mul"]
    p2 = captured_program(plain, sds((8, 4), jnp.float32),
                          sds((4, 16), jnp.float32),
                          sds((16, 4), jnp.float32))
    assert [(c.chain.producer_count, c.chain.elementwise)
            for c in p2.chains] == [(1, "identity")]


def test_chain_not_detected_for_non_kernel_combines():
    """Regression: combines outside the fused kernel's act(g)*u
    vocabulary — additive, or both producers activated — must be
    rejected rather than mislabelled (the FusedPlanEntry's elementwise
    tag drives kernel dispatch)."""
    def additive(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) + (x @ wu)) @ wd

    def both_activated(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * jax.nn.silu(x @ wu)) @ wd

    sds = jax.ShapeDtypeStruct
    args = (sds((8, 4), jnp.float32), sds((4, 16), jnp.float32),
            sds((4, 16), jnp.float32), sds((16, 4), jnp.float32))
    for fn in (additive, both_activated):
        assert captured_program(fn, *args).chains == []
    # the plain product g*u IS the kernel's identity combine
    def product(x, wg, wu, wd):
        return ((x @ wg) * (x @ wu)) @ wd
    prog = captured_program(product, *args)
    assert [(c.chain.producer_count, c.chain.elementwise)
            for c in prog.chains] == [(2, "identity")]


def test_chain_not_detected_through_reshape_or_softmax():
    """Shape-changing and reducing ops break the elementwise path —
    this is what keeps attention's per-head-slice ties out."""
    def reshaped(x, w1, w2):
        h = (x @ w1).reshape(4, 2, 8).reshape(8, 8)
        return h @ w2

    def softmaxed(x, w1, w2):
        return jax.nn.softmax(x @ w1, axis=-1) @ w2

    sds = jax.ShapeDtypeStruct
    for fn in (reshaped, softmaxed):
        prog = captured_program(fn, sds((8, 4), jnp.float32),
                                sds((4, 8), jnp.float32),
                                sds((8, 4), jnp.float32))
        assert prog.chains == []


def test_chain_not_detected_when_intermediate_escapes():
    """An intermediate consumed elsewhere still needs its DRAM write, so
    the residency credit would be unsound — no chain."""
    def escaping(x, wg, wu, wd):
        h = jax.nn.silu(x @ wg) * (x @ wu)
        return h @ wd, jnp.sum(h)

    sds = jax.ShapeDtypeStruct
    prog = captured_program(escaping, sds((8, 4), jnp.float32),
                            sds((4, 16), jnp.float32),
                            sds((4, 16), jnp.float32),
                            sds((16, 4), jnp.float32))
    assert prog.chains == []


def test_chain_not_detected_when_call_sibling_output_escapes():
    """Regression: a multi-output jit-wrapped elementwise helper whose
    *other* output escapes also invalidates the credit — the sibling is
    derived from the producer output, so the intermediate must still be
    written."""
    def escaping(x, wg, wu, wd):
        a, b = jax.jit(lambda h: (jax.nn.silu(h), h * 2))(x @ wg)
        return (a * (x @ wu)) @ wd, jnp.sum(b)

    sds = jax.ShapeDtypeStruct
    prog = captured_program(escaping, sds((8, 4), jnp.float32),
                            sds((4, 16), jnp.float32),
                            sds((4, 16), jnp.float32),
                            sds((16, 4), jnp.float32))
    assert prog.chains == []


# ------------------------------------------------ model apply capture

def _aval_params(init, key=0):
    return jax.eval_shape(init, jax.random.PRNGKey(key))


def test_capture_moe_apply():
    from repro.configs import get_config
    from repro.models.moe import moe_apply, moe_init
    cfg = get_config("deepseek-moe-16b", smoke=True)
    p = _aval_params(lambda k: moe_init(k, cfg, jnp.float32))
    x = jax.ShapeDtypeStruct((1, 8, cfg.d_model), jnp.float32)
    prog = captured_program(lambda p, x: moe_apply(p, cfg, x)[0], p, x,
                            name="moe")
    ms = prog.gemm_multiset()
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    assert ms[(8, E, d)] == 1                  # router
    assert ms[(8, E * ff, d)] == 2             # gate + up (dense dispatch)
    assert prog.chains, "MoE expert MLP chain should be detected"


def test_capture_ssm_apply():
    from repro.configs import get_config
    from repro.models.ssm import ssm_apply, ssm_dims, ssm_init
    cfg = get_config("zamba2-2.7b", smoke=True)
    d_inner, nh, hd, ns = ssm_dims(cfg)
    p = _aval_params(lambda k: ssm_init(k, cfg, jnp.float32))
    S = 16
    x = jax.ShapeDtypeStruct((1, S, cfg.d_model), jnp.float32)
    prog = captured_program(lambda p, x: ssm_apply(p, cfg, x)[0], p, x,
                            name="ssm")
    ms = prog.gemm_multiset()
    proj_out = 2 * d_inner + 2 * ns + nh
    assert ms[(S, proj_out, cfg.d_model)] == 1     # in_proj
    assert ms[(S, cfg.d_model, d_inner)] == 1      # out_proj
    assert len(ms) > 2                             # SSD scan contractions


def test_capture_rwkv_applies():
    from repro.configs import get_config
    from repro.models.rwkv import (rwkv_channel_apply, rwkv_channel_init,
                                   rwkv_time_apply, rwkv_time_init)
    cfg = get_config("rwkv6-7b", smoke=True)
    d, ff, S = cfg.d_model, cfg.d_ff, 16
    x = jax.ShapeDtypeStruct((1, S, d), jnp.float32)
    pt = _aval_params(lambda k: rwkv_time_init(k, cfg, jnp.float32))
    time_prog = captured_program(
        lambda p, x: rwkv_time_apply(p, cfg, x)[0], pt, x, name="time")
    # r/k/v/w/g generators + wo are all (S, d, d) projections
    assert time_prog.gemm_multiset()[(S, d, d)] == 6
    pc = _aval_params(lambda k: rwkv_channel_init(k, cfg, jnp.float32))
    chan_prog = captured_program(
        lambda p, x: rwkv_channel_apply(p, cfg, x)[0], pc, x,
        name="chan")
    ms = chan_prog.gemm_multiset()
    assert ms[(S, ff, d)] == 1 and ms[(S, d, ff)] == 1
    # k -> relu^2 -> wv is a sound single-producer chain
    assert chan_prog.chain_multiset() == {
        ((S, ff, d), (S, d, ff), 1, "sqrelu_mul"): 1}


# ------------------------------------------------ plan pass

def test_plan_program_zero_gap(tmp_path):
    from repro.planner.store import PlanStore
    hw = TEMPLATES["gemmini-like"]
    prog = capture_spec_prefill(TINY, 64)
    store = PlanStore(tmp_path)
    plan = plan_program(prog, hw, store=store, jobs=1)
    assert plan.feasible and plan.zero_gap
    assert len(plan.manifest.entries) == len(prog.gemms)
    assert len(plan.chain_rows) == len(prog.chains) == 1
    for e in store.entries():                 # per-GEMM certificates
        assert e.certificate.gap == 0.0
    assert store.num_fused() == 1
    # second pass: pure cache hits, no solver invocations
    reset_solver_stats()
    plan2 = plan_program(prog, hw, store=store, jobs=1)
    assert solver_stats()["calls"] == 0
    assert all(e.cached for e in plan2.manifest.entries)


def test_batch_planner_solves_each_unique_shape_once():
    """Satellite: scenario rows merge duplicate (Gemm, name) pairs and
    the batch planner solves each unique shape exactly once."""
    from repro.planner.batch import BatchPlanner
    hw = TEMPLATES["gemmini-like"]
    rows = scenario_gemms(TINY, prefill_seqs=(64, 64, 128),
                          decode_batches=(4,), cache_len=256)
    keys = {(t, g) for t, g, _ in rows}
    assert len(keys) == len(rows)             # merged, no duplicates
    unique_dims = {g.dims for _, g, _ in rows}
    planner = BatchPlanner(None, jobs=1, warm_start=False)
    reset_solver_stats()
    entries = planner.plan_gemms(rows, hw)
    assert solver_stats()["calls"] == len(unique_dims)
    assert planner.last_report.unique_gemms == len(unique_dims)
    assert len(entries) == len(unique_dims)


# ------------------------------------------------ CLI

def test_cli_capture_and_fused_inspect_verify(tmp_path, capsys):
    from repro.core.fusion import mlp_chain
    from repro.planner.batch import cached_solve_chain
    from repro.planner.cli import main
    from repro.planner.store import PlanStore
    db = str(tmp_path / "db")
    rc = main(["capture", "--arch", "stablelm-1.6b", "--smoke",
               "--phase", "decode", "--batch", "2", "--cache-len", "64",
               "--hw", "gemmini-like", "--store", db, "--jobs", "1",
               "--manifest", str(tmp_path / "m.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "[program]" in out and "[manifest]" in out
    # the capture run chain-solved the detected MLP chain already; add
    # one more and check inspect/verify see the fused section
    store = PlanStore(db)
    n0 = store.num_fused()
    assert n0 >= 1                # captured chain landed in fused/
    cached_solve_chain(mlp_chain(64, 128, 64, name="t"),
                       TEMPLATES["gemmini-like"], store=store)
    assert store.num_fused() == n0 + 1
    assert main(["inspect", "--store", db, "-v"]) == 0
    out = capsys.readouterr().out
    assert "fused chain plans" in out
    assert main(["verify", "--store", db]) == 0
    out = capsys.readouterr().out
    assert "chain certificates verified" in out and "FAILED" not in out
