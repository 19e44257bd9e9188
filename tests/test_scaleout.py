"""Serving scale-out (DESIGN.md §Scale-out): replica router, KV prefix
cache, speculative decoding.

The invariants under test:

  * **bit-identity everywhere** — prefix grafting, speculative
    verification (static and scheduler paths), and multi-replica
    routing all emit exactly the tokens the static ``Engine.generate``
    oracle emits; the optimizations change cost, never content,
  * **no planning in the fleet** — replicas are built alike and none
    plans: a fleet makes zero solver invocations at construction and
    over a trace (spec verify windows included),
  * **clear degradation** — unsupported families fail construction
    with a named error and the router degrades to the static path;
    the prefix cache evicts under byte pressure without losing
    correctness.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tpu_mapping
from repro.core.solver import reset_solver_stats, solver_stats
from repro.models import build_model
from repro.obs.registry import get_registry
from repro.planner import PlanStore
from repro.serving import Engine, ServeConfig
from repro.serving.sched import (SUPPORTED_FAMILIES, ContinuousScheduler,
                                 Request, SchedConfig, ServingMetrics,
                                 TrafficConfig, ensure_supported_family,
                                 shared_prefix_trace)
from repro.serving.router import (ModelDrafter, NgramDrafter, PrefixCache,
                                  ReplicaRouter, RouterConfig,
                                  spec_generate)

CACHE = 128


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(max_new_tokens=10,
                                               cache_len=CACHE))
    oracle = Engine(model, params, ServeConfig(max_new_tokens=10,
                                               cache_len=CACHE))
    return cfg, model, params, engine, oracle


def _oracle_tokens(oracle: Engine, req: Request) -> list[int]:
    oracle.cfg.max_new_tokens = req.max_new_tokens
    oracle.cfg.stop_token = req.stop_token
    row = oracle.generate(req.tokens[None])[0]
    out = []
    for t in row[:req.max_new_tokens]:
        out.append(int(t))
        if req.stop_token is not None and int(t) == req.stop_token:
            break
    return out


def _assert_oracle_identical(results, reqs, oracle):
    by_id = {r.req_id: r for r in results}
    assert sorted(by_id) == sorted(r.req_id for r in reqs)
    for req in reqs:
        assert by_id[req.req_id].tokens == _oracle_tokens(oracle, req), \
            req.req_id


def _shared_prefix_requests(cfg, *, n=6, prefix_len=32, tail=5,
                            max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, (prefix_len,)).astype(np.int32)
    reqs = []
    for i in range(n):
        t = rng.integers(0, cfg.vocab, (tail,)).astype(np.int32)
        reqs.append(Request(
            req_id=i, tokens=np.concatenate([shared, t]),
            max_new_tokens=max_new, arrival_s=0.001 * i))
    return reqs


# ------------------------------------------------------- prefix cache

def test_prefix_cache_units(setup):
    """Boundary quantization, exact-token hit/miss, LRU byte budget."""
    _, _, _, engine, _ = setup
    pc = PrefixCache(16, max_bytes=1 << 20)
    assert pc._boundary(17) == 16
    assert pc._boundary(16) == 0       # P <= prompt_len - 1 always
    assert pc._boundary(33) == 32
    toks = np.arange(40, dtype=np.int32)
    cache = engine.new_cache(1)
    assert pc.lookup(toks) is None                 # cold
    assert pc.insert(toks, cache)                  # stores P=32
    p, entry = pc.lookup(toks)
    assert p == 32 and entry.p == 32
    # same boundary, different tokens: no hit (exact-token guard)
    other = toks.copy()
    other[3] += 1
    assert pc.lookup(other) is None
    # shorter prompt sharing the 16-boundary prefix hits at P=16...
    # only if a P=16 entry exists — the P=32 entry does not serve it
    assert pc.lookup(toks[:20]) is None
    assert pc.insert(toks[:20], cache)
    p2, _ = pc.lookup(toks[:20])
    assert p2 == 16
    # prompts too short to quantize never store
    assert not pc.insert(toks[:9], cache)


def test_prefix_cache_lru_eviction_under_byte_pressure(setup):
    _, _, _, engine, _ = setup
    cache = engine.new_cache(1)
    one = jax.tree.leaves(jax.tree.map(
        lambda a: np.asarray(a[:, :, :16]), cache))
    entry_bytes = sum(leaf.nbytes for leaf in one)
    pc = PrefixCache(16, max_bytes=2 * entry_bytes)   # room for two
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 200, (20,)).astype(np.int32)
               for _ in range(3)]
    for p in prompts:
        assert pc.insert(p, cache)
    assert len(pc) == 2                               # oldest evicted
    assert pc.lookup(prompts[0]) is None
    assert pc.lookup(prompts[1]) is not None
    assert pc.lookup(prompts[2]) is not None
    snap = get_registry().snapshot()
    assert snap["prefix.evictions"] == 1
    assert pc.bytes_used <= pc.max_bytes


def test_prefix_serving_bit_identical_and_saves_prefill(setup):
    """Shared-prefix trace with the cache on: fewer prefill chunks,
    prefix.* traffic counted, tokens bit-identical to the oracle."""
    cfg, _, _, engine, oracle = setup
    reqs = _shared_prefix_requests(cfg)
    base = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 16)))
    base_results = base.run([Request(
        req_id=r.req_id, tokens=r.tokens,
        max_new_tokens=r.max_new_tokens) for r in reqs])
    _assert_oracle_identical(base_results, reqs, oracle)
    chunks_without = base.metrics.prefill_chunks

    get_registry().reset()
    pc = PrefixCache(16)
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 16)),
        prefix_cache=pc)
    results = sched.run(reqs)
    _assert_oracle_identical(results, reqs, oracle)
    assert sched.metrics.prefill_chunks < chunks_without
    snap = get_registry().snapshot()
    assert snap["prefix.hits"] >= len(reqs) - 1    # all but the first
    assert snap["sched.prefix_tokens_reused"] >= 32 * (len(reqs) - 1)


def test_prefix_eviction_during_serving_keeps_identity(setup):
    """A byte budget too small to hold every prefix thrashes the cache
    but never corrupts a stream."""
    cfg, _, _, engine, oracle = setup
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(6):       # three distinct prefixes, interleaved
        shared = rng.integers(0, cfg.vocab, (32,)).astype(np.int32)
        for j in range(2):
            tail = rng.integers(0, cfg.vocab, (4,)).astype(np.int32)
            reqs.append(Request(
                req_id=10 * i + j,
                tokens=np.concatenate([shared, tail]),
                max_new_tokens=5))
    cache = engine.new_cache(1)
    entry_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(
        jax.tree.map(lambda a: np.asarray(a[:, :, :32]), cache)))
    pc = PrefixCache(16, max_bytes=entry_bytes + entry_bytes // 2)
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 16)),
        prefix_cache=pc)
    results = sched.run(reqs)
    _assert_oracle_identical(results, reqs, oracle)
    assert get_registry().get("prefix.evictions") > 0


# ------------------------------------------------ speculative decoding

def test_spec_generate_ngram_byte_identical(setup):
    cfg, _, _, engine, oracle = setup
    rng = np.random.default_rng(0)
    for seed in range(3):
        prompt = np.random.default_rng(seed).integers(
            0, cfg.vocab, (11 + seed,)).astype(np.int32)
        oracle.cfg.max_new_tokens = 20
        oracle.cfg.stop_token = None
        want = [int(t) for t in oracle.generate(prompt[None])[0]]
        got = spec_generate(engine, prompt, NgramDrafter(),
                            max_new_tokens=20)
        assert list(got) == want
    assert get_registry().get("spec.rounds") > 0


def test_spec_generate_stop_token_identical(setup):
    """Stop tokens hit mid-verify-window truncate identically."""
    cfg, _, _, engine, oracle = setup
    # pick the stop token off the oracle's own stream so it fires
    # early; the first occurrence is the delivery boundary either way
    for seed in range(10):
        prompt = np.random.default_rng(seed).integers(
            0, cfg.vocab, (10,)).astype(np.int32)
        oracle.cfg.max_new_tokens = 16
        oracle.cfg.stop_token = None
        row = [int(t) for t in oracle.generate(prompt[None])[0]]
        stop = row[len(row) // 2]
        want = row[:row.index(stop) + 1]
        if len(want) == len(row):
            continue                     # stop would not fire early
        got = spec_generate(engine, prompt, NgramDrafter(),
                            max_new_tokens=16, stop_token=stop)
        assert list(got) == want
        return
    pytest.skip("no early-stopping prompt found")


def test_spec_generate_model_drafter_byte_identical(setup):
    """A draft model (different init => different predictions) through
    the same capture-served engine: still byte-identical — drafters
    set throughput, never content."""
    cfg, model, _, engine, oracle = setup
    dparams = model.init_params(jax.random.PRNGKey(9))
    draft = Engine(model, dparams, ServeConfig(cache_len=CACHE))
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab, (12,)).astype(np.int32)
    oracle.cfg.max_new_tokens = 16
    oracle.cfg.stop_token = None
    want = [int(t) for t in oracle.generate(prompt[None])[0]]
    got = spec_generate(engine, prompt, ModelDrafter(draft),
                        max_new_tokens=16)
    assert list(got) == want
    assert get_registry().get("spec.draft_steps") > 0


def test_scheduler_spec_decoding_token_identical(setup):
    cfg, _, _, engine, oracle = setup
    rng = np.random.default_rng(4)
    reqs = [Request(req_id=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        (9 + i,)).astype(np.int32),
                    max_new_tokens=10) for i in range(5)]
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=3, chunk_widths=(8, 16), spec_width=4),
        drafter=NgramDrafter())
    results = sched.run(reqs)
    _assert_oracle_identical(results, reqs, oracle)
    snap = get_registry().snapshot()
    assert snap["sched.spec.rounds"] > 0
    assert snap["sched.spec.drafted"] == 3 * snap["sched.spec.rounds"]


def test_spec_config_validation(setup):
    _, _, _, engine, _ = setup
    with pytest.raises(ValueError, match="greedy"):
        ContinuousScheduler(
            engine, SchedConfig(slots=2, temperature=0.7, spec_width=4),
            drafter=NgramDrafter())
    with pytest.raises(ValueError, match="spec_width"):
        ContinuousScheduler(
            engine, SchedConfig(slots=2), drafter=NgramDrafter())
    with pytest.raises(ValueError, match="cache positions"):
        # lookahead headroom: prompt + budget alone fit, + window not
        engine.validate_capacity(CACHE - 12, 12, lookahead=3)


# --------------------------------------------------------------- router

def test_router_oracle_identity_and_load_spread(setup):
    cfg, _, _, engine, oracle = setup
    rng = np.random.default_rng(6)
    reqs = [Request(req_id=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        (8 + i % 7,)).astype(np.int32),
                    max_new_tokens=6, arrival_s=0.0005 * i)
            for i in range(10)]
    router = ReplicaRouter(
        engine, RouterConfig(replicas=2, sched=SchedConfig(
            slots=2, chunk_widths=(8, 16))))
    results = router.route_trace(reqs)
    _assert_oracle_identical(results, reqs, oracle)
    snap = get_registry().snapshot()
    assert snap["router.routed"] == len(reqs)
    assert snap["router.replica0.routed"] > 0      # both replicas
    assert snap["router.replica1.routed"] > 0      # carried load
    assert router.summary()["requests"] == len(reqs)


def test_router_replicas_plan_nothing(setup, tmp_path):
    """Every replica is built alike and none plans: a 3-replica fleet
    over a plan-store engine makes zero solver invocations at
    construction and over a trace (chunk prefill, prefix grafts, spec
    verify windows), and serves the oracle's tokens."""
    cfg, model, params, _, oracle = setup
    store = PlanStore(tmp_path)
    try:
        engine = Engine(model, params,
                        ServeConfig(max_new_tokens=10, cache_len=CACHE),
                        plan_store=store)
        reset_solver_stats()
        router = ReplicaRouter(
            engine, RouterConfig(replicas=3, sched=SchedConfig(
                slots=2, chunk_widths=(4, 16), spec_width=4)),
            prefix_cache=PrefixCache(16), drafter=NgramDrafter())
        assert len(router.scheds) == 3
        assert solver_stats()["calls"] == 0        # construction
        reqs = _shared_prefix_requests(cfg, n=8, prefix_len=16,
                                       max_new=5, seed=7)
        results = router.route_trace(reqs)
        assert solver_stats()["calls"] == 0        # and the trace
        assert store.puts == 0
    finally:
        tpu_mapping.set_plan_store(None)
    _assert_oracle_identical(results, reqs, oracle)


def test_unsupported_family_error_and_static_fallback():
    cfg = get_config("rwkv6-7b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(max_new_tokens=5,
                                               cache_len=64))
    # the construction-time error names the supported families
    with pytest.raises(ValueError) as ei:
        ensure_supported_family(model.cfg)
    assert str(SUPPORTED_FAMILIES) in str(ei.value)
    with pytest.raises(ValueError, match="continuous batching supports"):
        ContinuousScheduler(engine, SchedConfig(slots=2))
    # the router degrades to Engine.generate instead of raising
    router = ReplicaRouter(engine, RouterConfig(replicas=2))
    assert router.static_reason is not None
    rng = np.random.default_rng(0)
    reqs = [Request(req_id=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        (10,)).astype(np.int32),
                    max_new_tokens=5, arrival_s=0.001 * i)
            for i in range(3)]
    results = router.route_trace(reqs)
    assert len(results) == len(reqs)
    assert all(len(r.tokens) == 5 and r.finish_reason == "length"
               for r in results)
    assert "static_fallback" in router.summary()
    assert get_registry().get("router.static_fallback") == 1


# ---------------------------------------------------------- SLO metrics

def _result(req_id, *, arrival=0.0, first=0.1, finish=1.0, n=10,
            reason="length"):
    from repro.serving.sched import RequestResult
    return RequestResult(
        req_id=req_id, tokens=list(range(n)), finish_reason=reason,
        prompt_len=8, arrival_s=arrival, first_token_s=first,
        finish_s=finish)


def test_slo_attainment_and_goodput():
    m = ServingMetrics(ttft_slo_s=0.5, tpot_slo_s=0.2)
    m.started_s, m.finished_s = 0.0, 2.0
    m.record_result(_result(0, first=0.1, finish=1.0, n=10))   # attains
    m.record_result(_result(1, first=0.9, finish=1.5, n=10))   # ttft miss
    m.record_result(_result(2, first=0.2, finish=3.0, n=10))   # tpot miss
    s = m.summary()
    assert s["slo_attainment"] == pytest.approx(1 / 3, abs=1e-4)
    assert s["goodput_tokens_per_s"] == pytest.approx(10 / 2.0)
    assert s["tokens_per_s"] == pytest.approx(30 / 2.0)


def test_slo_nan_and_empty_are_safe():
    # shed request (NaN first token) never attains, never crashes
    m = ServingMetrics(ttft_slo_s=0.5)
    m.started_s, m.finished_s = 0.0, 1.0
    m.record_result(_result(0, first=float("nan"), n=0,
                            reason="rejected"))
    s = m.summary()
    assert s["slo_attainment"] == 0.0
    assert s["goodput_tokens_per_s"] == 0.0
    # no SLO configured -> no SLO keys (summary unchanged)
    assert "slo_attainment" not in ServingMetrics().summary()


def test_merged_metrics_use_makespan():
    a = ServingMetrics()
    a.started_s, a.finished_s = 0.0, 2.0
    a.record_result(_result(0, n=4))
    b = ServingMetrics()
    b.started_s, b.finished_s = 0.0, 5.0
    b.record_result(_result(1, n=6))
    m = ServingMetrics.merged([a, b])
    assert m.elapsed_s == pytest.approx(5.0)       # slowest part
    assert m.total_generated == 10
    m2 = ServingMetrics.merged([a, b], elapsed_s=7.0)
    assert m2.elapsed_s == pytest.approx(7.0)
