"""Continuous-batching scheduler: token-identical to the static oracle
under arbitrary arrival schedules, plans resolved only at kernel dispatch
(never by the scheduler), bucket/slot unit semantics, and the engine
satellites (per-step rng split, capacity validation)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.solver import reset_solver_stats, solver_stats
from repro.models import build_model
from repro.planner import PlanStore
from repro.serving import Engine, ServeConfig
from repro.serving.sched import (BucketSpec, ContinuousScheduler, Request,
                                 SchedConfig, SlotManager, TraceClock,
                                 TrafficConfig, poisson_trace, replay)

CACHE = 96


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(max_new_tokens=10,
                                               cache_len=CACHE))
    # one shared oracle engine: cfg is mutated per request (the jitted
    # prefill/decode only close over cache_len)
    oracle = Engine(model, params, ServeConfig(max_new_tokens=10,
                                               cache_len=CACHE))
    return cfg, model, params, engine, oracle


def _oracle_tokens(oracle: Engine, req: Request) -> list[int]:
    """The request alone through static Engine.generate, trimmed to the
    delivered sequence (up to and including the first stop token)."""
    oracle.cfg.max_new_tokens = req.max_new_tokens
    oracle.cfg.stop_token = req.stop_token
    row = oracle.generate(req.tokens[None])[0]
    out = []
    for t in row[:req.max_new_tokens]:
        out.append(int(t))
        if req.stop_token is not None and int(t) == req.stop_token:
            break
    return out


def _check_against_oracle(results, reqs, oracle):
    by_id = {r.req_id: r for r in results}
    assert sorted(by_id) == sorted(r.req_id for r in reqs)
    for req in reqs:
        res = by_id[req.req_id]
        want = _oracle_tokens(oracle, req)
        assert res.tokens == want, (req.req_id, res.tokens, want)
        if res.finish_reason == "stop":
            assert res.tokens[-1] == req.stop_token
        else:
            assert len(res.tokens) == req.max_new_tokens


# ---------------------------------------------------------------- units

def test_bucket_quantization():
    spec = BucketSpec((4, 16))
    for L in (1, 3, 4, 5, 15, 16, 17, 33, 64):
        chunks = spec.plan_chunks(L)
        assert sum(c.n_real for c in chunks) == L
        assert all(c.width in (4, 16) for c in chunks)
        # contiguous, and only the final chunk may be padded
        pos = 0
        for c in chunks:
            assert c.start == pos
            pos += c.n_real
        assert all(not c.is_padded for c in chunks[:-1])
        assert spec.padded_len(L) >= L
        assert spec.padded_len(L) - L < 16    # waste < largest bucket
    # the jit/plan-key bound: distinct widths only, traffic-independent
    assert len({c.width for L in range(1, 100)
                for c in spec.plan_chunks(L)}) <= 2


def test_slot_free_list_recycling():
    sm = SlotManager(2)
    r = lambda i: Request(req_id=i, tokens=np.ones(3), max_new_tokens=2)
    a = sm.acquire(r(0))
    b = sm.acquire(r(1))
    assert {a.idx, b.idx} == {0, 1}
    assert sm.acquire(r(2)) is None          # pool exhausted
    sm.release(a)
    c = sm.acquire(r(3))
    assert c.idx == a.idx                    # LIFO recycling
    assert c.tokens == [] and c.emitted == 0   # state reset on acquire
    assert sm.n_busy == 2 and sm.n_free == 0


# --------------------------------------------- differential vs oracle

def test_smoke_staggered_arrivals_stop_token(setup):
    """The CI-lane smoke: 8 requests, staggered arrivals, stop token —
    outputs match the static-batch oracle row-for-row."""
    cfg, model, params, engine, oracle = setup
    rng = np.random.default_rng(0)
    stop = 7
    reqs = [Request(req_id=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        (int(rng.integers(3, 24)),)),
                    max_new_tokens=10, arrival_s=0.02 * i,
                    stop_token=stop)
            for i in range(8)]
    clock = TraceClock()
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=3, chunk_widths=(4, 16)),
        clock=clock.now)
    results = replay(sched, reqs, clock)
    _check_against_oracle(results, reqs, oracle)
    # slots were recycled (8 requests through 3 slots) and prefill was
    # genuinely chunked
    assert sched.metrics.prefill_chunks >= 8
    assert sched.metrics.summary()["mean_slot_occupancy"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("schedule", ["burst", "trickle", "poisson"])
def test_arrival_schedules_match_oracle(setup, schedule):
    """Arbitrary arrival schedules with mixed prompt lengths and
    per-request budgets stay token-identical to the oracle."""
    cfg, model, params, engine, oracle = setup
    rng = np.random.default_rng({"burst": 1, "trickle": 2,
                                 "poisson": 3}[schedule])
    n = 10
    if schedule == "poisson":
        reqs = poisson_trace(TrafficConfig(
            n_requests=n, arrival_rate=30.0,
            prompt_mix=((3, 10, 0.6), (11, 40, 0.4)),
            max_new_range=(3, 10), vocab=cfg.vocab, seed=5))
    else:
        arrivals = ([0.0] * n if schedule == "burst"
                    else [0.3 * i for i in range(n)])
        reqs = [Request(req_id=i,
                        tokens=rng.integers(0, cfg.vocab,
                                            (int(rng.integers(3, 40)),)),
                        max_new_tokens=int(rng.integers(3, 11)),
                        arrival_s=arrivals[i])
                for i in range(n)]
    clock = TraceClock()
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=3, chunk_widths=(4, 16),
                            prefill_chunks_per_step=2),
        clock=clock.now)
    results = replay(sched, reqs, clock)
    _check_against_oracle(results, reqs, oracle)


@pytest.mark.parametrize("mode", ["decode", "ngram_spec", "model_spec",
                                  "prefix"])
def test_donated_slot_cache_serves_the_same_tokens(setup, monkeypatch,
                                                   mode):
    """With the engine's programs donating the cache they are given (as
    on an accelerator), a scheduler run serves the oracle's tokens: every
    caller uses the cache a step returns, and none reads a donated one
    (JAX raises on a donated buffer on the CPU too).  The slot cache the
    run started from is gone; the one it ends with is live."""
    import repro.serving.engine as engine_mod
    from repro.serving.router import ModelDrafter, NgramDrafter, PrefixCache
    cfg, model, params, _, oracle = setup
    monkeypatch.setattr(engine_mod, "donate_default", lambda: True)

    def donating():
        return Engine(model, params,
                      ServeConfig(max_new_tokens=10, cache_len=CACHE))
    engine = donating()
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab, (16,))
    reqs = [Request(req_id=i,
                    tokens=np.concatenate([shared[:16 if mode == "prefix"
                                                  else 2 + i],
                                           rng.integers(0, cfg.vocab,
                                                        (3 + i,))]),
                    max_new_tokens=4 + i, arrival_s=0.02 * i)
            for i in range(5)]
    kw = {}
    if mode.endswith("spec"):
        kw["drafter"] = (NgramDrafter() if mode == "ngram_spec"
                         else ModelDrafter(donating()))
    if mode == "prefix":
        kw["prefix_cache"] = PrefixCache(16)
    clock = TraceClock()
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=3, chunk_widths=(4, 16),
                            spec_width=4 if mode.endswith("spec") else None),
        clock=clock.now, **kw)
    first = jax.tree.leaves(sched.slot_cache)
    results = replay(sched, reqs, clock)
    _check_against_oracle(results, reqs, oracle)
    assert all(leaf.is_deleted() for leaf in first)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(sched.slot_cache))


@pytest.mark.parametrize("head_dim", [16, 128])
def test_engine_donates_the_slot_cache_where_the_model_carries_it(
        monkeypatch, head_dim):
    """With donation on, the slot decode consumes the cache where the
    model carries it through the layer scan (head_dim 16: stored
    T-minor, read by the kernel) and keeps it where the scan slices it
    (head_dim 128); the verify step always keeps it, the graft always
    consumes it."""
    import jax.numpy as jnp

    import repro.serving.engine as engine_mod
    monkeypatch.setattr(engine_mod, "donate_default", lambda: True)
    cfg = get_config("llama3-8b", smoke=True).replace(head_dim=head_dim)
    model = build_model(cfg)
    engine = Engine(model, model.init_params(jax.random.PRNGKey(0)),
                    ServeConfig(cache_len=32))

    def gone(cache):
        return {leaf.is_deleted() for leaf in jax.tree.leaves(cache)}
    pos = jnp.asarray([3, 9], jnp.int32)
    cache = engine.new_cache(2)
    _, out = engine.decode_slots(cache, jnp.ones((2, 1), jnp.int32), pos)
    assert gone(cache) == {head_dim % 128 != 0} and gone(out) == {False}
    _, _, new = engine.verify_step(out, jnp.ones((2, 3), jnp.int32), pos)
    assert gone(out) == {False}
    engine.insert_row(new, engine.new_cache(1), 1)
    assert gone(new) == {True}


def test_streaming_callbacks_and_metrics(setup):
    cfg, model, params, engine, oracle = setup
    rng = np.random.default_rng(3)
    reqs = [Request(req_id=i, tokens=rng.integers(0, cfg.vocab, (5,)),
                    max_new_tokens=4) for i in range(2)]
    streamed: dict[int, list[int]] = {}
    finished = []
    clock = TraceClock()
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=3, chunk_widths=(4, 16)),
        on_token=lambda req, tok: streamed.setdefault(req.req_id,
                                                      []).append(tok),
        on_finish=finished.append, clock=clock.now)
    results = replay(sched, reqs, clock)
    for res in results:
        assert streamed[res.req_id] == res.tokens   # streamed in order
        assert res.first_token_s <= res.finish_s
        # the pinned trace clock counts in-tick compute, so TTFT is
        # strictly positive (prefill work happened before the token)
        assert res.ttft_s > 0
    assert {f.req_id for f in finished} == {0, 1}
    summ = sched.metrics.summary()
    assert summ["requests"] == 2
    assert summ["total_generated_tokens"] == 8


def test_scheduler_rejects_recurrent_families():
    cfg = get_config("rwkv6-7b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(cache_len=32))
    with pytest.raises(ValueError, match="continuous batching supports"):
        ContinuousScheduler(engine, SchedConfig(slots=2))


# ------------------------------------------------- plan-DB integration

def _requests(cfg, n, prompt_len, max_new, seed):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i, tokens=rng.integers(0, cfg.vocab,
                                                  (prompt_len,)),
                    max_new_tokens=max_new, arrival_s=0.0)
            for i in range(n)]


def _fused_llama3():
    return dataclasses.replace(get_config("llama3-8b", smoke=True),
                               fused_mlp=True)


@pytest.mark.parametrize("case", ["dense", "moe", "fused"])
def test_scheduler_construction_plans_nothing(case, tmp_path):
    """Building a scheduler over a plan-store engine runs no solver and
    writes nothing to the store — a plan is resolved only where a kernel
    that dispatches it is traced — and the scheduler then serves the
    static oracle's tokens."""
    from repro.core import tpu_mapping
    arch = "deepseek-moe-16b" if case == "moe" else "llama3-8b"
    cfg = _fused_llama3() if case == "fused" else get_config(arch,
                                                              smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    oracle = Engine(model, params,
                    ServeConfig(max_new_tokens=6, cache_len=CACHE))
    store = PlanStore(tmp_path)
    try:
        engine = Engine(model, params,
                        ServeConfig(max_new_tokens=6, cache_len=CACHE),
                        plan_store=store)
        reset_solver_stats()
        clock = TraceClock()
        sched = ContinuousScheduler(
            engine, SchedConfig(slots=2, chunk_widths=(4, 16)),
            arch_id=arch, clock=clock.now)
        assert solver_stats()["calls"] == 0
        assert store.puts == 0
        reqs = _requests(cfg, 3, 10, 4, seed=3)
        results = replay(sched, reqs, clock)
    finally:
        tpu_mapping.set_plan_store(None)
    _check_against_oracle(results, reqs, oracle)


def test_fused_mlp_plans_resolve_at_dispatch(tmp_path):
    """A fused-MLP model resolves its chain plans when its programs are
    traced.  The first serve fills the store's fused section; a fresh
    engine over the same store directory, with no plan cached and no
    program traced (a restarted process), serves the same requests from
    the store with zero solver invocations and the oracle's tokens.
    Plans are keyed by the dispatch dtype: the f32 model's chains hit,
    the same chain under bf16 misses and solves."""
    from repro.core import tpu_mapping
    cfg = _fused_llama3()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    oracle = Engine(model, params,
                    ServeConfig(max_new_tokens=6, cache_len=CACHE))
    serve = ServeConfig(max_new_tokens=6, cache_len=CACHE)
    sched_cfg = SchedConfig(slots=2, chunk_widths=(4, 16))
    reqs = _requests(cfg, 3, 10, 4, seed=5)
    try:
        jax.clear_caches()              # trace the fused kernel anew
        store = PlanStore(tmp_path)
        first = ContinuousScheduler(
            Engine(model, params, serve, plan_store=store),
            sched_cfg).run(reqs)
        assert store.num_fused() > 0
        tpu_mapping.set_plan_store(None)
        jax.clear_caches()
        store = PlanStore(tmp_path)
        engine = Engine(model, params, serve, plan_store=store)
        reset_solver_stats()
        results = ContinuousScheduler(engine, sched_cfg).run(reqs)
        assert solver_stats()["calls"] == 0   # no GEMM or chain solves
        assert store.hits > 0 and store.misses == 0 and store.puts == 0
        assert jnp.dtype(cfg.compute_dtype).itemsize == 4
        tpu_mapping.plan_fused_mlp(2, cfg.d_ff, cfg.d_model,
                                   dtype_bytes=2)
        assert store.misses > 0
        assert solver_stats()["calls"] > 0
    finally:
        tpu_mapping.set_plan_store(None)
    _check_against_oracle(results, reqs, oracle)
    assert [r.tokens for r in first] == [r.tokens for r in results]


def test_engine_installs_its_plan_store(setup, tmp_path):
    """``Engine(plan_store=s)`` installs ``s`` for dispatch: a GOMA
    kernel traced afterwards plans through it."""
    from repro.core import tpu_mapping
    from repro.kernels.ops import gemm
    _, model, params, _, _ = setup
    store = PlanStore(tmp_path)
    try:
        Engine(model, params, ServeConfig(cache_len=CACHE),
               plan_store=store)
        assert tpu_mapping.get_plan_store() is store
        jax.clear_caches()              # gemm traces anew, so it plans
        a = jnp.asarray(np.random.default_rng(0).normal(size=(24, 40)),
                        jnp.float32)
        b = jnp.asarray(np.random.default_rng(1).normal(size=(40, 56)),
                        jnp.float32)
        np.testing.assert_allclose(np.asarray(gemm(a, b)),
                                   np.asarray(a) @ np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        assert store.puts > 0 and len(store) > 0
    finally:
        tpu_mapping.set_plan_store(None)


# ------------------------------------------------- engine satellites

def test_generate_rng_splits_per_step(setup):
    """Regression: temperature sampling must draw fresh Gumbel noise per
    decode step.  At temperature >> |logits| sampling is pure noise, so
    reusing one key would emit the same token every step."""
    cfg, model, params, engine, oracle = setup
    eng = Engine(model, params, ServeConfig(
        max_new_tokens=8, cache_len=CACHE, temperature=1e6))
    prompts = np.array([[1, 2, 3, 4]], np.int32)
    out = eng.generate(prompts, rng=jax.random.PRNGKey(0))
    assert len(set(out[0].tolist())) > 1, out
    # deterministic given the key
    out2 = eng.generate(prompts, rng=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(out, out2)


def test_capacity_validation(setup):
    cfg, model, params, engine, oracle = setup
    eng = Engine(model, params, ServeConfig(max_new_tokens=64,
                                            cache_len=CACHE))
    with pytest.raises(ValueError, match="cache_len"):
        eng.generate(np.ones((1, 40), np.int32))     # 40 + 64 > 96
    clock = TraceClock()
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=3, chunk_widths=(4, 16), max_queue=1),
        clock=clock.now)
    with pytest.raises(ValueError, match="cache_len"):
        sched.submit(Request(req_id=0, tokens=np.ones(90),
                             max_new_tokens=10))
    sched.submit(Request(req_id=1, tokens=np.ones(4), max_new_tokens=2))
    with pytest.raises(RuntimeError, match="queue full"):   # admission
        sched.submit(Request(req_id=2, tokens=np.ones(4),
                             max_new_tokens=2))
    sched.run()                                     # drain for isolation
