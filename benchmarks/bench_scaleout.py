"""Serving scale-out gates -> BENCH_scaleout.json.

Three claims, all measured on the smoke config in virtual trace time
(DESIGN.md §Scale-out):

  * **router** — a 4-replica ``ReplicaRouter`` (discrete-event replay,
    per-replica virtual clocks, least-loaded admission) delivers
    >= 2.5x the tokens/s of a single replica on a saturating Poisson
    burst, and under moderate overload its SLO attainment / goodput
    beat the single replica's (latency-SLO percentile gates);
  * **prefix** — the KV prefix cache cuts prefill compute (chunk
    dispatches) by >= 50% on a shared-prefix trace while every stream
    stays bit-identical to the static oracle;
  * **spec** — n-gram speculative decoding with the adaptive verify-
    window ladder delivers >= 1.3x decode tokens/s on long sequential
    generations, byte-identical to target-only greedy decoding.

    PYTHONPATH=src python benchmarks/bench_scaleout.py             # full
    PYTHONPATH=src python benchmarks/bench_scaleout.py --smoke     # CI

Full mode replays ~1e5 tiny requests through the router gates (the
scale the DES harness exists for); ``--requests`` scales that down.
Smoke mode is the CI fast-lane: oracle-identity across all three
mechanisms, no throughput gates (CI wall clock is too noisy to gate
ratios on).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from common import ROOT, emit

from repro.configs import get_config
from repro.models import build_model
from repro.serving import Engine, ServeConfig
from repro.serving.router import (NgramDrafter, PrefixCache, ReplicaRouter,
                                  RouterConfig, spec_generate)
from repro.serving.sched import (ContinuousScheduler, Request, SchedConfig,
                                 TraceClock, TrafficConfig, poisson_trace,
                                 replay, shared_prefix_trace)

BENCH_PATH = ROOT / "BENCH_scaleout.json"

ARCH = "llama3-8b"

# router gates: tiny per-request work so ~1e5 requests stay tractable
ROUTER_SLOTS = 8
ROUTER_WIDTHS = (8,)
ROUTER_CACHE = 48

# spec gate (frozen design, see DESIGN.md §Scale-out): long sequential
# generations where acceptance compounds; B=1 so each stream pays for
# its own verify windows
SPEC_STREAMS = 16
SPEC_GEN = 512
SPEC_CACHE = 576
SPEC_PROMPT = 12
SPEC_WIDTHS = (2, 4, 8)


def _build(cache_len: int, max_new: int):
    cfg = get_config(ARCH, smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(max_new_tokens=max_new,
                                               cache_len=cache_len))
    return cfg, model, params, engine


def _router_trace(vocab: int, *, n: int, rate: float,
                  seed: int) -> list[Request]:
    return poisson_trace(TrafficConfig(
        n_requests=n, arrival_rate=rate, prompt_mix=((4, 7, 1.0),),
        max_new_range=(1, 3), vocab=vocab, seed=seed))


def _route(engine, trace, *, replicas: int, ttft_slo=None,
           tpot_slo=None) -> dict:
    router = ReplicaRouter(
        engine, RouterConfig(
            replicas=replicas,
            sched=SchedConfig(slots=ROUTER_SLOTS,
                              chunk_widths=ROUTER_WIDTHS),
            ttft_slo_s=ttft_slo, tpot_slo_s=tpot_slo))
    t0 = time.perf_counter()
    results = router.route_trace([Request(**vars(r)) for r in trace])
    wall = time.perf_counter() - t0
    assert len(results) == len(trace), (replicas, len(results))
    summ = router.summary()
    summ["wall_s"] = round(wall, 3)
    return summ


def bench_router(engine, vocab: int, *, n_burst: int, n_slo: int) -> dict:
    """Gate 1: saturating burst, fleet-vs-single tokens/s >= 2.5x.
    Gate 2: moderate overload, fleet SLO attainment/goodput >= single's."""
    # warm every (batch, width) jit signature off a tiny trace first so
    # both passes measure steady-state compute
    warm = _router_trace(vocab, n=32, rate=1e9, seed=99)
    _route(engine, warm, replicas=1)

    burst = _router_trace(vocab, n=n_burst, rate=1e9, seed=0)
    single = _route(engine, burst, replicas=1)
    fleet = _route(engine, burst, replicas=4)
    speedup = fleet["tokens_per_s"] / max(single["tokens_per_s"], 1e-9)
    emit("scaleout_router_single_tok_s", single["tokens_per_s"],
         f"{n_burst} reqs, makespan={single['makespan_s']}s")
    emit("scaleout_router_fleet_tok_s", fleet["tokens_per_s"],
         f"4 replicas, makespan={fleet['makespan_s']}s")
    emit("scaleout_router_speedup", speedup, "fleet/single tokens/s")

    # SLO scenario: offered load = 60% of the fleet's measured burst
    # throughput — 2.4x what one replica can serve, so the single
    # replica's queue grows without bound while the fleet keeps up
    fleet_req_s = n_burst / max(fleet["makespan_s"], 1e-9)
    offered = 0.6 * fleet_req_s
    slo_trace = _router_trace(vocab, n=n_slo, rate=offered, seed=1)
    ttft_slo, tpot_slo = 0.25, 0.1
    slo_single = _route(engine, slo_trace, replicas=1,
                        ttft_slo=ttft_slo, tpot_slo=tpot_slo)
    slo_fleet = _route(engine, slo_trace, replicas=4,
                       ttft_slo=ttft_slo, tpot_slo=tpot_slo)
    emit("scaleout_slo_attainment_single", slo_single["slo_attainment"],
         f"ttft_p95={slo_single['ttft_p95_s']}s")
    emit("scaleout_slo_attainment_fleet", slo_fleet["slo_attainment"],
         f"ttft_p95={slo_fleet['ttft_p95_s']}s")

    assert speedup >= 2.5, \
        f"4-replica speedup {speedup:.2f}x < 2.5x gate"
    assert slo_fleet["slo_attainment"] >= slo_single["slo_attainment"]
    assert slo_fleet["goodput_tokens_per_s"] >= \
        slo_single["goodput_tokens_per_s"]
    return {"n_burst": n_burst, "n_slo": n_slo,
            "burst_single": single, "burst_fleet": fleet,
            "tokens_per_s_speedup": round(speedup, 3),
            "slo": {"ttft_slo_s": ttft_slo, "tpot_slo_s": tpot_slo,
                    "offered_req_s": round(offered, 1),
                    "single": slo_single, "fleet": slo_fleet}}


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


def bench_prefix(cfg, model, params, *, n: int) -> dict:
    """Gate: >= 50% prefill-compute cut on a shared-prefix trace, every
    stream bit-identical to the static oracle."""
    engine = Engine(model, params, ServeConfig(max_new_tokens=4,
                                               cache_len=96))
    oracle = Engine(model, params, ServeConfig(max_new_tokens=4,
                                               cache_len=96))
    trace = shared_prefix_trace(
        TrafficConfig(n_requests=n, arrival_rate=1e9,
                      prompt_mix=((1, 8, 1.0),), max_new_tokens=4,
                      vocab=cfg.vocab, seed=2),
        prefix_len=64, n_prefixes=4)

    def one_pass(prefix_cache):
        clock = TraceClock()
        sched = ContinuousScheduler(
            engine, SchedConfig(slots=4, chunk_widths=(16,)),
            clock=clock.now, prefix_cache=prefix_cache)
        results = replay(sched, [Request(**vars(r)) for r in trace],
                         clock)
        return results, sched.metrics.summary()

    one_pass(None)                              # jit warmup
    base_results, base = one_pass(None)
    hit_results, hit = one_pass(PrefixCache(16, max_bytes=64 << 20))

    # chunk widths are uniform, so chunk count is prefill compute
    cut = 1.0 - hit["prefill_chunks"] / max(base["prefill_chunks"], 1)
    by_id = {r.req_id: r for r in hit_results}
    for req in trace:
        want = _oracle_tokens(oracle, req)
        assert by_id[req.req_id].tokens == want, req.req_id
        base_r = next(r for r in base_results if r.req_id == req.req_id)
        assert base_r.tokens == want, req.req_id

    emit("scaleout_prefix_chunk_cut", cut,
         f"{base['prefill_chunks']} -> {hit['prefill_chunks']} chunks, "
         f"{n} reqs bit-identical")
    emit("scaleout_prefix_tok_s", hit["tokens_per_s"],
         f"baseline {base['tokens_per_s']} tok/s")
    assert cut >= 0.5, f"prefix cache cut {cut:.1%} < 50% gate"
    return {"n_requests": n, "prefix_len": 64, "n_prefixes": 4,
            "prefill_chunks_base": base["prefill_chunks"],
            "prefill_chunks_cached": hit["prefill_chunks"],
            "prefill_compute_cut": round(cut, 4),
            "tokens_per_s_base": base["tokens_per_s"],
            "tokens_per_s_cached": hit["tokens_per_s"],
            "bit_identical": True}


def bench_spec(cfg, model, params) -> dict:
    """Gate: >= 1.3x decode tokens/s over target-only greedy on long
    sequential generations, byte-identical streams."""
    engine = Engine(model, params, ServeConfig(max_new_tokens=SPEC_GEN,
                                               cache_len=SPEC_CACHE))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (SPEC_PROMPT,)).astype(np.int32)
               for _ in range(SPEC_STREAMS)]

    # compile both paths off-measurement (every ladder width + the
    # static prefill/decode programs)
    engine.cfg.max_new_tokens = 32
    engine.generate(prompts[0][None])
    spec_generate(engine, prompts[0], NgramDrafter(), max_new_tokens=32,
                  widths=SPEC_WIDTHS)
    engine.cfg.max_new_tokens = SPEC_GEN

    t0 = time.perf_counter()
    base = [[int(t) for t in engine.generate(p[None])[0]]
            for p in prompts]
    t_base = time.perf_counter() - t0

    drafter = NgramDrafter()
    t0 = time.perf_counter()
    spec = [list(spec_generate(engine, p, drafter,
                               max_new_tokens=SPEC_GEN,
                               widths=SPEC_WIDTHS))
            for p in prompts]
    t_spec = time.perf_counter() - t0

    assert spec == base, "speculative stream diverged from greedy oracle"
    n_tok = SPEC_STREAMS * SPEC_GEN
    speedup = t_base / max(t_spec, 1e-9)     # same token count both ways
    from repro.obs.registry import get_registry
    snap = get_registry().snapshot("spec")
    rounds = max(snap.get("spec.rounds", 0), 1)
    mean_acc = snap.get("spec.accepted", 0) / rounds
    emit("scaleout_spec_base_tok_s", n_tok / t_base,
         f"{SPEC_STREAMS} streams x {SPEC_GEN} tokens")
    emit("scaleout_spec_tok_s", n_tok / t_spec,
         f"mean accepted/round={mean_acc:.2f}")
    emit("scaleout_spec_speedup", speedup, "byte-identical to greedy")
    assert speedup >= 1.3, f"spec speedup {speedup:.2f}x < 1.3x gate"
    return {"streams": SPEC_STREAMS, "gen_tokens": SPEC_GEN,
            "cache_len": SPEC_CACHE, "widths": list(SPEC_WIDTHS),
            "tokens_per_s_base": round(n_tok / t_base, 1),
            "tokens_per_s_spec": round(n_tok / t_spec, 1),
            "speedup": round(speedup, 3),
            "mean_accepted_per_round": round(mean_acc, 3),
            "byte_identical": True}


def run(*, n_requests: int = 100_000) -> dict:
    cfg, model, params, engine = _build(ROUTER_CACHE, 4)
    out = {"generated_unix": time.time(), "mode": "full",
           "arch": ARCH, "n_requests": n_requests}
    out["router"] = bench_router(engine, cfg.vocab,
                                 n_burst=(n_requests * 4) // 5,
                                 n_slo=n_requests // 5)
    out["prefix"] = bench_prefix(cfg, model, params,
                                 n=max(n_requests // 250, 16))
    out["spec"] = bench_spec(cfg, model, params)
    BENCH_PATH.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {BENCH_PATH}")
    return out


def smoke() -> None:
    """CI gate: oracle identity across router + prefix + spec.  No
    throughput ratios (CI wall clock is too noisy to gate on)."""
    cfg, model, params, engine = _build(96, 8)
    oracle = Engine(model, params, ServeConfig(max_new_tokens=8,
                                               cache_len=96))
    trace = shared_prefix_trace(
        TrafficConfig(n_requests=8, arrival_rate=200.0,
                      prompt_mix=((1, 8, 1.0),), max_new_tokens=8,
                      vocab=cfg.vocab, seed=0),
        prefix_len=16)
    router = ReplicaRouter(
        engine, RouterConfig(replicas=2, sched=SchedConfig(
            slots=2, chunk_widths=(4, 16), spec_width=4)),
        prefix_cache=PrefixCache(16), drafter=NgramDrafter())
    results = {r.req_id: r for r in router.route_trace(trace)}
    for req in trace:
        want = _oracle_tokens(oracle, req)
        assert results[req.req_id].tokens == want, \
            (req.req_id, results[req.req_id].tokens, want)
    # static spec path byte-identity on one long stream
    engine.cfg.max_new_tokens = 24
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab, (10,)).astype(np.int32)
    want = [int(t) for t in engine.generate(prompt[None])[0]]
    got = list(spec_generate(engine, prompt, NgramDrafter(),
                             max_new_tokens=24))
    assert got == want, (got, want)
    out = {"generated_unix": time.time(), "mode": "smoke",
           "arch": ARCH,
           "router_requests_bit_identical": len(trace),
           "spec_byte_identical": True}
    BENCH_PATH.write_text(json.dumps(out, indent=1) + "\n")
    print(f"scaleout smoke OK: {len(trace)}/{len(trace)} routed "
          f"requests bit-identical across 2 replicas (prefix+spec on), "
          f"spec stream byte-identical")
    print(f"wrote {BENCH_PATH}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=100_000,
                    help="router-gate trace scale (burst + SLO split)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    run(n_requests=args.requests)


if __name__ == "__main__":
    main()
