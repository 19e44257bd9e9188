"""Serving driver: batched generation with any --arch.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke

Two modes:
  * static (default): one batch of identical-arrival prompts through
    ``Engine.generate``, run to completion.
  * ``--continuous``: the continuous-batching scheduler
    (``repro.serving.sched``) replaying a synthetic Poisson trace —
    chunked prefill interleaved with in-flight decode, slot recycling,
    per-request streaming.  Dense/MoE archs only.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import Engine, ServeConfig

# prefill chunk buckets of the continuous deployment
CHUNK_WIDTHS = (8, 32)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--plan-db", default=None,
                    help="GOMA plan database dir: GOMA kernels resolve "
                         "their tilings through it when traced (also: "
                         "$GOMA_PLAN_DB)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching scheduler over a Poisson "
                         "trace instead of one static batch")
    ap.add_argument("--requests", type=int, default=16,
                    help="--continuous: synthetic trace length")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="--continuous: Poisson arrivals per second")
    ap.add_argument("--fused-mlp", action="store_true",
                    help="route gated-MLP blocks through the GOMA-chain-"
                         "planned fused Pallas kernel (token-identical; "
                         "chain plans read through --plan-db)")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="--continuous: stream one JSON line per "
                         "scheduler tick (registry counter snapshot + "
                         "live metrics) to PATH")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="--continuous: admission-queue bound; overflow "
                         "is shed with a terminal REJECTED result")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="--continuous: per-request deadline relative "
                         "to arrival; requests still queued past it are "
                         "EXPIRED instead of served late")
    ap.add_argument("--watchdog-tick-s", type=float, default=None,
                    help="--continuous: wall-clock budget per scheduler "
                         "tick; slower ticks count sched.watchdog_trips")
    ap.add_argument("--replicas", type=int, default=1,
                    help="--continuous: scheduler replica count; > 1 "
                         "routes the trace through the replica router "
                         "(least-loaded admission, virtual per-replica "
                         "clocks)")
    ap.add_argument("--prefix-cache", type=int, default=None,
                    metavar="MB",
                    help="--continuous: enable the KV prefix cache with "
                         "this byte budget in MiB — shared-prefix "
                         "admissions graft cached KV rows instead of "
                         "re-prefilling them (token-identical)")
    ap.add_argument("--draft-model", default=None, metavar="DRAFTER",
                    help="--continuous: speculative decoding drafter: "
                         "'ngram' (prompt-lookup, zero model calls) or "
                         "an arch name served as a draft model through "
                         "its own engine.  Greedy only; streams stay "
                         "byte-identical")
    ap.add_argument("--spec-width", type=int, default=4,
                    help="--draft-model: verify window width (1 "
                         "committed + spec-width-1 draft tokens)")
    ap.add_argument("--ttft-slo-s", type=float, default=None,
                    help="--continuous: TTFT SLO for the attainment/"
                         "goodput summary fields")
    ap.add_argument("--tpot-slo-s", type=float, default=None,
                    help="--continuous: per-token latency SLO for the "
                         "attainment/goodput summary fields")
    ap.add_argument("--inject", default=None, metavar="SPECS",
                    help="chaos fault schedule, e.g. "
                         "'store.corrupt:0.01,kernel.nan_row@3' "
                         "(see repro.faults.parse_faults)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed of the fault-injection RNG streams")
    args = ap.parse_args()
    enable_compile_cache()

    if args.inject:
        from repro.faults import FaultInjector, parse_faults, set_injector
        set_injector(FaultInjector(parse_faults(args.inject),
                                   seed=args.chaos_seed))

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.fused_mlp:
        import dataclasses
        cfg = dataclasses.replace(cfg, fused_mlp=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    store = None
    if args.plan_db:
        from repro.planner import PlanStore
        store = PlanStore(args.plan_db)

    if args.continuous:
        _serve_continuous(args, cfg, model, params, store)
        return

    eng = Engine(model, params, ServeConfig(
        max_new_tokens=args.new_tokens, temperature=args.temperature,
        cache_len=args.prompt_len + args.new_tokens + 8),
        plan_store=store)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab,
                           (args.batch, args.prompt_len)).astype(np.int32)
    extra = None
    if cfg.family == "vlm":
        extra = {"patches": jax.numpy.zeros(
            (args.batch, cfg.frontend_len, cfg.d_model))}
    if cfg.family == "encdec":
        extra = {"frames": jax.numpy.zeros(
            (args.batch, cfg.frontend_len, cfg.d_model))}
    import time
    t0 = time.perf_counter()
    out = eng.generate(prompts, extra_batch=extra,
                       rng=jax.random.PRNGKey(1)
                       if args.temperature > 0 else None)
    dt = time.perf_counter() - t0
    tok_s = args.batch * args.new_tokens / dt
    print(f"{cfg.name}: generated {out.shape} in {dt:.2f}s "
          f"({tok_s:.1f} tok/s incl. compile)")
    print(out[:, :12])


def continuous_engine(model, params, store, *, prompt_len: int,
                      new_tokens: int, temperature: float = 0.0) -> Engine:
    """The engine of a continuous deployment whose prompts are at most
    ``prompt_len`` tokens, its cache sized for the bucketed prefill."""
    from repro.serving.sched import BucketSpec
    # every prompt is <= prompt_len; its bucket-padded prefill fits in
    # ceil(prompt_len / max_width) full-width chunks
    wmax = BucketSpec(CHUNK_WIDTHS).max_width
    padded_cap = -(-prompt_len // wmax) * wmax
    return Engine(model, params, ServeConfig(
        max_new_tokens=new_tokens, temperature=temperature,
        cache_len=padded_cap + new_tokens), plan_store=store)


def _serve_continuous(args, cfg, model, params, store) -> None:
    from repro.serving.sched import (BucketSpec, ContinuousScheduler,
                                     SchedConfig, TraceClock,
                                     TrafficConfig, poisson_trace, replay)
    widths = CHUNK_WIDTHS
    wmax = BucketSpec(widths).max_width
    eng = continuous_engine(model, params, store,
                            prompt_len=args.prompt_len,
                            new_tokens=args.new_tokens,
                            temperature=args.temperature)
    cache_len = eng.cfg.cache_len
    trace = poisson_trace(TrafficConfig(
        n_requests=args.requests, arrival_rate=args.arrival_rate,
        prompt_mix=((max(args.prompt_len // 4, 1), args.prompt_len, 1.0),),
        max_new_tokens=args.new_tokens, vocab=cfg.vocab))
    clock = TraceClock()
    on_tick = None
    metrics_fh = None
    if args.metrics_jsonl:
        import json

        from repro.obs.registry import get_registry

        metrics_fh = open(args.metrics_jsonl, "w")
        reg = get_registry()

        def on_tick(s) -> None:
            m = s.metrics
            line = {"tick": m.steps, "t": clock.now(),
                    "busy_slots": s.slots.n_busy,
                    "queued": len(s.queue),
                    "counters": reg.snapshot()}
            metrics_fh.write(json.dumps(line, sort_keys=True) + "\n")

    # scale-out options (repro.serving.router)
    prefix_cache = None
    if args.prefix_cache is not None:
        from repro.serving.router import PrefixCache
        prefix_cache = PrefixCache(wmax,
                                   max_bytes=args.prefix_cache << 20)
    drafter = None
    spec_width = None
    if args.draft_model is not None:
        spec_width = args.spec_width
        if args.draft_model == "ngram":
            from repro.serving.router import NgramDrafter
            drafter = NgramDrafter()
        else:
            from repro.serving.router import ModelDrafter
            dcfg = get_config(args.draft_model, smoke=args.smoke)
            dmodel = build_model(dcfg)
            dparams = dmodel.init_params(jax.random.PRNGKey(7))
            drafter = ModelDrafter(Engine(
                dmodel, dparams, ServeConfig(cache_len=cache_len),
                plan_store=store))
    sched_cfg = SchedConfig(slots=args.batch, chunk_widths=widths,
                            temperature=args.temperature,
                            max_queue=args.max_queue,
                            shed_on_full=args.max_queue is not None,
                            default_deadline_s=args.deadline_s,
                            watchdog_tick_s=args.watchdog_tick_s,
                            spec_width=spec_width)

    if args.replicas > 1:
        from repro.serving.router import ReplicaRouter, RouterConfig
        router = ReplicaRouter(
            eng, RouterConfig(replicas=args.replicas, sched=sched_cfg,
                              ttft_slo_s=args.ttft_slo_s,
                              tpot_slo_s=args.tpot_slo_s),
            prefix_cache=prefix_cache, drafter=drafter)
        results = router.route_trace(trace)
        summ = router.summary()
        print(f"{cfg.name} router x{args.replicas}: {len(results)} "
              f"requests, {summ['total_generated_tokens']} tokens in "
              f"{summ['makespan_s']:.2f}s makespan "
              f"({summ['tokens_per_s']:.1f} tok/s incl. compile)")
        if "slo_attainment" in summ:
            print(f"  slo attainment: {summ['slo_attainment']:.2%}  "
                  f"goodput: {summ['goodput_tokens_per_s']:.1f} tok/s")
        if metrics_fh is not None:
            metrics_fh.close()
        return

    sched = ContinuousScheduler(
        eng, sched_cfg, clock=clock.now, on_tick=on_tick,
        prefix_cache=prefix_cache, drafter=drafter)
    sched.metrics.ttft_slo_s = args.ttft_slo_s
    sched.metrics.tpot_slo_s = args.tpot_slo_s
    try:
        results = replay(sched, trace, clock)
    finally:
        if metrics_fh is not None:
            metrics_fh.close()
            print(f"metrics stream: {args.metrics_jsonl}")
    summ = sched.metrics.summary()
    print(f"{cfg.name} continuous: {len(results)} requests, "
          f"{summ['total_generated_tokens']} tokens in "
          f"{summ['elapsed_s']:.2f}s trace-time "
          f"({summ['tokens_per_s']:.1f} tok/s incl. compile)")
    print(f"  ttft p50/p95: {summ['ttft_p50_s']:.3f}/"
          f"{summ['ttft_p95_s']:.3f}s  occupancy: "
          f"{summ['mean_slot_occupancy']:.2f}  chunks: "
          f"{summ['prefill_chunks']}")
    if summ["rejected"] or summ["expired"] or summ["errored"]:
        print(f"  degraded: rejected={summ['rejected']} "
              f"expired={summ['expired']} errored={summ['errored']} "
              f"(served {summ['served']}/{summ['requests']})")


if __name__ == "__main__":
    main()
