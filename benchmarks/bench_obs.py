"""Observability suite -> BENCH_obs.json.

Two certifications (EXPERIMENTS.md §Fidelity-replay, DESIGN.md
§Observability):

  * **tracer overhead** — the serving smoke config replayed with the
    span tracer disabled vs installed, interleaved passes, comparing
    wall-clock medians.  Gate: tracing costs <= 5% throughput.  The
    tracer is pure-Python bookkeeping at dispatch/tick granularity
    (never inside jit), so the overhead should be far below the gate —
    the bench exists to keep it that way.
  * **plan fidelity** — replay a manifest's plans through the real
    Pallas GEMM path and gate on the Spearman rank correlation between
    predicted energy and measured kernel time per GEMM family
    (``repro.obs.fidelity``).  Smoke mode uses a synthetic manifest of
    well-separated volumes on the interpreter path (dispatch overhead
    floors sub-0.1ms shapes, so tiny shapes can swap ranks); full mode
    captures the llama3-8b smoke deployment's own prefill+decode
    programs and replays that manifest.

    PYTHONPATH=src python benchmarks/bench_obs.py           # full
    PYTHONPATH=src python benchmarks/bench_obs.py --smoke   # CI gate
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from common import ROOT, emit, median

from repro.configs import get_config
from repro.models import build_model
from repro.obs.registry import get_registry
from repro.obs.tracing import Tracer, set_tracer
from repro.serving import Engine, ServeConfig
from repro.serving.sched import (ContinuousScheduler, Request, SchedConfig,
                                 TraceClock, TrafficConfig, poisson_trace,
                                 replay)

BENCH_PATH = ROOT / "BENCH_obs.json"
OVERHEAD_GATE = 1.05            # tracing-enabled wall <= 1.05x disabled
FIDELITY_GATE = 0.9             # Spearman(predicted energy, measured time)


# ------------------------------------------------------------- overhead
def _serving_pass(engine, trace, *, traced: bool) -> tuple[float, int]:
    """One full trace replay; returns (wall_s, n_spans)."""
    tracer = Tracer() if traced else None
    prev = set_tracer(tracer)
    try:
        clock = TraceClock()
        sched = ContinuousScheduler(
            engine, SchedConfig(slots=4, chunk_widths=(8, 32)),
            clock=clock.now)
        t0 = time.perf_counter()
        results = replay(sched, [Request(**vars(r)) for r in trace],
                         clock)
        wall = time.perf_counter() - t0
        assert len(results) == len(trace)
        return wall, len(tracer.spans) if tracer else 0
    finally:
        set_tracer(prev)


def tracer_overhead(*, n_requests: int = 16, passes: int = 3) -> dict:
    """Interleaved traced/untraced replays of the serving smoke config.

    The first (untraced) pass compiles every signature the trace
    touches, so both arms measure steady-state compute; arms alternate
    so drift (thermal, allocator state) cancels in the medians."""
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(max_new_tokens=24,
                                               cache_len=112))
    trace = poisson_trace(TrafficConfig(
        n_requests=n_requests, arrival_rate=40.0,
        prompt_mix=((4, 12, 0.5), (16, 40, 0.35), (48, 64, 0.15)),
        max_new_range=(8, 24), vocab=cfg.vocab, seed=0))

    _serving_pass(engine, trace, traced=False)          # jit warmup
    off, on_ = [], []
    n_spans = 0
    for _ in range(passes):
        w, _n = _serving_pass(engine, trace, traced=False)
        off.append(w)
        w, n_spans = _serving_pass(engine, trace, traced=True)
        on_.append(w)
    off_med, on_med = median(off), median(on_)
    ratio = on_med / off_med
    row = {"n_requests": n_requests, "passes": passes,
           "wall_disabled_s": round(off_med, 4),
           "wall_enabled_s": round(on_med, 4),
           "overhead_ratio": round(ratio, 4),
           "spans_per_pass": n_spans,
           "gate": OVERHEAD_GATE, "passes_gate": ratio <= OVERHEAD_GATE}
    emit("obs_tracer_overhead_ratio", ratio,
         f"enabled/disabled wall, {n_spans} spans/pass, "
         f"gate<={OVERHEAD_GATE}")
    assert ratio <= OVERHEAD_GATE, \
        (f"tracing overhead {ratio:.3f}x exceeds the "
         f"{OVERHEAD_GATE}x gate (disabled {off_med:.3f}s, "
         f"enabled {on_med:.3f}s)")
    return row


# ------------------------------------------------------------- fidelity
def _synthetic_manifest():
    """Well-separated GEMM volumes: each ~4x the last, all >= (128,
    256, 256) so none sits on the dispatch-overhead floor where ranks
    can swap."""
    from repro.planner.manifest import ManifestEntry, ModelMappingManifest

    shapes = [(128, 256, 256), (256, 256, 512), (256, 512, 1024),
              (512, 1024, 1024), (1024, 1024, 2048)]
    entries = [ManifestEntry(
        gemm_type="synthetic", dims=dims, weight=1,
        digest=f"synthetic-{i}", objective=0.0, feasible=True,
        solve_time_s=0.0, cached=False, warm_started=False, gap=0.0)
        for i, dims in enumerate(shapes)]
    return ModelMappingManifest(
        model="obs-smoke", hw_name="tpuv5e-like", objective="energy",
        prefill_seqs=(), decode_batches=(), cache_len=0,
        entries=entries)


def fidelity_smoke(*, repeats: int = 3, warmup: int = 1) -> dict:
    from repro.obs.fidelity import replay_manifest

    manifest = _synthetic_manifest()
    rep = replay_manifest(manifest, repeats=repeats, warmup=warmup,
                          interpret=True, gate=FIDELITY_GATE)
    row = {"manifest": manifest.model, "interpret": True,
           **rep.summary()}
    emit("obs_fidelity_smoke_spearman", rep.overall,
         f"{len(rep.rows)} rows, gate>={FIDELITY_GATE}")
    assert rep.passes(), f"fidelity smoke gate failed: {rep.summary()}"
    return row


def fidelity_full(*, repeats: int = 15, warmup: int = 5) -> dict:
    """Capture the llama3-8b smoke deployment's own programs, plan
    them, and replay the resulting manifest through the kernels.

    The smoke model's GEMMs run in tens of µs, where dispatch noise
    dominates a median — min-of-N is the stable estimator at that
    scale (see ``obs.fidelity._time_gemm``)."""
    from repro.capture import (capture_model_decode, capture_model_prefill,
                               plan_program)
    from repro.core import TEMPLATES
    from repro.obs.fidelity import replay_manifest
    from repro.planner.manifest import ModelMappingManifest

    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    hw = TEMPLATES["eyeriss-like"]
    prefill = plan_program(capture_model_prefill(model, 4, 64), hw)
    decode = plan_program(
        capture_model_decode(model, 4, 112, slot_indexed=True), hw)
    entries = prefill.manifest.entries + decode.manifest.entries
    manifest = ModelMappingManifest(
        model=f"{cfg.name}_serving", hw_name=hw.name,
        objective="energy", prefill_seqs=(64,), decode_batches=(4,),
        cache_len=112, entries=entries)
    rep = replay_manifest(manifest, repeats=repeats, warmup=warmup,
                          gate=FIDELITY_GATE, estimator="min")
    row = {"manifest": manifest.model, "estimator": "min",
           "entries": len(manifest.entries), **rep.summary()}
    emit("obs_fidelity_full_spearman", rep.overall,
         f"{len(rep.rows)} rows ({len({r.dims for r in rep.rows})} "
         f"unique shapes), gate>={FIDELITY_GATE}")
    assert rep.passes(), f"fidelity full gate failed: {rep.summary()}"
    return row


# ------------------------------------------------------------ registry
def registry_snapshot() -> dict:
    """Counter totals accumulated across this bench run — doubles as a
    liveness check that the instrumented paths actually count."""
    snap = get_registry().snapshot()
    keep = {k: v for k, v in snap.items()
            if k.startswith(("sched.", "jit.", "solver.",
                             "plan_store.", "planner.", "capture."))}
    assert keep.get("sched.ticks", 0) > 0, \
        f"scheduler counters never fired: {sorted(snap)}"
    assert keep.get("jit.compiles", 0) > 0, \
        f"compile counter never fired: {sorted(snap)}"
    return keep


def run(*, smoke: bool = False) -> dict:
    get_registry().reset()
    out = {"generated_unix": time.time(), "smoke": smoke,
           "overhead": tracer_overhead(
               n_requests=8 if smoke else 16,
               passes=2 if smoke else 3),
           "fidelity_smoke": fidelity_smoke()}
    if not smoke:
        out["fidelity_full"] = fidelity_full()
    out["counters"] = registry_snapshot()
    BENCH_PATH.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {BENCH_PATH}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
