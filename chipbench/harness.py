"""One run of one cell: build the deployment, warm it up, drive open-loop
traffic on the wall clock, read the metrics, check the outputs.

The deployment is built as ``repro.launch.serve --continuous`` builds
it (``continuous_engine``, ``CHUNK_WIDTHS``, ``SchedConfig``,
``ContinuousScheduler`` with a plan store and capture prewarm).  The
window drives ``ContinuousScheduler.submit`` and ``.step``; every token
is timed on the host clock as the scheduler streams it.  Nothing here is
specific to a cell: the configuration, the traffic mix and the metric
readers are found by the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "BENCHMARK.json"
CACHE = ROOT / ".chipbench_cache"
PEAKS = ROOT / "chipbench" / "peaks.json"
# Seconds of the window that a --trace 1 run records with the profiler,
# from the window's start: traces are large and slow to read.
TRACE_S = 5.0
# How long past the window's close a steady cell waits for the first
# token of a request that arrived inside it.
CARRY_S = 60.0
# Finished requests the output check compares, the longest among them.
CHECK_REQUESTS = 12

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


# ------------------------------------------------------------------ specs
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    bench: dict


def load_cell(name: str, bench_path=BENCH) -> Cell:
    bench = json.loads(pathlib.Path(bench_path).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    root = pathlib.Path(bench_path).resolve().parent
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "chipbench" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), config, mix, bench)


def require_chips(n: int):
    """The devices of this run; raises NoChip where JAX finds no TPU or
    fewer than ``n`` of them.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"{n} chips needed, JAX found {len(devs)}")
    return devs


def device_peaks(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS}")
    return table[kind]


def arch_config(config: dict):
    """The program's ArchConfig for a configuration file, checked
    against the file's published sizes."""
    from repro.configs import get_config
    cfg = get_config(config["arch"]).replace(
        param_dtype=config["param_dtype"],
        compute_dtype=config["compute_dtype"])
    check_sizes(cfg, config)
    return cfg


def check_sizes(cfg, config: dict) -> None:
    pairs = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.layers,
             "num_attention_heads": cfg.n_heads,
             "num_key_value_heads": cfg.kv_heads,
             "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
             "num_local_experts": cfg.n_experts,
             "num_experts_per_tok": cfg.top_k}
    bad = {k: (config[k], v) for k, v in pairs.items()
           if k in config and config[k] != v}
    if bad:
        raise ValueError(f"configuration file and program differ "
                         f"(file, program): {bad}")


def as_run(config: dict) -> dict:
    """The configuration as the program runs it: the published keys with
    the program's departures from them."""
    out = dict(config)
    out.update({k: d["run"] for k, d in config.get("departures", {}).items()})
    return out


def family_module(kind: str, config: dict):
    return importlib.import_module(f"chipbench.{kind}.{config['family']}")


# ------------------------------------------------------------- recording
class Recorder:
    """Host-clock record of every request and tick of a run."""

    def __init__(self):
        from chipbench.window import RequestRecord
        self._Record = RequestRecord
        self.records: dict[int, RequestRecord] = {}
        self.tick = 0
        self.ticks: list[tuple[int, float, float]] = []
        self.compiles: list[float] = []

    def add(self, a, due: float):
        r = self._Record(a.idx, due, int(a.prompt.size), a.max_new_tokens,
                         a.prompt)
        self.records[a.idx] = r
        return r

    def on_token(self, req, tok: int) -> None:
        r = self.records.get(req.req_id)
        if r is not None:
            r.token_times.append(time.perf_counter())
            r.tokens.append(int(tok))
            r.token_ticks.append(self.tick)

    def on_finish(self, res) -> None:
        r = self.records.get(res.req_id)
        if r is not None:
            r.finish_reason = res.finish_reason

    def on_compile(self, name: str, secs: float, **_) -> None:
        if name == COMPILE_EVENT:
            self.compiles.append(time.perf_counter())


# ------------------------------------------------------------ deployment
@dataclasses.dataclass
class Deployment:
    cfg: object
    engine: object
    sched: object
    rec: Recorder
    prewarm_s: float


def build(cfg, params, config: dict, mix: dict, rec: Recorder,
          *, engine=None) -> Deployment:
    """The engine (unless given) and a fresh scheduler over it."""
    from chipbench.traffic import length_cap
    from repro.launch.serve import CHUNK_WIDTHS, continuous_engine
    from repro.models import build_model
    from repro.planner import PlanStore
    from repro.serving.sched import ContinuousScheduler, SchedConfig
    if engine is None:
        engine = continuous_engine(build_model(cfg), params,
                                   PlanStore(CACHE / "plan_db"),
                                   prompt_len=length_cap(mix["prompt_len"]),
                                   new_tokens=length_cap(mix["output_len"]))
    t0 = time.perf_counter()
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=int(mix["slots"]),
                            chunk_widths=CHUNK_WIDTHS),
        arch_id=config["arch"], on_token=rec.on_token,
        on_finish=rec.on_finish)
    return Deployment(cfg, engine, sched, rec, time.perf_counter() - t0)


def warm_up(dep: Deployment) -> None:
    """Run every program the window will: both chunk widths at B=1, the
    graft into the slot cache, decode over all slots and sampling; then
    the host-side slicing of each chunk's logits row, which compiles once
    per (width, row) pair."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import CHUNK_WIDTHS
    from repro.serving.sched import Request
    sched, eng = dep.sched, dep.engine
    wmax = max(CHUNK_WIDTHS)
    vocab = dep.cfg.vocab
    rng = np.random.default_rng(0)
    for i, n in enumerate((wmax + min(CHUNK_WIDTHS), 2 * wmax)):
        sched.submit(Request(req_id=-1 - i, max_new_tokens=3,
                             tokens=rng.integers(0, vocab, n, np.int32)))
    sched.run()
    cache = eng.new_cache(1)
    for w in CHUNK_WIDTHS:
        logits, _ = eng.prefill_chunk(cache, np.zeros((1, w), np.int32), 0)
        for n in range(1, w + 1):
            row = logits[0, n - 1]
            np.asarray(row)
            int(jnp.argmax(row))
    del cache, logits, row
    jax.block_until_ready(sched.slot_cache)
    sched.results.clear()


# ----------------------------------------------------------------- drive
def drive(dep: Deployment, arrivals, *, ramp_s: float, seconds: float,
          carry: bool, profile=None):
    """Offer ``arrivals`` on the wall clock and step the scheduler.

    The window opens ``ramp_s`` after the first arrival and lasts
    ``seconds``.  With ``carry``, the run goes on after the close (and
    arrivals with it) until every request due in the window has its
    first token, or ``CARRY_S`` has passed.  ``profile`` (a Profile)
    records the first part of the window.  Returns the Window."""
    import jax

    from chipbench.window import Window
    from repro.serving.sched import Request
    Ann = jax.profiler.TraceAnnotation
    sched, rec = dep.sched, dep.rec
    t0 = time.perf_counter()
    win = Window(t0 + ramp_s, t0 + ramp_s + seconds)
    due = [t0 + a.due_s for a in arrivals]
    clock_off = time.perf_counter() - sched.clock()
    i, n = 0, len(arrivals)
    while True:
        now = time.perf_counter()
        if profile is not None:
            profile.poll(now, win)
        if now >= win.end:
            if not carry or now >= win.end + CARRY_S:
                break
            if all(r.first_token is not None or r.shed
                   for r in rec.records.values() if win.holds(r.due)):
                break
        if i < n and due[i] <= now:
            with Ann("chipbench.submit"):
                while i < n and due[i] <= now:
                    a = arrivals[i]
                    r = rec.add(a, due[i])
                    res = sched.submit(Request(
                        req_id=a.idx, tokens=a.prompt,
                        max_new_tokens=a.max_new_tokens,
                        arrival_s=due[i] - clock_off))
                    r.submitted = time.perf_counter()
                    if res is not None:
                        r.finish_reason = res.finish_reason
                    i += 1
        if sched.busy:
            rec.tick += 1
            t_a = time.perf_counter()
            with Ann("chipbench.step"):
                sched.step()
            rec.ticks.append((rec.tick, t_a, time.perf_counter()))
        elif i < n:
            until = due[i] if now >= win.end else min(due[i], win.end)
            with Ann("chipbench.wait"):
                time.sleep(max(0.0, until - time.perf_counter()))
        elif now >= win.end:
            break
        else:
            with Ann("chipbench.wait"):
                time.sleep(max(0.0, win.end - time.perf_counter()))
    if profile is not None:
        profile.stop()
    return win


class Profile:
    """The profiler over the first ``TRACE_S`` seconds of the window,
    inside a ``chipbench.window`` annotation."""

    def __init__(self, out_dir: pathlib.Path, seconds: float):
        self.dir = out_dir
        self.seconds = seconds
        self.ann = None
        self.perf = None          # (start, end) on the host clock

    def poll(self, now: float, win) -> None:
        import jax
        if self.ann is None and self.perf is None and now >= win.start:
            import shutil
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation("chipbench.window")
            self.perf = (time.perf_counter(), None)
            self.ann.__enter__()
        elif self.ann is not None and now >= min(
                win.end, self.perf[0] + self.seconds):
            self.stop()

    def stop(self) -> None:
        import jax
        if self.ann is None:
            return
        self.perf = (self.perf[0], time.perf_counter())
        self.ann.__exit__(None, None, None)
        self.ann = None
        jax.profiler.stop_trace()


# ------------------------------------------------------------- the check
def sample(records, seed: int) -> list:
    """Finished requests to compare, drawn from the seed: the one with the
    most served tokens, then up to CHECK_REQUESTS - 1 others."""
    done = sorted((r for r in records if r.finished),
                  key=lambda r: (-len(r.tokens), r.idx))
    if not done:
        return []
    rng = np.random.default_rng([int(seed) % 2**64, 1])
    rest = rng.permutation(len(done) - 1)[:CHECK_REQUESTS - 1] + 1
    return [done[0]] + [done[j] for j in rest]


def sequences(picked, length: int):
    """(tokens, cands, mask) arrays (len(picked), length): each prompt
    followed by its served tokens, and at each position the served token
    that follows it."""
    B = len(picked)
    toks = np.zeros((B, length), np.int32)
    cands = np.zeros((B, length), np.int32)
    mask = np.zeros((B, length), bool)
    for b, r in enumerate(picked):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        if seq.size - 1 > length:
            raise ValueError(f"request {r.idx} is longer than {length}")
        toks[b, :seq.size - 1] = seq[:-1]
        p = r.prompt_len
        cands[b, p - 1:p - 1 + len(r.tokens)] = r.tokens
        mask[b, p - 1:p - 1 + len(r.tokens)] = True
    return toks, cands, mask


def reference_gaps(config: dict, params, picked, length: int, *,
                   control: bool = False) -> list[np.ndarray]:
    """Per request, the gap by which each served token's logit lies below
    the float32 reference's best at its position (0 where the reference
    puts it first).  With ``control``, the tokens compared are those the
    configuration's control precision puts first there, not the served
    ones."""
    ref = family_module("reference", config)
    run_as = as_run(config)
    f32 = ref.gap_fn(run_as, "float32")
    low = ref.gap_fn(run_as, config["check"]["control"]) if control else None
    toks, cands, mask = sequences(picked, length)
    out = []
    for b in range(len(picked)):
        t, c = toks[b:b + 1], cands[b:b + 1]
        if low is not None:
            c = np.asarray(low(params, t, c)[1])
        gap = np.asarray(f32(params, t, c)[0])
        out.append(gap[mask[b:b + 1]])
    return out


def gap_numbers(gaps: list[np.ndarray]) -> dict:
    """The numbers an output check can compare, over all tokens compared:
    the widest gap, the mean gap, and the share of tokens that are not
    the reference's first choice."""
    if not gaps or not sum(g.size for g in gaps):
        return {"max_logit_gap": float("inf"),
                "mean_logit_gap": float("inf"),
                "off_argmax_share": 1.0}
    g = np.concatenate(gaps)
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "off_argmax_share": float(np.mean(g > 0))}


def judge(config: dict, numbers: dict, unserved: int = 0):
    """(check, correct): each number the configuration compares beside
    its limit, and whether every one is within it."""
    check = {k: {"value": numbers[k], "limit": float(v)}
             for k, v in config["check"]["limits"].items()}
    check["unserved"] = {"value": unserved, "limit": 0}
    return check, all(c["value"] <= c["limit"] for c in check.values())


# ------------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    window: object
    records: list
    setup_s: float
    prewarm_s: float
    ticks: list
    spans: list
    trace: dict | None
    tick_calls: dict
    peaks: dict


def metrics_for(cell: Cell, trace: bool) -> list[dict]:
    """The cell's metric entries: end-to-end without a trace, per-layer
    with one."""
    e2e = [m for m in cell.bench["end_to_end"]
           if cell.name in m.get("workloads", [cell.name])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in cell.bench["per_layer"]
            if cell.name in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def read_metrics(run: Run, entries) -> dict:
    out = {}
    for m in entries:
        reader = importlib.import_module(
            "chipbench.metrics." + m["name"].split(".")[0])
        v = reader.read(run, m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def tick_calls(rec: Recorder, spans, records) -> dict:
    """Per tick, its program calls as rows (q, past, logits) for the
    work counters: the prefill chunk, then the decode step."""
    import bisect
    starts = [t for _, t, _ in rec.ticks]
    first_tick = {r.token_ticks[0] for r in records if r.token_ticks}
    calls: dict[int, list] = {}
    for sp in spans:
        if sp.name != "sched.prefill_chunk":
            continue
        k = bisect.bisect_right(starts, sp.t0) - 1
        if k < 0:
            continue
        tick = rec.ticks[k][0]
        a = sp.attrs
        calls.setdefault(tick, []).append(
            [(a["real"], a["start"], int(tick in first_tick))])
    decode: dict[int, list] = {}
    for r in records:
        for j in range(1, len(r.token_ticks)):
            decode.setdefault(r.token_ticks[j], []).append(
                (1, r.prompt_len + j - 1, 1))
    for tick, rows in decode.items():
        calls.setdefault(tick, []).append(rows)
    return calls


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False) -> dict:
    """One run of ``cell``.  With ``control``, the output check compares
    the tokens the configuration's control precision puts first, in
    place of the served ones (for tests of the check)."""
    import jax
    import jax.monitoring

    from chipbench import traffic, weights
    from repro.models import build_model
    from repro.obs.tracing import Tracer, set_tracer

    if not cell.config["check"]["limits"]:
        raise ValueError("the configuration sets no output-check limit")
    devs = require_chips(cell.chips)
    dev = devs[0]
    parts = {"start": time.perf_counter() - t_start}
    rec = Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec.on_compile)
    cfg = arch_config(cell.config)
    t = time.perf_counter()
    params = jax.block_until_ready(
        weights.make(build_model(cfg).init_params, seed))
    parts["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    dep = build(cfg, params, cell.config, cell.mix, rec)
    parts["build"] = time.perf_counter() - t
    del params
    t = time.perf_counter()
    warm_up(dep)
    parts["warm_up"] = time.perf_counter() - t
    rec.records.clear()
    setup_s = time.perf_counter() - t_start

    mix = cell.mix
    steady = mix["regime"] == "steady"
    horizon = mix["ramp_s"] + seconds + (mix["tail_s"] if steady else 0.0)
    arrivals = traffic.schedule(mix, seed=seed, horizon_s=horizon,
                                vocab=cfg.vocab)
    tracer = profile = None
    if trace:
        tracer = Tracer()
        set_tracer(tracer)
        profile = Profile(CACHE / "trace" / cell.name,
                          min(TRACE_S, seconds))
    try:
        win = drive(dep, arrivals, ramp_s=mix["ramp_s"], seconds=seconds,
                    carry=steady, profile=profile)
    finally:
        if trace:
            set_tracer(None)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    records = sorted(rec.records.values(), key=lambda r: r.idx)
    compiles = sum(1 for t in rec.compiles if win.holds(t))

    tr = None
    spans = tracer.spans if tracer is not None else []
    if trace:
        tr = read_trace(profile, spans)
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    run = Run(cell=cell, window=win, records=records,
              setup_s=setup_s, prewarm_s=dep.prewarm_s, ticks=rec.ticks,
              spans=spans, trace=tr,
              tick_calls=tick_calls(rec, spans, records) if trace else {},
              peaks=device_peaks(dev.device_kind)
              if dev.platform == "tpu" else {})
    metrics = read_metrics(run, metrics_for(cell, trace))

    # the output check: after the window, on the program's state freed
    engine_cache_len = dep.engine.cfg.cache_len
    picked = sample(records, seed)
    in_win = [r for r in records if win.holds(r.due)]
    shed = sum(1 for r in in_win if r.shed)
    unserved = (sum(1 for r in in_win
                    if r.first_token is None and not r.shed)
                if steady else 0)
    del dep, rec
    gc.collect()
    params = weights.make(build_model(cfg).init_params, seed)
    gaps = reference_gaps(cell.config, params, picked, engine_cache_len,
                          control=control)
    del params
    numbers = gap_numbers(gaps)
    check, correct = judge(cell.config, numbers, unserved)
    compared = sum(len(r.tokens) for r in picked)
    late = [r.submitted - r.due for r in in_win if r.submitted is not None]
    diag = {"setup_parts_s": parts, "compiles_in_window": compiles,
            "generator_late_p99_ms": 1e3 * float(np.percentile(late, 99))
            if late else None,
            "window_requests": len(in_win),
            "ticks": sum(1 for t in run.ticks if win.holds(t[1])),
            "requests_compared": len(picked), "tokens_compared": compared,
            "gap_numbers": numbers,
            "per_request_max_gap": [float(g.max()) if g.size else None
                                    for g in gaps]}
    if tr is not None:
        diag["programs_s"] = tr["programs"]
    result = {"correct": correct, "attempted": len(in_win),
              "failed": shed + unserved, "metrics": metrics,
              "device": device}
    if trace and tr is not None:
        result["breakdown"] = {"device_ops": tr["top_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["check"] = check
    return {"result": result, "diag": diag}


def read_trace(profile: Profile, spans) -> dict:
    """Reduce the profiler's trace of the traced window; obs spans are
    moved onto the trace's clock by the window annotation."""
    from chipbench import trace_reduce as tr
    trace = tr.load(tr.find_xplane(profile.dir))
    lo, hi = tr.window_of(trace)
    red = tr.reduce(trace, lo, hi)
    off = lo - profile.perf[0] * 1e9
    acts = [(n, a, b) for n, a, b in trace["host"]
            if n != "chipbench.window"]
    acts += [(s.name, s.t0 * 1e9 + off, s.t1 * 1e9 + off) for s in spans
             if s.t1 is not None and s.name in (
                 "sched.tick", "sched.prefill_chunk", "sched.decode_batch")]
    idle = [g for gs in red["idle"].values() for g in gs]
    red["idle_gaps"] = tr.attribute(idle, acts)
    red["perf_window"] = profile.perf
    return red


def main(argv=None, *, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    res = out["result"]
    print("chipbench: " + json.dumps(out["diag"]), file=sys.stderr)
    for k, v in res["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(res))
    return 0
