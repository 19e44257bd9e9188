"""Observability subsystem: tracer, registry, fidelity recorder.

Covers the contracts the rest of the repo leans on: span nesting and
JSONL round-trips, virtual-clock replay determinism, scoped registry
reset, the registry-backed ``solver_stats()``/``axis_cache_stats()``
shims, store-counter mirroring, scheduler tick/request spans, the
NaN-safe metrics summary, and a small fidelity replay.
"""
from __future__ import annotations

import json
import re

import numpy as np
import pytest

from repro.obs.registry import Registry, get_registry
from repro.obs.tracing import NULL_SPAN, Tracer, get_tracer, set_tracer
from repro.obs.tracing import span as obs_span
from repro.obs.tracing import trace_event


# ---------------------------------------------------------------- tracer
class TestTracer:
    def test_nesting_parents(self):
        tr = Tracer()
        with tr.span("outer"):
            with tr.span("inner"):
                tr.event("leaf")
        outer, inner, leaf = tr.spans
        assert outer.parent is None
        assert inner.parent == outer.sid
        assert leaf.parent == inner.sid
        assert leaf.t0 == leaf.t1                    # zero-length event
        assert [s.name for s in tr.children(outer)] == ["inner"]

    def test_siblings_share_parent(self):
        tr = Tracer()
        with tr.span("p"):
            with tr.span("a"):
                pass
            with tr.span("b"):
                pass
        p, a, b = tr.spans
        assert a.parent == p.sid and b.parent == p.sid

    def test_detached_span_straddles_stack(self):
        """Detached spans (per-request lifecycle) record a parent but
        never become the implicit parent of stacked spans."""
        tr = Tracer()
        with tr.span("tick0"):
            req = tr.start("request", detached=True, req_id=7)
        with tr.span("tick1"):
            pass
        tr.end(req, n=3)
        names = {s.name: s for s in tr.spans}
        assert names["request"].parent == names["tick0"].sid
        assert names["tick1"].parent is None         # not under "request"
        assert names["request"].t1 >= names["tick1"].t1
        assert names["request"].attrs == {"req_id": 7, "n": 3}

    def test_virtual_clock_replay_determinism(self):
        """Two runs on the same fake clock serialize identically."""
        def run():
            t = [0.0]

            def clock():
                t[0] += 0.125
                return t[0]

            tr = Tracer(clock=clock)
            with tr.span("solve", dims=[4, 4, 4]):
                tr.event("node", depth=2)
            return tr.dumps_jsonl()

        assert run() == run()
        spans = [json.loads(l) for l in run().splitlines()]
        assert [s["t0"] for s in spans] == [0.125, 0.25]

    def test_jsonl_round_trip(self, tmp_path):
        tr = Tracer()
        with tr.span("a", k="v", n=2):
            tr.event("b")
        path = tmp_path / "spans.jsonl"
        tr.to_jsonl(path)
        back = Tracer.from_jsonl(path)
        assert len(back) == 2
        assert [(s.sid, s.parent, s.name, s.attrs) for s in back] == \
            [(s.sid, s.parent, s.name, s.attrs) for s in tr.spans]
        assert back[0].duration == pytest.approx(tr.spans[0].duration)

    def test_module_level_span_null_when_disabled(self):
        assert get_tracer() is None
        cm = obs_span("anything", k=1)
        assert cm is NULL_SPAN and not cm
        with cm as sp:
            assert sp is None
        assert trace_event("nothing") is None

    def test_set_tracer_returns_previous(self):
        t1, t2 = Tracer(), Tracer()
        assert set_tracer(t1) is None
        assert set_tracer(t2) is t1
        with obs_span("x") as sp:
            assert sp is not None
        assert [s.name for s in t2.spans] == ["x"]
        assert t1.spans == []
        set_tracer(None)

    def test_profiler_annotations_follow_stacked_spans(self, monkeypatch):
        """Stacked spans enter a profiler annotation of their name and
        opening attributes (a step annotation with ``step_num``) and
        leave it when they end; detached spans and events enter none,
        and with no tracer installed, or no profile recording, none is
        even built."""
        jax = pytest.importorskip("jax")
        log = []
        recording = [False]

        class Ann:
            step = False

            @staticmethod
            def is_enabled():
                return recording[0]

            def __init__(self, name, **kw):
                log.append(("new", name, self.step, kw))
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        class StepAnn(Ann):
            step = True

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", StepAnn)
        recording[0] = True
        with obs_span("sched.tick", step_num=3, tick=3):
            with obs_span("sched.emit", tick=3):
                pass
        assert log == []                          # no tracer: nothing built
        tr = Tracer()
        set_tracer(tr)
        recording[0] = False
        with obs_span("sched.tick", step_num=2, tick=2):
            pass
        assert log == []                          # no profile: none built
        recording[0] = True
        with obs_span("sched.tick", step_num=3, tick=3):
            req = tr.start("sched.request", detached=True, req_id=1)
            with obs_span("sched.emit", tick=3):
                trace_event("sched.first_token", req_id=1)
        tr.end(req)
        assert log == [
            ("new", "sched.tick", True, {"step_num": 3, "tick": 3}),
            ("enter", "sched.tick"),
            ("new", "sched.emit", False, {"tick": 3}),
            ("enter", "sched.emit"), ("exit", "sched.emit"),
            ("exit", "sched.tick")]
        # the host record is unchanged: step_num is not an attribute
        assert [sp.attrs for sp in tr.by_name("sched.tick")] == [
            {"tick": 2}, {"tick": 3}]


# -------------------------------------------------------------- registry
class TestRegistry:
    def test_counters_and_scoped_reset(self):
        reg = Registry()
        reg.inc("a.x")
        reg.inc("a.y", 4)
        reg.inc("b.z")
        reg.set_gauge("a.g", 0.5)
        assert reg.counters("a.") == {"a.x": 1, "a.y": 4}
        reg.reset("a.")
        # counters zero in place (keys survive); gauges are deleted
        assert reg.counters("a.") == {"a.x": 0, "a.y": 0}
        assert reg.get("b.z") == 1
        assert reg.gauges() == {}
        reg.reset()
        assert all(v == 0 for v in reg.snapshot().values())

    def test_snapshot_merges_sorted(self):
        reg = Registry()
        reg.inc("z.c")
        reg.set_gauge("a.g", 2.0)
        assert list(reg.snapshot()) == ["a.g", "z.c"]

    def test_solver_stats_shim_reads_registry(self):
        from repro.core import EYERISS_LIKE, Gemm
        from repro.core.solver import (reset_solver_stats, solve,
                                       solver_stats)

        reset_solver_stats()
        assert solver_stats() == {"calls": 0}
        solve(Gemm(16, 16, 16, name="t"), EYERISS_LIKE)
        assert solver_stats() == {"calls": 1}
        assert get_registry().get("solver.calls") == 1
        reset_solver_stats()
        assert solver_stats() == {"calls": 0}

    def test_axis_cache_stats_shim(self):
        from repro.core import EYERISS_LIKE, Gemm
        from repro.core.solver import (axis_cache_stats, clear_axis_cache,
                                       solve)

        clear_axis_cache()
        solve(Gemm(24, 24, 24, name="t"), EYERISS_LIKE)
        st = axis_cache_stats()
        assert st["misses"] > 0 and st["entries"] == st["misses"]
        solve(Gemm(24, 24, 24, name="t2"), EYERISS_LIKE)
        assert axis_cache_stats()["hits"] > 0
        clear_axis_cache()
        assert axis_cache_stats() == {"hits": 0, "misses": 0,
                                      "entries": 0}

    def test_store_counters_mirrored(self, tmp_path):
        from repro.core import EYERISS_LIKE, Gemm
        from repro.planner import PlanStore
        from repro.planner.batch import BatchPlanner

        store = PlanStore(tmp_path / "db")
        planner = BatchPlanner(store)
        rows = [("qkv", Gemm(16, 48, 16, name="qkv"), 1)]
        planner.plan_gemms(rows, EYERISS_LIKE)
        planner.plan_gemms(rows, EYERISS_LIKE)
        reg = get_registry()
        assert reg.get("plan_store.misses") == store.misses == 1
        assert reg.get("plan_store.hits") == store.hits == 1
        assert reg.get("plan_store.puts") == store.puts == 1
        assert reg.get("planner.batches") == 2


# ------------------------------------------------------------- scheduler
@pytest.mark.slow
class TestSchedulerSpans:
    def test_tick_and_request_spans(self):
        jax = pytest.importorskip("jax")
        from repro.configs import get_config
        from repro.models import build_model
        from repro.serving import Engine, ServeConfig
        from repro.serving.sched import (ContinuousScheduler, Request,
                                         SchedConfig, TraceClock, replay)

        cfg = get_config("llama3-8b", smoke=True)
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        engine = Engine(model, params,
                        ServeConfig(max_new_tokens=4, cache_len=32))
        rng = np.random.default_rng(0)
        reqs = [Request(req_id=i,
                        tokens=rng.integers(0, cfg.vocab, (6,)),
                        max_new_tokens=4, arrival_s=0.01 * i)
                for i in range(2)]
        tr = Tracer()
        set_tracer(tr)
        ticks = []
        try:
            clock = TraceClock()
            sched = ContinuousScheduler(
                engine, SchedConfig(slots=2, chunk_widths=(4, 8)),
                clock=clock.now,
                on_tick=lambda s: ticks.append(s.metrics.steps))
            results = replay(sched, reqs, clock)
        finally:
            set_tracer(None)
        assert len(results) == 2
        names = [s.name for s in tr.spans]
        assert names.count("sched.tick") == sched.metrics.steps
        assert names.count("sched.request") == 2
        assert names.count("sched.first_token") == 2
        assert "sched.decode_batch" in names and \
            "sched.prefill_chunk" in names
        for rs in tr.by_name("sched.request"):
            assert rs.t1 is not None
            assert rs.attrs["n_generated"] == 4
            kids = [s.name for s in tr.children(rs)]
            assert kids == ["sched.first_token"]
            assert set(rs.attrs) >= {"req_id"}
        # the tick's sub-spans, each under its parent, each with tick=
        by_sid = {s.sid: s for s in tr.spans}
        parent_of = {"sched.admit": "sched.tick",
                     "sched.prefill_chunk": "sched.tick",
                     "sched.graft": "sched.tick",
                     "sched.decode_batch": "sched.tick",
                     "sched.emit": "sched.tick",
                     "sched.decode.dispatch": "sched.decode_batch",
                     "sched.decode.read": "sched.decode_batch"}
        assert set(parent_of) <= set(names)
        for sp in tr.spans:
            if sp.name in parent_of:
                up = by_sid[sp.parent]
                assert up.name == parent_of[sp.name], sp
                assert sp.attrs["tick"] == up.attrs["tick"]
                assert up.t0 <= sp.t0 <= sp.t1 <= up.t1
        assert names.count("sched.admit") == 2
        assert names.count("sched.graft") == 2
        for db in tr.by_name("sched.decode_batch"):
            assert [c.name for c in tr.children(db)] == [
                "sched.decode.dispatch", "sched.decode.read"]
        # what the benchmark reads keeps its names and attributes
        assert [s.attrs["tick"] for s in tr.by_name("sched.tick")] == \
            list(range(sched.metrics.steps))
        for pc in tr.by_name("sched.prefill_chunk"):
            assert {"real", "start", "width"} <= set(pc.attrs)
            assert by_sid[pc.parent].name == "sched.tick"
        for db in tr.by_name("sched.decode_batch"):
            assert by_sid[db.parent].name == "sched.tick"
        # on_tick fired once per step, after the tick span closed
        assert ticks == list(range(1, sched.metrics.steps + 1))
        reg = get_registry()
        assert reg.get("sched.ticks") == sched.metrics.steps
        assert reg.get("sched.finished") == 2
        assert reg.get("sched.tokens") == 8
        assert reg.get("sched.padded_decode_rows") == \
            sched.metrics.padded_decode_rows


    def test_spans_on_the_profiler_clock(self, tmp_path):
        """A scheduler ticking under a tracer inside a profile leaves
        ``sched.tick`` and its sub-spans in the trace's host plane, each
        sub-span inside its tick."""
        jax = pytest.importorskip("jax")
        from jax.profiler import ProfileData

        from repro.serving.sched import ContinuousScheduler, Request
        from repro.serving.sched import SchedConfig

        engine = _smoke_engine(jax)
        rng = np.random.default_rng(1)
        sched = ContinuousScheduler(
            engine, SchedConfig(slots=2, chunk_widths=(4, 8)))
        sched.run([Request(req_id=0, max_new_tokens=3,
                           tokens=rng.integers(0, 64, (6,)))])  # compile
        before = sched.metrics.steps
        set_tracer(Tracer())
        try:
            with jax.profiler.trace(str(tmp_path)):
                sched.run([Request(req_id=1, max_new_tokens=3,
                                   tokens=rng.integers(0, 64, (6,)))])
        finally:
            set_tracer(None)
        path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
        events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                  for plane in ProfileData.from_file(str(path)).planes
                  if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events
                  if e.name.startswith("sched.")]
        ticks = [e for e in events if e[0] == "sched.tick"]
        assert len(ticks) == sched.metrics.steps - before > 0
        assert [t[3]["tick"] for t in ticks] == \
            [t[3]["step_num"] for t in ticks]
        subs = {"sched.admit", "sched.prefill_chunk", "sched.graft",
                "sched.decode_batch", "sched.decode.dispatch",
                "sched.decode.read", "sched.emit"}
        assert subs <= {e[0] for e in events}
        for name, t0, t1, stats in events:
            if name in subs:
                tick = [t for t in ticks if t[3]["tick"] == stats["tick"]]
                assert len(tick) == 1, (name, stats)
                assert tick[0][1] <= t0 <= t1 <= tick[0][2], name


def _smoke_engine(jax):
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Engine, ServeConfig

    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return Engine(model, params, ServeConfig(max_new_tokens=4,
                                             cache_len=32))


# ---------------------------------------------------------- device names
class TestDeviceNames:
    def test_engine_programs_and_layer_scopes(self):
        """Prefill chunks and decode are programs of their own names, and
        their ops carry the model's layer scopes in ``op_name``."""
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        engine = _smoke_engine(jax)
        texts = {}
        for name, fn, batch, index in (
                ("prefill_chunk", engine._prefill_chunk, 1,
                 jnp.int32(0)),
                ("decode_slots", engine._decode_slots, 2,
                 jnp.zeros((2,), jnp.int32))):
            width = 4 if batch == 1 else 1
            lowered = fn.lower(engine.params, engine.new_cache(batch),
                               jnp.zeros((batch, width), jnp.int32), index)
            texts[name] = lowered.compile().as_text()
        for name, text in texts.items():
            assert text.startswith(f"HloModule jit_{name}"), text[:80]
            ops = " ".join(re.findall(r'op_name="([^"]*)"', text))
            assert f"jit({name})/" in ops
            for scope in ("/attention/", "/mlp/", "/lm_head/", "/embed/"):
                assert scope in ops, (name, scope)

    def test_kernel_scopes(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        from repro.kernels.ops import fused_mlp, gemm

        a = jnp.ones((8, 16), jnp.float32)
        w = jnp.ones((16, 32), jnp.float32)
        wd = jnp.ones((32, 16), jnp.float32)
        text = jax.jit(lambda a, w, wd: fused_mlp(
            gemm(a, w, force_xla=True) @ wd, w, w, wd,
            force_xla=True)).lower(a, w, wd).compile().as_text()
        assert "/goma_gemm/" in text and "/goma_fused_mlp/" in text

    def test_compile_counter(self):
        """Each backend compile counts in ``jit.compiles`` and, under a
        tracer, leaves a ``jit.compile`` event naming the program."""
        jax = pytest.importorskip("jax")

        from repro.obs.tracing import count_compiles

        count_compiles()
        count_compiles()                 # one listener, however called

        def compile_counter_probe(x):
            return x * 3 + 1

        tr = Tracer()
        set_tracer(tr)
        try:
            jax.jit(compile_counter_probe)(np.ones((7, 5), np.float32))
        finally:
            set_tracer(None)
        assert get_registry().get("jit.compiles") == 1
        (ev,) = tr.by_name("jit.compile")
        assert "compile_counter_probe" in ev.attrs["fun_name"]
        assert ev.attrs["duration_s"] > 0 and ev.t0 == ev.t1


# --------------------------------------------------------------- metrics
class TestMetrics:
    def test_tpot_nan_safe_and_padded_rows(self):
        from repro.serving.sched.metrics import ServingMetrics
        from repro.serving.sched.requests import RequestResult

        m = ServingMetrics()
        m.record_result(RequestResult(
            req_id=0, tokens=[5], finish_reason="length", prompt_len=4,
            arrival_s=0.0, first_token_s=0.1, finish_s=0.1))
        m.record_tick(active=1, slots=4, decoded=True, chunks=0,
                      padded_tokens=0, padded_rows=3)
        m.record_tick(active=2, slots=4, decoded=True, chunks=1,
                      padded_tokens=4, padded_rows=2)
        s = m.summary()
        # single-token request: no tpot samples -> 0.0, never NaN
        assert s["tpot_p50_s"] == 0.0 and s["tpot_p95_s"] == 0.0
        assert s["padded_decode_rows"] == 5
        assert json.loads(json.dumps(s)) == s


# -------------------------------------------------------------- fidelity
class TestFidelity:
    def test_spearman(self):
        from repro.obs.fidelity import spearman

        assert spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
        assert spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
        assert spearman([1.0], [5.0]) == 1.0          # degenerate: <2 pts
        assert spearman([1, 1, 1], [1, 2, 3]) == 0.0  # one side constant
        assert spearman([2, 2], [7, 7]) == 1.0        # both constant
        # monotone under ties
        assert spearman([1, 2, 2, 3], [1, 2, 3, 4]) > 0.9

    @pytest.mark.slow
    def test_replay_records_and_gates(self, tmp_path):
        pytest.importorskip("jax")
        from repro.obs.fidelity import (load_rows, record_rows,
                                        replay_manifest)
        from repro.planner.manifest import (ManifestEntry,
                                            ModelMappingManifest)

        shapes = [(128, 256, 256), (256, 512, 512), (512, 1024, 1024)]
        entries = [ManifestEntry(
            gemm_type="mlp", dims=d, weight=1, digest=f"e{i}",
            objective=0.0, feasible=True, solve_time_s=0.0,
            cached=False) for i, d in enumerate(shapes)]
        # an infeasible entry must be skipped, a duplicate-dims entry
        # must reuse the measurement under its own family
        entries.append(ManifestEntry(
            gemm_type="skip", dims=(8, 8, 8), weight=1, digest="bad",
            objective=0.0, feasible=False, solve_time_s=0.0,
            cached=False))
        entries.append(ManifestEntry(
            gemm_type="attn", dims=shapes[0], weight=3, digest="dup",
            objective=0.0, feasible=True, solve_time_s=0.0,
            cached=False))
        manifest = ModelMappingManifest(
            model="t", hw_name="tpuv5e-like", objective="energy",
            prefill_seqs=(), decode_batches=(), cache_len=0,
            entries=entries)
        rep = replay_manifest(manifest, repeats=2, warmup=1,
                              interpret=True, gate=0.9)
        assert len(rep.rows) == 4                     # 3 + dup, no skip
        assert rep.rows[-1].measured_time_s == \
            rep.rows[0].measured_time_s                # reused measurement
        assert rep.rows[-1].gemm_type == "attn"
        assert {r.gemm_type for r in rep.rows} == {"mlp", "attn"}
        assert all(np.isfinite(r.measured_rel_rank_error)
                   for r in rep.rows)
        assert all(lvl in r.predicted_bytes_per_level
                   for r in rep.rows for lvl in ("dram", "sram", "rf"))
        # "attn" has 1 row < min_family: reported, not gated
        assert "attn" in rep.families
        assert set(rep.gated_families) == {"all", "mlp"}
        assert rep.passes(), rep.summary()

        path = record_rows(rep, tmp_path, "t")
        assert path == tmp_path / "fidelity" / "t.jsonl"
        summary, rows = load_rows(path)
        assert summary["rows"] == 4 and summary["passes"] is True
        assert [r.plan_key for r in rows] == \
            [r.plan_key for r in rep.rows]
        assert rows[0].dims == shapes[0]
