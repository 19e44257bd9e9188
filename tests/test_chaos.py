"""Chaos suite: seeded fault schedules over the store, the solver and
the serving scheduler (ISSUE 7, DESIGN.md §Resilience).

Three invariants under injected faults:

  * **token identity** — every request the scheduler *serves* under
    store faults is token-identical to the fault-free static oracle
    (degradation may shed work, never corrupt it),
  * **blast-radius** — a poisoned NaN logits row evicts only its own
    request; survivors keep decoding oracle-identically,
  * **explicit terminal states** — shed/expired/errored requests get a
    terminal ``RequestResult`` streamed through ``on_finish``, never a
    hang or an exception out of the tick loop.

Plus store durability (checksum → quarantine → cold re-solve, torn-
write-free concurrent builders) and anytime-solver bound soundness.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.certificate import verify
from repro.core.geometry import Gemm
from repro.core.hardware import EYERISS_LIKE
from repro.core.solver import solve
from repro.faults import (FaultInjector, FaultSpec, inject, parse_faults,
                          set_injector)
from repro.obs.registry import get_registry
from repro.planner.store import PlanEntry, PlanKey, PlanStore

GEMM = (64, 96, 48)


def _store_with_entry(root) -> tuple[PlanStore, PlanKey]:
    store = PlanStore(root)
    key = PlanKey(gemm_dims=GEMM, hw=EYERISS_LIKE, objective="energy")
    res = solve(Gemm(*GEMM), EYERISS_LIKE, objective="energy")
    assert store.put(PlanEntry.from_solve(key, res.certificate,
                                          EYERISS_LIKE))
    return store, key


def _entry_path(store: PlanStore, key: PlanKey):
    d = key.digest
    return store.root / "objects" / d[:2] / f"{d}.json"


# ------------------------------------------------------------- injector

def test_injector_deterministic_and_interleaving_independent():
    """Same (seed, specs) -> same per-site fire schedule, regardless of
    how invocations at *other* sites interleave."""
    specs = [FaultSpec("store.read_io", prob=0.3),
             FaultSpec("store.corrupt", prob=0.3)]

    def run(noise: int) -> list[int]:
        inj = FaultInjector(specs, seed=42)
        fired = []
        for i in range(50):
            for _ in range(noise):          # extra traffic at another site
                inj.fires("store.corrupt")
            if inj.fires("store.read_io") is not None:
                fired.append(i)
        return fired

    assert run(0) == run(3)                 # per-site streams independent
    assert run(0)                           # and the schedule does fire


def test_injector_explicit_indices_and_limit():
    inj = FaultInjector([FaultSpec("kernel.nan_row", at=(2, 5, 7),
                                   limit=2)], seed=0)
    hits = [i for i in range(10)
            if inj.fires("kernel.nan_row") is not None]
    assert hits == [2, 5]                   # limit caps the third index
    assert inj.counts()["kernel.nan_row"] == (10, 2)


def test_injector_unknown_site_rejected():
    with pytest.raises(KeyError, match="unknown fault site"):
        FaultSpec("store.read_oi")


def test_parse_faults_roundtrip():
    specs = parse_faults("store.corrupt:0.01,kernel.nan_row@5,"
                         "sched.slow_tick@2+9,solver.over_budget:0.5@1")
    by_site = {s.site: s for s in specs}
    assert by_site["store.corrupt"].prob == 0.01
    assert by_site["kernel.nan_row"].at == (5,)
    assert by_site["sched.slow_tick"].at == (2, 9)
    assert by_site["solver.over_budget"].prob == 0.5
    assert by_site["solver.over_budget"].at == (1,)


def test_inject_without_injector_is_noop():
    set_injector(None)
    assert inject("store.read_io") is None


# ------------------------------------------------------- store durability

def test_corrupt_entry_quarantined_then_cold_resolved(tmp_path):
    store, key = _store_with_entry(tmp_path)
    path = _entry_path(store, key)
    path.write_text(path.read_text()[:40] + "\x00garbage")
    fresh = PlanStore(tmp_path)             # cold in-process cache
    assert fresh.get(key) is None           # corrupt -> miss, no raise
    assert fresh.num_quarantined() == 1
    assert not path.exists()                # moved, not left in place
    snap = get_registry().snapshot()
    assert snap["errors.store.corrupt"] == 1
    assert snap["degraded.store.quarantined"] == 1
    assert snap["degraded.store.cold_resolves"] == 1
    # quarantine log names the reason
    log = (fresh.root / "quarantine" / "log.jsonl").read_text()
    assert key.digest in log
    # the key can be re-solved and re-persisted over the same digest
    res = solve(Gemm(*GEMM), EYERISS_LIKE, objective="energy")
    assert fresh.put(PlanEntry.from_solve(key, res.certificate,
                                          EYERISS_LIKE))
    assert PlanStore(tmp_path).get(key) is not None


def test_injected_read_fault_is_transient_miss(tmp_path):
    store, key = _store_with_entry(tmp_path)
    set_injector(FaultInjector([FaultSpec("store.read_io", at=(0,))],
                               seed=0))
    fresh = PlanStore(tmp_path)
    assert fresh.get(key) is None           # injected OSError -> miss
    assert fresh.get(key) is not None       # next read succeeds
    snap = get_registry().snapshot()
    assert snap["errors.store.read_io"] == 1
    assert snap["faults.injected.store.read_io"] == 1


def test_injected_corrupt_read_quarantines(tmp_path):
    store, key = _store_with_entry(tmp_path)
    set_injector(FaultInjector([FaultSpec("store.corrupt", at=(0,))],
                               seed=0))
    fresh = PlanStore(tmp_path)
    assert fresh.get(key) is None
    assert fresh.num_quarantined() == 1
    assert get_registry().snapshot()["errors.store.corrupt"] == 1


def test_injected_write_fault_keeps_entry_in_memory(tmp_path):
    set_injector(FaultInjector([FaultSpec("store.write_io", at=(0,))],
                               seed=0))
    store = PlanStore(tmp_path)
    key = PlanKey(gemm_dims=GEMM, hw=EYERISS_LIKE, objective="energy")
    res = solve(Gemm(*GEMM), EYERISS_LIKE, objective="energy")
    entry = PlanEntry.from_solve(key, res.certificate, EYERISS_LIKE)
    assert store.put(entry) is False        # write failed ...
    assert store.get(key) is not None       # ... but serving continues
    assert PlanStore(tmp_path).get(key) is None   # and nothing persisted
    assert get_registry().snapshot()["errors.store.write_io"] == 1


def test_fsck_flags_and_repair_quarantines(tmp_path):
    store, key = _store_with_entry(tmp_path)
    # a legacy (pre-checksum) entry alongside a corrupt one
    path = _entry_path(store, key)
    d = json.loads(path.read_text())
    d.pop("checksum")
    legacy = store.root / "objects" / "00" / ("0" * 64 + ".json")
    legacy.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(d))          # now checksum-less
    legacy.write_text("{torn")
    report = PlanStore(tmp_path).fsck()
    assert report["checked"] == 2
    assert report["legacy"] == 1
    assert len(report["corrupt"]) == 1
    rep = PlanStore(tmp_path).repair()
    assert rep["rewritten"] == 1
    after = PlanStore(tmp_path).fsck()
    assert after["corrupt"] == [] and after["legacy"] == 0
    assert after["quarantined"] == 1


_BUILDER = r"""
import sys
sys.path.insert(0, {src!r})
from repro.core.geometry import Gemm
from repro.core.hardware import EYERISS_LIKE
from repro.core.solver import solve
from repro.planner.store import PlanEntry, PlanKey, PlanStore

store = PlanStore({root!r})
dims_list = [(16, 16, 16), (16, 32, 16), (32, 16, 16), (16, 16, 32)]
for round in range(4):
    for dims in dims_list:      # both builders rewrite the same digests
        key = PlanKey(gemm_dims=dims, hw=EYERISS_LIKE, objective="energy")
        res = solve(Gemm(*dims), EYERISS_LIKE, objective="energy")
        with store.lock():
            assert store.put(PlanEntry.from_solve(
                key, res.certificate, EYERISS_LIKE))
print("done")
"""


def test_concurrent_builders_no_torn_writes(tmp_path):
    """Two builder processes hammer the same four entries under the
    advisory lock: no torn writes, every surviving object passes fsck."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    script = _BUILDER.format(src=src, root=str(tmp_path))
    procs = [subprocess.Popen([sys.executable, "-c", script],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err
        assert "done" in out
    report = PlanStore(tmp_path).fsck()
    assert report["corrupt"] == []
    assert report["ok"] == 4
    store = PlanStore(tmp_path)
    for dims in [(16, 16, 16), (16, 32, 16), (32, 16, 16), (16, 16, 32)]:
        key = PlanKey(gemm_dims=dims, hw=EYERISS_LIKE, objective="energy")
        assert store.get(key) is not None


# --------------------------------------------------------- anytime solver

def test_forced_over_budget_yields_sound_bounded_cert():
    """The chaos site makes solve() expire right after its first
    incumbent; the bounded certificate's [LB, UB] must bracket the true
    optimum (known here from the full zero-gap solve)."""
    full = solve(Gemm(*GEMM), EYERISS_LIKE, objective="energy")
    assert not full.certificate.bounded and full.certificate.gap <= 1e-9
    opt = full.certificate.objective
    set_injector(FaultInjector([FaultSpec("solver.over_budget",
                                          prob=1.0)], seed=0))
    res = solve(Gemm(*GEMM), EYERISS_LIKE, objective="energy")
    set_injector(None)
    cert = res.certificate
    assert cert.bounded and cert.feasible
    assert cert.lower_bound <= opt + 1e-9 * max(1.0, opt)
    assert opt <= cert.upper_bound + 1e-9 * max(1.0, opt)
    assert verify(cert, EYERISS_LIKE)
    snap = get_registry().snapshot()
    assert snap["degraded.solver.bounded"] == 1
    assert snap["faults.injected.solver.over_budget"] == 1


def test_tiny_budget_bounded_cert_brackets_optimum():
    full = solve(Gemm(*GEMM), EYERISS_LIKE, objective="edp")
    opt = full.certificate.objective
    res = solve(Gemm(*GEMM), EYERISS_LIKE, objective="edp",
                budget_s=1e-7)
    cert = res.certificate
    assert cert.feasible                    # anytime: always an incumbent
    if cert.bounded:                        # (a fast machine may finish)
        assert cert.lower_bound <= opt + 1e-9 * max(1.0, opt)
        assert opt <= cert.upper_bound + 1e-9 * max(1.0, opt)
    assert verify(cert, EYERISS_LIKE)


def test_bounded_entry_persists_and_upgrades(tmp_path):
    from repro.planner.batch import upgrade_bounded
    set_injector(FaultInjector([FaultSpec("solver.over_budget",
                                          prob=1.0, limit=1)], seed=0))
    res = solve(Gemm(*GEMM), EYERISS_LIKE, objective="energy")
    set_injector(None)
    assert res.certificate.bounded
    store = PlanStore(tmp_path)
    key = PlanKey(gemm_dims=GEMM, hw=EYERISS_LIKE, objective="energy")
    assert store.put(PlanEntry.from_solve(key, res.certificate,
                                          EYERISS_LIKE))
    entry = PlanStore(tmp_path).get(key)
    assert entry is not None and entry.certificate.bounded  # round-trips
    # background upgrade: same digest, zero-gap, never worse than the UB
    store2 = PlanStore(tmp_path)
    assert upgrade_bounded(store2) == 1
    upgraded = PlanStore(tmp_path).get(key)
    assert not upgraded.certificate.bounded
    assert upgraded.certificate.gap <= 1e-9
    assert upgraded.certificate.objective <= \
        res.certificate.upper_bound * (1 + 1e-9)
    assert get_registry().snapshot()["planner.upgraded"] == 1


def test_cached_solve_serves_bounded_and_counts(tmp_path):
    from repro.planner.batch import cached_solve
    set_injector(FaultInjector([FaultSpec("solver.over_budget",
                                          prob=1.0, limit=1)], seed=0))
    store = PlanStore(tmp_path)
    e1 = cached_solve(Gemm(*GEMM), EYERISS_LIKE, store=store,
                      objective="energy")
    set_injector(None)
    assert e1.certificate.bounded
    e2 = cached_solve(Gemm(*GEMM), EYERISS_LIKE, store=store,
                      objective="energy")
    assert e2.certificate.bounded           # hit served as-is ...
    snap = get_registry().snapshot()
    assert snap["degraded.plans.bounded_served"] == 1   # ... and counted


# ------------------------------------------------------- serving chaos

CACHE = 96


@pytest.fixture(scope="module")
def serving():
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Engine, ServeConfig
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params,
                    ServeConfig(max_new_tokens=10, cache_len=CACHE))
    oracle = Engine(model, params,
                    ServeConfig(max_new_tokens=10, cache_len=CACHE))
    return cfg, model, params, engine, oracle


def _mk_requests(cfg, n=4, max_new=6, seed=0):
    from repro.serving.sched import Request
    rng = np.random.default_rng(seed)
    return [Request(req_id=i,
                    tokens=rng.integers(0, cfg.vocab, (12,)).astype(
                        np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def _oracle_tokens(oracle, req) -> list[int]:
    oracle.cfg.max_new_tokens = req.max_new_tokens
    oracle.cfg.stop_token = req.stop_token
    return [int(t) for t in
            oracle.generate(req.tokens[None])[0][:req.max_new_tokens]]


# Store chaos runs the fused-MLP smoke model, whose kernels read their
# chain plans through the plan store when traced.  A prefill chunk wider
# than the MXU (padded M 256) and the 2-slot decode (padded M 128)
# dispatch two distinct chains: two store reads, one per store fault.
FUSED_CACHE = 176
FUSED_WIDTHS = (8, 160)


@pytest.fixture(scope="module")
def fused_serving():
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Engine, ServeConfig
    cfg = dataclasses.replace(get_config("llama3-8b", smoke=True),
                              fused_mlp=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    oracle = Engine(model, params,
                    ServeConfig(max_new_tokens=10, cache_len=FUSED_CACHE))
    return cfg, model, params, oracle


def _fill_store_then_drop_caches(model, params, root, reqs) -> None:
    """Serve ``reqs`` fault-free over a store at ``root``, then drop
    every warm cache — the plan caches and the traced programs — so the
    next serve reads its plans back off disk."""
    import jax

    from repro.core import tpu_mapping
    from repro.serving import Engine, ServeConfig
    from repro.serving.sched import ContinuousScheduler, SchedConfig
    jax.clear_caches()
    engine = Engine(model, params,
                    ServeConfig(max_new_tokens=10, cache_len=FUSED_CACHE),
                    plan_store=PlanStore(root))
    ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=FUSED_WIDTHS)).run(reqs)
    tpu_mapping.set_plan_store(None)
    tpu_mapping.plan_gemm_tiling.cache_clear()
    jax.clear_caches()


def test_store_faults_keep_tokens_identical(fused_serving, tmp_path):
    """Injected store read faults + corruption during a plan-store
    serving run: cold re-solves fill the gaps and every served request
    stays token-identical to the fault-free oracle."""
    from repro.core import tpu_mapping
    from repro.serving import Engine, ServeConfig
    from repro.serving.sched import ContinuousScheduler, SchedConfig
    cfg, model, params, oracle = fused_serving
    reqs = _mk_requests(cfg)
    root = tmp_path / "plans"
    try:
        _fill_store_then_drop_caches(model, params, root, reqs)
        set_injector(FaultInjector(     # the first read fails, the
            [FaultSpec("store.read_io", at=(0,)),     # second comes
             FaultSpec("store.corrupt", at=(0,))],    # back corrupt
            seed=7))
        store = PlanStore(root)             # cold in-process cache
        engine = Engine(model, params,
                        ServeConfig(max_new_tokens=10,
                                    cache_len=FUSED_CACHE),
                        plan_store=store)
        sched = ContinuousScheduler(
            engine, SchedConfig(slots=2, chunk_widths=FUSED_WIDTHS))
        results = sched.run(reqs)
    finally:
        set_injector(None)
        tpu_mapping.set_plan_store(None)
        tpu_mapping.plan_gemm_tiling.cache_clear()
    assert len(results) == len(reqs)
    by_id = {r.req_id: r for r in results}
    for req in reqs:
        assert by_id[req.req_id].tokens == _oracle_tokens(oracle, req)
    # the schedule really exercised the fault paths
    snap = get_registry().snapshot()
    assert snap.get("faults.injected.store.read_io", 0) > 0
    assert snap.get("faults.injected.store.corrupt", 0) > 0
    assert snap.get("degraded.store.cold_resolves", 0) > 0


def test_nan_row_evicts_only_poisoned_request(serving):
    from repro.serving.sched import ContinuousScheduler, SchedConfig
    cfg, _, _, engine, oracle = serving
    reqs = _mk_requests(cfg)
    set_injector(FaultInjector([FaultSpec("kernel.nan_row", at=(2,))],
                               seed=1))
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 32)))
    results = sched.run(reqs)
    set_injector(None)
    errored = [r for r in results if r.finish_reason == "errored"]
    served = [r for r in results if not r.shed]
    assert len(errored) == 1                # blast radius: one row
    assert len(served) == len(reqs) - 1
    for r in served:                        # survivors oracle-identical
        req = next(q for q in reqs if q.req_id == r.req_id)
        assert r.tokens == _oracle_tokens(oracle, req)
    # the poisoned row kept its pre-fault prefix (a valid partial answer)
    bad_req = next(q for q in reqs if q.req_id == errored[0].req_id)
    want = _oracle_tokens(oracle, bad_req)
    assert errored[0].tokens == want[:len(errored[0].tokens)]
    snap = get_registry().snapshot()
    assert snap["errors.sched.nan_row"] == 1
    assert snap["sched.errored"] == 1


def test_inf_row_also_evicted(serving):
    from repro.serving.sched import ContinuousScheduler, SchedConfig
    cfg, _, _, engine, _ = serving
    set_injector(FaultInjector(
        [FaultSpec("kernel.nan_row", at=(1,),
                   payload={"value": float("inf")})], seed=1))
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 32)))
    results = sched.run(_mk_requests(cfg, n=2))
    set_injector(None)
    assert sum(r.finish_reason == "errored" for r in results) == 1


def test_shed_and_expired_get_terminal_states(serving):
    from repro.serving.sched import (ContinuousScheduler, Request,
                                     SchedConfig)
    cfg, _, _, engine, _ = serving
    finished = []
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 32), max_queue=1,
                            shed_on_full=True, default_deadline_s=0.0),
        on_finish=finished.append)
    reqs = _mk_requests(cfg)
    shed = [sched.submit(r) for r in reqs]
    # no tick has run, so no slot was claimed yet: the first submit
    # queues, the other three overflow the 1-deep queue -> REJECTED
    # synchronously, each with a terminal result (not an exception)
    assert sum(r is not None and r.finish_reason == "rejected"
               for r in shed) == 3
    while sched.busy:
        sched.step()
    reasons = {r.req_id: r.finish_reason for r in sched.results}
    assert sorted(reasons) == [r.req_id for r in reqs]  # all terminal
    assert list(reasons.values()).count("rejected") == 3
    # deadline 0 relative to arrival: the queued request expired at the
    # first tick's deadline sweep; nothing hangs, nothing raises
    assert list(reasons.values()).count("expired") == 1
    assert len(finished) == len(reqs)       # every outcome was streamed
    summ = sched.metrics.summary()
    assert summ["rejected"] == 3
    assert summ["expired"] == 1
    assert summ["served"] + summ["rejected"] + summ["expired"] \
        + summ["errored"] == len(reqs)
    snap = get_registry().snapshot()
    assert snap["degraded.sched.shed"] == 3
    assert snap["degraded.sched.expired"] == 1


def test_queue_full_still_raises_without_shedding(serving):
    from repro.serving.sched import ContinuousScheduler, SchedConfig
    cfg, _, _, engine, _ = serving
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 32), max_queue=1))
    reqs = _mk_requests(cfg, n=2)
    sched.submit(reqs[0])                   # fills the 1-deep queue
    with pytest.raises(RuntimeError, match="queue full"):
        sched.submit(reqs[1])


def test_slow_tick_trips_watchdog(serving):
    from repro.serving.sched import ContinuousScheduler, SchedConfig
    cfg, _, _, engine, _ = serving
    set_injector(FaultInjector(
        [FaultSpec("sched.slow_tick", at=(1,),
                   payload={"stall_s": 0.05})], seed=0))
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 32),
                            watchdog_tick_s=0.04))
    sched.run(_mk_requests(cfg, n=2))
    set_injector(None)
    snap = get_registry().snapshot()
    assert snap["sched.watchdog_trips"] >= 1
    assert snap["faults.injected.sched.slow_tick"] == 1


def test_traffic_burst_exercises_shedding(serving):
    from repro.serving.sched import (ContinuousScheduler, SchedConfig,
                                     TraceClock, TrafficConfig,
                                     poisson_trace, replay)
    cfg, _, _, engine, _ = serving
    trace = poisson_trace(TrafficConfig(
        n_requests=6, arrival_rate=0.5, vocab=cfg.vocab,
        prompt_mix=((4, 12, 1.0),), max_new_tokens=4))
    clock = TraceClock()
    set_injector(FaultInjector([FaultSpec("traffic.burst", prob=1.0)],
                               seed=0))
    sched = ContinuousScheduler(
        engine, SchedConfig(slots=2, chunk_widths=(8, 32), max_queue=1,
                            shed_on_full=True),
        clock=clock.now)
    results = replay(sched, trace, clock)
    set_injector(None)
    assert len(results) == 6                # every request got a result
    assert any(r.finish_reason == "rejected" for r in results)


# ------------------------------------------------------- router chaos

def test_replica_down_failover_keeps_fidelity(serving):
    """Killing a replica mid-trace: its queued / in-flight-prefill
    requests fail over and finish oracle-identically on survivors; its
    decode slots are evicted as ERRORED keeping their streamed prefix —
    truncation, never divergence."""
    from repro.serving.router import ReplicaRouter, RouterConfig
    from repro.serving.sched import SchedConfig
    cfg, _, _, engine, oracle = serving
    reqs = _mk_requests(cfg, n=8, max_new=8)
    for i, r in enumerate(reqs):
        r.arrival_s = 0.0005 * i
    set_injector(FaultInjector(
        [FaultSpec("router.replica_down", at=(6,))], seed=0))
    router = ReplicaRouter(
        engine, RouterConfig(replicas=2, sched=SchedConfig(
            slots=2, chunk_widths=(8, 32))))
    results = router.route_trace(reqs)
    set_injector(None)
    assert sum(router.alive) == 1
    assert len(results) == len(reqs)        # every request got a result
    for r in results:
        req = next(q for q in reqs if q.req_id == r.req_id)
        want = _oracle_tokens(oracle, req)
        if r.finish_reason == "errored":    # died mid-decode: prefix kept
            assert r.tokens == want[:len(r.tokens)]
        else:                               # failed over: full fidelity
            assert r.tokens == want
    snap = get_registry().snapshot()
    assert snap["faults.injected.router.replica_down"] == 1
    assert snap["router.replica_downs"] == 1
    assert snap.get("sched.evacuated", 0) + \
        snap.get("errors.sched.replica_down", 0) > 0


def test_replica_down_last_replica_survives(serving):
    """With one replica left the chaos site keeps firing but the router
    refuses to kill the last replica — the trace still drains."""
    from repro.serving.router import ReplicaRouter, RouterConfig
    from repro.serving.sched import SchedConfig
    cfg, _, _, engine, oracle = serving
    reqs = _mk_requests(cfg, n=3, max_new=4, seed=3)
    set_injector(FaultInjector(
        [FaultSpec("router.replica_down", prob=1.0)], seed=0))
    router = ReplicaRouter(
        engine, RouterConfig(replicas=2, sched=SchedConfig(
            slots=2, chunk_widths=(8, 32))))
    results = router.route_trace(reqs)
    set_injector(None)
    assert sum(router.alive) == 1           # exactly one kill honored
    served = [r for r in results if not r.shed]
    for r in served:
        req = next(q for q in reqs if q.req_id == r.req_id)
        assert r.tokens == _oracle_tokens(oracle, req)
    assert get_registry().snapshot()["router.replica_downs"] == 1


def test_router_store_corruption_keeps_tokens_identical(fused_serving,
                                                        tmp_path):
    """Store chaos under the router: replica-down + corrupt/IO-faulted
    plan reads during a fleet trace — cold re-solves fill the gaps and
    every *served* request stays token-identical to the oracle."""
    from repro.core import tpu_mapping
    from repro.serving import Engine, ServeConfig
    from repro.serving.router import ReplicaRouter, RouterConfig
    from repro.serving.sched import SchedConfig
    cfg, model, params, oracle = fused_serving
    reqs = _mk_requests(cfg, n=6, max_new=6, seed=11)
    for i, r in enumerate(reqs):
        r.arrival_s = 0.0005 * i
    root = tmp_path / "plans"
    try:
        _fill_store_then_drop_caches(model, params, root, reqs)
        set_injector(FaultInjector(
            [FaultSpec("store.read_io", at=(0,)),
             FaultSpec("store.corrupt", at=(0,)),
             FaultSpec("router.replica_down", at=(4,))], seed=7))
        store = PlanStore(root)             # cold in-process cache
        engine = Engine(model, params,
                        ServeConfig(max_new_tokens=10,
                                    cache_len=FUSED_CACHE),
                        plan_store=store)
        router = ReplicaRouter(
            engine, RouterConfig(replicas=2, sched=SchedConfig(
                slots=2, chunk_widths=FUSED_WIDTHS)))
        results = router.route_trace(reqs)
    finally:
        set_injector(None)
        tpu_mapping.set_plan_store(None)
        tpu_mapping.plan_gemm_tiling.cache_clear()
    assert len(results) == len(reqs)
    for r in results:
        req = next(q for q in reqs if q.req_id == r.req_id)
        want = _oracle_tokens(oracle, req)
        if r.finish_reason == "errored":
            assert r.tokens == want[:len(r.tokens)]
        else:
            assert r.tokens == want
    snap = get_registry().snapshot()
    assert snap.get("faults.injected.store.read_io", 0) > 0
    assert snap.get("faults.injected.store.corrupt", 0) > 0
    assert snap.get("faults.injected.router.replica_down", 0) == 1
