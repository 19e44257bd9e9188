"""Certified (energy, delay) Pareto frontiers -> BENCH_pareto.json.

Two gates, both asserted:

1. **Frontier soundness.**  For every (GEMM, spec) pair in the sweep,
   ``core.solver.solve_pareto`` (epsilon-constraint over the achievable
   spatial-product levels) yields a frontier that passes the independent
   ``core.pareto.verify_pareto`` re-check — every point's zero-gap slice
   certificate verifies, its stored (energy, delay, edp) match a fresh
   oracle evaluation under the recorded bandwidth, and the point set is
   mutually non-dominated.  The frontier's energy-optimal endpoint must
   match the existing unconstrained ``solve`` optimum bit-for-bit
   (same mapping, same objective scalar) — stored plan identities are
   untouched by the whole feature.

2. **Calibration regression gate.**  ``obs.calibrate.fit_rows`` on a
   deterministic synthetic fidelity workload must cut the held-out
   delay-prediction error vs the compute-only baseline (the gate
   ``plan calibrate`` enforces).

    PYTHONPATH=src python benchmarks/bench_pareto.py           # full
    PYTHONPATH=src python benchmarks/bench_pareto.py --smoke   # CI gate

Both modes write BENCH_pareto.json at the repo root (the CI "Pareto
smoke" step publishes it as an artifact).
"""
from __future__ import annotations

import argparse
import json
import time

from common import ROOT, emit

from repro.core import TEMPLATES
from repro.core.geometry import Gemm
from repro.core.pareto import select_frontier_point, verify_pareto
from repro.core.solver import solve, solve_pareto

BENCH_PATH = ROOT / "BENCH_pareto.json"

HW_NAMES = ("eyeriss-like", "gemmini-like")
SMOKE_GEMMS = (
    Gemm(64, 96, 128, "edge_qkv"),
    Gemm(128, 128, 256, "edge_mlp"),
    Gemm(48, 512, 64, "score"),
    # ragged extents whose energy optimum under-fills the array — the
    # shapes where the (energy, delay) trade-off is real (the sweep's
    # multi-point frontiers; smooth powers of two mostly collapse to the
    # single full-array point)
    Gemm(96, 56, 72, "ragged_a"),
    Gemm(56, 120, 88, "ragged_b"),
    Gemm(88, 104, 24, "ragged_c"),
)
FULL_GEMMS = SMOKE_GEMMS + (
    Gemm(256, 256, 512, "center_proj"),
    Gemm(512, 512, 512, "square"),
    Gemm(1024, 128, 256, "tall"),
    Gemm(64, 2048, 128, "wide"),
    Gemm(112, 48, 80, "ragged_d"),
    Gemm(120, 40, 88, "ragged_e"),
)


def frontier_case(gemm: Gemm, hw_name: str, *,
                  max_points: int | None) -> dict:
    hw = TEMPLATES[hw_name]
    t0 = time.perf_counter()
    res = solve_pareto(gemm, hw, spatial_mode="le", max_points=max_points)
    wall = time.perf_counter() - t0
    pc = res.certificate
    assert verify_pareto(pc, hw), (gemm, hw_name)
    # endpoint bit-match: the frontier's energy-optimal point IS the
    # unconstrained optimum — same mapping, same objective scalar
    base = solve(gemm, hw, spatial_mode="le")
    ep = pc.energy_optimal
    assert ep is not None and base.mapping is not None, (gemm, hw_name)
    assert ep.mapping == base.mapping, (gemm, hw_name, ep.mapping,
                                        base.mapping)
    assert ep.certificate.objective == base.certificate.objective, \
        (gemm, hw_name)
    pts = pc.points
    speedup = pts[0].delay_ns / pts[-1].delay_ns if pts else 0.0
    cost = pts[-1].energy_pj / pts[0].energy_pj if pts else 0.0
    return {
        "gemm": gemm.name or str(gemm.dims), "dims": list(gemm.dims),
        "hw": hw_name, "n_points": len(pts),
        "levels_total": pc.levels_total, "levels_swept": pc.levels_swept,
        "n_solves": res.n_solves, "solve_wall_s": wall,
        "energy_pj": [p.energy_pj for p in pts],
        "delay_ns": [p.delay_ns for p in pts],
        "num_pe_used": [p.num_pe_used for p in pts],
        "max_speedup": speedup, "energy_cost_of_speedup": cost,
    }


def calibration_case() -> dict:
    """Deterministic synthetic workload: measured time = compute term +
    a DRAM-bandwidth term the compute-only baseline cannot express; the
    fit must recover both rates and win on the held-out split."""
    from repro.obs.calibrate import fit_rows
    from repro.obs.fidelity import FidelityRow

    ns_per_macc, ns_per_dram_byte = 0.002, 0.05
    rows = []
    for i in range(24):
        M, N, K = 8 * (i + 1), 16, 32
        bpl = {"dram": 100.0 * (i + 1) ** 2, "sram": 10.0 * (i + 1),
               "rf": 5.0}
        t_ns = ns_per_macc * M * N * K + ns_per_dram_byte * bpl["dram"]
        rows.append(FidelityRow(
            plan_key=f"k{i}", manifest_digest=f"m{i}", gemm_type="synth",
            dims=(M, N, K), weight=1, predicted_energy=1.0,
            predicted_bytes_per_level=bpl, measured_time_s=t_ns * 1e-9))
    rep = fit_rows(rows)
    return {"passes": rep.passes(), "improvement": rep.improvement,
            "holdout_err": rep.holdout_err,
            "baseline_holdout_err": rep.baseline_holdout_err,
            "true_ns_per_macc": ns_per_macc,
            "fit_ns_per_macc": rep.model.ns_per_macc,
            "true_ns_per_dram_byte": ns_per_dram_byte,
            "fit_ns_per_dram_byte": rep.model.ns_per_byte["dram"]}


def run(smoke: bool) -> dict:
    gemms = SMOKE_GEMMS if smoke else FULL_GEMMS
    max_points = 8 if smoke else 24
    rows = []
    for gemm in gemms:
        for hw_name in HW_NAMES:
            rows.append(frontier_case(gemm, hw_name,
                                      max_points=max_points))
    multi = [r for r in rows if r["n_points"] > 1]
    for hw_name in HW_NAMES:
        assert any(r["hw"] == hw_name for r in multi), \
            f"no multi-point frontier on {hw_name}"
    for r in rows:
        emit(f"pareto_{r['gemm']}_{r['hw']}", r["solve_wall_s"] * 1e6,
             f"points={r['n_points']} solves={r['n_solves']} "
             f"speedup={r['max_speedup']:.2f}x "
             f"energy_cost={r['energy_cost_of_speedup']:.3f}x")

    # SLO selection sanity on the biggest multi-point frontier: a tight
    # SLO picks a faster, costlier point than the energy optimum
    from repro.core.pareto import ParetoPoint  # noqa: F401 (doc import)
    best = max(multi, key=lambda r: r["n_points"])
    hw = TEMPLATES[best["hw"]]
    res = solve_pareto(Gemm(*best["dims"]), hw, spatial_mode="le",
                       max_points=max_points)
    tight = select_frontier_point(res.points,
                                  res.points[-1].delay_ns * 1.001)
    assert tight is not None and tight.delay_ns < res.points[0].delay_ns

    cal = calibration_case()
    emit("pareto_calibration", 0.0,
         f"passes={cal['passes']} improvement={cal['improvement']:.3f} "
         f"holdout_err={cal['holdout_err']:.4f} "
         f"baseline={cal['baseline_holdout_err']:.4f}")
    assert cal["passes"], cal
    assert cal["improvement"] > 0.0, cal

    out = {"schema": 1, "smoke": smoke, "hw": list(HW_NAMES),
           "n_cases": len(rows),
           "n_multi_point": len(multi),
           "frontiers": rows, "calibration": cal}
    BENCH_PATH.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {BENCH_PATH}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate (reduced sweep, same asserts)")
    args = ap.parse_args()
    out = run(smoke=args.smoke)
    print(f"pareto {'smoke ' if args.smoke else ''}OK: "
          f"{out['n_cases']} frontiers verified "
          f"({out['n_multi_point']} multi-point), endpoint bit-match "
          f"everywhere, calibration gate passes")


if __name__ == "__main__":
    main()
