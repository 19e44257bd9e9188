"""Readings for a cell's output-check limit, run by hand on the chip.

    python chipbench/control.py --workload <cell> --seeds 1,2,3,4 --control-seeds 1,2,3 --seconds 10

One process builds the cell's deployment and, for each seed, makes that
seed's weights and traffic, drives the scheduler at the cell's own load
for the mix's ramp and ``--seconds`` (carrying a steady mix's requests to
their first token), samples the finished requests as a run does, and
reads the numbers the output check can compare (``gap_numbers``: widest
and mean logit gap of the served tokens against the float32 reference,
share not the reference's first choice): the lower readings.  For the
control seeds it also reads them for the tokens that the configuration's
control precision puts first at the same positions: the upper readings.
Each reading also goes through the run's own verdict (``harness.judge``
against the configuration's limits), so the control must come out not
correct.  One JSON line per seed, also appended to
``chipbench_out/control_<cell>.jsonl``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".chipbench_cache"
                                              / "jax")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    import jax

    from chipbench import harness, traffic, weights
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    cfg = harness.arch_config(cell.config)
    init = build_model(cfg).init_params
    mix = cell.mix
    steady = mix["regime"] == "steady"
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out_dir = ROOT / "chipbench_out"
    out_dir.mkdir(exist_ok=True)
    engine = None
    with open(out_dir / f"control_{cell.name}.jsonl", "a") as out:
        for seed in (int(s) for s in args.seeds.split(",")):
            rec = harness.Recorder()
            params = weights.make(init, seed)
            if engine is not None:
                engine.params = params
            dep = harness.build(cfg, params, cell.config, mix, rec,
                                engine=engine)
            if engine is None:
                engine = dep.engine
                harness.warm_up(dep)
                rec.records.clear()
            arr = traffic.schedule(
                mix, seed=seed, vocab=cfg.vocab,
                horizon_s=mix["ramp_s"] + args.seconds
                + (mix["tail_s"] if steady else 0.0))
            harness.drive(dep, arr, ramp_s=mix["ramp_s"],
                          seconds=args.seconds, carry=steady)
            picked = harness.sample(list(rec.records.values()), seed)
            del dep
            gc.collect()
            length = engine.cfg.cache_len
            prog = harness.reference_gaps(cell.config, params, picked,
                                          length)
            row = {"cell": cell.name, "seed": seed,
                   "requests": len(picked),
                   "tokens": sum(len(r.tokens) for r in picked),
                   "program": harness.gap_numbers(prog)}
            row["program_correct"] = harness.judge(cell.config,
                                                   row["program"])[1]
            if seed in controls:
                ctl = harness.reference_gaps(cell.config, params, picked,
                                             length, control=True)
                row["control"] = harness.gap_numbers(ctl)
                row["control_correct"] = harness.judge(cell.config,
                                                       row["control"])[1]
            line = json.dumps(row)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
    print(f"total {time.perf_counter() - T_START:.1f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
