"""Knee sweep of a cell's traffic mix, run by hand on the chip.

    python chipbench/sweep.py --workload <cell> --rates 2,3,4,5 --seconds 30 --seed 1

One process builds the cell's deployment once and offers its mix at each
rate in turn, on a fresh scheduler, for the mix's ramp and then a window
of ``--seconds``.  For each rate it prints one JSON line: the backlog
(requests queued or prefilling) at the window's open and close, the share
of the window's requests that met the mix's ``knee_limits`` (TTFT and
mean gap), TTFT and gap percentiles, and output tokens per second.  The
knee is the highest rate whose backlog does not grow over the window and,
for a steady mix, whose attainment meets the limits' share.  The lines
also go to ``chipbench_out/sweep_<cell>.jsonl``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".chipbench_cache"
                                              / "jax")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def backlog(sched) -> int:
    return len(sched.queue) + (1 if sched._prefill is not None else 0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax

    from chipbench import harness, traffic, weights, window
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    cfg = harness.arch_config(cell.config)
    params = weights.make(build_model(cfg).init_params, args.seed)
    rec = harness.Recorder()
    dep = harness.build(cfg, params, cell.config, cell.mix, rec)
    harness.warm_up(dep)
    engine = dep.engine
    print(f"setup {time.perf_counter() - T_START:.1f}s", file=sys.stderr)
    out_dir = ROOT / "chipbench_out"
    out_dir.mkdir(exist_ok=True)
    out = open(out_dir / f"sweep_{cell.name}.jsonl", "a")
    lim = cell.mix["knee_limits"]
    for rate in (float(r) for r in args.rates.split(",")):
        del dep
        gc.collect()
        rec = harness.Recorder()
        dep = harness.build(cfg, None, cell.config, cell.mix, rec,
                            engine=engine)
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate_per_s"] = rate
        arr = traffic.schedule(mix, seed=args.seed, vocab=cfg.vocab,
                               horizon_s=mix["ramp_s"] + args.seconds)
        marks = {}

        class Mark:
            def poll(self, now, win):
                if "open" not in marks and now >= win.start:
                    marks["open"] = backlog(dep.sched)
                if "close" not in marks and now >= win.end:
                    marks["close"] = backlog(dep.sched)
                    marks["busy"] = dep.sched.slots.n_busy

            def stop(self):
                pass

        win = harness.drive(dep, arr, ramp_s=mix["ramp_s"],
                            seconds=args.seconds, carry=False,
                            profile=Mark())
        recs = list(rec.records.values())
        tt = window.ttfts(recs, win)
        row = {"cell": cell.name, "rate_per_s": rate,
               "arrived": len(window.arrived_in(recs, win)),
               "backlog_open": marks.get("open"),
               "backlog_close": marks.get("close"),
               "busy_slots_close": marks.get("busy"),
               "ttft_p50_s": window.percentile(tt, 50),
               "ttft_p90_s": window.percentile(tt, 90),
               "itl_p95_ms": (window.percentile(
                   window.token_gaps(recs, win), 95) or 0) * 1e3,
               "output_tokens_per_s": window.tokens_in(recs, win)
               / win.seconds}
        if "ttft_s" in lim:
            row["attainment"] = window.attainment(
                recs, win, ttft_s=lim["ttft_s"],
                mean_gap_ms=lim["mean_gap_ms"])
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
