"""mfu.<regime>: model FLOPs of the prompt and output tokens processed in
the traced window (weights, attention over valid positions, routed
experts only) over device-busy time x chips x peak FLOP/s, in percent."""
from chipbench.metrics._work import call_work, traced_calls


def read(run, name):
    calls = traced_calls(run)
    busy = run.trace["busy_s"] if run.trace else 0.0
    if not calls or busy <= 0:
        return None
    flops = sum(f for f, _ in call_work(run, calls))
    return 100.0 * flops / (busy * run.cell.chips
                            * run.peaks["flops_per_s"])
