"""step_roofline_pct.<regime>: the least time the traced window's program
calls need on this chip -- per call the larger of its least FLOPs over
peak FLOP/s and its least bytes over peak bandwidth -- over the device's
busy time in that window, in percent."""
from chipbench.metrics._work import call_work, traced_calls


def read(run, name):
    calls = traced_calls(run)
    busy = run.trace["busy_s"] if run.trace else 0.0
    if not calls or busy <= 0:
        return None
    pk = run.peaks
    least = sum(max(f / pk["flops_per_s"], b / pk["hbm_bytes_per_s"])
                for f, b in call_work(run, calls))
    return 100.0 * least / busy
