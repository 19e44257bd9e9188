"""The least work of the program calls made inside the traced window,
for the device-trace readers."""
import importlib


def traced_calls(run):
    """Rows of every program call of the ticks wholly inside the traced
    window."""
    if run.trace is None or not run.peaks:
        return None
    lo, hi = run.trace["perf_window"]
    calls = []
    for tick, t0, t1 in run.ticks:
        if t0 >= lo and t1 <= hi:
            calls.extend(run.tick_calls.get(tick, []))
    return calls


def call_work(run, calls):
    """[(flops, bytes)] of each call, by the configuration's family."""
    work = importlib.import_module(
        f"chipbench.work.{run.cell.config['family']}")
    return [work.call_work(run.cell.config, rows) for rows in calls]
