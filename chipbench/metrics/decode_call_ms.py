"""decode_call_ms.<regime>: mean device duration of the runs of the
engine's ``decode_slots`` program (the device's XLA Modules line) that
lie wholly inside the traced window, in milliseconds."""
from chipbench import trace_reduce as tr
from chipbench.metrics import _xplane

PROGRAM = "jit_decode_slots"


def value(trace):
    lo, hi = tr.window_of(trace)
    d = [t1 - t0 for lines in trace["devices"].values()
         for name, t0, t1 in lines["modules"]
         if name.split("(")[0] == PROGRAM and t0 >= lo and t1 <= hi]
    return sum(d) / len(d) / 1e6 if d else None


def read(run, name):
    trace = _xplane.of_run(run)
    return None if trace is None else value(trace)
