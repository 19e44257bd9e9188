"""host_idle_ms.<regime>: device-idle time inside the scheduler's
``sched.tick`` annotations, both on the profiler's clock, summed over
the ticks wholly inside the traced window and divided by their number,
in milliseconds.  Idle is the tick's length less the union of the
device's op intervals inside it: the time the chip waited on the
host."""
from chipbench import trace_reduce as tr
from chipbench.metrics import _xplane


def value(trace):
    ticks = _xplane.ticks(trace)
    planes = _xplane.device_planes(trace)
    if not ticks or not planes:
        return None
    idle = 0.0
    for plane in planes:
        ops = tr.device_ops(trace, plane)
        busy = tr.union([(e[1], e[2]) for e in ops], ticks[0][0],
                        ticks[-1][1])
        idle += sum((t1 - t0) - _xplane.busy_within(busy, t0, t1)
                    for t0, t1 in ticks)
    return idle / len(planes) / len(ticks) / 1e6


def read(run, name):
    trace = _xplane.of_run(run)
    return None if trace is None else value(trace)
