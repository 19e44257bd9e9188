"""attention_ms.<regime>: device self time of the ops whose HLO
``op_name`` lies under the model's ``attention`` scope (qkv projections,
rope, cache write, flash attention, output projection), inside the
``sched.tick`` annotations wholly inside the traced window, per tick, in
milliseconds.  Ops of the layer scan that no scope claims (the slicing
of the stacked cache) are not counted."""
import bisect

from chipbench import trace_reduce as tr
from chipbench.metrics import _xplane

SCOPE = "attention"


def value(trace):
    ticks = _xplane.ticks(trace)
    planes = _xplane.device_planes(trace)
    if not ticks or not planes:
        return None
    total, seen = 0.0, False
    for plane in planes:
        ops = sorted(trace["devices"][plane]["ops"], key=lambda e: e[1])
        seen = seen or any(_xplane.has_scope(e[3], SCOPE) for e in ops)
        starts = [e[1] for e in ops]
        longest = max((e[2] - e[1] for e in ops), default=0)
        for t0, t1 in ticks:
            near = ops[bisect.bisect_left(starts, t0 - longest):
                       bisect.bisect_left(starts, t1)]
            labelled = [(SCOPE if _xplane.has_scope(e[3], SCOPE) else "",
                         e[1], e[2]) for e in near if e[2] > t0]
            total += tr.self_times(labelled, t0, t1)[SCOPE]
    if not seen:
        return None
    return 1e3 * total / len(planes) / len(ticks)


def read(run, name):
    trace = _xplane.of_run(run)
    return None if trace is None else value(trace)
