"""tick_ms.<regime>: mean length of the scheduler's ``sched.tick`` span
(admission, one prefill chunk, one decode step over the slot pool and
sampling) over the ticks that start in the window, in milliseconds."""


def read(run, name):
    d = [sp.t1 - sp.t0 for sp in run.spans
         if sp.name == "sched.tick" and sp.t1 is not None
         and run.window.holds(sp.t0)]
    return 1e3 * sum(d) / len(d) if d else None
