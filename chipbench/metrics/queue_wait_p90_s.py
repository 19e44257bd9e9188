"""queue_wait_p90_s: 90th percentile of admission (the start of the
scheduler's ``sched.request`` span) minus due arrival, over the requests
due in the window that were admitted."""
from chipbench.window import percentile


def read(run, name):
    due = {r.idx: r.due for r in run.records if run.window.holds(r.due)}
    waits = [sp.t0 - due[sp.attrs["req_id"]] for sp in run.spans
             if sp.name == "sched.request" and sp.attrs.get("req_id") in due]
    return percentile(waits, 90)
