"""From a profiler trace to device busy time, idle share and top ops.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict (also what the recorded test trace holds)::

    {"devices": {"/device:TPU:0": {"ops": [[name, t0_ns, t1_ns], ...],
                                   "modules": [[name, t0_ns, t1_ns], ...]}},
     "host": [[name, t0_ns, t1_ns], ...]}

``ops`` are the events of a device's ``XLA Ops`` line, ``modules`` those
of its ``XLA Modules`` line (whole program runs), and ``host`` the
harness's own ``chipbench.*`` annotations.  All times share the trace's
clock.  Busy time is the union of op intervals, clipped to the window;
the idle share is one minus busy over the window.
"""
from __future__ import annotations

import bisect
import collections
import pathlib

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "chipbench."


def find_xplane(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    lines[key].extend([e.name, e.start_ns, e.end_ns]
                                      for e in line.events)
            out["devices"][plane.name] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.end_ns]
                                   for e in line.events
                                   if e.name.startswith(HOST_PREFIX))
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Sorted disjoint union of (t0, t1) intervals clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for t0, t1 in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if t1 <= t0:
            continue
        if merged and t0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t1)
        else:
            merged.append([t0, t1])
    return [(a, b) for a, b in merged]


def gaps_of(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] between busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def window_of(trace: dict) -> tuple[float, float]:
    """The traced window: the span of the ``chipbench.window``
    annotation."""
    spans = [(t0, t1) for name, t0, t1 in trace["host"]
             if name == "chipbench.window"]
    if not spans:
        raise ValueError("trace holds no chipbench.window annotation")
    return spans[0]


def device_ops(trace: dict, plane: str):
    lines = trace["devices"][plane]
    return lines["ops"] or lines["modules"]


def self_times(ops, lo: float, hi: float) -> collections.Counter:
    """Seconds in [lo, hi] that each op name ran outside the ops nested in
    it (a loop's body ops nest in the loop's own event)."""
    out = collections.Counter()
    stack: list[list] = []              # [name, t0, t1, child seconds]

    def close(ev):
        d = max(0.0, min(ev[2], hi) - max(ev[1], lo)) / 1e9
        out[ev[0]] += d - ev[3]
        if stack:
            stack[-1][3] += d

    for name, t0, t1 in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= t0:
            close(stack.pop())
        stack.append([name, t0, t1, 0.0])
    while stack:
        close(stack.pop())
    return out


def short_name(op: str) -> str:
    """An HLO op's name without its shapes and operands."""
    return op.split(" = ")[0].strip()


def reduce(trace: dict, lo: float, hi: float, *, top: int = 10) -> dict:
    """Busy seconds (averaged over the devices that ran anything), window
    seconds, idle share in percent, the ``top`` ops by self time and the
    programs by time, and each device's idle intervals."""
    busy_s, idle = [], {}
    per_op, per_prog = collections.Counter(), collections.Counter()
    for plane in sorted(trace["devices"]):
        ops = device_ops(trace, plane)
        if not ops:
            continue
        busy = union([(t0, t1) for _, t0, t1 in ops], lo, hi)
        busy_s.append(sum(b - a for a, b in busy) / 1e9)
        idle[plane] = gaps_of(busy, lo, hi)
        for name, d in self_times(ops, lo, hi).items():
            per_op[short_name(name)] += d
        for name, t0, t1 in trace["devices"][plane]["modules"]:
            d = min(t1, hi) - max(t0, lo)
            if d > 0:
                per_prog[name.split("(")[0]] += d / 1e9
    window_s = (hi - lo) / 1e9
    if not busy_s or window_s <= 0:
        return {"busy_s": 0.0, "window_s": window_s, "idle_pct": None,
                "top_ops": [], "programs": [], "idle": idle}
    mean_busy = sum(busy_s) / len(busy_s)
    return {"busy_s": mean_busy, "window_s": window_s,
            "idle_pct": 100.0 * (1.0 - mean_busy / window_s),
            "top_ops": [[n, s] for n, s in per_op.most_common(top)
                        if s > 0],
            "programs": [[n, s] for n, s in per_prog.most_common(top)],
            "idle": idle}


def segments(activities) -> tuple[list[float], list[str]]:
    """Cut the timeline at every activity boundary; each piece is labelled
    with the innermost (shortest) activity covering it, or None.
    Returns (boundaries, labels), with labels[i] for
    [boundaries[i], boundaries[i + 1])."""
    cuts = sorted({t for _, a0, a1 in activities for t in (a0, a1)})
    starts = collections.defaultdict(list)
    ends = collections.defaultdict(list)
    for i, (_, a0, a1) in enumerate(activities):
        starts[a0].append(i)
        ends[a1].append(i)
    active: set[int] = set()
    labels = []
    for t in cuts:
        active.difference_update(ends[t])
        active.update(starts[t])
        inner = min(active, key=lambda i: activities[i][2]
                    - activities[i][1], default=None)
        labels.append(None if inner is None else activities[inner][0])
    return cuts, labels


def attribute(gaps, activities, *, top: int = 10) -> list[list]:
    """Idle seconds by what the host was doing: each gap's time goes to
    the innermost activity covering it, and what none covers to
    ``"host.other"``.  ``activities`` are (name, t0, t1) on the trace
    clock; the result is the ``top`` names by idle seconds."""
    cuts, labels = segments(activities)
    total = collections.Counter()
    for g0, g1 in gaps:
        i = max(bisect.bisect_right(cuts, g0) - 1, 0)
        t = g0
        while t < g1:
            if i >= len(cuts) or cuts[i] > t:
                nxt = cuts[i] if i < len(cuts) else g1
                label = None
            else:
                nxt = cuts[i + 1] if i + 1 < len(cuts) else g1
                label = labels[i]
                i += 1
            end = min(nxt, g1)
            total[label or "host.other"] += (end - t) / 1e9
            t = end
    return [[n, s] for n, s in total.most_common(top)]
