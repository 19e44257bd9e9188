"""The readers of the program's own spans and scopes: decode_call_ms,
host_idle_ms and attention_ms, on a hand-made trace, on a slice recorded
on a v5e, and on a trace of a program that names neither."""
import gzip
import json
import pathlib
import types

import pytest

from chipbench.metrics import _xplane, attention_ms, decode_call_ms
from chipbench.metrics import host_idle_ms

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1e6
BODY = "jit(decode_slots)/while/body/closed_call"


def hand_trace():
    """Two ticks wholly inside the window [0, 100] ms, a third that runs
    past its end."""
    att, mlp = f"{BODY}/attention/dot_general", f"{BODY}/mlp/dot_general"
    ops = [
        # tick 1, [5, 35]: busy [6, 30] + [31, 33], so 4 ms idle
        ["while.1", 6, 30, "jit(decode_slots)/while"],
        ["fusion.1", 7, 12, att],
        ["copy.2", 12, 20, "jit(decode_slots)/while/body/dynamic_slice"],
        ["dot.3", 20, 26, mlp],
        ["fusion.4", 31, 33, "jit(decode_slots)/lm_head/dot_general"],
        # tick 2, [40, 70]: busy [41, 51] + [52, 62] + [68, 70], 8 ms idle
        ["fusion.1", 41, 51, att],
        ["while.7", 52, 62, f"{BODY}/attention/while"],
        ["fusion.8", 54, 58, att],          # nested: 4 ms of its own
        ["copy.9", 58, 60, ""],             # nested, no scope
        ["copy.10", 68, 73, att],           # 2 ms of it inside the tick
        # tick 3, [75, 110], not wholly inside the window
        ["fusion.1", 80, 90, att],
    ]
    return {"devices": {"/device:TPU:0": {
        "ops": [[n, a * MS, b * MS, o] for n, a, b, o in ops],
        "modules": [[n, a * MS, b * MS] for n, a, b in (
            ["jit_decode_slots(12)", 6, 30],
            ["jit_prefill_chunk(34)", 31, 33],
            ["jit_decode_slots(12)", 41, 62],
            ["jit_decode_slots(12)", 90, 105])]}},
        "host": [[n, a * MS, b * MS] for n, a, b in (
            ["chipbench.window", 0, 100],
            ["chipbench.step", 4, 36], ["sched.tick", 5, 35],
            ["sched.decode_batch", 6, 34],
            ["chipbench.step", 39, 71], ["sched.tick", 40, 70],
            ["chipbench.step", 74, 111], ["sched.tick", 75, 110])]}


def test_hand_trace():
    t = hand_trace()
    assert _xplane.ticks(t) == [(5 * MS, 35 * MS), (40 * MS, 70 * MS)]
    # two decode runs wholly inside the window: 24 and 21 ms
    assert decode_call_ms.value(t) == pytest.approx(22.5)
    # (4 + 8) ms idle over two ticks
    assert host_idle_ms.value(t) == pytest.approx(6.0)
    # tick 1: 5 ms; tick 2: 10 + (10 - 4 - 2) + 4 + 2 = 20 ms
    assert attention_ms.value(t) == pytest.approx(12.5)


def test_parent_program_reads_nothing():
    """A trace of a program with no tick annotations, no decode_slots
    program and no scopes gives no reading, and raises nothing."""
    t = hand_trace()
    t["host"] = [e for e in t["host"] if not e[0].startswith("sched.")]
    for lines in t["devices"].values():
        lines["modules"] = [["jit_decode_step(12)", a, b]
                            for _, a, b in lines["modules"]]
        lines["ops"] = [e[:3] + [""] for e in lines["ops"]]
    assert decode_call_ms.value(t) is None
    assert host_idle_ms.value(t) is None
    assert attention_ms.value(t) is None


def test_unscoped_ops_read_nothing():
    t = hand_trace()
    for lines in t["devices"].values():
        lines["ops"] = [e[:3] + [""] for e in lines["ops"]]
    assert attention_ms.value(t) is None
    assert host_idle_ms.value(t) == pytest.approx(6.0)


def test_untraced_run_reads_nothing():
    run = types.SimpleNamespace(trace=None)
    for reader in (decode_call_ms, host_idle_ms, attention_ms):
        assert reader.read(run, "x.steady") is None


def test_load_reads_host_spans_of_every_name(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            with jax.profiler.StepTraceAnnotation("sched.tick",
                                                  step_num=0):
                f(jnp.ones(4)).block_until_ready()
    from chipbench import trace_reduce as tr
    path = tr.find_xplane(tmp_path)
    t = _xplane.load(path)
    names = [e[0] for e in t["host"]]
    assert "chipbench.window" in names and "sched.tick" in names
    (tick,) = _xplane.ticks(t)
    assert tick[0] < tick[1]
    # the same events on the same clock as jax.profiler.ProfileData
    pd = jax.profiler.ProfileData.from_file(str(path))
    want = [[e.name, e.start_ns, e.end_ns] for plane in pd.planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    assert t["host"] == want


RECORDED = sorted((DATA / "scopes").glob("*.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_v5e_slice(path):
    """Readings of a slice of a traced window on a v5e, against the
    values computed by hand from the same slice when it was cut."""
    with gzip.open(path, "rt") as f:
        t = json.load(f)
    want = t.pop("expected")
    assert decode_call_ms.value(t) == pytest.approx(
        want["decode_call_ms"], rel=1e-9)
    assert host_idle_ms.value(t) == pytest.approx(
        want["host_idle_ms"], rel=1e-9)
    assert attention_ms.value(t) == pytest.approx(
        want["attention_ms"], rel=1e-9)
