"""Trace reduction: busy union, idle share, top ops and the attribution
of idle time, on a hand-made trace and on a slice recorded on a v5e."""
import gzip
import json
import pathlib

import pytest

from chipbench import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def hand_trace():
    ms = 1e6
    return {"devices": {"/device:TPU:0": {
        "ops": [["while.4", 0 * ms, 6 * ms], ["fusion.1", 0.5 * ms, 2 * ms],
                ["dot.2", 3 * ms, 5 * ms], ["fusion.1", 8 * ms, 9 * ms],
                ["copy.3", 12 * ms, 20 * ms]],
        "modules": [["jit_decode_step(123)", 0, 9 * ms]]}},
        "host": [["chipbench.window", 1 * ms, 15 * ms],
                 ["chipbench.step", 0 * ms, 9.5 * ms],
                 ["chipbench.wait", 9.5 * ms, 12 * ms]]}


def test_busy_union_idle_share_and_top_ops():
    t = hand_trace()
    lo, hi = tr.window_of(t)
    red = tr.reduce(t, lo, hi)
    # busy in [1, 15] ms: [1, 6] + [8, 9] + [12, 15] = 9 ms of 14
    assert red["busy_s"] == pytest.approx(9e-3)
    assert red["window_s"] == pytest.approx(14e-3)
    assert red["idle_pct"] == pytest.approx(100 * 5 / 14)
    # self time: the loop's own 2 ms, without the ops nested in it
    assert red["top_ops"][0] == ["copy.3", pytest.approx(3e-3)]
    assert dict(red["top_ops"]) == pytest.approx(
        {"copy.3": 3e-3, "fusion.1": 2e-3, "dot.2": 2e-3, "while.4": 2e-3})
    assert red["programs"] == [["jit_decode_step", pytest.approx(8e-3)]]
    gaps = red["idle"]["/device:TPU:0"]
    assert gaps == [(6e6, 8e6), (9e6, 12e6)]
    acts = [a for a in t["host"] if a[0] != "chipbench.window"]
    got = dict(tr.attribute(gaps, acts))
    assert got == pytest.approx({"chipbench.step": 2.5e-3,
                                 "chipbench.wait": 2.5e-3})


def test_uncovered_idle_goes_to_other():
    gaps = [(0.0, 10.0), (20.0, 30.0)]
    got = dict(tr.attribute(gaps, [("a", 5.0, 25.0), ("b", 6.0, 7.0)]))
    assert got == pytest.approx({"a": 9e-9, "b": 1e-9,
                                 "host.other": 10e-9})


def test_no_device_ops_reads_nothing():
    red = tr.reduce({"devices": {}, "host": []}, 0.0, 1e9)
    assert red["idle_pct"] is None and red["busy_s"] == 0.0


RECORDED = sorted(DATA.glob("*.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_v5e_slice(path):
    with gzip.open(path, "rt") as f:
        t = json.load(f)
    lo, hi = tr.window_of(t)
    red = tr.reduce(t, lo, hi)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["idle_pct"] < 100
    ops = tr.device_ops(t, "/device:TPU:0")
    busy = tr.union([(a, b) for _, a, b in ops], lo, hi)
    # the union never double-counts overlapping ops
    assert sum(b - a for a, b in busy) <= sum(
        min(b, hi) - max(a, lo) for _, a, b in ops if b > lo and a < hi)
    assert red["top_ops"] and all(s > 0 for _, s in red["top_ops"])
    idle = sum(b - a for a, b in red["idle"]["/device:TPU:0"]) / 1e9
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
