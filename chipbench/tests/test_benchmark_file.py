"""BENCHMARK.json holds together: every name it uses is found, every
metric has a reader, and a run without a TPU prints no result."""
import importlib
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"]
             + METRICS]
    assert all(NAME.match(n) for n in names)
    assert len(set(m["name"] for m in METRICS)) == len(METRICS)
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    assert all(m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["reduced"] == conf["reduced"]
    assert data["source"].startswith(conf["source"])
    assert data["check"]["control"] in ("int8", "fp8")
    from chipbench.harness import gap_numbers
    assert data["check"]["limits"] and set(data["check"]["limits"]) <= set(
        gap_numbers([]))
    assert all(v > 0 for v in data["check"]["limits"].values())
    for kind in ("reference", "work"):
        importlib.import_module(f"chipbench.{kind}.{data['family']}")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells_resolve(cell):
    from chipbench import harness
    c = harness.load_cell(cell["name"], ROOT / "BENCHMARK.json")
    e2e = harness.metrics_for(c, trace=False)
    per = harness.metrics_for(c, trace=True)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert per
    moved = {m["name"] for m in e2e}
    assert all(m["moves"] in moved for m in per)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    mod = importlib.import_module(
        "chipbench.metrics." + metric["name"].split(".")[0])
    assert callable(mod.read)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
