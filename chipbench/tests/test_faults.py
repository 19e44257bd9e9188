"""A whole run at a tiny size on the CPU, past the harness's look for a
chip, held to the committed configuration's own check: sound, it is
correct; with the timed path broken underneath, or with the control's
tokens in place of the served ones, the output check says not."""
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests.helpers import smoke
from repro.serving.engine import Engine

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = BENCH["workloads"][0]
CONFIG = {c["name"]: c for c in BENCH["configs"]}[CELL["config"]]
CHECK = json.loads((ROOT / CONFIG["file"]).read_text())["check"]


def state_unchanged(orig):
    def decode_slots(self, cache, tokens, positions):
        logits, _ = orig(self, cache, tokens, positions)
        return logits, cache
    return "decode_slots", decode_slots


def half_the_batch(orig):
    def decode_slots(self, cache, tokens, positions):
        logits, cache = orig(self, cache, tokens, positions)
        h = logits.shape[0] // 2
        mean = jnp.mean(logits[:h], axis=0, keepdims=True)
        return logits.at[h:].set(jnp.broadcast_to(
            mean, logits[h:].shape)), cache
    return "decode_slots", decode_slots


def token_altered(orig):
    def sample(self, logits, rng):
        tok = orig(self, logits, rng)
        return (tok + 1) % logits.shape[-1]
    return "sample", sample


FAULTS = {"state_unchanged": state_unchanged,
          "half_the_batch": half_the_batch,
          "token_altered": token_altered}


def tiny_cell():
    cfg, config = smoke("dense", "bfloat16")
    config["check"] = CHECK
    mix = {"regime": "steady",
           "arrivals": {"process": "poisson", "rate_per_s": 25.0},
           "prompt_len": {"dist": "uniform", "min": 20, "max": 50},
           "output_len": {"dist": "uniform", "min": 6, "max": 12},
           "slots": 4, "ramp_s": 0.3, "tail_s": 0.3}
    cell = harness.Cell(CELL["name"], 1, config, mix, BENCH)
    return cfg, cell


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    cfg, cell = tiny_cell()
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(harness, "arch_config", lambda config: cfg)
    monkeypatch.setattr(harness, "CACHE", tmp_path)
    return cell


def run(cell, seed=2**32 + 9, **kw):
    return harness.run_cell(cell, seed=seed, seconds=0.6, trace=False,
                            t_start=0.0, **kw)


def over_limit(res):
    """The compared numbers that exceed their limits."""
    return [k for k, c in res["check"].items() if c["value"] > c["limit"]]


def test_sound_run_is_correct(tiny):
    out = run(tiny)
    res = out["result"]
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert out["diag"]["compiles_in_window"] == 0
    assert out["diag"]["tokens_compared"] > 0
    assert set(res["metrics"]) == {
        m["name"] for m in harness.metrics_for(tiny, trace=False)}
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(tiny, monkeypatch, fault):
    name, fn = FAULTS[fault](getattr(Engine, name_of(fault)))
    monkeypatch.setattr(Engine, name, fn)
    res = run(tiny)["result"]
    assert not res["correct"], res["check"]
    assert set(over_limit(res)) & set(CHECK["limits"]), res["check"]


# The committed limits at this size: the program's readings here (d 64,
# vocab 257) lie lower than at the published widths, and the control's
# far lower, so the control is held to the same number under a limit set
# between them: the program read mean_logit_gap 0.00007-0.00039 and the
# int8 control 0.0015-0.0054 on these seeds.
TINY_LIMITS = {"mean_logit_gap": 0.001}


@pytest.mark.parametrize("seed", [2**32 + 9, 5, 6])
def test_control_is_not_correct(tiny, seed):
    assert set(TINY_LIMITS) == set(CHECK["limits"])
    tiny.config["check"] = dict(CHECK, limits=TINY_LIMITS)
    assert run(tiny, seed=seed)["result"]["correct"]
    res = run(tiny, seed=seed, control=True)["result"]
    assert not res["correct"], res["check"]
    assert set(over_limit(res)) & set(CHECK["limits"]), res["check"]


def name_of(fault):
    return FAULTS[fault](None)[0]
