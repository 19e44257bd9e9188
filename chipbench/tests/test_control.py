"""The control -- the reference in int8 put in the program's place --
against the program in bfloat16, at smoke sizes on the CPU: on the same
prompts and served tokens, the tokens the control puts first lie further
below the float32 reference's best than the program's do.  On the chip
the same comparison, at each cell's size, sets the upper reading of the
cell's limit (chipbench/control.py)."""
import numpy as np
import pytest

from chipbench import harness, weights
from chipbench.tests.helpers import smoke
from chipbench.window import RequestRecord
from repro.models import build_model
from repro.serving import Engine, ServeConfig


def served(cfg, params, seed, rows=4, prompt=24, new=60):
    eng = Engine(build_model(cfg), params,
                 ServeConfig(cache_len=128, max_new_tokens=new))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (rows, prompt)).astype(np.int32)
    out = eng.generate(prompts)
    recs = []
    for b in range(rows):
        r = RequestRecord(b, 0.0, prompt, new, prompts[b])
        r.tokens = [int(t) for t in out[b]]
        recs.append(r)
    return recs


NUMBERS = {"dense": ("max_logit_gap", "mean_logit_gap"),
           "moe": ("mean_logit_gap", "off_argmax_share")}


@pytest.mark.parametrize("family,control,ratio",
                         [("dense", "int8", 3.0), ("moe", "fp8", 1.0)])
def test_control_reads_above_the_program(family, control, ratio):
    cfg, config = smoke(family, "bfloat16")
    config["check"] = {"control": control}
    for seed in (1, 2, 3):
        params = weights.make(build_model(cfg).init_params, seed)
        recs = served(cfg, params, seed)
        prog = harness.gap_numbers(
            harness.reference_gaps(config, params, recs, 128))
        ctrl = harness.gap_numbers(
            harness.reference_gaps(config, params, recs, 128, control=True))
        for k in NUMBERS[family]:
            assert ctrl[k] > ratio * prog[k], (seed, k, prog, ctrl)
