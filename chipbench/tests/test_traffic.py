"""The traffic generator: deterministic per seed, the same set of work for
every seed in the seed's order, and the distributions it states."""
import json
import math
import pathlib

import numpy as np
import pytest

from chipbench import traffic

MIXES = sorted((pathlib.Path(__file__).resolve().parents[1]
                / "traffic").glob("*.json"))


def mix(rate=4.0):
    return {"arrivals": {"process": "poisson", "rate_per_s": rate},
            "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                           "min": 32, "max": 512},
            "output_len": {"dist": "uniform", "min": 24, "max": 40}}


def test_same_seed_same_schedule():
    a = traffic.schedule(mix(), seed=2**33 + 5, horizon_s=20, vocab=1000)
    b = traffic.schedule(mix(), seed=2**33 + 5, horizon_s=20, vocab=1000)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]


def test_seeds_share_the_work_and_the_times():
    a = traffic.schedule(mix(), seed=1, horizon_s=30, vocab=1000)
    b = traffic.schedule(mix(), seed=2, horizon_s=30, vocab=1000)
    quant = traffic.gaps(mix()["arrivals"], len(a))
    for s in (a, b):
        # the gaps are the distribution's quantiles, each used once; the
        # one before the first request is not seen
        gaps = np.diff([x.due_s for x in s])
        unseen = quant.sum() - gaps.sum()
        assert np.allclose(np.sort(np.append(gaps, unseen)), quant)
    assert sorted(x.prompt.size for x in a) == \
        sorted(x.prompt.size for x in b)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    # in the seed's own order, with the seed's own tokens
    assert [x.prompt.size for x in a] != [x.prompt.size for x in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_fill_is_due_at_the_start():
    plain = traffic.schedule(mix(), seed=4, horizon_s=30, vocab=1000)
    filled = traffic.schedule(dict(mix(), fill=6), seed=4, horizon_s=30,
                              vocab=1000)
    assert len(filled) == len(plain) + 6
    assert [x.due_s for x in filled[:7]] == [0.0] * 7
    assert filled[-1].due_s == pytest.approx(plain[-1].due_s, rel=0.1)
    assert [x.idx for x in filled] == list(range(len(filled)))


def test_distributions():
    n = 4000
    gaps = traffic.gaps({"process": "poisson", "rate_per_s": 4.0}, n)
    assert np.mean(gaps) == pytest.approx(0.25, rel=0.01)
    assert np.median(gaps) == pytest.approx(math.log(2) / 4.0, rel=0.01)
    ln = traffic.quantiles({"dist": "lognormal", "median": 128,
                            "sigma": 0.7, "min": 32, "max": 512}, n)
    assert np.median(ln) == pytest.approx(128, abs=1)
    assert ln.min() >= 32 and ln.max() <= 512
    # the share clipped at 512 is the lognormal's tail beyond it
    tail = 1 - 0.5 * (1 + math.erf(math.log(4) / (0.7 * math.sqrt(2))))
    assert np.mean(ln == 512) == pytest.approx(tail, abs=0.005)
    un = traffic.quantiles({"dist": "uniform", "min": 24, "max": 40}, n)
    assert un.min() == 24 and un.max() == 40
    assert np.mean(un) == pytest.approx(32, abs=0.05)
    counts = np.bincount(un - 24)
    assert counts.max() - counts.min() <= 2


def test_rate_and_horizon():
    s = traffic.schedule(mix(rate=5.0), seed=3, horizon_s=40, vocab=50)
    assert len(s) == 200
    assert s[0].due_s == 0.0
    assert s[-1].due_s == pytest.approx(40, rel=0.05)
    assert all(0 <= x.prompt.min() and x.prompt.max() < 50 for x in s)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_mix_files_are_well_formed(path):
    m = json.loads(path.read_text())
    assert m["regime"] in ("steady", "overload")
    s = traffic.schedule(m, seed=7, horizon_s=5, vocab=100)
    assert s and all(x.max_new_tokens >= 1 for x in s)
    assert traffic.length_cap(m["prompt_len"]) >= max(x.prompt.size for x in s)
    assert int(m["slots"]) >= 1 and m["ramp_s"] >= 0
