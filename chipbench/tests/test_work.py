"""Least-work counts against hand arithmetic, and the properties that
keep a share of the roofline at or under 100%."""
import json
import pathlib

import pytest

from chipbench.work import dense, moe

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
STABLELM = json.loads((CONFIGS / "stablelm-1.6b.json").read_text())
GRANITE = json.loads((CONFIGS / "granite-moe-1b-a400m.json").read_text())


def test_dense_decode_row_by_hand():
    P = 300                                   # positions already cached
    flops, nbytes = dense.call_work(STABLELM, [(1, P, 1)])
    attn = 2048 * (3 * 2048) + 2048 * 2048    # q, k, v, o
    mlp = 3 * 2048 * 5632
    want = (24 * 2 * (attn + mlp)             # weights
            + 4 * 24 * 32 * 64 * (P + 1)      # scores and values
            + 2 * 2048 * 100352)              # output head
    assert flops == want
    weights = 24 * (attn + mlp + 4 * 2048) + 2048 * 100352 + 2 * 2048
    kv = (P + 1) * 24 * 2 * 32 * 64 * 2
    assert nbytes == 2 * weights + kv + 2048 * 2
    assert dense.kv_bytes_per_token(STABLELM) == 196608


def test_moe_decode_row_by_hand():
    P = 100
    flops, nbytes = moe.call_work(GRANITE, [(1, P, 1)])
    attn = 1024 * (1024 + 2 * 512) + 1024 * 1024
    expert = 3 * 1024 * 512
    per_token = 2 * (attn + 8 * expert + 1024 * 32)
    want = (24 * per_token + 4 * 24 * 16 * 64 * (P + 1)
            + 2 * 1024 * 49155)
    assert flops == want
    layer = (attn + 2 * 1024) * 2 + 1024 * 32 * 4 + 8 * expert * 2
    head = (1024 * 49155 + 1024) * 2
    kv = (P + 1) * 24 * 2 * 8 * 64 * 2
    assert nbytes == 24 * layer + head + kv + 1024 * 2
    assert dense.kv_bytes_per_token(GRANITE) == 49152


def test_moe_counts_routed_experts_only():
    rows = [(1, 200, 1)] * 16
    flops, nbytes = moe.call_work(GRANITE, rows)
    all32 = dict(GRANITE, num_experts_per_tok=32)
    f32, b32 = moe.call_work(all32, rows)
    expert = 3 * 1024 * 512
    assert f32 - flops == pytest.approx(16 * 24 * 2 * 24 * expert)
    assert b32 - nbytes == 24 * 24 * expert * 2


def test_attention_counts_valid_positions_not_cache_len():
    short = dense.call_work(STABLELM, [(1, 10, 1)])
    long = dense.call_work(STABLELM, [(1, 1000, 1)])
    kvb = dense.kv_bytes_per_token(STABLELM)
    assert long[1] - short[1] == 990 * kvb
    assert long[0] - short[0] == 4 * 24 * 32 * 64 * 990


def test_chunk_attention_is_causal_and_head_only_where_needed():
    f, _ = dense.call_work(STABLELM, [(32, 64, 0)])
    attn = 2048 * (3 * 2048) + 2048 * 2048
    mlp = 3 * 2048 * 5632
    keys = sum(64 + i + 1 for i in range(32))
    assert f == 32 * 24 * 2 * (attn + mlp) + 4 * 24 * 32 * 64 * keys
    f_last, _ = dense.call_work(STABLELM, [(32, 64, 1)])
    assert f_last - f == 2 * 2048 * 100352


@pytest.mark.parametrize("cfg,work", [(STABLELM, dense), (GRANITE, moe)])
def test_least_work_reads_at_most_full_roofline(cfg, work):
    """An implementation that does exactly the counted work at the
    peaks reads 100%; one that walks the whole cache, or every expert,
    takes longer and reads less."""
    peak_f, peak_b = 197e12, 819e9
    rows = [(1, 100 + 7 * i, 1) for i in range(24)]
    f, b = work.call_work(cfg, rows)
    least = max(f / peak_f, b / peak_b)
    padded = [(1, 1023, 1)] * 24
    fp, bp = work.call_work(cfg, padded)
    real = max(fp / peak_f, bp / peak_b)
    assert least / least == 1.0 and least / real < 1.0
