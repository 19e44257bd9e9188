"""The plain references against the program's chunked prefill into a B=1
cache, the graft into a slot cache and slot-indexed decode, at smoke
sizes on the CPU, comparing logits."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import common
from chipbench.tests.helpers import smoke
from repro.models import build_model
from repro.serving import Engine, ServeConfig


def program_logits(cfg, params, prompt, follow, *, slots=3, slot=1):
    """Logits at every position of prompt + follow: prefill in chunks of
    8 at B=1, graft into slot ``slot``, then decode ``follow`` one token
    a step among ``slots`` rows."""
    eng = Engine(build_model(cfg), params, ServeConfig(cache_len=64))
    cache = eng.new_cache(1)
    out = []
    for s in range(0, len(prompt), 8):
        chunk = np.zeros((1, 8), np.int32)
        real = prompt[s:s + 8]
        chunk[0, :len(real)] = real
        logits, cache = eng.prefill_chunk(cache, chunk, s)
        out.append(np.asarray(logits[0, :len(real)]))
    big = eng.insert_row(eng.new_cache(slots), cache, slot)
    pos = np.full((slots,), 0, np.int32)
    toks = np.zeros((slots, 1), np.int32)
    for j, t in enumerate(follow):
        pos[slot] = len(prompt) + j
        toks[slot, 0] = t
        logits, big = eng.decode_slots(big, toks, pos)
        out.append(np.asarray(logits[slot]))
    return np.concatenate(out, axis=0)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_reference_matches_program_logits(family):
    from chipbench.harness import family_module
    cfg, config = smoke(family)
    params = weights.make(build_model(cfg).init_params, 2**35 + 1)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 21).astype(np.int32)
    follow = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    got = program_logits(cfg, params, prompt, follow)
    ref = family_module("reference", config)
    seq = jnp.asarray(np.concatenate([prompt, follow])[None])
    want = np.asarray(common.forward(common.Numerics("float32"), params,
                                     config, seq, _mlp(ref, config)))[0]
    assert got.shape == want.shape
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-4, err
    gap, top = ref.gap_fn(config)(params, seq,
                                  jnp.asarray(np.argmax(got, -1)[None]))
    assert float(jnp.max(gap)) < 1e-4
    assert np.array_equal(np.asarray(top)[0], np.argmax(want, -1))


def _mlp(ref, config):
    if config["family"] == "moe":
        k = config["num_experts_per_tok"]
        return lambda num, h, lp: ref.mlp(num, h, lp, top_k=k)
    return ref.mlp


def test_weights_depend_on_the_whole_seed():
    cfg, _ = smoke("dense")
    init = build_model(cfg).init_params
    a = weights.make(init, 5)
    b = weights.make(init, 5 + 2**32)
    c = weights.make(init, 5)
    wa, wb, wc = (np.asarray(p["lm_head"]["w"]) for p in (a, b, c))
    assert np.array_equal(wa, wc) and not np.array_equal(wa, wb)
    assert np.all(np.asarray(a["layers"]["ln1"]["scale"]) == 1)
