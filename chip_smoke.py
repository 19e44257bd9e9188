"""Smoke test of the serving system on a TPU: stablelm-1.6b at full width.

    python chip_smoke.py              # one chip: phases serve + kernels
    python chip_smoke.py --chips 4    # four chips: the TP-sharded engine

Weights are random, drawn from ``--seed``; all work runs in this one
process, which holds the chip(s).

* ``serve``: the continuous scheduler over an engine with a plan store,
  built as ``repro.launch.serve --continuous`` builds it, answers seeded
  requests.  Every request must be served.
* ``kernels``: the same model with ``fused_mlp`` routes its MLPs through
  the GOMA Pallas kernels.  Its compiled prefill and decode programs must
  hold a Mosaic kernel (``tpu_custom_call``) and agree with the default
  XLA path within ``LOGIT_RTOL``.  Each kernel family of
  ``repro.kernels`` is also checked against its reference at real widths.
* ``--chips 4``: the engine sharded over four chips (``model_axis=4``)
  must decode the same greedy tokens as the engine on one of them, with
  f32 activations (see ``phase_sharded``).

The last line of standard output is one JSON object; it is printed only
when every phase passed on a TPU.  Anything else exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import itertools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.tpu_mapping import TpuTilePlan, plan_fused_mlp  # noqa: E402
from repro.dist.serve import shard_engine  # noqa: E402
from repro.kernels.goma_gemm import goma_matmul  # noqa: E402
from repro.kernels.mamba2_ssd import ssd_pallas  # noqa: E402
from repro.kernels.ops import (fused_mlp, fused_mlp_composition,  # noqa: E402
                               interpret_default)
from repro.kernels.ref import matmul_ref, ssd_ref, wkv6_ref  # noqa: E402
from repro.kernels.wkv6 import wkv6_pallas  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import CHUNK_WIDTHS, continuous_engine  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.planner import PlanStore  # noqa: E402
from repro.serving import Engine, ServeConfig  # noqa: E402
from repro.serving.sched import (ContinuousScheduler,  # noqa: E402
                                 SchedConfig, TraceClock, TrafficConfig,
                                 poisson_trace, replay)

ARCH = "stablelm-1.6b"
PLAN_DB = ROOT / ".plan_db"
# Prefill and decode logits of the Pallas MLP path against XLA's, as
# ||a - b|| / ||b||.  Both run bf16 GEMMs with f32 accumulation but round
# in different places (K blocking, where the MLP output is cast), and a
# random-init residual stream amplifies that over 24 layers: a 24-layer,
# 256-wide bf16 copy drifts by 2e-2 (5 bf16 eps of 2^-8) on the CPU.  A
# wrong kernel is off by O(1); 1e-1 is about 25 eps.
LOGIT_RTOL = 1e-1
# f32 scans at HIGHEST matmul precision against sequential references
SCAN_TOL = 2e-3


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --------------------------------------------------------------- serve
def phase_serve(cfg, params, *, seed: int, n_requests: int = 8,
                prompt_lo: int = 64, prompt_hi: int = 256,
                new_tokens: int = 32) -> None:
    model = build_model(cfg)
    store = PlanStore(PLAN_DB)
    eng = continuous_engine(model, params, store, prompt_len=prompt_hi,
                            new_tokens=new_tokens)
    trace = poisson_trace(TrafficConfig(
        n_requests=n_requests, arrival_rate=8.0,
        prompt_mix=((prompt_lo, prompt_hi, 1.0),),
        max_new_tokens=new_tokens, vocab=cfg.vocab, seed=seed))
    clock = TraceClock()
    sched = ContinuousScheduler(
        eng, SchedConfig(slots=n_requests, chunk_widths=CHUNK_WIDTHS),
        clock=clock.now)
    results = replay(sched, trace, clock)
    summ = sched.metrics.summary()
    print(f"serve: {summ['served']}/{summ['requests']} served, "
          f"{summ['total_generated_tokens']} tokens, rejected="
          f"{summ['rejected']} expired={summ['expired']} errored="
          f"{summ['errored']}  store={store.stats()}")
    # information only, on the trace clock, compilation included
    print(f"serve (info, incl. compile): ttft_p50={summ['ttft_p50_s']}s "
          f"tokens_per_s={summ['tokens_per_s']}")
    check(len(results) == n_requests
          and summ["served"] == n_requests
          and not (summ["rejected"] or summ["expired"] or summ["errored"]),
          f"not every request was served: {summ}")
    check(all(r.n_generated == new_tokens for r in results),
          "a request stopped short of its token budget")


# ------------------------------------------------------------- kernels
def _compiled(fn, *args):
    """(compiled program, whether it holds a Mosaic kernel)."""
    c = jax.jit(fn).lower(*args).compile()
    return c, "tpu_custom_call" in c.as_text()


def check_model_kernels(cfg, params, *, seed: int, batch: int = 2,
                        prompt: int = 128, steps: int = 4) -> None:
    """Prefill + a few greedy decode steps through the fused-MLP model's
    compiled programs against the default model's, fed the same tokens."""
    ref_model = build_model(cfg)
    pl_model = build_model(dataclasses.replace(cfg, fused_mlp=True))
    cache_len = prompt + steps
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt), 0, cfg.vocab, jnp.int32)
    outs = {}
    for name, model in (("xla", ref_model), ("pallas", pl_model)):
        prefill, has_pre = _compiled(
            functools.partial(model.prefill, max_len=cache_len),
            params, {"tokens": tokens})
        logits, cache = prefill(params, {"tokens": tokens})
        tok = jnp.zeros((batch, 1), jnp.int32)
        decode, has_dec = _compiled(model.decode_step, params, cache, tok,
                                    jnp.int32(prompt))
        outs[name] = {"prefill": logits, "custom": (has_pre, has_dec),
                      "decode": decode, "cache": cache}
    check(outs["pallas"]["custom"] == (True, True),
          f"fused-MLP programs hold no Mosaic kernel "
          f"(prefill, decode) = {outs['pallas']['custom']}")
    err = rel_err(outs["pallas"]["prefill"], outs["xla"]["prefill"])
    print(f"kernels: fused_mlp prefill logits rel_err={err:.3e} "
          f"(limit {LOGIT_RTOL})")
    check(err <= LOGIT_RTOL, "prefill logits disagree")
    tok = jnp.argmax(outs["xla"]["prefill"][:, -1], -1)[:, None]
    for t in range(steps):
        step = {}
        for name, o in outs.items():
            logits, o["cache"] = o["decode"](params, o["cache"], tok,
                                             jnp.int32(prompt + t))
            step[name] = logits
        err = rel_err(step["pallas"], step["xla"])
        print(f"kernels: fused_mlp decode step {t} logits "
              f"rel_err={err:.3e}")
        check(err <= LOGIT_RTOL, f"decode step {t} logits disagree")
        tok = jnp.argmax(step["xla"][:, -1], -1)[:, None].astype(jnp.int32)


def check_gemm_grid_orders(dtype=jnp.bfloat16) -> None:
    """goma_matmul gives the same bits whichever grid axis is innermost
    when k takes one step, and matches the reference."""
    M, N, K = 512, 768, 1024
    ka, kb = jax.random.split(jax.random.PRNGKey(3))
    a = jax.random.normal(ka, (M, K), jnp.float32).astype(dtype)
    b = jax.random.normal(kb, (K, N), jnp.float32).astype(dtype)
    outs = []
    for order in itertools.permutations("mnk"):
        plan = TpuTilePlan(M=M, N=N, K=K, padded=(M, N, K),
                           block=(128, 256, K), grid_order=order,
                           walk={"m": "x", "n": "y", "k": "z"}[order[-1]],
                           objective=0.0, solve_time_s=0.0)
        c, has = _compiled(lambda x, y, p=plan: goma_matmul(
            x, y, p, interpret=interpret_default()), a, b)
        check(has, f"goma_matmul {order} compiled without a Mosaic kernel")
        outs.append(np.asarray(c(a, b)))
    check(all(np.array_equal(outs[0], o) for o in outs[1:]),
          "goma_matmul results depend on the grid order")
    with jax.default_matmul_precision("highest"):
        ref = matmul_ref(a, b)
    err = rel_err(outs[0], ref)
    print(f"kernels: goma_matmul 6 grid orders bit-identical, "
          f"rel_err vs ref={err:.3e}")
    check(err <= 1e-2, "goma_matmul disagrees with its reference")


def check_fused_chain(dtype=jnp.bfloat16) -> None:
    """The fused kernel, at a chain that its VMEM holds, is bit-identical
    to the unfused composition of the same tiles (both grid paths)."""
    M, FF, K = 256, 1024, 512
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    a, wg, wu = (jax.random.normal(k_, s, jnp.float32).astype(dtype) * 0.1
                 for k_, s in zip(ks, ((M, K), (K, FF), (K, FF))))
    wd = (jax.random.normal(ks[3], (FF, K), jnp.float32) * 0.1
          ).astype(dtype)
    plan = plan_fused_mlp(M, FF, K, dtype_bytes=jnp.dtype(dtype).itemsize)
    check(plan.fused, f"chain {M}x{FF}x{K} planned unfused: {plan}")
    for bk in sorted({plan.bk, 128}):
        p = dataclasses.replace(plan, bk=bk)
        out = fused_mlp(a, wg, wu, wd, plan=p)
        comp = fused_mlp_composition(a, wg, wu, wd, p)
        same = np.array_equal(np.asarray(out), np.asarray(comp))
        print(f"kernels: fused chain {M}x{FF}x{K} bm={p.bm} bk={bk} "
              f"nk={p.grid[1]} bit-identical to composition: {same}")
        check(same, "fused kernel differs from the composition")


def check_scans() -> None:
    """wkv6 at rwkv6-7b head widths and ssd at zamba2-2.7b head widths
    against their sequential references."""
    B, S, H, P = 1, 256, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, P)) * 0.5
               for i in range(3))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, P)) - 2.0)
    u = jax.random.normal(ks[4], (H, P)) * 0.3
    c, has = _compiled(lambda *t: wkv6_pallas(
        *t, chunk=64, interpret=interpret_default())[0], r, k, v, logw, u)
    check(has, "wkv6_pallas compiled without a Mosaic kernel")
    y = c(r, k, v, logw, u)
    with jax.default_matmul_precision("highest"):
        ref = wkv6_ref(r, k, v, logw, u)
    err = float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))
    print(f"kernels: wkv6_pallas H={H} P={P} max_err/max_ref={err:.3e}")
    check(err <= SCAN_TOL, "wkv6_pallas disagrees with wkv6_ref")

    B, S, H, P, N = 1, 256, 80, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    xh = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a_log = jax.random.normal(ks[2], (H,)) * 0.2
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    c, has = _compiled(lambda *t: ssd_pallas(
        *t, chunk=64, interpret=interpret_default())[0], xh, dt, a_log, Bm, Cm)
    check(has, "ssd_pallas compiled without a Mosaic kernel")
    y = c(xh, dt, a_log, Bm, Cm)
    with jax.default_matmul_precision("highest"):
        ref = ssd_ref(xh, dt, a_log, Bm, Cm, jnp.zeros((H,)))
    err = float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))
    print(f"kernels: ssd_pallas H={H} P={P} N={N} "
          f"max_err/max_ref={err:.3e}")
    check(err <= SCAN_TOL, "ssd_pallas disagrees with ssd_ref")


def phase_kernels(cfg, params, *, seed: int) -> None:
    check_model_kernels(cfg, params, seed=seed)
    check_gemm_grid_orders()
    check_fused_chain()
    check_scans()


# ---------------------------------------------------------- four chips
def phase_sharded(cfg, params, *, seed: int, chips: int = 4,
                  batch: int = 4, prompt: int = 64,
                  new_tokens: int = 16) -> None:
    check(len(jax.devices()) >= chips,
          f"{chips} devices needed, {len(jax.devices())} found")
    # f32 activations: tensor parallelism reorders each contraction's
    # f32 sums, and in bf16 the re-rounded activations would drift by an
    # ulp here and there until a random-init model's near-tied logits
    # flip.  In f32 the drift stays near 1e-6, so any token difference
    # is a sharding fault.
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    eng = Engine(build_model(cfg), params, ServeConfig(
        max_new_tokens=new_tokens, cache_len=prompt + new_tokens))
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (batch, prompt)).astype(np.int32)
    t0 = time.perf_counter()
    one = eng.generate(prompts)
    print(f"sharded: one chip generated {one.shape} in "
          f"{time.perf_counter() - t0:.2f}s (incl. compile)")
    shard_engine(eng, model_axis=chips)
    per_dev = {d.id: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(eng.params):
        for s in leaf.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    print(f"sharded: parameter bytes per device {per_dev}")
    total = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    check(sum(1 for b in per_dev.values() if b > 0) == chips
          and max(per_dev.values()) < total,
          "parameters are not spread over the devices")
    t0 = time.perf_counter()
    four = eng.generate(prompts)
    print(f"sharded: {chips} chips generated {four.shape} in "
          f"{time.perf_counter() - t0:.2f}s (incl. compile)")
    print(f"sharded: first row one chip {one[0].tolist()}")
    print(f"sharded: first row {chips} chips {four[0].tolist()}")
    check(np.array_equal(one, four),
          f"greedy tokens differ: {int((one != four).sum())} of "
          f"{one.size}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.jit(build_model(cfg).init_params)(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{ARCH}: {n / 1e9:.3f}B parameters on {dev.device_kind} "
          f"in {time.perf_counter() - t0:.2f}s")
    if args.chips == 4:
        phases = [("sharded", phase_sharded)]
    else:
        phases = [("serve", phase_serve), ("kernels", phase_kernels)]
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(cfg, params, seed=args.seed)
        except SmokeFailure as e:
            print(f"phase {name} FAILED: {e}", file=sys.stderr)
            return 1
        print(f"phase {name} ok in {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
