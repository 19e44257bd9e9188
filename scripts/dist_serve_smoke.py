"""Sharded-serving smoke (run as a subprocess with 4 fake devices —
keeps the main test process at 1 device per the dry-run rule).

Covers the dist subsystem's serving path on a real mesh: TP-sharded
greedy serving token-identical to the single-chip oracle with zero
steady-state solver invocations (TOKENS_OK).  The sharded plan store's
round trip is covered by tests/test_dist_solver.py.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.core.solver import solver_stats
from repro.dist.serve import shard_engine
from repro.models import build_model
from repro.obs.registry import get_registry
from repro.serving import Engine, ServeConfig


def main():
    assert len(jax.devices()) == 4, jax.devices()
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    sc = ServeConfig(max_new_tokens=12, temperature=0.0, cache_len=64)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, size=(4, 10)).astype(np.int32)

    # ---- TP-sharded greedy serving == single-chip oracle ---------------
    oracle = Engine(model, params, sc)
    want = oracle.generate(prompts)

    sharded = Engine(model, params, sc)
    mesh = shard_engine(sharded, model_axis=4)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \
        {"data": 1, "model": 4}, mesh
    n_placed = sum(1 for p in jax.tree.leaves(sharded.params)
                   if not p.sharding.is_fully_replicated)
    assert n_placed > 3, n_placed       # params really live on the mesh
    calls0 = solver_stats()["calls"]
    got = sharded.generate(prompts)
    assert solver_stats()["calls"] == calls0      # zero steady-state solves
    assert np.array_equal(want, got), (want, got)
    assert get_registry().get("dist.engines_sharded") >= 1
    print("TOKENS_OK", got.shape)
    print("ALL_OK")


if __name__ == "__main__":
    main()
