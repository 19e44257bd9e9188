"""Production training driver: --arch <id> on the current device set.

On a real TPU cluster this runs under the production mesh; on CPU it runs
the smoke config on a host mesh (the dry-run validates the production
configuration without hardware).

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 30
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, get_config
from repro.data import DataConfig, global_arrays
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.sharding import data_shardings, param_shardings
from repro.training import LoopConfig, optimizer as opt, run_training
from repro.training.train_step import jit_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 production mesh (needs 256 devices)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family == "encdec" or cfg.family == "vlm":
        print(f"note: {cfg.family} frontend is stubbed; training uses "
              "random prefix embeddings")
    model = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name}")

    params_host = model.init_params(jax.random.PRNGKey(0))
    n = sum(p.size for p in jax.tree.leaves(params_host))
    print(f"params: {n / 1e6:.2f}M")
    params_sh = param_shardings(params_host, mesh)
    params = jax.device_put(params_host, params_sh)
    opt_host = opt.init_state(params_host)
    opt_sh = param_shardings(opt_host, mesh)
    opt_state = jax.device_put(opt_host, opt_sh)

    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch, seed=0)
    dummy = {"tokens": np.zeros((args.batch, args.seq), np.int32),
             "labels": np.zeros((args.batch, args.seq), np.int32)}
    data_sh = data_shardings(dummy, mesh)
    ocfg = opt.AdamWConfig(lr=args.lr, warmup_steps=10,
                           total_steps=args.steps)
    step = jit_train_step(model, ocfg, mesh, params_sh, opt_sh, data_sh,
                          microbatches=args.microbatches)

    if cfg.family in ("encdec", "vlm"):
        # wrap: add the stub frontend embeddings per batch
        key_name = "frames" if cfg.family == "encdec" else "patches"

        def step_with_stub(p, s, batch):
            stub = jnp.zeros((args.batch, cfg.frontend_len, cfg.d_model),
                             jnp.float32)
            return step(p, s, {**batch, key_name: stub})
        run_step = step_with_stub
    else:
        run_step = step

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    params, opt_state, state = run_training(
        run_step, params, opt_state, data_cfg, data_sh,
        LoopConfig(total_steps=args.steps, ckpt_every=50, log_every=10),
        ckpt)
    print(f"finished at step {state.step}; "
          f"loss {state.losses[0]:.4f} -> {state.losses[-1]:.4f}")


if __name__ == "__main__":
    main()
