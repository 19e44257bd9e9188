"""Multi-device distributed smoke (run as a subprocess with 8 fake
devices — keeps the main test process at 1 device per the dry-run rule).

Covers: sharded params (TP+FSDP) on a (4,2) mesh, jitted train step with
GSPMD collectives, loss descent, checkpoint save on (4,2) and
reshard-on-load onto (2,4) [elastic scaling], and int8 error-feedback
gradient all-reduce across real shards.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, global_arrays
from repro.models import build_model
from repro.sharding import data_shardings, param_shardings
from repro.training import optimizer as opt
from repro.training.train_step import jit_train_step


def main():
    assert len(jax.devices()) == 8, jax.devices()
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    params_host = model.init_params(jax.random.PRNGKey(0))
    params_sh = param_shardings(params_host, mesh, mode="fsdp")
    params = jax.device_put(params_host, params_sh)
    opt_state = jax.device_put(opt.init_state(params_host),
                               param_shardings(opt.init_state(params_host),
                                               mesh, mode="fsdp"))
    # sanity: at least one param is actually sharded over both axes
    n_sharded = sum(
        1 for p in jax.tree.leaves(params)
        if not p.sharding.is_fully_replicated)
    assert n_sharded > 5, n_sharded

    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8,
                          seed=0)
    dummy = {"tokens": np.zeros((8, 16), np.int32),
             "labels": np.zeros((8, 16), np.int32)}
    data_sh = data_shardings(dummy, mesh)
    ocfg = opt.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=50)
    step = jit_train_step(model, ocfg, mesh, params_sh,
                          param_shardings(opt.init_state(params_host),
                                          mesh, mode="fsdp"),
                          data_sh, remat=True)

    losses = []
    for i in range(10):
        batch = global_arrays(data_cfg, i, data_sh)
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
    print("LOSSES_OK", losses[0], losses[-1])

    # ---- checkpoint on (4,2); restore onto (2,4): elastic reshard -------
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(10, params)
        mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                              axis_types=(AxisType.Auto,) * 2)
        sh2 = param_shardings(params_host, mesh2, mode="fsdp")
        restored, step0 = mgr.restore(
            jax.eval_shape(lambda: params_host), shardings=sh2)
        assert step0 == 10
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(restored)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=0)
    print("RESHARD_OK")

    # ---- int8 error-feedback all-reduce over 4 real data shards ---------
    from repro.training.grad_compression import (
        init_error_buffers, make_compressed_allreduce)
    reduce = make_compressed_allreduce(mesh, axis_names=("data",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (4, 128, 128))}
    errs = init_error_buffers(g)
    out, errs = reduce(g, errs)
    exact = jnp.broadcast_to(jnp.mean(g["w"], axis=0, keepdims=True),
                             g["w"].shape)
    err0 = float(jnp.max(jnp.abs(out["w"] - exact)))
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert err0 <= 4 * scale, (err0, scale)
    # error feedback: accumulated mean over repeats converges to exact
    acc = np.zeros(g["w"].shape, np.float32)
    for _ in range(8):
        out, errs = reduce(g, errs)
        acc += np.asarray(out["w"])
    err_avg = float(np.max(np.abs(acc / 8 - np.asarray(exact))))
    assert err_avg < err0 + 1e-7
    print("GRADCOMP_OK", err0, err_avg)
    print("ALL_OK")


if __name__ == "__main__":
    main()
