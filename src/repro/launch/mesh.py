"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS *before* any jax
import to fake 512 host devices (launch/dryrun.py lines 1-2).
"""
from __future__ import annotations

import os
import re

import jax
from jax.sharding import AxisType

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def forced_host_device_count() -> int | None:
    """The host-device count requested via ``XLA_FLAGS``, or None.

    Parsed from the environment (not from jax) so a mismatch between
    what was requested and what jax actually initialized — the flag was
    set after the first jax import — is detectable."""
    m = re.search(rf"{_FORCE_FLAG}=(\d+)", os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else None


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Local-device ("data", "model") mesh for smoke tests / CPU CI.

    Default (no arguments) keeps the historical shape: every local
    device on the data axis, model=1.  Pass ``model=n`` (and optionally
    ``data``) for a deterministic TP/DP mesh: ``data`` defaults to
    whatever the local devices fill (devices // model).

    Respects ``XLA_FLAGS=--xla_force_host_platform_device_count=N``:
    that flag is how CPU CI fakes an N-device host, but it only works
    when set *before the first jax import* (see launch/dryrun.py lines
    1-2) — if the environment requests N and jax reports something
    else, or the requested mesh needs more devices than exist, the
    error says exactly which flag to set rather than failing inside
    ``jax.make_mesh``."""
    n = len(jax.devices())
    forced = forced_host_device_count()
    if forced is not None and forced != n and \
            jax.default_backend() == "cpu":
        raise RuntimeError(
            f"XLA_FLAGS requests {_FORCE_FLAG}={forced} but jax "
            f"initialized with {n} device(s): the flag was set after the "
            f"first jax import — export it before python starts (or set "
            f"os.environ['XLA_FLAGS'] at the very top of the entry "
            f"script, as launch/dryrun.py does)")
    if data is None:
        if n % model:
            raise ValueError(
                f"make_host_mesh(model={model}) cannot tile {n} local "
                f"device(s) evenly; set {_FORCE_FLAG}=<multiple of "
                f"{model}> in XLA_FLAGS before the first jax import")
        data = n // model
    need = data * model
    if need > n:
        raise ValueError(
            f"host mesh ({data} data x {model} model) needs {need} "
            f"devices but only {n} are visible; set XLA_FLAGS="
            f"{_FORCE_FLAG}={need} before the first jax import "
            f"(see launch/dryrun.py lines 1-2)")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
