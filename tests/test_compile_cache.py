"""Where the entry points keep JAX's persistent compilation cache."""
import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache as cc

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_cache_dir_from_environment_is_used(tmp_path):
    """With $JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the helper sets nothing itself."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "print(enable_compile_cache() == before)\n"
        "x = jnp.ones((64, 64))\n"
        "jax.jit(lambda x: jnp.sin(x) @ x)(x).block_until_ready()\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "True"
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(cc.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cc.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
