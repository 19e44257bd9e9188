"""Run one benchmark cell once, on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers the output check compared,
each beside its limit.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.  JAX's persistent
compilation cache and the plan store live in ``.chipbench_cache/`` of
the checkout.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".chipbench_cache"
                                              / "jax")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import jax

    from chipbench import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, however quick to compile, so that a second
    # run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return harness.main(t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
