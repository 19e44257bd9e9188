"""Sharded serving integration via subprocess (4 fake CPU devices), so
the main test session keeps the default single device."""
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow    # subprocess with 4 fake devices

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_dist_serve_smoke():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "dist_serve_smoke.py")],
        capture_output=True, text=True, timeout=880)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    for marker in ("TOKENS_OK", "ALL_OK"):
        assert marker in proc.stdout, proc.stdout
