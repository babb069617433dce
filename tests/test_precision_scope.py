"""Importing the float64 benchmarks and example leaves the process's
precision as it was: each scopes ``jax_enable_x64`` to its own run."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_importing_float64_modules_keeps_float32():
    code = textwrap.dedent("""
        import importlib, importlib.util, jax
        importlib.import_module("benchmarks.bench_tuner")
        importlib.import_module("benchmarks.bench_mr_fit")
        spec = importlib.util.spec_from_file_location(
            "calibrate_and_tune", "examples/calibrate_and_tune.py")
        spec.loader.exec_module(importlib.util.module_from_spec(spec))
        print("x64", jax.config.jax_enable_x64)
    """)
    env = dict(os.environ, PYTHONPATH=f"{ROOT / 'src'}{os.pathsep}{ROOT}")
    env.pop("JAX_ENABLE_X64", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "False"
