"""Launching the benchmarks that emulate a multi-device mesh on the CPU.

The STAP, serving, async, calibration and quantization benchmarks (and
``benchmarks.smoke``) run their pipelines on ``N_DEVICES`` CPU devices,
which XLA creates only when its flags are set before the backend starts.
So on the CPU backend such a benchmark runs in a child process that
starts with the flags. On an accelerator there is nothing to emulate, and
a chip belongs to the one process that first touches JAX: there no child
is ever started, and ``benchmarks.run`` skips these entries.

    PYTHONPATH=src python -m benchmarks.occam_stap    # any of them
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_DEVICES = 8

_COUNT_FLAG = "--xla_force_host_platform_device_count"
_EIGEN_FLAG = "--xla_cpu_multi_thread_eigen"


def merged_flags(existing: str) -> str | None:
    """XLA_FLAGS the emulated mesh needs, merged into ``existing`` (both
    matter: the device count emulates the mesh, single-threaded Eigen
    keeps one body from hogging every core and hiding the STAP effect).
    A pre-set but too-small device count is raised to N_DEVICES — unlike
    tests/conftest.py, which never overrides a user flag and lets tests
    skip instead, a benchmark subprocess owns its env. Returns None when
    ``existing`` is already sufficient."""
    parts = existing.split()
    have = None
    for f in parts:
        if f.startswith(_COUNT_FLAG + "="):
            try:
                have = int(f.split("=", 1)[1])
            except ValueError:
                have = None
    changed = False
    if have is None or have < N_DEVICES:
        parts = [f for f in parts if not f.startswith(_COUNT_FLAG)]
        parts.append(f"{_COUNT_FLAG}={N_DEVICES}")
        changed = True
    if not any(f.startswith(_EIGEN_FLAG) for f in parts):
        parts.append(f"{_EIGEN_FLAG}=false")
        changed = True
    return " ".join(parts) if changed else None


def on_cpu() -> bool:
    """Whether this process's JAX backend is the CPU (initializes it)."""
    import jax

    return jax.default_backend() == "cpu"


def launch(module: str, run) -> None:
    """``__main__`` entry of an emulating benchmark: on the CPU backend
    without the flags, re-run ``python -m module`` in a child that has
    them; otherwise call ``run()`` here, in the process that holds the
    backend."""
    flags = merged_flags(os.environ.get("XLA_FLAGS", ""))
    if flags is not None and on_cpu():
        env = dict(os.environ, XLA_FLAGS=flags)
        sys.exit(subprocess.run([sys.executable, "-m", module], cwd=ROOT,
                                env=env).returncode)
    run()


def run_child(module: str, out_path: str) -> dict:
    """Harness entry (``benchmarks.run``): run ``python -m module`` in a
    flagged child and return the JSON row it wrote to ``out_path``. CPU
    backend only — a child could not reach a chip its parent holds."""
    if not on_cpu():
        raise RuntimeError(f"{module} emulates a {N_DEVICES}-device mesh "
                           f"on the CPU backend; it does not run on an "
                           f"accelerator")
    env = dict(os.environ)
    env["XLA_FLAGS"] = merged_flags(env.get("XLA_FLAGS", "")) \
        or env.get("XLA_FLAGS", "")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    res = subprocess.run([sys.executable, "-m", module], cwd=ROOT, env=env,
                         capture_output=True, text=True)
    if res.returncode != 0:
        raise RuntimeError(f"{module} subprocess failed:\n"
                           f"{res.stderr[-2000:]}")
    with open(out_path) as f:
        return json.load(f)
