"""Benchmark helpers: wall timing + CPU subprocess runs with N virtual devices."""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def timed(fn, *args, repeats: int = 3, warmup: int = 1, **kw):
    """Median wall seconds over repeats (after warmup/compile)."""
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], out


def run_with_devices(script: str, num_devices: int, timeout: int = 1200) -> str:
    """Run a python snippet under N virtual CPU devices; return stdout.

    A CPU rehearsal, never a device measurement: the child is pinned to
    ``JAX_PLATFORMS=cpu``.  Used for par(1)/par(2) runs (the paper's
    'available processors' column) — jax fixes the device count at first
    init, so a fresh process is the only way to vary it.  The parent
    imports JAX, and a chip belongs to one process at a time, so a child
    must never ask for the accelerator.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={num_devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=timeout,
        stdin=subprocess.DEVNULL,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return proc.stdout


def csv_row(name: str, seconds: float, derived: str = "") -> str:
    return f"{name},{seconds * 1e6:.1f},{derived}"
