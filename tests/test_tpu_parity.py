"""TPU test tier: spawn tpu_parity_main.py against the real chip.  The
suite itself is pinned to the virtual-CPU backend by conftest.py before
any device call, so this pytest process never holds the chip and the
child (which takes the environment's default platform) can."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def test_tpu_backend_parity():
    env = dict(os.environ)
    # Drop the virtual-CPU-mesh flag the suite injects; keep the
    # environment's default platform.
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    )
    # Fast probe first: a backend init that hangs (it does not raise)
    # would eat the full parity run's whole timeout before failing.  A
    # 90s bounded probe turns that into a skip.
    try:
        probe = subprocess.run(
            [sys.executable, "-c", "import jax; print(jax.devices()[0].platform)"],
            capture_output=True,
            text=True,
            timeout=90,
            cwd=REPO,
            env=env,
        )
    except subprocess.TimeoutExpired:
        pytest.skip("TPU backend init timed out")
    if probe.returncode != 0:
        pytest.skip(f"no TPU available: {probe.stderr.strip()[-200:]}")
    try:
        proc = subprocess.run(
            [sys.executable, str(REPO / "tests" / "tpu_parity_main.py")],
            capture_output=True,
            text=True,
            timeout=580,
            cwd=REPO,
            env=env,
        )
    except subprocess.TimeoutExpired:
        pytest.skip("TPU parity run timed out")
    if proc.returncode == 42:
        pytest.skip(f"no TPU available: {proc.stderr.strip()[-200:]}")
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-2000:]}"
