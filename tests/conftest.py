"""Shared test fixtures.

NOTE: the fake-device XLA flag is deliberately NOT set here — unit/smoke
tests must see the real single CPU device.  Multi-device tests (sharding,
dry-run) spawn subprocesses that set XLA_FLAGS before importing jax.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


@pytest.fixture(scope="session")
def repo_root() -> str:
    return REPO


def run_child(code: str, *, devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a fresh python with ``devices`` fake XLA devices,
    on the CPU backend (a TPU, if the host has one, belongs to the test
    process, never to its children)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("REPRO_EXTRA_XLA_FLAGS", "")
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
        cwd=REPO,
    )
    assert out.returncode == 0, f"child failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout
