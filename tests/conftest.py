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


def layer_sliced_arena(setattr) -> None:
    """Make every paged attention layer run over its own layer's slab, as
    a layer scan that carries arena slices as ``xs`` and stacks them back
    as ``ys`` does: slab ``l`` is indexed out of the arena, the layer
    writes and attends it as a one-layer arena (``layer=None``), and the
    slab is written back at ``l``.  The serving program instead writes and
    reads the whole arena at ``[l, page]`` in place; the tests hold the two
    to the same tokens, logits and arena bytes.  ``setattr`` is pytest's
    ``monkeypatch.setattr`` (undone after the test) or the builtin in a
    child process.  The process compile cache is swapped for an empty one,
    so executors built afterwards trace the patched layers."""
    from repro.models import layers
    from repro.serve import scheduler

    def sliced(attend):
        def run(p, x, kv, *args, layer, **kw):
            slab = {k: a[layer] for k, a in kv.items()}
            y, slab = attend(p, x, slab, *args, layer=None, **kw)
            return y, {k: kv[k].at[layer].set(slab[k]) for k in kv}
        return run

    for name in ("attn_decode_paged", "attn_verify_paged",
                 "attn_prefill_bucketed"):
        setattr(layers, name, sliced(getattr(layers, name)))
    setattr(scheduler, "_PROCESS_CACHE", {})
