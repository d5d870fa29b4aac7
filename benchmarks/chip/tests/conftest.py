import os
import shutil
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(REPO, "src"))

DATA_DIRS = ("configs", "traffic", "limits", "metrics")


def make_root(base: str) -> str:
    """A checkout-shaped directory for CPU runs: the test BENCHMARK.json,
    copies of the benchmark's data files with the tiny test ones beside
    them, and the program."""
    root = os.path.join(base, "root")
    data = os.path.join(root, "benchmarks", "chip")
    td = os.path.join(CHIP, "testdata")
    os.makedirs(data)
    shutil.copy(os.path.join(td, "BENCHMARK.json"), root)
    for d in DATA_DIRS:
        shutil.copytree(os.path.join(CHIP, d), os.path.join(data, d))
        extra = os.path.join(td, d)
        if os.path.isdir(extra):
            shutil.copytree(extra, os.path.join(data, d), dirs_exist_ok=True)
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    return root


@pytest.fixture
def root(tmp_path):
    return make_root(str(tmp_path))


def run_tiny(root: str, workload: str, seed: int = 12345678901,
             seconds: float = 1.0):
    import time

    import harness

    cell = harness.Cell(workload, root=root)
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                            process_start=time.perf_counter(),
                            require_tpu=False, log=lambda m: None)
