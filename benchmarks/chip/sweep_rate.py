"""Offered-rate sweep of an open-loop cell, to find the highest rate the
program sustains without a growing backlog; not part of a benchmark run.

    python benchmarks/chip/sweep_rate.py --workload <name> \
        --rates 1.0,1.5,2.0 --seconds 30 --seed 7

For each rate, in one process, the cell runs with its mix's
``rate_per_s`` replaced; each line reports the requests due and served in
the window, the backlog (submitted, not yet served a first token) at the
window's end, and the TTFT percentiles.  A sustained rate serves what is
due and ends with a backlog of a few requests; above it the backlog grows
with the window.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    harness.check_devices(cell.chips)
    cell.limits = {"token_gap": float("inf"), "mean_gap": float("inf")}
    base = dict(cell.mix)
    for rate in [float(x) for x in args.rates.split(",")]:
        cell.mix = {**base, "rate_per_s": rate}
        logs = []
        t = time.perf_counter()
        r = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                             trace=False, process_start=t, strict=False,
                             log=logs.append)
        late = [m for m in logs if m.startswith("generator") or
                m.startswith("window")]
        print(json.dumps({"rate_per_s": rate, "attempted": r["attempted"],
                          "failed": r["failed"],
                          "metrics": {k: v["value"] for k, v in
                                      r["metrics"].items()},
                          "log": late}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
