"""Readings that the correctness limits are set from; not part of a
benchmark run.

    python benchmarks/chip/calibrate.py --workload <name> \
        --mode program|control|half_batch|token --seeds 1,2,3 --seconds 2

Runs the cell once per seed in this one process (the compiled programs
are shared) and prints each run's compared numbers as a JSON line:

* ``program``: the cell as the benchmark runs it, with the limits file's
  limits ignored: the lower readings;
* ``control``: training, the reference at int4 operands against the
  reference (no program runs); serving, the program's run read by the
  reference, and the reference at int4 operands (the control) and at the
  floats ``CONTROL_READINGS`` names, each read at its own top token;
* ``half_batch``: training with a step that leaves half of each batch out
  and takes the mean over the rest;
* ``token``: serving with row 0's token altered in every decode call.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

sys.path.insert(0, os.path.join(harness.ROOT, "src"))


# the control (int4) and, beside it, the floats one mantissa bit below the
# configuration's (1,5,2) operands
CONTROL_READINGS = ("int4", "e5m1", "e4m1")


def readings(cell, mode: str, seed: int, seconds: float,
             require_tpu: bool = True) -> dict:
    drv = cell.driver()
    cell.limits = {k: float("inf") for k in ("loss_gap", "grad_gap",
                                            "update_gap", "token_gap",
                                            "mean_gap")}
    undo = []
    if mode == "control" and cell.mix["driver"] == "train":
        return train_control(cell, seed)
    if mode == "control":
        orig = drv.reference_gaps
        ctrl = {}

        def gaps(*a, **k):
            ref = orig(*a, lows=CONTROL_READINGS, **k)
            ctrl["token_gap"] = ref["low"]["int4"]["worst"]
            ctrl["mean_gap"] = ref["low"]["int4"]["mean"]
            for p, g in ref["low"].items():
                ctrl[f"{p}_token_gap"] = g["worst"]
                ctrl[f"{p}_mean_gap"] = g["mean"]
            return ref

        drv.reference_gaps = gaps
        undo.append(lambda: setattr(drv, "reference_gaps", orig))
    elif mode == "half_batch":
        from repro.train import loop

        real = loop.make_train_step

        def half(model, tc, *a, **k):
            step = real(model, tc, *a, **k)

            def broken(state, batch):
                b = batch["tokens"].shape[0]
                return step(state, {"tokens": batch["tokens"][:b // 2]})
            return broken

        loop.make_train_step = half
        undo.append(lambda: setattr(loop, "make_train_step", real))
    elif mode == "token":
        from repro.serve import scheduler

        real = scheduler.ModelExecutor.decode
        vocab = cell.config["vocab_size"]

        def altered(self, req):
            toks = real(self, req)
            toks[0] = (toks[0] + 1) % vocab
            return toks

        scheduler.ModelExecutor.decode = altered
        undo.append(lambda: setattr(scheduler.ModelExecutor, "decode", real))
    try:
        r = harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                             process_start=time.perf_counter(),
                             require_tpu=require_tpu, strict=False,
                             log=lambda m: print(m, file=sys.stderr,
                                                 flush=True))
    finally:
        for u in undo:
            u()
    out = {k: v["value"] for k, v in r["compared"].items()}
    if mode == "control" and "token_gap" in out:
        out = {"program_token_gap": out["token_gap"],
               "program_mean_gap": out["mean_gap"], **ctrl}
    out["metrics"] = {k: v["value"] for k, v in r["metrics"].items()}
    out["memory_peak_bytes"] = r["device"]["memory_peak_bytes"]
    return out


def train_control(cell, seed: int) -> dict:
    import jax

    drv = cell.driver()
    from traffic import jax_seed

    cfg, mix = cell.config, cell.mix
    b, s = mix["batch"], mix["seq"]
    wkey = jax.random.PRNGKey(jax_seed(seed, 0))
    feed = drv.make_feed(mix, b, s, cfg["vocab_size"], seed)
    ref = drv.reference_steps(cfg, b, wkey, feed)
    low = drv.reference_steps(cfg, b, wkey, feed, prec="int4")
    return drv.gaps(low, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=("program", "control", "half_batch", "token"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    harness.check_devices(cell.chips)
    for seed in [int(x) for x in args.seeds.split(",")]:
        t = time.perf_counter()
        rec = {"workload": args.workload, "mode": args.mode, "seed": seed,
               **readings(cell, args.mode, seed, args.seconds),
               "run_s": time.perf_counter() - t}
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
