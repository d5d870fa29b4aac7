"""The benchmark's data-driven core.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``: the configuration as it is run (the ``file``
  of the ``configs`` entry);
* ``traffic/<traffic>.json``: the mix; its ``driver`` names the driver in
  ``drivers/<driver>.py`` that runs the program's entry point;
* ``limits/<workload>.json``: the limits the cell's correctness numbers are
  held to;
* ``metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(ctx) -> float | None``.

A later PR adds a cell, a configuration or a metric by adding such files
and entries, never by editing one that is here.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))  # the checkout


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class WindowCompiled(RuntimeError):
    """A program was compiled, or loaded from the cache, inside the
    measured window: the window's numbers include that time."""


class CompileWatch:
    """Records each program XLA compiles or loads from the persistent
    cache (JAX's backend-compile event) while ``on``: on for the measured
    window, its ``seen`` is reported as ``window_compiles``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on = False
        self.seen: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, secs, **kw):
        if self.on and event == self.EVENT:
            self.seen.append((str(kw.get("fun_name")), float(secs)))

    def close(self) -> list[tuple[str, float]]:
        import jax

        self.on = False
        jax.monitoring.unregister_event_duration_listener(self._hear)
        return self.seen


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        byname = {w["name"]: w for w in bench["workloads"]}
        if workload not in byname:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.workload = byname[workload]
        self.name = workload
        conf = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config_name = conf["name"]
        self.config = load_json(os.path.join(root, conf["file"]))
        data = os.path.join(root, os.path.relpath(HERE, ROOT))
        self.data = data
        self.traffic_name = self.workload["traffic"]
        self.mix = load_json(os.path.join(data, "traffic",
                                          self.traffic_name + ".json"))
        self.chips = int(self.workload["chips"])
        limits = os.path.join(data, "limits", workload + ".json")
        self.limits = load_json(limits) if os.path.exists(limits) else {}

        def mine(metric):
            return workload in metric.get("workloads", [workload])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        names = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if mine(m) and m["moves"] in names]

    def reader(self, metric: str):
        path = os.path.join(self.data, "metrics", metric + ".py")
        return load_module(path, "metric_" + metric.replace(".", "_")
                           .replace("-", "_"))

    def driver(self):
        if getattr(self, "_driver", None) is None:
            self._driver = load_module(
                os.path.join(HERE, "drivers", self.mix["driver"] + ".py"),
                "driver_" + self.mix["driver"])
        return self._driver


def check_devices(chips: int) -> dict:
    """The platform, kind and count of the chips, or ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (first device: {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             process_start: float, require_tpu: bool = True,
             strict: bool = True, log=print) -> dict:
    """Run ``cell`` once; returns the result object of the last line.
    ``strict``: a program compiled inside the window is an error (off only
    for readings that are no benchmark run, such as ``calibrate.py``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if require_tpu:
        device = check_devices(cell.chips)
        # every program, the eager ops' small ones too, goes into the
        # cache, so that only a cell's first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": cell.chips}
    sys.path.insert(0, os.path.join(cell.root, "src"))
    from repro.runtime import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    table = os.path.join(cell.root, ".autotune", "table.json")
    log(f"autotune table {table} "
        f"{'exists' if os.path.exists(table) else 'does not exist'}; the "
        "tuner is never run, so blocks are the program's defaults unless "
        "it exists")
    from work import peaks

    peak = peaks(device["kind"]) if require_tpu else None
    trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = cell.driver().run(cell, seed=seed, seconds=seconds,
                            trace_dir=trace_dir if trace else None,
                            process_start=process_start, log=log)
    device["memory_peak_bytes"] = out.pop("memory_peak_bytes")
    compiled = out.pop("window_compiles")
    log(f"programs compiled or loaded inside the window: {len(compiled)}")
    if compiled and strict:
        raise WindowCompiled(f"{len(compiled)} programs compiled or loaded "
                             f"inside the measured window: {compiled}")
    ok, rows = judge(out["checks"], cell.limits)
    result = {"correct": bool(out["complete"] and ok),
              "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if trace:
        from trace_reduce import summary

        flat = out["trace"]
        summ = summary(flat)
        device["busy_s"] = summ["busy_s"]
        device["window_s"] = summ["window_s"]
        result["breakdown"] = summ["breakdown"]
        ctx = {"trace": flat, "counters": out["counters"],
               "config": cell.config, "mix": cell.mix, "peak": peak,
               "log": log}
        for m in cell.per_layer:
            try:
                value = cell.reader(m["name"]).read(ctx)
            except Exception:  # one reader's fault must not lose the run
                import traceback

                log(f"metric {m['name']}: reader failed, left out\n"
                    + traceback.format_exc())
                continue
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell.end_to_end:
            if m["name"] in out["metrics"]:
                result["metrics"][m["name"]] = {
                    "value": out["metrics"][m["name"]], "unit": m["unit"]}
    # last key: every number compared, beside its limit
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in rows}
    return result


def judge(compared: list, limits: dict) -> tuple[bool, list]:
    """Each compared number against its limit: [name, value, limit]."""
    rows, ok = [], True
    for name, value in compared:
        limit = limits.get(name)
        if limit is None:
            raise KeyError(f"no limit for {name!r} in the cell's limits file")
        good = value is not None and value == value and value <= limit
        ok = ok and good
        rows.append([name, value, limit])
    return ok, rows


def main(argv, *, process_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("benchmark: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    cell = Cell(args.workload)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), process_start=process_start,
                          log=lambda m: print(m, file=sys.stderr, flush=True))
    except NoChip as e:
        print(f"benchmark: {e}; nothing was run", file=sys.stderr)
        return 3
    except WindowCompiled as e:
        print(f"benchmark: {e}; no result", file=sys.stderr)
        return 4
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
