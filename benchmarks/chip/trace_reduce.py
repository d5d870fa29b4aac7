"""From a profiler trace to the numbers the metric readers take.

A trace is first flattened into plain data (``flatten``): the device's op
events and the host's annotations, each ``[name, start_ns, end_ns]``, plus
the bounds of the traced window.  The recorded trace in ``testdata/`` is
kept in that form.  Everything after that is arithmetic on intervals:

* device busy time is the union of the op intervals, never their sum;
* kernel time is grouped by the op names the trace carries;
* each idle gap is labelled by the innermost host annotation that encloses
  its midpoint.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict

# the line of a device plane that holds one event per executed op
OPS_LINE = "XLA Ops"
# the host annotations the benchmark writes all start with this prefix
ANNOTATION_PREFIX = "bench."


def _xplane_path(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def flatten(trace_dir: str, window: tuple[int, int] | None = None) -> dict:
    """Device op events of every TPU plane and the benchmark's host
    annotations, as plain lists.  ``window`` (start, end ns on the trace's
    clock) defaults to the span of the annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_xplane_path(trace_dir))
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, int(e.start_ns), int(e.end_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        host.append([e.name, int(e.start_ns), int(e.end_ns)])
    host.sort(key=lambda e: e[1])
    if window is None and host:
        window = (min(e[1] for e in host), max(e[2] for e in host))
    return {"devices": devices, "host": host,
            "window": list(window) if window else None}


def save(flat: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(flat, f)


def load(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def clip(events, window) -> list:
    """Events cut to the window; those wholly outside it are dropped."""
    lo, hi = window
    out = []
    for name, s, e in events:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            out.append([name, s2, e2])
    return out


def union(events) -> list[tuple[int, int]]:
    """Merged busy intervals of possibly overlapping events."""
    merged: list[list[int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events, window) -> int:
    return sum(e - s for s, e in union(clip(events, window)))


_SUFFIX = re.compile(r"(\.\d+)+$")
# ops whose event spans the ops of their body: counted once, by the body
CONTAINERS = frozenset({"while", "conditional", "call"})


def base_name(name: str) -> str:
    """An op's name without its HLO text and XLA's numeric suffixes: a TPU
    trace names each op by its instruction (``%fusion.12 = f32[...] ...``)
    -> ``fusion``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def by_name(events, window) -> dict[str, list]:
    """{base op name: [total ns, calls]} over the window, largest first;
    container ops (a scan's ``while``) are left out, their body's ops are
    not."""
    acc: dict[str, list] = defaultdict(lambda: [0, 0])
    for name, s, e in clip(events, window):
        base = base_name(name)
        if base in CONTAINERS:
            continue
        a = acc[base]
        a[0] += e - s
        a[1] += 1
    return dict(sorted(acc.items(), key=lambda kv: -kv[1][0]))


def matching(events, window, pattern: str) -> tuple[int, int]:
    """(total ns, calls) of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    total = calls = 0
    for name, s, e in clip(events, window):
        if rx.search(name):
            total += e - s
            calls += 1
    return total, calls


def gaps(events, window) -> list[tuple[int, int]]:
    """Idle intervals of the device inside the window."""
    lo, hi = window
    out, t = [], lo
    for s, e in union(clip(events, window)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(host, t: int) -> str:
    """Innermost (latest-starting) host annotation that encloses ``t``."""
    best = None
    for name, s, e in host:
        if s <= t <= e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "host: outside any annotation"


def labelled_gaps(events, host, window) -> list[tuple[str, int]]:
    """Every idle gap as (what the host was doing, ns), longest first."""
    out = [(label_at(host, (s + e) // 2), e - s)
           for s, e in gaps(events, window)]
    return sorted(out, key=lambda x: -x[1])


def summary(flat: dict, top: int = 10) -> dict:
    """busy and window seconds averaged over the chips, and the breakdown
    the result line carries: the longest device ops and idle gaps."""
    window = flat["window"]
    devs = flat["devices"]
    if not devs or window is None:
        return {"busy_s": 0.0, "window_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    busy = [busy_ns(ev, window) for ev in devs.values()]
    first = devs[sorted(devs)[0]]
    ops = by_name(first, window)
    idle = labelled_gaps(first, flat["host"], window)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "breakdown": {
            "device_ops": [[k, v[0] / 1e9] for k, v in
                           list(ops.items())[:top]],
            "idle_gaps": [[k, v / 1e9] for k, v in idle[:top]],
        },
    }
