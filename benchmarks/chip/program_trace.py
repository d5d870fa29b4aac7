"""The program's own spans and the device's XLA modules, from the profile
of a traced run.

The serve path writes its host work into the profile as
``jax.profiler.TraceAnnotation`` scopes (``repro.obs.trace.span``): the
engine's ``serve.*`` and the executor's ``executor.*``, some with stats
(``rows``, ``tokens``, ``final``).  Its jitted programs carry their names
onto the device's ``XLA Modules`` line (``jit_serve_decode``,
``jit_serve_prefill``, ``jit_serve_prefill_final``).  ``trace_reduce.
flatten`` keeps neither, so the readers of those metrics find the traced
run's profile again here:

* ``locate`` lists the ``*.xplane.pb`` files under
  ``<checkout>/.bench_trace/``, which the harness removes only after the
  readers have run;
* ``read`` turns one into plain data on the profile's clock: the
  ``bench.*`` annotations, the program's spans ``[name, start, end,
  stats]`` and each TPU plane's module events ``[name, start, end]``;
* ``program(ctx)`` keeps the profile whose ``bench.*`` annotations are
  those of ``ctx["trace"]["host"]``, cut to the traced window, in
  ``ctx["program"]``, where a recorded fixture can stand in for it.

A profile of a program without these spans or names (an older checkout)
gives empty lists, and the readers then return None.
"""

from __future__ import annotations

import glob
import os
import re

from trace_reduce import ANNOTATION_PREFIX, clip, gaps, union

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))  # the checkout

SPAN_PREFIXES = ("serve.", "executor.")
MODULES_LINE = "XLA Modules"
DECODE_MODULE = r"^jit_serve_decode\b"
PREFILL_MODULE = r"^jit_serve_prefill(?:_final)?\b"
# the layer an idle instant is put down to, by its innermost span's prefix
LAYERS = (("serve.", "engine"), ("executor.", "executor"))


def locate(root: str | None = None) -> list[str]:
    """The profiles of traced runs in the checkout, newest first."""
    paths = glob.glob(os.path.join(root or ROOT, ".bench_trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return sorted(paths, key=os.path.getmtime, reverse=True)


def read(path: str) -> dict:
    """The benchmark's annotations, the program's spans and the device's
    module events of one profile, as plain lists."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, spans, modules = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        [e.name, int(e.start_ns), int(e.end_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        host.append([e.name, int(e.start_ns), int(e.end_ns)])
                    elif e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.end_ns), dict(e.stats)])
    host.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: (e[1], -e[2]))
    return {"host": host, "spans": spans, "modules": modules}


def cut(prog: dict, window) -> dict:
    """Spans and module events cut to the window; those wholly outside it
    are dropped."""
    lo, hi = window
    spans = [[n, max(s, lo), min(e, hi), st] for n, s, e, st in prog["spans"]
             if min(e, hi) > max(s, lo)]
    return {"spans": spans,
            "modules": {p: clip(ev, window)
                        for p, ev in prog["modules"].items()}}


def program(ctx) -> dict | None:
    """The program's spans and module events of the traced window, or None
    where no profile in the checkout is the traced run's.  Kept in
    ``ctx["program"]``, which every reader of the run is handed."""
    if "program" not in ctx:
        ctx["program"] = None
        flat = ctx.get("trace") or {}
        for path in locate() if flat.get("window") else ():
            prog = read(path)
            if prog["host"] == flat["host"]:
                ctx["program"] = cut(prog, flat["window"])
                break
    return ctx["program"]


def named(spans, name: str) -> list:
    return [sp for sp in spans if sp[0] == name]


def self_ns(spans, name: str, inner: str) -> list[int]:
    """For each span called ``name``: its duration less the part of it
    that spans whose names start with ``inner`` cover."""
    kids = [sp for sp in spans if sp[0].startswith(inner) and sp[0] != name]
    out = []
    for _, s, e, _ in named(spans, name):
        inside = [[n, max(s, a), min(e, b)] for n, a, b, _ in kids
                  if min(e, b) > max(s, a)]
        out.append(e - s - sum(b - a for a, b in union(inside)))
    return out


def modules(prog: dict, pattern: str) -> list[int]:
    """Durations of the first device's module events matching
    ``pattern``."""
    if not prog["modules"]:
        return []
    rx = re.compile(pattern)
    first = prog["modules"][sorted(prog["modules"])[0]]
    return [e - s for n, s, e in first if rx.search(n)]


def innermost(spans) -> list[tuple[int, int, str]]:
    """The host timeline cut at every span's start and end: each piece
    with the innermost span over it (the latest to start, then the first
    to end); pieces no span covers are left out."""
    cuts = sorted({t for _, s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(s, -e, n) for n, s, e, _ in spans if s <= a and e >= b]
        if over:
            out.append((a, b, max(over)[2]))
    return out


def idle_split(ctx) -> dict | None:
    """Device-idle ns of the traced window on the first device, put down
    to the layer of the innermost program span at each instant:
    ``engine`` (``serve.*``), ``executor`` (``executor.*``) and
    ``outside`` (no program span: the caller's loop); with ``window`` ns.
    None where the trace has no device or the program no spans."""
    flat = ctx["trace"]
    prog = program(ctx)
    if not flat["devices"] or not flat["window"] or not prog \
            or not prog["spans"]:
        return None
    window = flat["window"]
    first = flat["devices"][sorted(flat["devices"])[0]]
    pieces = innermost(prog["spans"])
    split = {"engine": 0, "executor": 0, "outside": 0,
             "window": window[1] - window[0]}
    j = 0
    for gs, ge in gaps(first, window):
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        covered = 0
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            a, b, name = pieces[k]
            ns = min(b, ge) - max(a, gs)
            if ns > 0:
                layer = next(lay for p, lay in LAYERS if name.startswith(p))
                split[layer] += ns
                covered += ns
            k += 1
        split["outside"] += ge - gs - covered
    return split
