"""Device idle share of the traced serving window, the mean over the
cell's chips that ``device_idle.decode`` reads, in %; logs each chip's."""

import os

import harness
from trace_reduce import busy_ns

_MEAN = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "device_idle.decode.py"), "metric_device_idle_tp4").read


def read(ctx):
    flat = ctx["trace"]
    if flat["devices"] and flat["window"]:
        lo, hi = flat["window"]
        ctx["log"]("device_idle.tp4 by chip (%): " + str({
            d: 100.0 * (1.0 - busy_ns(ev, flat["window"]) / (hi - lo))
            for d, ev in sorted(flat["devices"].items())}))
    return _MEAN(ctx)
