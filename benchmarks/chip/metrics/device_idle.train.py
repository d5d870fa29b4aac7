"""Device idle share of the traced training steps: 1 - (union of the
device's op intervals / traced window), in %."""

from trace_reduce import busy_ns


def read(ctx):
    flat = ctx["trace"]
    devs = flat["devices"]
    if not devs or not flat["window"]:
        return None
    lo, hi = flat["window"]
    busy = sum(busy_ns(ev, flat["window"]) for ev in devs.values()) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
