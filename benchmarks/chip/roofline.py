"""A kernel's share of its roofline in the trace: the least time its work
could take on this chip (operations over peak FLOP/s or bytes over peak
bandwidth, whichever is larger) over the device time of its ops."""

from __future__ import annotations

import work as W
from trace_reduce import matching


def share(ctx, pattern: str, work: W.Work, calls: int, what: str):
    """% of the roofline, or None when the trace holds no such op.  The
    work is that of the ``calls`` GEMMs or attention calls the traced
    steps make; the program may split one into several ops (the head's
    backward pair is split over N), so the op count is logged beside it,
    and the share must not pass 100%."""
    flat = ctx["trace"]
    devs = flat["devices"]
    if not devs or not flat["window"] or ctx["peak"] is None:
        return None
    ev = devs[sorted(devs)[0]]
    ns, found = matching(ev, flat["window"], pattern)
    if found == 0:
        return None
    if found < calls:
        raise ValueError(f"{what}: the trace holds {found} ops matching "
                         f"{pattern!r}, fewer than the {calls} calls the "
                         "work is for")
    t_min, bound = W.roofline_s(work, ctx["peak"])
    ctx["log"](f"{what}: {found} ops for {calls} calls, {ns / 1e6:.3f} ms "
               f"on the device, "
               f"{work.flops:.4g} FLOP, {work.bytes:.4g} B, "
               f"bound by {bound}")
    return 100.0 * t_min / (ns / 1e9)
