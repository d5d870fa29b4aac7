"""Mean device milliseconds of one prefill slab, final or not: the
duration of each ``jit_serve_prefill`` and ``jit_serve_prefill_final``
event on the first device's ``XLA Modules`` line; None when no slab ran
in the traced window."""

import program_trace as PT


def read(ctx):
    prog = PT.program(ctx)
    ns = PT.modules(prog, PT.PREFILL_MODULE) if prog else []
    if not ns:
        return None
    final = len(PT.modules(prog, r"^jit_serve_prefill_final\b"))
    ctx["log"](f"prefill_device_ms: {len(ns)} slabs, {final} final")
    return sum(ns) / len(ns) / 1e6
