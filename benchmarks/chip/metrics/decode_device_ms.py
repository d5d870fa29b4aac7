"""Mean device milliseconds of one decode call: the duration of each
``jit_serve_decode`` event on the first device's ``XLA Modules`` line."""

import program_trace as PT


def read(ctx):
    prog = PT.program(ctx)
    ns = PT.modules(prog, PT.DECODE_MODULE) if prog else []
    if not ns:
        return None
    ctx["log"](f"decode_device_ms: {len(ns)} calls")
    return sum(ns) / len(ns) / 1e6
