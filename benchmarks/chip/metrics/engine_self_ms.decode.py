"""Engine host milliseconds per tick, from the program's own spans: the
mean over ``serve.step`` spans of each one's duration less what its
``executor.*`` spans cover."""

import program_trace as PT


def read(ctx):
    prog = PT.program(ctx)
    own = PT.self_ns(prog["spans"], "serve.step", "executor.") if prog else []
    if not own:
        return None
    ctx["log"](f"engine_self_ms.decode: {len(own)} ticks")
    return sum(own) / len(own) / 1e6
