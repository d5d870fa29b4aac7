"""Share of the traced window in which the device idles while the host's
innermost program span is the engine's (``serve.*``), in %.  With
``idle_executor.decode`` and the logged ``idle_outside`` (no program span:
the caller's loop) it adds up to ``device_idle.decode``."""

import program_trace as PT


def read(ctx):
    split = PT.idle_split(ctx)
    if split is None:
        return None
    pct = {k: 100.0 * split[k] / split["window"]
           for k in ("engine", "executor", "outside")}
    ctx["log"](f"idle split of the window (%): {pct}, together "
               f"{sum(pct.values())}; "
               f"{len(PT.named(PT.program(ctx)['spans'], 'serve.step'))} "
               "ticks")
    return pct["engine"]
