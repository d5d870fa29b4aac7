"""Share of the traced window in which the device idles while the host's
innermost program span is the executor's (``executor.*``), in %."""

import program_trace as PT


def read(ctx):
    split = PT.idle_split(ctx)
    if split is None:
        return None
    ctx["log"](f"idle_outside: {100.0 * split['outside'] / split['window']}"
               " % of the window")
    return 100.0 * split["executor"] / split["window"]
