"""Executor host milliseconds per decode call, from the program's own
spans: the mean over ``executor.decode`` spans of each one's duration
less its ``executor.decode.wait`` (padding, host-to-device copies and
dispatch)."""

import program_trace as PT


def read(ctx):
    prog = PT.program(ctx)
    if not prog:
        return None
    own = PT.self_ns(prog["spans"], "executor.decode", "executor.decode.wait")
    if not own:
        return None
    rows = [st.get("rows", 0) for _, _, _, st in
            PT.named(prog["spans"], "executor.decode")]
    counted = [r for _, r, _ in (ctx.get("counters") or {}).get("decodes",
                                                                 [])]
    ctx["log"](f"executor_host_ms.decode: {len(own)} calls, mean rows stat "
               f"{sum(rows) / len(rows)}, decode_batch_rows "
               f"{sum(counted) / len(counted) if counted else None}")
    return sum(own) / len(own) / 1e6
