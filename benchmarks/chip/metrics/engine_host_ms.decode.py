"""Host milliseconds per engine tick outside the executor's calls: time in
``ServeEngine.step`` minus time in ``ModelExecutor.prefill``/``decode``."""


def read(ctx):
    ticks = ctx["counters"].get("ticks") or []
    if not ticks:
        return None
    return sum(t - e for t, e in ticks) / len(ticks)
