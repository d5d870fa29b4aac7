"""Mean host milliseconds around ``ModelExecutor.decode``; the call
returns host ints, so it waits for the device."""


def read(ctx):
    calls = ctx["counters"].get("decodes") or []
    if not calls:
        return None
    return sum(ms for ms, _, _ in calls) / len(calls)
