"""Mean live rows per ``ModelExecutor.decode`` call (a count)."""


def read(ctx):
    calls = ctx["counters"].get("decodes") or []
    if not calls:
        return None
    return sum(rows for _, rows, _ in calls) / len(calls)
