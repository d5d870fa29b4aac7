"""Collective device milliseconds per decode call on chip 0: the
durations of the collective ops (``all-gather*``, ``all-reduce*``,
``collective-permute*``, ``reduce-scatter*``, ``all-to-all*``) inside
each ``jit_serve_decode`` module event, over the calls.  Logs the ops a
call holds beside what the decode step's jaxpr holds (per layer 3 of the
carry merge, 3 gathers and 2 page-scale pmaxes, then the head's gather)
and what XLA makes of them (6 a layer: the two pmaxes combined, the o
and l psums combined; on a TPU v5e 2x2), the bytes the executor's
``executor.decode`` spans carry (``collective_bytes``) and their rate."""

import re

import program_trace as PT
import tpwork as TP
from trace_reduce import clip


def read(ctx):
    prog, flat = PT.program(ctx), ctx["trace"]
    if not prog or not flat["devices"]:
        return None
    dev = sorted(flat["devices"])[0]
    rx = re.compile(PT.DECODE_MODULE)
    calls = [m for m in prog["modules"].get(dev, []) if rx.search(m[0])]
    if not calls:
        return None
    ev = flat["devices"][dev]
    ns = ops = 0
    for _, s, e in calls:
        for name, a, b in clip(ev, (s, e)):
            if TP.is_collective(name):
                ns += b - a
                ops += 1
    if ns == 0:
        return None
    per_call = ns / len(calls)
    stats = [st.get("collective_bytes") for _, _, _, st in
             PT.named(prog["spans"], "executor.decode")]
    stats = [b for b in stats if b]
    nbytes = sum(stats) / len(stats) if stats else None
    layers = ctx["config"]["num_hidden_layers"]
    ctx["log"](
        f"collective_ms.tp4: {len(calls)} decode calls, {ops / len(calls)} "
        f"collective ops a call (jaxpr {8 * layers + 1}, XLA-combined "
        f"{6 * layers + 1}), "
        f"{per_call / 1e6:.4f} ms a call; executor.decode collective_bytes "
        f"{nbytes} a call"
        + (f", {nbytes / per_call:.4f} GB/s" if nbytes else ""))
    return per_call / 1e6
