"""Request-lifecycle tracing: causally-linked spans with injectable time.

The serve scheduler emits one **root span per request** (``name="request"``,
``trace_id`` = the request id) whose children cover every scheduler state
the request passes through::

    request(rid)
    ├─ queued                 admission wait (submit -> admit)
    ├─ serve.prefill_slab ×N  one per chunked-prefill slab
    │  └─ executor.prefill    (⊃ executor.prefill.wait on the final slab)
    ├─ swapped ×M             preempt -> swap-out ... swap-in -> restored
    └─ [token events]         one per emitted token, on the root span

plus one engine-level tree per tick (no trace_id — a tick batches many
requests; ``serve.decode_step``'s ``rids`` attr links it to them)::

    serve.step
    ├─ serve.admit            restore-or-admit
    ├─ serve.decode_step      page table, executor call, token bookkeeping
    │  └─ executor.decode     padding, host-to-device copies, dispatch
    │     └─ executor.decode.wait   the host blocks on the device
    └─ serve.monitor          the VRR probe, on its cadence

Token events on the root span make every emitted token attributable to
exactly one request, which is what the sim fuzz suite pins and what
TTFT/TPOT are computed from (``request_latencies``).

Every scoped span goes through ``span``, which also writes it into the
profiler's trace as a ``jax.profiler.TraceAnnotation`` of the same name, so
a ``jax.profiler`` capture puts the engine's and executor's host work beside
the device's ops.  ``request``, ``queued`` and ``swapped`` live across many
ticks and stay tracer-only: profiler scopes must nest on one thread.

Timestamps come from an injected ``Clock`` (``repro.obs.clock``), so the
scheduler sim's virtual clock produces schedule-deterministic span trees;
span ids are a per-tracer counter, deterministic by construction.  Spans
land in a ``RingBuffer`` (bounded memory) and export as JSONL.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from repro.obs.clock import Clock, SystemClock
from repro.obs.sink import RingBuffer, jsonl_append

__all__ = ["Span", "Tracer", "span", "span_forest", "request_latencies",
           "percentile"]


@dataclass
class Span:
    span_id: int
    name: str
    t_start: float
    trace_id: int | str | None = None
    parent_id: int | None = None
    t_end: float | None = None
    attrs: dict = field(default_factory=dict)
    events: list = field(default_factory=list)

    @property
    def open(self) -> bool:
        return self.t_end is None

    @property
    def duration(self) -> float | None:
        return None if self.t_end is None else self.t_end - self.t_start

    def to_dict(self) -> dict:
        return {
            "span_id": self.span_id, "name": self.name,
            "trace_id": self.trace_id, "parent_id": self.parent_id,
            "t_start": self.t_start, "t_end": self.t_end,
            "attrs": dict(self.attrs), "events": list(self.events),
        }


class Tracer:
    """Span factory + store.  All mutation goes through the tracer (it owns
    the clock and the id counter); spans are plain data."""

    def __init__(self, clock: Clock | None = None,
                 capacity: int | None = None):
        self.clock = clock if clock is not None else SystemClock()
        self.spans: RingBuffer = RingBuffer(capacity)
        self._next_id = 1
        self._open: list[Span] = []  # the ``span`` scopes now entered

    # ------------------------------ record ---------------------------------
    def start(self, name: str, *, trace_id=None,
              parent: "Span | None" = None, **attrs) -> Span:
        s = Span(span_id=self._next_id, name=name, t_start=self.clock.now(),
                 trace_id=trace_id if trace_id is not None
                 else (parent.trace_id if parent is not None else None),
                 parent_id=parent.span_id if parent is not None else None,
                 attrs=attrs)
        self._next_id += 1
        self.spans.append(s)
        return s

    def end(self, span: Span, **attrs) -> Span:
        span.t_end = self.clock.now()
        if attrs:
            span.attrs.update(attrs)
        return span

    def event(self, span: Span, name: str, **attrs) -> dict:
        e = {"name": name, "t": self.clock.now(), **attrs}
        span.events.append(e)
        return e

    # ------------------------------ read-out -------------------------------
    def export_jsonl(self, path: str) -> int:
        """Append every stored span to ``path``; returns the span count."""
        rows = [s.to_dict() for s in self.spans]
        jsonl_append(path, rows)
        return len(rows)

    def to_dicts(self) -> list[dict]:
        return [s.to_dict() for s in self.spans]


class span:
    """One scope of host work, in the profiler's trace and in ``tracer``.

    Always enters ``jax.profiler.TraceAnnotation(name, **stats)``, about a
    microsecond when no profiler session records; ``stats`` (scalars)
    become the event's stats in the profile.  With a tracer it also
    records a ``Span`` of the same name whose attributes are ``stats`` and
    ``attrs`` and whose parent is ``parent``, else the innermost ``span``
    scope still open.  ``with`` yields that ``Span``, or None without a
    tracer."""

    __slots__ = ("tracer", "name", "parent", "trace_id", "attrs", "stats",
                 "_scope", "_span")

    def __init__(self, tracer: Tracer | None, name: str, *,
                 parent: Span | None = None, trace_id=None,
                 attrs: dict | None = None, **stats):
        self.tracer, self.name, self.parent = tracer, name, parent
        self.trace_id, self.attrs, self.stats = trace_id, attrs, stats

    def __enter__(self) -> Span | None:
        self._scope = TraceAnnotation(self.name, **self.stats)
        self._scope.__enter__()
        tr = self.tracer
        if tr is None:
            self._span = None
            return None
        parent = self.parent
        if parent is None and tr._open:
            parent = tr._open[-1]
        self._span = tr.start(self.name, parent=parent,
                              trace_id=self.trace_id, **self.stats,
                              **(self.attrs or {}))
        tr._open.append(self._span)
        return self._span

    def __exit__(self, *exc) -> None:
        # the span starts and ends just after the profiler's scope: the
        # profiler's end stamp of a long scope can take tens of µs to
        # take, and reading the tracer's clock after it keeps the two
        # durations within a few µs of each other
        self._scope.__exit__(*exc)
        if self._span is not None:
            self.tracer.end(self._span)
            self.tracer._open.pop()


def span_forest(spans) -> dict:
    """``{span_id: {"span": Span-dict, "children": [span_id, ...]}}`` over
    dicts or ``Span`` objects — the tree view tests and tools walk.  Raises
    on a dangling ``parent_id`` (an orphan span is an instrumentation bug,
    exactly what the fuzz suite wants loud)."""
    nodes = {}
    for s in spans:
        d = s.to_dict() if isinstance(s, Span) else dict(s)
        nodes[d["span_id"]] = {"span": d, "children": []}
    for sid, node in nodes.items():
        pid = node["span"]["parent_id"]
        if pid is None:
            continue
        if pid not in nodes:
            raise ValueError(f"span {sid} has dangling parent_id {pid}")
        nodes[pid]["children"].append(sid)
    return nodes


def request_latencies(spans) -> list[dict]:
    """Per-request latency attribution from span token events.

    For every closed root ``request`` span with >= 1 token event returns
    ``{"rid", "ttft", "tpot", "total", "tokens"}`` where TTFT is first
    token time - admission to the engine (span start) and TPOT the mean
    inter-TOKEN gap (None with a single token).  TPOT is derived from the
    per-token event timestamps, never from a decode-step count: one step
    may emit several tokens (a speculative round commits 1..k+1 at one
    timestamp — zero-gap runs in the event stream), and dividing the span
    by steps would overstate the per-token latency by the acceptance
    factor.  Events are time-sorted first so merged or re-ordered span
    streams cannot yield negative gaps.  Clock units pass through
    (seconds under SystemClock, ticks under the sim's VirtualClock).
    """
    out = []
    for s in spans:
        d = s.to_dict() if isinstance(s, Span) else dict(s)
        if d["name"] != "request" or d["t_end"] is None:
            continue
        toks = sorted(e["t"] for e in d["events"] if e["name"] == "token")
        if not toks:
            continue
        ttft = toks[0] - d["t_start"]
        gaps = [t1 - t0 for t0, t1 in zip(toks, toks[1:])]
        tpot = sum(gaps) / len(gaps) if gaps else None
        out.append({"rid": d["trace_id"], "ttft": ttft, "tpot": tpot,
                    "total": d["t_end"] - d["t_start"], "tokens": len(toks)})
    return out


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100]); None on empty input."""
    vals = sorted(v for v in values if v is not None)
    if not vals:
        return None
    k = max(0, min(len(vals) - 1, int(round(q / 100.0 * (len(vals) - 1)))))
    return vals[k]
