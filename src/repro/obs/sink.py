"""Shared event sinks: the one JSONL appender + a bounded ring buffer.

``jsonl_append`` is the single implementation of the
make-the-directory-then-append-one-object-per-line logic that used to be
copy-pasted between ``serve/scheduler.py`` (monitor log) and
``telemetry/controller.py`` (controller event log).  ``RingBuffer`` bounds
in-memory event growth (``ServeEngine.events`` used to grow without limit
for the life of the engine).
"""

from __future__ import annotations

import json
import os
from collections import deque
from typing import Iterable, Iterator

__all__ = ["jsonl_append", "RingBuffer"]


def jsonl_append(path: str, records: Iterable[dict]) -> None:
    """Append ``records`` to ``path`` as JSON Lines, creating the parent
    directory if needed.  One ``open`` per call (batched callers pay one
    syscall set per flush, not per record)."""
    records = list(records)
    if not records:
        return
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


class RingBuffer:
    """Bounded append-only event store with list-like reads.

    Drop-in for the ``list`` previously backing ``ServeEngine.events``:
    supports ``append``, iteration, ``len``, indexing and ``list(...)``.
    ``capacity=None`` means unbounded (the old behavior); otherwise the
    oldest events are evicted and ``dropped`` counts them.
    """

    def __init__(self, capacity: int | None = None):
        if capacity is not None and capacity <= 0:
            raise ValueError(f"RingBuffer capacity must be positive, got {capacity}")
        self.capacity = capacity
        self._q: deque = deque(maxlen=capacity)
        self.dropped = 0

    def append(self, item) -> None:
        if self.capacity is not None and len(self._q) == self.capacity:
            self.dropped += 1
        self._q.append(item)

    def extend(self, items: Iterable) -> None:
        for it in items:
            self.append(it)

    def clear(self) -> None:
        self._q.clear()
        self.dropped = 0

    def __iter__(self) -> Iterator:
        return iter(self._q)

    def __len__(self) -> int:
        return len(self._q)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return list(self._q)[i]
        return self._q[i]

    def __bool__(self) -> bool:
        return bool(self._q)

    def __repr__(self) -> str:
        return (f"RingBuffer(capacity={self.capacity}, len={len(self._q)}, "
                f"dropped={self.dropped})")
