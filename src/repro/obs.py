"""Spans and counters of served batches, on the profiler's clock.

Every batch the serving stack runs gets one :class:`BatchRecord`: the
spans of the host work done for it and the counters of the work it asked
of the device. Records live in a bounded ring (the last :data:`RING`
batches), so :func:`batches` is a flight recorder of what the server just
did. Recording is always on.

* :func:`span` times a block of host code. It reads
  ``time.perf_counter_ns()`` at entry and exit, enters a
  ``jax.profiler.TraceAnnotation`` of the same name (so a profiler trace
  shows the span on the host plane, beside the device ops), and appends
  ``(name, batch, parent, t0_ns, t1_ns)`` to the current batch's record.
  ``parent`` is the enclosing span's name on this thread.
* :func:`batch` makes a record current on this thread: the one given,
  else the one already current, else a new one.
* :func:`interval` records a wait that crosses threads (a request's time
  in the queue, a batch's hand-off between pipeline stages). It has the
  same tuple but no annotation, since an annotation cannot span threads.
* :func:`count` sets a counter on the current batch's record.

Outside a batch, spans are still annotated but nothing is recorded. The
one clock is :func:`now_ns`; every stage timer of the stack reads it.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from contextlib import contextmanager
from typing import NamedTuple

from jax.profiler import TraceAnnotation

RING = 4096          # batch records kept: a 30 s window at ~130 batches/s

now_ns = time.perf_counter_ns


class Span(NamedTuple):
    name: str
    batch: int
    parent: str | None
    t0_ns: int
    t1_ns: int

    @property
    def ns(self) -> int:
        return self.t1_ns - self.t0_ns


class BatchRecord:
    """One batch's spans (in the order they ended) and counters."""

    __slots__ = ("id", "spans", "counters")

    def __init__(self, batch_id: int):
        self.id = batch_id
        self.spans: list[Span] = []
        self.counters: dict[str, int] = {}

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def total_ns(self, name: str) -> int:
        """Summed duration of every span called ``name``."""
        return sum(s.ns for s in self.named(name))

    def self_ns(self, name: str) -> int:
        """``total_ns(name)`` less the time of the spans directly inside
        those spans."""
        own = self.named(name)
        inner = sum(c.ns for c in self.spans if c.parent == name
                    and any(o.t0_ns <= c.t0_ns and c.t1_ns <= o.t1_ns
                            for o in own))
        return sum(s.ns for s in own) - inner

    def __repr__(self) -> str:
        return (f"BatchRecord(id={self.id}, spans={len(self.spans)}, "
                f"counters={self.counters})")


class _Thread(threading.local):
    def __init__(self):
        self.record: BatchRecord | None = None
        self.stack: list[str] = []


_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count()
_local = _Thread()


def new_batch() -> BatchRecord:
    """Open a new batch record at the end of the ring."""
    rec = BatchRecord(next(_ids))
    _ring.append(rec)
    return rec


def batches() -> list[BatchRecord]:
    """The ring's batch records, oldest first."""
    return list(_ring)


def current() -> BatchRecord | None:
    return _local.record


@contextmanager
def batch(record: BatchRecord | None = None):
    """Make ``record`` (else the current one, else a new one) this
    thread's current batch for the block; yields it."""
    prev = _local.record
    _local.record = record or prev or new_batch()
    try:
        yield _local.record
    finally:
        _local.record = prev


class span:
    """``with span(name) as s:`` times the block; ``s.seconds`` after."""

    __slots__ = ("name", "t0_ns", "t1_ns", "_ann", "_record", "_parent")

    def __init__(self, name: str):
        self.name = name
        self.t0_ns = self.t1_ns = 0

    def __enter__(self) -> "span":
        stack = _local.stack
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._record = _local.record
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0_ns = now_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1_ns = now_ns()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        rec = self._record
        if rec is not None:
            rec.spans.append(Span(self.name, rec.id, self._parent,
                                  self.t0_ns, self.t1_ns))

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


def interval(record: BatchRecord | None, name: str, t0_ns: int,
             t1_ns: int) -> None:
    """Record a cross-thread wait ``[t0_ns, t1_ns)`` on ``record``."""
    if record is not None:
        record.spans.append(Span(name, record.id, None, t0_ns, t1_ns))


def count(name: str, value: int) -> None:
    """Set counter ``name`` of the current batch (none: nothing kept)."""
    rec = _local.record
    if rec is not None:
        rec.counters[name] = value


__all__ = ["RING", "BatchRecord", "Span", "batch", "batches", "count",
           "current", "interval", "new_batch", "now_ns", "span"]
