"""Program spans: named, timed regions of the training hot path.

A span is a ``jax.profiler.TraceAnnotation`` named ``hps:<name>``. In a
profiler trace it shows on the host thread that ran it, on the trace's own
clock, nested in whatever span encloses it on that thread; its ``attrs`` (a
batch id, a job index, a count) ride along as the event's metadata.

Every span also lands, when it ends, in a bounded record of ``(name,
start_ns, dur_ns, thread, attrs)`` on the ``time.perf_counter_ns`` clock.
A reader that ran no profiler, or started one late, still sees each span
whole: ``recorded()`` returns them. Like the profiler's trace, the record
is one per process, shared by every thread that ends a span.

Spans mark one call of a stage or of a PS operation, never one row or one
file, so a batch makes a few dozen of them. With no profiler running a span
costs about a microsecond.
"""

from __future__ import annotations

import collections
import threading
import time

from jax.profiler import TraceAnnotation

PREFIX = "hps:"
RECORD_LIMIT = 1 << 16  # spans kept; the oldest go first

_record: collections.deque = collections.deque(maxlen=RECORD_LIMIT)


class span:
    """``with span("ps.pull", batch=3) as sp:`` — times the block as
    ``hps:ps.pull``; ``sp.set(rows=n)`` adds a count learned inside it."""

    __slots__ = ("name", "attrs", "_ann", "_t0")

    def __init__(self, name: str, **attrs):
        self.name = PREFIX + name
        self.attrs = attrs

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _record.append(
            (self.name, self._t0, t1 - self._t0, threading.current_thread().name, self.attrs)
        )


def recorded() -> list[tuple]:
    """The spans that ended so far, oldest first (at most ``RECORD_LIMIT``)."""
    return list(_record)


def clear() -> None:
    _record.clear()
