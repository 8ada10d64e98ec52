"""Host spans of the serving loop.

A span is a ``jax.profiler.TraceAnnotation``, so a profiler trace shows
it on the host timeline with its attributes, and is also kept with its
wall-clock start and end (``time.time_ns``, the profiler's host clock)
in ``RECENT``, the process's last ``KEEP`` spans. ``RECENT`` is
process-wide, as the profiler's trace is, so that a reader holding no
handle on the engine, only the device events of a trace, joins them
with it: which device work each host step dispatched, and how long the
device sat idle inside a call. With no trace running a span costs a
few microseconds.
"""

from __future__ import annotations

import collections
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

# A few dozen decode calls of 192 tokens; older spans are dropped.
KEEP = 8192


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    attrs: dict


RECENT: collections.deque[Span] = collections.deque(maxlen=KEEP)


class span:
    """``with span(name, **attrs):`` times the block as a host span."""

    __slots__ = ("name", "attrs", "_annotation", "_start")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self._annotation = TraceAnnotation(name, **attrs)

    def __enter__(self):
        self._start = time.time_ns()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        RECENT.append(Span(self.name, self._start, time.time_ns(),
                           self.attrs))
