"""Spans and counters of the program: its one tracing system.

``span(name)`` marks a layer boundary.  It always opens a
``jax.profiler.TraceAnnotation("repro." + name)``, which costs well under a
microsecond when no profiler is collecting, and otherwise puts the span in
the profiler's trace beside the device's events.  Inside a ``recording()``
block it also keeps a :class:`Span` on ``time.perf_counter_ns`` in the
recording's ``spans`` list; nested spans share the ``call_id`` of the
outermost span open on their thread.  ``count(name, n)`` adds to one of
the process's counters and ``counters()`` returns a copy of them.  Nothing
is written to disk: whoever turned recording on collects the spans.

    with obs.recording() as rec:
        execute(...)
    rec.spans     # [Span("execute.resolve_inputs", "execute", 1, ...), ...]
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

PREFIX = "repro."


class Span(NamedTuple):
    name: str
    parent: str | None     # the enclosing recorded span's name
    call_id: int           # shared by a top-level span and all inside it
    t0_ns: int             # time.perf_counter_ns
    t1_ns: int


_sink: list | None = None          # the innermost open recording's spans
_ids = itertools.count(1)
_local = threading.local()         # .open: this thread's open spans
_counters: dict[str, int] = {}
_count_lock = threading.Lock()


def span(name: str):
    """A context manager around one layer's work (see the module doc)."""
    if _sink is None:
        return TraceAnnotation(PREFIX + name)
    return _Recorded(name, _sink)


class _Recorded:
    __slots__ = ("name", "sink", "ann", "parent", "call_id", "t0")

    def __init__(self, name: str, sink: list):
        self.name = name
        self.sink = sink
        self.ann = TraceAnnotation(PREFIX + name)

    def __enter__(self):
        stack = _local.__dict__.setdefault("open", [])
        if stack:
            self.parent, self.call_id = stack[-1].name, stack[-1].call_id
        else:
            self.parent, self.call_id = None, next(_ids)
        stack.append(self)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _local.open.pop()
        self.sink.append(Span(self.name, self.parent, self.call_id,
                              self.t0, t1))
        return False


class recording:
    """Keep every span that opens inside the block in ``self.spans``."""

    def __init__(self):
        self.spans: list[Span] = []

    def __enter__(self) -> "recording":
        global _sink
        self._prev, _sink = _sink, self.spans
        return self

    def __exit__(self, *exc):
        global _sink
        _sink = self._prev
        return False


def count(name: str, n: int = 1) -> None:
    with _count_lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> dict[str, int]:
    with _count_lock:
        return dict(_counters)
