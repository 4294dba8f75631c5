"""Host spans and counters at the program's layer boundaries, in memory.

A span is one timed stretch of host work (``plan.call``, ``serve.admit``,
``serve.execute``, ...): its name, ``time.perf_counter()`` start and end,
the id of the span open around it on the same thread (``parent``), and a
few attributes (a serving request's ``rid``, a collection's ``gen``).
Spans go into a bounded ring, oldest out first, and each one also opens
``jax.profiler.TraceAnnotation(name)``, so in a profiler trace they sit
on the same host clock as the device's ops.  Counters (``count``) and
gauges (``gauge``) are numbers by name.  Everything records at all times;
nothing exports the ring: read it with ``spans()`` and ``counters()``.

Every Python garbage collection is recorded as a ``host.gc`` span with its
generation, parented to the span it interrupted.

The ring holds plain tuples of numbers and strings (attributes as
``(key, value)`` pairs), which the collector stops tracking, so a full
ring adds nothing to a collection's work.
"""

from __future__ import annotations

import atexit
import collections
import gc
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: spans kept; a traced 10 s window of small forwards holds ~10k
RING = 1 << 17

#: ``(name, t0, t1, parent, attr pairs or None, id)``, oldest first
_ring: collections.deque = collections.deque(maxlen=RING)
_counters: Dict[str, float] = {}
_ids = itertools.count()


class _Local(threading.local):
    def __init__(self):
        self.stack: List[int] = []      # ids of open spans, innermost last


_local = _Local()


class Span(NamedTuple):
    """One recorded span, as ``spans()`` returns it."""

    name: str
    t0: float
    t1: float
    parent: Optional[int]
    attrs: Dict
    id: int

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class span:
    """``with span(name, **attrs) as sp:`` records the block as a span;
    ``sp.t0`` / ``sp.t1`` / ``sp.id`` hold its readings once set."""

    __slots__ = ("name", "attrs", "id", "parent", "t0", "t1", "_note")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = tuple(attrs.items()) if attrs else None

    def __enter__(self) -> "span":
        stack = _local.stack
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._note.__exit__(None, None, None)
        _local.stack.pop()
        _ring.append((self.name, self.t0, self.t1, self.parent, self.attrs,
                      self.id))


def record(name: str, t0: float, t1: float, **attrs) -> None:
    """Record a span whose ends were read elsewhere (a request's time in
    the queue); it has no parent and no profiler annotation."""
    _ring.append((name, t0, t1, None, tuple(attrs.items()) or None,
                  next(_ids)))


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name``."""
    _counters[name] = _counters.get(name, 0) + n


def gauge(name: str, value: float) -> None:
    """Set ``name`` to ``value``."""
    _counters[name] = value


def spans(name: Optional[str] = None,
          since: Optional[float] = None) -> List[Span]:
    """Recorded spans, oldest first: those called ``name`` (all if None)
    that started at ``since`` or later."""
    return [Span(n, t0, t1, parent, dict(attrs or ()), i)
            for n, t0, t1, parent, attrs, i in list(_ring)
            if (name is None or n == name)
            and (since is None or t0 >= since)]


def counters() -> Dict[str, float]:
    return dict(_counters)


def reset() -> None:
    """Drop every recorded span and counter."""
    _ring.clear()
    _counters.clear()


_gc_open: List = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        note = TraceAnnotation("host.gc")
        note.__enter__()
        _gc_open.append((time.perf_counter(), note))
    elif _gc_open:
        t0, note = _gc_open.pop()
        t1 = time.perf_counter()
        note.__exit__(None, None, None)
        stack = _local.stack
        _ring.append(("host.gc", t0, t1, stack[-1] if stack else None,
                      (("gen", info.get("generation")),), next(_ids)))


gc.callbacks.append(_on_gc)
atexit.register(gc.callbacks.remove, _on_gc)
