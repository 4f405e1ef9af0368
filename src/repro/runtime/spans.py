"""Program spans and counters: a flight recorder on the profiler's clock.

One small facility for the host code of the program (``laplace_gpc``,
``SolveService``) to say where its time goes:

* :func:`span` is a context manager.  It enters a
  ``jax.profiler.TraceAnnotation`` of the same name, so while a profiler
  trace is recorded the span lands in the trace's host plane beside the
  device's ops, and it appends a :class:`Record` (name, parent,
  ``perf_counter_ns`` start and end, attrs) to a ring of the last
  :data:`CAPACITY` records in the process.  Attrs may be set on the
  yielded record until the span closes.
* :func:`fetch` and :func:`block` are the two ways host code waits for
  the device (``jax.device_get`` and ``jax.block_until_ready``): each
  opens a span of the caller's name around the wait and adds one to the
  ``syncs`` attr of every span open around it.
* :func:`count` adds to a counter attr of the innermost open span.  A
  ``jax.monitoring`` listener, registered on the first span, counts
  ``compiles`` and ``compile_s`` there whenever XLA compiles: the span
  that recompiled says so.  Code that runs while a function is traced
  counts the same way (the Gram kernel's ``gram_kv_vpu`` /
  ``gram_kv_mxu``: which unit each traced call site's ``K·v`` took).
* :func:`interval` appends a record whose start and end the caller
  measured, such as a served ticket's submit and redeem.

The ring is a flight recorder, not a log: :func:`recent` returns what it
holds, newest last, and old records fall out.  :func:`enable` turns the
annotation and the ring off for an operator who wants no cost; a span
then still times itself (two clock reads), for callers that read its
duration.  Spans belong to eager host code only, never to a traced
function: their clock would run at trace time.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Deque, Dict, List, Optional

import jax
import numpy as np

CAPACITY = 8192
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record:
    """One span or interval: ``end_ns`` is ``None`` while the span is open."""

    __slots__ = ("id", "name", "parent", "start_ns", "end_ns", "attrs")

    def __init__(self, id: int, name: str, parent: Optional[int],
                 attrs: Dict[str, Any]):
        self.id, self.name, self.parent, self.attrs = id, name, parent, attrs
        self.start_ns: int = 0
        self.end_ns: Optional[int] = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __repr__(self) -> str:
        return (f"Record({self.id}, {self.name!r}, parent={self.parent}, "
                f"start_ns={self.start_ns}, end_ns={self.end_ns}, "
                f"attrs={self.attrs})")


_ring: Deque[Record] = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_enabled = True
_listening = False
_listen_lock = threading.Lock()


def enable(on: bool = True) -> None:
    """Turn the profiler annotations and the ring on (default) or off."""
    global _enabled
    _enabled = bool(on)


def recent() -> List[Record]:
    """The ring's records, oldest first (open spans included)."""
    return list(_ring)


def _stack() -> List[Record]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def count(attr: str, amount=1) -> None:
    """Add ``amount`` to ``attr`` of the innermost open span, if any."""
    stack = getattr(_local, "stack", None)
    if stack:
        attrs = stack[-1].attrs
        attrs[attr] = attrs.get(attr, 0) + amount


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        count("compiles")
        count("compile_s", duration)


def _listen() -> None:
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True


class span:
    """``with span(name, **attrs) as record:`` — see the module docstring."""

    __slots__ = ("record", "_annotation")

    def __init__(self, name: str, **attrs):
        self.record = Record(0, name, None, attrs)
        self._annotation = None

    def __enter__(self) -> Record:
        rec = self.record
        if _enabled:
            if not _listening:
                _listen()
            stack = _stack()
            rec.id = next(_ids)
            rec.parent = stack[-1].id if stack else None
            self._annotation = jax.profiler.TraceAnnotation(rec.name)
            self._annotation.__enter__()
            stack.append(rec)
            _ring.append(rec)
        rec.start_ns = time.perf_counter_ns()
        return rec

    def __exit__(self, *exc) -> None:
        self.record.end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            _stack().pop()
            self._annotation.__exit__(*exc)


def _count_sync() -> None:
    for rec in getattr(_local, "stack", ()):
        rec.attrs["syncs"] = rec.attrs.get("syncs", 0) + 1


def fetch(x, name: str):
    """``jax.device_get(x)`` inside a span ``name``, counted as one sync.

    A single array is read with ``np.asarray``, which gives the same
    value as ``jax.device_get`` at a fraction of its host cost (a few µs
    against tens per scalar read on a CPU host, below ``float(x)``'s)."""
    _count_sync()
    with span(name):
        if isinstance(x, jax.Array):
            return np.asarray(x)
        return jax.device_get(x)


def block(x, name: str):
    """``jax.block_until_ready(x)`` inside a span ``name``, counted as one
    sync."""
    _count_sync()
    with span(name):
        return jax.block_until_ready(x)


def interval(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """Append a finished record that the caller timed on
    ``perf_counter_ns``; its parent is the innermost open span."""
    if not _enabled:
        return
    stack = _stack()
    rec = Record(next(_ids), name, stack[-1].id if stack else None, attrs)
    rec.start_ns, rec.end_ns = start_ns, end_ns
    _ring.append(rec)
