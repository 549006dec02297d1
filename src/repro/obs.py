"""Spans and counters inside the program.

The program's one tracing mechanism: the layers open a named span around
their work and add to named counters where the work is counted.

* ``span(name, **attrs)``: a context manager (also usable as a function
  decorator) that times its body with ``time.perf_counter_ns()``.  It
  always opens ``jax.profiler.TraceAnnotation(f"repro.{name}")``, so the
  span lands in any running ``jax.profiler`` trace on the same clock as
  the device's ops; with no profiler running, a span costs a few
  microseconds of host time.  Spans nest through a ``contextvars`` stack:
  each knows its parent, and its request is the id of its root span, so
  every span of one admission shares a request id.  ``with span(...) as
  s`` gives the :class:`Span`, whose ``seconds`` is set on exit; its
  ``attrs`` start as the keyword arguments, and the code in the span may
  add to them (``device_solve`` carries its solve's loop counts).
* ``count(name, n=1)``: adds ``n`` to a named counter.
* ``recording()``: attaches a :class:`Recorder` for the duration of its
  body.  The recorder keeps every span that closes and the counter totals.
  With none attached, spans are timed but not kept and ``count`` returns
  after one check.

A recorder is attached to the current context, so work that another thread
runs is recorded only where that thread runs in a copy of it
(``contextvars.copy_context``).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import time
from typing import Iterator, Optional

import jax


@dataclasses.dataclass
class Span:
    """One timed interval of the program's work."""

    name: str
    id: int
    parent: int          # id of the enclosing span; 0 for a root
    request: int         # id of the root span
    attrs: dict
    start_ns: int
    end_ns: int = 0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class Recorder:
    """The spans that closed and the counter totals while it was attached."""

    def __init__(self):
        self.spans: list[Span] = []
        self.counters: dict[str, float] = {}

    def total(self, name: str) -> tuple[float, int]:
        """(seconds, count) of the spans named ``name``."""
        ds = [s.seconds for s in self.spans if s.name == name]
        return float(sum(ds)), len(ds)

    def self_seconds(self, root: str) -> list[dict[str, float]]:
        """Per request whose root span is named ``root``, in the order the
        roots closed: the self seconds of its spans by name, a span's self
        time being its own seconds less those of its children.  The values
        of one request add up to its root's seconds."""
        child_s: dict[int, float] = {}
        for s in self.spans:
            if s.parent:
                child_s[s.parent] = child_s.get(s.parent, 0.0) + s.seconds
        by_request: dict[int, dict[str, float]] = {}
        for s in self.spans:
            d = by_request.setdefault(s.request, {})
            d[s.name] = d.get(s.name, 0.0) + s.seconds - child_s.get(s.id, 0.0)
        return [by_request[s.id] for s in self.spans
                if s.parent == 0 and s.name == root]


_IDS = itertools.count(1)
_OPEN: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "repro_obs_open", default=None)
_RECORDER: contextvars.ContextVar[Optional[Recorder]] = contextvars.ContextVar(
    "repro_obs_recorder", default=None)


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Span]:
    parent = _OPEN.get()
    sid = next(_IDS)
    s = Span(name=name, id=sid, parent=parent.id if parent else 0,
             request=parent.request if parent else sid, attrs=attrs,
             start_ns=time.perf_counter_ns())
    token = _OPEN.set(s)
    try:
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            yield s
    finally:
        s.end_ns = time.perf_counter_ns()
        _OPEN.reset(token)
        rec = _RECORDER.get()
        if rec is not None:
            rec.spans.append(s)


def count(name: str, n: float = 1) -> None:
    rec = _RECORDER.get()
    if rec is None:
        return
    rec.counters[name] = rec.counters.get(name, 0) + n


def recorder() -> Optional[Recorder]:
    """The attached recorder, or None."""
    return _RECORDER.get()


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    rec = Recorder()
    token = _RECORDER.set(rec)
    try:
        yield rec
    finally:
        _RECORDER.reset(token)
