"""Spans of the program's own layers, recorded in memory when enabled.

``span(name)`` times the body of a ``with`` as one ``Span`` record
``(name, start_ns, end_ns, span_id, parent_id, query_id)`` on
``time.perf_counter_ns()``, and opens a ``jax.profiler.TraceAnnotation``
of the same name inside it, so a profiler trace carries every span on
the device trace's clock as well. The parent is the innermost span open
when the span began; the query id is the id of the enclosing
``executor.query`` span (None outside a query). One stack serves the
process: ``FrontDoor`` lanes run one query at a time.

A name starts with the layer doing the work: ``planner.``,
``executor.``, ``semantic.``, ``backend.`` or ``serving.``.

Recording is off by default. Then ``span`` returns one shared null
context manager: no allocation, no clock read, no annotation.
``enable()`` turns recording on, ``take()`` hands the completed spans to
a reader and forgets them, ``disable()`` turns recording off again.
"""
from __future__ import annotations

import time
from typing import NamedTuple, Optional, Sequence

import jax

QUERY = "executor.query"


class Span(NamedTuple):
    """One completed span, on the ``perf_counter_ns`` clock."""

    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    query_id: Optional[int]


class _Recorder:
    """The process's recorder: whether it is on, the spans open now
    (innermost last) and the spans completed since the last ``take``."""

    def __init__(self):
        self.on = False
        self.open: list[_Open] = []
        self.done: list[Span] = []
        self.last_id = 0


_REC = _Recorder()


class _Null:
    """What ``span`` returns while recording is off: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _Open:
    """One span being recorded."""

    __slots__ = ("name", "start", "span_id", "parent_id", "query_id",
                 "annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        rec = _REC
        outer = rec.open[-1] if rec.open else None
        rec.last_id += 1
        self.span_id = rec.last_id
        self.parent_id = outer.span_id if outer else None
        self.query_id = (self.span_id if self.name == QUERY
                         else outer.query_id if outer else None)
        rec.open.append(self)
        self.start = time.perf_counter_ns()
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        end = time.perf_counter_ns()
        rec = _REC
        rec.open.remove(self)
        rec.done.append(Span(self.name, self.start, end, self.span_id,
                             self.parent_id, self.query_id))
        return False


def span(name: str, op: Optional[str] = None):
    """A context manager recording its body as span ``name`` (named
    ``name.op`` when ``op`` is given) while recording is on; otherwise
    the shared null context manager."""
    if not _REC.on:
        return _NULL
    return _Open(name if op is None else f"{name}.{op}")


def enable() -> None:
    """Start recording spans."""
    _REC.on = True


def disable() -> None:
    """Stop recording spans; spans still open complete as recorded."""
    _REC.on = False


def take() -> list[Span]:
    """The spans completed since the last ``take``, in the order they
    ended; the recorder forgets them."""
    done, _REC.done = _REC.done, []
    return done


def self_ns(spans: Sequence[Span]) -> dict[int, int]:
    """span_id -> self time in ns: the span's duration less the time its
    child spans among ``spans`` cover. Children of one span never
    overlap (spans nest on one stack), so that is their summed
    duration."""
    out = {s.span_id: s.end_ns - s.start_ns for s in spans}
    for s in spans:
        if s.parent_id in out:
            out[s.parent_id] -= s.end_ns - s.start_ns
    return out
