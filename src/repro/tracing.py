"""The program's spans and counters: one in-memory record, on the
profiler's clock when a profiler trace runs.

``span(name, **attrs)`` times a block of work. It enters
``jax.profiler.TraceAnnotation(name, **attrs)``, so that while a profiler
trace runs the span is a host event in the same ``.xplane.pb`` as the
device's operations and on their clock; and it records a :class:`Span`
(perf_counter nanoseconds, for durations) in a bounded record. The
parent is the innermost open span of the same thread, and a span takes
its parent's attributes under its own: the training ``step`` of the
``train`` root, the ``restart`` ordinal of ``adcc.recover``.
``step(name, step_num)`` opens a root through
``jax.profiler.StepTraceAnnotation``. ``counter_group(name)`` is the
registry's ``collections.Counter`` of that name. The groups in use:

* ``moe`` — the held-experts layer's routing, added once a step from
  the values the ledger record's fetch brings
  (``launch/train.py::count_routing``): ``("rows", layer, expert)``
  rows routed to each held expert, ``("overflow", layer)`` held
  assignments the dropless buffer could not take (0 by construction).

Always on: with no profiler running a span costs a few
microseconds, against a training step of hundreds of milliseconds. Spans
time blocks of work (a step, a dispatch, a recovery, a slot read); a
per-leaf or per-operation quantity goes to a counter.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

__all__ = ["MAX_SPANS", "Span", "span", "step", "counter_group", "spans",
           "counters", "reset"]

# A 24 s training window on one TPU v5e holds ~58 steps of 2 spans, a
# restart cycle of ~3 s some 8 spans: 65,536 spans hold such a window,
# with its set-up, over a hundred times (~200 bytes a span: ~13 MB).
MAX_SPANS = 1 << 16


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]          # id of the enclosing span, same thread
    thread: int
    attrs: Dict[str, Any]
    id: int

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_RECORD: "collections.deque[Span]" = collections.deque(maxlen=MAX_SPANS)
_COUNTERS: Dict[str, collections.Counter] = {}
_IDS = itertools.count()
_OPEN = threading.local()


class _Open:
    """An open span; after the block, ``seconds`` is its duration."""

    __slots__ = ("name", "attrs", "_annotate", "_ann", "id", "parent",
                 "start_ns", "end_ns")

    def __init__(self, name: str, attrs: Dict[str, Any], annotate):
        self.name, self.attrs, self._annotate = name, attrs, annotate

    def __enter__(self) -> "_Open":
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        if stack:
            self.attrs = {**stack[-1].attrs, **self.attrs}
            self.parent = stack[-1].id
        else:
            self.parent = None
        self.id = next(_IDS)
        self._ann = self._annotate(self.attrs)
        self._ann.__enter__()
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        _OPEN.stack.pop()
        self._ann.__exit__(*exc)
        _RECORD.append(Span(self.name, self.start_ns, self.end_ns,
                            self.parent, threading.get_ident(), self.attrs,
                            self.id))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def span(name: str, **attrs) -> _Open:
    return _Open(name, attrs, lambda a: jax.profiler.TraceAnnotation(
        name, **a))


def step(name: str, step_num: int) -> _Open:
    """A step root: ``StepTraceAnnotation(name, step_num=...)``, recorded
    with the attribute ``step``."""
    return _Open(name, {"step": step_num},
                 lambda a: jax.profiler.StepTraceAnnotation(
                     name, step_num=step_num))


def counter_group(name: str) -> collections.Counter:
    """The registry's counter ``name``, made on first use; it is added to
    and read as any ``collections.Counter``."""
    return _COUNTERS.setdefault(name, collections.Counter())


def spans(name: Optional[str] = None) -> List[Span]:
    """The recorded spans (of ``name``), in the order they ended."""
    return [s for s in list(_RECORD) if name is None or s.name == name]


def counters() -> Dict[str, collections.Counter]:
    """A copy of every counter, by name."""
    return {k: collections.Counter(c) for k, c in _COUNTERS.items()}


def reset() -> None:
    """Forget the recorded spans and zero every counter (in place: a
    module's reference to its counter stays valid)."""
    _RECORD.clear()
    for c in _COUNTERS.values():
        c.clear()
