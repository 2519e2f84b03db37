"""Spans, phase scopes and counters: the program's one tracing system.

Two kinds of record, each with its own cost:

* ``span(name)`` times a stretch of host code.  Off (the default) it
  returns one shared no-op object: a flag check, nothing allocated, no
  clock read.  On (``enable()``), it appends ``[name, start_ns, end_ns,
  parent]`` to an in-memory list (``time.perf_counter_ns``; ``parent`` is
  the index of the enclosing span of the same thread, ``None`` at the
  top) and enters ``jax.profiler.TraceAnnotation(name)``, so a profiler
  trace shows the span on its host plane, on the clock of the device ops.
  Every span name starts with ``repro.``.
* ``counter(group)`` returns the ``collections.Counter`` registered under
  ``group``.  Counters are always on: they count host events (a kernel
  backend resolved, a dispatch, a jit trace), which cost a dict update.

The phases of the jitted round are named with ``jax.named_scope``
(``draw``, ``d_step``, ``gradient_penalty``, ``g_step``, ``merge``,
``kernel_layout``).  That is compile-time metadata only: it always
applies, on or off, so traced and untraced runs compile the same
program.  A profiler reads it back from each HLO op's
``metadata={op_name=...}``; backward ops carry it as
``transpose(jvp(<scope>))``.

``snapshot()`` returns what was recorded as plain lists and dicts, ready
for ``json.dumps``; the spans stay in memory until the caller takes and
exports them, and ``reset()`` forgets them and zeroes every counter.

    >>> from repro import obs
    >>> obs.enable()
    >>> with obs.span("repro.example.outer"):
    ...     with obs.span("repro.example.inner"):
    ...         obs.counter("example")["calls"] += 1
    >>> snap = obs.snapshot()
    >>> [(s[0], s[3]) for s in snap["spans"]]
    [('repro.example.outer', None), ('repro.example.inner', 0)]
    >>> snap["counters"]["example"]
    {'calls': 1}
    >>> obs.disable(); obs.reset()
"""
from __future__ import annotations

import collections
import threading
import time

import jax

PREFIX = "repro."

_on = False
_spans: list[list] = []
_local = threading.local()
_counters: dict[str, collections.Counter] = {}


class _Off:
    """The shared no-op a span returns while tracing is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "index", "annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _stack()
        self.index = len(_spans)
        _spans.append([self.name, time.perf_counter_ns(), None,
                       stack[-1] if stack else None])
        stack.append(self.index)
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        _spans[self.index][2] = time.perf_counter_ns()
        _stack().pop()
        return False


def _stack() -> list[int]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str):
    """Context manager timing a stretch of host code (see the module
    docstring); a no-op while tracing is off."""
    if not _on:
        return _OFF
    if not name.startswith(PREFIX):
        raise ValueError(f"span {name!r}: program span names start with "
                         f"{PREFIX!r}")
    return _Span(name)


def counter(group: str) -> collections.Counter:
    """The counter registered under ``group`` (made on first use)."""
    c = _counters.get(group)
    if c is None:
        c = _counters[group] = collections.Counter()
    return c


def enable():
    global _on
    _on = True


def disable():
    global _on
    _on = False


def reset():
    """Forget every span and zero every counter (the objects stay the
    same, so modules holding a counter keep counting into it)."""
    _spans.clear()
    for c in _counters.values():
        c.clear()


def snapshot() -> dict:
    """``{"spans": [[name, start_ns, end_ns, parent], ...], "counters":
    {group: {key: count}}}``; a span still open has ``end_ns`` None."""
    return {"spans": [list(s) for s in _spans],
            "counters": {g: dict(c) for g, c in _counters.items()}}
