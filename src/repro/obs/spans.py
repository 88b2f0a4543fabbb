"""Host spans and the slot's named scopes (DESIGN.md §11).

Two kinds of marks put the program's own layer boundaries on the
profiler's clock:

* **Named scopes** (device side).  `slot_step` and the fleet runner's
  `slot` wrap each sub-step in `jax.named_scope` under one of
  `SLOT_SCOPES`.  A scope is metadata only: it lands in every HLO op's
  ``op_name`` path (``.../while/body/.../route/matching/...``) and so in
  the device trace, and changes no computation.
* **Host spans** (host side).  ``with span("atlas.launch", n=lanes):``
  times one stretch of an engine's host loop.  Recording is off by
  default: `span` then returns one shared no-op context (no clock read,
  no allocation, no profiler event), so the engines pay nothing.
  `enable()` turns it on until `collect()` hands the records over.  While
  on, each span is also a `jax.profiler.TraceAnnotation`, so a profiler
  trace shows it on the host plane, on the device trace's clock.

A record is ``(name, start_ns, end_ns, parent, job, n)``: times from
`time.perf_counter_ns`, ``parent`` the name of the enclosing span (None
for the outermost), ``job`` the id of the outermost span (``atlas.sweep``
or ``fleet.job``) shared by every span of that call, ``n`` a count the
span reports (lanes, probes), or None.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import List, NamedTuple

import jax

#: Every named scope of the slot: `slot_step`'s three sub-steps (with the
#: wireless matching inside ``route``) and the fleet runner's own work
#: around them.
SLOT_SCOPES = ("balance", "route", "matching", "compute",
               "arrive", "events", "stats", "verdict", "freeze")


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: str | None
    job: int
    n: int | None


class _Off:
    """The shared no-op span: entering, leaving and setting ``n`` do
    nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def __setattr__(self, name, value):
        pass


_OFF = _Off()
_on = False
_records: List[Span] = []
_jobs = itertools.count()
_local = threading.local()


class _Live:
    __slots__ = ("name", "n", "parent", "job", "start", "_ann")

    def __init__(self, name: str, n: int | None):
        self.name, self.n = name, n

    def __enter__(self):
        stack = _local.__dict__.setdefault("stack", [])
        self.parent = stack[-1] if stack else None
        self.job = self.parent.job if self.parent else next(_jobs)
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        if _on:
            _records.append(Span(
                self.name, self.start, end,
                self.parent.name if self.parent else None, self.job,
                None if self.n is None else int(self.n)))
        return None


def span(name: str, n: int | None = None):
    """A context timing one stretch of host work; ``n`` may also be set
    on the entered span (``sp.n = k``) once the count is known."""
    return _Live(name, n) if _on else _OFF


def spanned(name: str):
    """Decorator: the whole call is one span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kw):
            with span(name):
                return fn(*args, **kw)
        return call
    return wrap


def enable() -> None:
    """Start recording spans (and writing them into a profiler trace)."""
    global _on
    _on = True


def collect() -> List[Span]:
    """Stop recording; return the spans recorded since `enable`, in the
    order they ended, and clear them."""
    global _on, _records
    _on = False
    out, _records = _records, []
    return out
