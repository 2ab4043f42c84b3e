"""Stage spans and counters of the save and restore paths, kept in memory.

``root(name, trace_id)`` opens the span a save round or a restore hangs
from: ``ckpt.save`` (the caller's snapshot), ``ckpt.persist`` (the write
and commit, same id) and ``ckpt.restore``. ``span(name,
trace_id)`` times one stage inside it and ``count(trace_id, **deltas)``
adds integers to its counters. Both find their root by ``trace_id``
alone (a save round's step; a per-call id for a restore), because the
chunk-pool and writer threads the work runs on inherit no context;
rounds in flight at once have distinct steps, so their spans never mix.
Spans whose root is not open are not kept.

Times are ``time.monotonic_ns()``, the clock ``time.monotonic()`` reads.
Every span and context-managed root also enters a
``jax.profiler.TraceAnnotation`` of its name, so under a profiler session
the stages land on the profiler's host plane beside the device planes;
with no session that costs a flag check.

``finished(name)`` gives the last ``KEEP`` closed roots of a name, oldest
first: a job that saves every N steps for days holds a bounded record.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

KEEP = 2            # closed roots kept per root name


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    end_ns: int
    thread: int       # threading.get_ident() of the thread it ran on
    parent: str       # the enclosing span on that thread, else the root


@dataclass(eq=False)          # a root is equal only to itself
class Root:
    name: str
    trace_id: object
    start_ns: int
    end_ns: int | None = None
    spans: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    error: str | None = None

    def union_s(self, name: str, lo_ns: int | None = None,
                hi_ns: int | None = None) -> float:
        """Seconds of ``[lo_ns, hi_ns]`` (default: the root's extent) in
        which at least one thread was inside a span called ``name``."""
        lo = self.start_ns if lo_ns is None else lo_ns
        hi = (self.end_ns or time.monotonic_ns()) if hi_ns is None \
            else hi_ns
        total, covered = 0, lo
        for s, e in sorted((sp.start_ns, sp.end_ns) for sp in self.spans
                           if sp.name == name):
            s, e = max(s, covered), min(e, hi)
            if e > s:
                total += e - s
                covered = e
        return total / 1e9


_lock = threading.Lock()
_open: dict = {}          # trace_id → open roots, newest last
_done: dict = {}          # root name → deque of its last KEEP closed roots
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def open_root(name: str, trace_id) -> Root:
    """Open a root; pair with ``close_root``. ``root()`` is the usual form;
    this one serves a root that outlives the call that opens it (the
    streaming restore)."""
    r = Root(name, trace_id, time.monotonic_ns())
    with _lock:
        _open.setdefault(trace_id, []).append(r)
    return r


def close_root(r: Root, error: BaseException | None = None):
    """Close ``r`` (once; later calls do nothing), with ``error`` set when
    the work it timed failed."""
    with _lock:
        if r.end_ns is not None:
            return
        r.end_ns = time.monotonic_ns()
        if error is not None:
            r.error = f"{type(error).__name__}: {error}"
        roots = _open.get(r.trace_id, [])
        if r in roots:
            roots.remove(r)
        if not roots:
            _open.pop(r.trace_id, None)
        _done.setdefault(r.name, deque(maxlen=KEEP)).append(r)


@contextmanager
def root(name: str, trace_id):
    r = open_root(name, trace_id)
    try:
        with jax.profiler.TraceAnnotation(name, id=trace_id):
            yield r
    except BaseException as e:
        close_root(r, e)
        raise
    close_root(r)


@contextmanager
def span(name: str, trace_id):
    stack = _stack()
    parent = next((n for t, n in reversed(stack) if t == trace_id), None)
    stack.append((trace_id, name))
    start = time.monotonic_ns()
    try:
        with jax.profiler.TraceAnnotation(name, id=trace_id):
            yield
    finally:
        end = time.monotonic_ns()
        stack.pop()
        with _lock:
            roots = _open.get(trace_id)
            if roots:
                r = roots[-1]
                r.spans.append(Span(name, start, end, threading.get_ident(),
                                    parent or r.name))


def count(trace_id, **deltas):
    """Add each of ``deltas`` to the counters of the open root of
    ``trace_id``."""
    with _lock:
        roots = _open.get(trace_id)
        if roots:
            c = roots[-1].counters
            for k, v in deltas.items():
                c[k] = c.get(k, 0) + int(v)


def finished(name: str) -> list:
    """The last ``KEEP`` closed roots called ``name``, oldest first."""
    with _lock:
        return list(_done.get(name, ()))
