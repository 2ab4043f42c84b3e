"""The program's own stage spans (``repro.core.trace``), as the per-layer
readers take them.

The window's save round is the ``ckpt.save`` / ``ckpt.persist`` root
whose id is the step of the window's save (``run.save["persist"]
["step"]``); the resume is the last ``ckpt.restore`` root. Every function
returns ``None`` where the program keeps no such record: a program
without ``repro.core.trace``, or no matching root.

"Union seconds" are the seconds of an interval in which at least one
thread was inside a span of the name, from ``reduce_trace.union``.
"""
from __future__ import annotations

import reduce_trace


def _trace():
    try:
        from repro.core import trace
    except ImportError:
        return None
    return trace


def _finished(name: str) -> list:
    trace = _trace()
    return trace.finished(name) if trace is not None else []


def save_root(run, name: str = "ckpt.save"):
    """The window's round: its ``name`` root (``ckpt.save`` or
    ``ckpt.persist``), or None."""
    step = (run.save or {}).get("persist", {}).get("step")
    if step is None:
        return None
    found = [r for r in _finished(name) if r.trace_id == step]
    return found[-1] if found else None


def restore_root(run):
    """The resume's ``ckpt.restore`` root, or None."""
    if not run.restore:
        return None
    found = _finished("ckpt.restore")
    return found[-1] if found else None


def union_s(root, name: str, lo_ns: int, hi_ns: int) -> float:
    """Seconds of ``[lo_ns, hi_ns]`` inside some span ``name`` of
    ``root``."""
    spans = [(s.start_ns, s.end_ns - s.start_ns) for s in root.spans
             if s.name == name]
    return sum(e - s for s, e in reduce_trace.union(spans, lo_ns, hi_ns)) \
        / 1e9


def persist_union_s(run, name: str):
    """Union seconds of ``name`` in the window's round between the end of
    its snapshot and its commit: the interval ``persist_gbps`` times."""
    root = save_root(run, "ckpt.persist")
    if root is None:
        return None
    return union_s(root, name, int(run.save["t_snapshot_end"] * 1e9),
                   int(run.save["t_commit"] * 1e9))


def restore_union_s(run, name: str):
    """Union seconds of ``name`` within the resume's ``ckpt.restore``."""
    root = restore_root(run)
    if root is None:
        return None
    return union_s(root, name, root.start_ns, root.end_ns)
