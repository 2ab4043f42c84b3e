"""Device-to-host copy rate of the window's save: the round's
``snapshot_bytes`` counter over its ``ckpt.snapshot`` span
(``core/save_path.snapshot_items``), without the quiescence wait, the
registry and the capacity preflight that ``snapshot_gbps`` includes."""
import program_spans


def read(run):
    root = program_spans.save_root(run)
    if root is None:
        return None
    ns = sum(s.end_ns - s.start_ns for s in root.spans
             if s.name == "ckpt.snapshot")
    nbytes = root.counters.get("snapshot_bytes")
    if not ns or not nbytes:
        return None
    return nbytes / ns
