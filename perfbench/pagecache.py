"""A cold restore: the checkpoint's files leave the page cache first, as
on a node that did not write them, and ``/proc/self/io`` shows how many
bytes the restore then read from storage."""
from __future__ import annotations

import os
from pathlib import Path


def evict(path) -> bool:
    """Drop ``path``'s pages from the page cache; True where the kernel
    took the advice. Its dirty pages are written first, since only clean
    pages can go."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        return True
    except OSError:
        return False
    finally:
        os.close(fd)


def evict_tree(root) -> tuple:
    """(files, files evicted) under ``root``."""
    files = [p for p in Path(root).rglob("*") if p.is_file()]
    return len(files), sum(evict(p) for p in files)


def read_bytes() -> int:
    """Bytes this process has had read from storage (``read_bytes`` of
    ``/proc/self/io``); page-cache hits do not count."""
    with open("/proc/self/io") as f:
        for line in f:
            if line.startswith("read_bytes:"):
                return int(line.split()[1])
    raise RuntimeError("/proc/self/io has no read_bytes")
