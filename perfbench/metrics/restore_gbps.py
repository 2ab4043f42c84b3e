"""Restore rate: state bytes over the host-clock seconds from the fresh
``Trainer``'s construction, on files out of the page cache, until its
restored state is resident on the device (before its first step)."""


def read(run):
    if not run.restore:
        return None
    return run.restore["state_bytes"] / run.restore["resident_s"] / 1e9
