"""Persist rate of the window's save: state bytes over the host-clock
seconds from the snapshot's end to the round's ``on_commit`` hook (codec,
CDC scan, hashing, CAS writes, fsync barrier, manifest and commit)."""


def read(run):
    if not run.save:
        return None
    return run.save["bytes"] / (run.save["t_commit"]
                                - run.save["t_snapshot_end"]) / 1e9
