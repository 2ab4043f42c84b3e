"""Device-to-host snapshot rate of the window's save: state bytes over
the save report's ``snapshot_s`` (``core/save_path.snapshot_items``)."""


def read(run):
    if not run.save or not run.save["snapshot_s"]:
        return None
    return run.save["bytes"] / run.save["snapshot_s"] / 1e9
