"""Share of the traced window in which no operation ran on the device:
1 - (union of the op intervals) / window, averaged over the chips used."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_frac
