"""Seconds of the resume's restore (``ckpt.restore``) that the main
thread spent placing leaves on the device (``restore.place``, the
host-to-device transfer)."""
import program_spans


def read(run):
    return program_spans.restore_union_s(run, "restore.place")
