"""Seconds of the resume's restore (``ckpt.restore``) in which some
thread was decoding a reassembled payload (``restore.decode``)."""
import program_spans


def read(run):
    return program_spans.restore_union_s(run, "restore.decode")
