"""Seconds of the resume's restore (``ckpt.restore``) in which some
thread was reading chunk objects or shard files from the store
(``restore.read``, the whole-payload crc gate included)."""
import program_spans


def read(run):
    return program_spans.restore_union_s(run, "restore.read")
