"""Seconds of the window's persist round (snapshot end to commit) in
which some chunk-pool thread was hashing and writing a chunk object
(``ckpt.store``: blake2b, tmp file, rename)."""
import program_spans


def read(run):
    return program_spans.persist_union_s(run, "ckpt.store")
