"""Seconds of the window's persist round (snapshot end to commit) in
which some writer rank was in its directory-fsync durability barrier
(``ckpt.fsync``)."""
import program_spans


def read(run):
    return program_spans.persist_union_s(run, "ckpt.fsync")
