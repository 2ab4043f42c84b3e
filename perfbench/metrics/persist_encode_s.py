"""Seconds of the window's persist round (snapshot end to commit) in
which some writer rank was encoding a leaf with the host codec
(``ckpt.encode``)."""
import program_spans


def read(run):
    return program_spans.persist_union_s(run, "ckpt.encode")
