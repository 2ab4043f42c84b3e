"""Seconds of the window's persist round (snapshot end to commit) in
which some writer rank was blocked on the device's CDC gear scan
(``ckpt.scan_wait``)."""
import program_spans


def read(run):
    return program_spans.persist_union_s(run, "ckpt.scan_wait")
