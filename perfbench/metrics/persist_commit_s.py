"""Seconds the window's round spent committing (``ckpt.commit``): the
manifest, the commit rename, LATEST and the refcount publish, from the
writers' barrier to the ``on_commit`` hooks."""
import program_spans


def read(run):
    return program_spans.persist_union_s(run, "ckpt.commit")
