"""Extractions of the window's persist round that found the device's
gear-scan segment not yet computed: the round's ``scan_blocked`` counter
(``core/cdc_scan.ScanTicket.blocked``, counted by ``SaveSession``), the
device round trips a writer waited for. ``None`` where the program keeps
no such counter."""
import program_spans


def read(run):
    root = program_spans.save_root(run, "ckpt.persist")
    if root is None:
        return None
    return root.counters.get("scan_blocked")
