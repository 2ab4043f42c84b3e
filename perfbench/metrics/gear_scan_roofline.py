"""Share of its roofline that the Pallas gear-scan kernel
(``core/cdc_scan``, program ``jit_scan``) reached, in %: the least time
the chip needs to read every payload byte the device scanned and write
one candidate-mask byte for it, over HBM bandwidth, against the kernel
program's device time. The trace runs on past the window until the
save's round has committed, so it holds every scan of the save. The scan
does no floating-point work, so bandwidth is its only roofline."""

PROGRAM = "jit_scan"


def read(run):
    if run.trace is None or not run.scan_payload_bytes:
        return None
    runs, seconds = run.trace.program(PROGRAM, window_only=False)
    if not runs or not seconds:
        return None
    least = 2 * run.scan_payload_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
