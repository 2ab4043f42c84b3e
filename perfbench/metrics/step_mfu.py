"""Model FLOP utilisation of the train step, in %: the model FLOPs of the
steps the trace holds (6 x matmul parameters incl. the LM head x tokens,
``flops/<family>.py``; recomputation not counted) over the device time of
the step program (``jit_train_step``) times the chip's bf16 peak."""

PROGRAM = "jit_train_step"


def read(run):
    if run.trace is None:
        return None
    steps, seconds = run.trace.program(PROGRAM)
    if not steps or not seconds:
        return None
    return 100.0 * steps * run.flops_per_step / (
        seconds * run.peaks["bf16_flops"])
