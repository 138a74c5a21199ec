"""Share of the device's busy time in the window spent in scatter /
segment-reduce operations, grouped as ``bench.trace.op_group`` says.

The trace names no slab phase, so a scatter that XLA fused is known by its
operand shapes alone: an s32 operand with more elements than the output,
beside a floating-point operand of as many elements.  A change that keeps
the work but leaves no such float operand (the gather fused into the
phase-2 scatter, say) reads near 0 here without being faster: judge this
metric beside ``result_s`` and the breakdown until the program names its
phases in the trace."""
from bench.trace import SCATTER


def read(run):
    return None if run.trace is None else run.trace.share(SCATTER)
