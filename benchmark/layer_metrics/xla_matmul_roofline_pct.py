"""The least time the chip could take for every operation XLA compiled
with a matrix product in it (``work.flops`` at the bf16 peak or
``work.bytes`` at the memory bandwidth, whichever is longer, execution by
execution) over the device time those operations took, in %."""

from benchmark import scope_work


def read(run):
    return scope_work.roofline_pct(run, lambda parts, row: row["flops"] > 0)
