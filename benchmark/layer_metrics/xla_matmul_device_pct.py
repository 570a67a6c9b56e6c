"""Device op time in the operations XLA compiled with a matrix product in
them (``work.flops`` > 0 in the traced program's scope table: every ``dot``
and ``convolution`` fusion, forward, rematerialized and backward; the
Mosaic kernels state no product and fall out) over device op time, in %."""

from benchmark import scope_work


def read(run):
    return scope_work.device_pct(run, lambda parts, row: row["flops"] > 0)
