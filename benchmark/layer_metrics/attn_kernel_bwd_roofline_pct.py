"""The attention backward kernels' matmul operations
(``flops/kernels.py`` x calls, the recomputed scores included) over their
device time and the chip's bf16 peak, in %."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernels_roofline_pct(run, trace_scopes.ATTENTION_BWD)
