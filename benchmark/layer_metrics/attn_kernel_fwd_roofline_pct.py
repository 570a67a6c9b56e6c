"""The attention forward kernel's matmul operations
(``flops/kernels.py`` x calls) over its device time and the chip's bf16
peak, in %.  Compute-bound by its shapes (D = 64, L = 512: 128 operations
per byte of q, k, v, o), so the bound is the MXU's."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernels_roofline_pct(run, trace_scopes.ATTENTION_FWD)
