"""Device op time in the attention forward kernel (``flash_fwd`` or
``fullrow_attn_fwd``, by the kernel's ``name=``) over device op time, in %."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernels_pct(run, trace_scopes.ATTENTION_FWD)
