"""Device op time in the attention backward kernels (``flash_bwd_dq`` +
``flash_bwd_dkv`` + ``flash_bwd_dbias``, or ``fullrow_attn_bwd``) over
device op time, in %."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernels_pct(run, trace_scopes.ATTENTION_BWD)
