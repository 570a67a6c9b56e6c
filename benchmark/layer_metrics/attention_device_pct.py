"""Device op time under a ``self_attn`` scope (projections, bias, kernels,
layout copies; forward and backward) over device op time, in %."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.group_pct(run, "attention")
