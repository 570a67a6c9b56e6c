"""Device op time under ``lm_head`` or the loss over device op time, in %."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.group_pct(run, "lm_head_loss")
