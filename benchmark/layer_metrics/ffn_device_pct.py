"""Device op time under ``fc1`` / ``fc2`` of the encoder layers (the
activation is fused into them; forward and backward) over device op time,
in %."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.group_pct(run, "ffn")
