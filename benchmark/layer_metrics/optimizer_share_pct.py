"""Device op time under the trainer's ``optimizer``, ``clip-grads`` and
``multiply-grads`` scopes over device op time, in %."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.group_pct(run, "optimizer")
