"""Median per update of the ``unicore:launch`` spans (the calls of the
jitted step programs) inside ``unicore:train_step``, in ms, under the
profiler."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.host_value(run, "launch_ms")
