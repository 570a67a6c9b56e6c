"""Median per update of the ``unicore:h2d`` spans inside
``unicore:train_step`` on the training thread (the batch's transfer to the
device), in ms, under the profiler."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.host_value(run, "h2d_ms")
