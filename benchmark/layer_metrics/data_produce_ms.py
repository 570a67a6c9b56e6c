"""Median time to build one batch on the thread that builds it (the
``unicore:data_produce`` span), in ms, under the profiler."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.host_value(run, "data_produce_ms")
