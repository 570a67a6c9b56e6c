"""Median host time of one update inside ``Trainer.train_step`` (the
``unicore:train_step`` span) in the traced loop, in ms.  Taken under the
profiler, whose Python tracer slows the host: an upper bound on the
untraced figure."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.host_value(run, "train_step_ms")
