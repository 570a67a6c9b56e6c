"""Device op time of operations the scope table has no path for, over
device op time, in %: how much of the trace the other shares cannot see."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.group_pct(run, "unattributed")
