"""Median number of ready batches ``BufferedIterator.__next__`` found
waiting (the ``depth`` stat of ``unicore:data_next``): the room the data
layer has left; 0 means the training thread is about to wait."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.host_value(run, "data_depth")
