"""Median seconds per update the training thread spent inside the batch
iterator's ``next()`` (the harness's ``data`` span), in ms."""


def read(run):
    return run.get("data_wait_ms_median")
