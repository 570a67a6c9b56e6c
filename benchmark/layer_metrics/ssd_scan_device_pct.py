"""Device op time under the ``ssd_scan`` scope (``ops/ssd_scan.py``: the
chunked state-space scan alone, XLA's products and the recurrence over
chunk states) over device op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "ssd_scan")
