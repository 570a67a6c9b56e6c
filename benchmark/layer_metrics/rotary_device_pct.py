"""Device op time under the ``rotary`` scope (``modules/rotary.py``: the
angles, their sines and the rotation of ``q`` and ``k``; forward,
rematerialized forward and backward) over device op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "rotary")
