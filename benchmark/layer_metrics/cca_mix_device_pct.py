"""Device op time under the ``cca_mix`` scope (``modules/cca.py``: both
convolutions over ``[q ; k]``, the mean added back, the late values, the L2
norms and the temperature; forward, rematerialized forward and backward)
over device op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "cca_mix")
