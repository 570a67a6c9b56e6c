"""Device op time under the ``mtp`` scope (``modules/mtp.py``: the
prediction module's two norms and ``2d -> d`` projection, its attention and
expert sublayers, its final norm, and the head's second pass over its
stream, which ``lm_head_loss_device_pct`` counts as well: the two overlap)
over device op time, in %; 0 where the program named its operations and
none ran under the scope."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "mtp")
