"""The least time the chip could take for the joint softmax of one update
(``flops/evabyte_scopes.py``: the score and weighted-sum products of the
keys each query may SEE, forward and backward, over the bf16 peak, or the
bytes of ``q, k, v``, the summaries and the output over the memory
bandwidth, whichever is larger) over the device time under ``eva_agg`` per
update, in %.  The dense windows score about twice the visible keys and
the layers' rematerialization runs the forward twice: both are in the
denominator, so a kernel that skips masked blocks raises it."""

from benchmark import harness, scope_shares


def read(run):
    def count(run):  # reached only where device time ran under the scope
        return harness.load_module(
            "flops", "evabyte_scopes", run["base"]).eva_agg(run)

    return scope_shares.scope_roofline_pct(run, "eva_agg", count)
