"""The least time the chip could take for the chunk summaries of one
update (``flops/evabyte_scopes.py``: ``k, v`` read once and a
``chunk_size``-th written, forward and backward, over the memory
bandwidth; its few operations over the bf16 peak if larger) over the
device time under ``eva_prep_kv`` per update, in %."""

from benchmark import harness, scope_shares


def read(run):
    def count(run):  # reached only where device time ran under the scope
        return harness.load_module(
            "flops", "evabyte_scopes", run["base"]).eva_prep_kv(run)

    return scope_shares.scope_roofline_pct(run, "eva_prep_kv", count)
