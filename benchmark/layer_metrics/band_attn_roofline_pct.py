"""The least time the chip could take for the banded softmax of one update
(``flops/mellum2_scopes.py``: the score and weighted-sum products of the
keys each query may SEE, forward and backward, over the bf16 peak, or the
bytes of ``q, k, v, o`` over the memory bandwidth, whichever is larger)
over the device time under ``band_attn`` per update, in %.  The partly
masked blocks, the map's dead steps, the layout around the kernels and the
layers' second forward are all in the denominator."""

from benchmark import harness, scope_shares


def read(run):
    def count(run):  # reached only where device time ran under the scope
        return harness.load_module(
            "flops", "mellum2_scopes", run["base"]).band_attn(run)

    return scope_shares.scope_roofline_pct(run, "band_attn", count)
