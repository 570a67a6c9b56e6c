"""The least time the chip could take for latent attention proper in one
update (``flops/joyai_scopes.py``: the EQUATIONS' score and weighted-sum
products, keys 192 wide and values 128, over the keys each query may see,
every held attention sublayer, forward and backward, over the bf16 peak; or
the bytes of ``q, k, v, o`` over the memory bandwidth if that is longer)
over the device time under ``mla_attn`` per update, in %.  The kernels run
the second product at the keys' width (the values are padded), the partly
masked blocks whole and the scores again in the backward pass: all of that
is time below the line, none of it operations above.  0 where nothing ran
under the scope; None for a configuration whose ``flops`` file has no such
count."""

from benchmark import harness, scope_shares


def read(run):
    try:
        count = harness.load_module("flops", "joyai_scopes", run["base"])
        return scope_shares.scope_roofline_pct(
            run, "mla_attn", count.mla_attn)
    except (KeyError, AttributeError, harness.Refused):
        return None
