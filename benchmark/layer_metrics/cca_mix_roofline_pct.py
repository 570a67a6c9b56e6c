"""The least time the chip could take for what runs under ``cca_mix``
(``work.bytes`` of each of its operations in the traced program's scope
table over the memory bandwidth, or, for the per-head convolution's
product, ``work.flops`` over the bf16 peak if that is longer) over their
device time, in %.  The scope is memory-bound: two passes over a latent
five heads wide.  A Mosaic kernel under the scope would state its own
operands; a program without the scope reads 0."""

from benchmark import scope_work


def read(run):
    return scope_work.roofline_pct(run, lambda parts, row: "cca_mix" in parts)
