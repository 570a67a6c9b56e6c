"""Median time to pack one block of ``--tokens-per-sample`` tokens out of
the documents (the ``unicore:data_pack`` span of
``TokenBlockDataset.__getitem__``, on whichever thread packs: inside
``data_produce``), in ms, under the profiler; 0 where the program wrote
its annotations and packed no block."""

from benchmark import scope_work


def read(run):
    return scope_work.span_median_ms(run, "data_pack")
