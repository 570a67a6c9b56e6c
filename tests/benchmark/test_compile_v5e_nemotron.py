"""The step of ``nemotron3_super_120b.train_pack8k`` compiled for a
described v5e at its real shapes (published widths, one period, 8,192
tokens), the way ``test_compile_v5e.py`` does for the cells before it: the
record of how the batch was chosen (the cell's file quotes these bytes) and
the proof that the chip's compiler takes the program.  No chip, no chip
time; a compile that passes is not a chip run."""

import numpy as np
import pytest

import test_compile_v5e as rehearsal
from bench_tiny import ROOT, load
from benchmark import harness
from test_compile_v5e import one_chip  # noqa: F401  (the module's fixture)

CELL = "nemotron3_super_120b.train_pack8k"


def packed_batch(cell, length):
    tok = np.full((int(cell.traffic["batch_size"]), length), 5, np.int64)
    return {"net_input": {"src_tokens": tok}, "target": tok}


def test_cell_step_compiles_for_v5e(one_chip, monkeypatch):  # noqa: F811
    import os

    monkeypatch.setattr(rehearsal, "example_batch", packed_batch)
    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    compiled = rehearsal.compile_step(
        cell, cell.traffic["task_args"]["tokens_per_sample"], one_chip,
        monkeypatch,
    )
    text = compiled.as_text()
    # the blockwise attention kernels (forward, its rematerialized copy, dq,
    # dkv); the constant causal mask needs no bias gradient
    assert text.count("tpu_custom_call") >= 4
    assert "flash_bwd_dbias" not in text
    # one traced body per layer kind: the five EM units are one while loop
    assert text.count("ssd_scan") > 0
    # read when the batch was chosen: total_bytes 13,835,767,296 (2 x 8,192
    # needs about 2.5 GB more); since PR 36 names what the E layers keep,
    # peak 12,724,507,136 and total_bytes 14,228,393,472.  Held: the peak,
    # with 1 GB of the chip left (a further kept activation has that room)
    rehearsal.fits_the_chip(compiled, CELL)
    m = compiled.memory_analysis()
    # the state is donated: parameters, master and moments are updated in place
    assert m.alias_size_in_bytes > 9.5e9
