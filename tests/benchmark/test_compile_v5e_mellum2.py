"""The step of ``mellum2_12b.train_pack32k`` compiled for a described v5e
at its real shapes (published widths, one period of four layers, one row
of 32,768 tokens), the way ``test_compile_v5e_evabyte.py`` does for the
cell before it: the proof that the chip's compiler takes the program (the
band kernels with their scalar-prefetch maps, the gated experts' loops)
and the record of what it holds.  No chip, no chip time; a compile that
passes is not a chip run."""

import os
import re

import numpy as np

import test_compile_v5e as rehearsal
from bench_tiny import ROOT, load
from benchmark import harness
from test_compile_v5e import one_chip  # noqa: F401  (the module's fixture)

CELL = "mellum2_12b.train_pack32k"


def packed_batch(cell, length):
    tok = np.full((int(cell.traffic["batch_size"]), length), 70, np.int64)
    return {"net_input": {"src_tokens": tok}, "target": tok}


def test_cell_step_compiles_for_v5e(one_chip, monkeypatch):  # noqa: F811
    monkeypatch.setattr(rehearsal, "example_batch", packed_batch)
    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    length = cell.traffic["task_args"]["tokens_per_sample"]
    compiled = rehearsal.compile_step(cell, length, one_chip, monkeypatch)
    text = compiled.as_text()
    m = compiled.memory_analysis()
    # four layers' blockwise kernels (forward, its rematerialized copy, dq,
    # dkv); the band is no operand, so nothing asks for a bias gradient
    assert text.count("tpu_custom_call") >= 16
    assert "flash_bwd_dbias" not in text
    for scope in ("band_attn", "rotary", "moe_router", "moe_routed"):
        assert scope in text, scope
    # no array of L x L elements, of any dtype, forward or backward: at
    # 32,768 a bfloat16 one is 2 GB and the model has two mask kinds
    square = re.compile(r"\[(?:\d+,)*%d,%d\]" % (length, length))
    assert not square.search(text)
    # the peak leaves 1 GB of the described chip and is over a quarter of it
    rehearsal.fits_the_chip(compiled, CELL)
    # the state is donated: parameters, master and moments are updated in place
    assert m.alias_size_in_bytes > 7.4e9
