"""The step of ``laguna_s_2_1.train_pack32k`` compiled for a described v5e
at its real shapes (published widths, the dense layer and the four that
follow it, one row of 32,768 tokens), the way ``test_compile_v5e_mellum2.py``
does for the cell before it: the proof that the chip's compiler takes the
program (the band kernels at 6 and 9 heads on one KV head, the gate, the
gated experts' wide and narrow loops beside a shared expert, the dense MLP
in row chunks) and the record of what it holds.  No chip, no chip time; a
compile that passes is not a chip run."""

import os
import re

import numpy as np

import test_compile_v5e as rehearsal
from bench_tiny import ROOT, load
from benchmark import harness
from test_compile_v5e import one_chip  # noqa: F401  (the module's fixture)

CELL = "laguna_s_2_1.train_pack32k"


def packed_batch(cell, length):
    tok = np.full((int(cell.traffic["batch_size"]), length), 70, np.int64)
    return {"net_input": {"src_tokens": tok}, "target": tok}


def test_cell_step_compiles_for_v5e(one_chip, monkeypatch):  # noqa: F811
    monkeypatch.setattr(rehearsal, "example_batch", packed_batch)
    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    length = cell.traffic["task_args"]["tokens_per_sample"]
    assert length == 32768
    compiled = rehearsal.compile_step(cell, length, one_chip, monkeypatch)
    text = compiled.as_text()
    m = compiled.memory_analysis()
    # five layers' blockwise kernels (forward, its rematerialized copy, dq,
    # dkv); the band is no operand, so nothing asks for a bias gradient
    assert text.count("tpu_custom_call") >= 20
    assert "flash_bwd_dbias" not in text
    for scope in ("band_attn", "attn_gate", "rotary", "moe_router",
                  "moe_routed", "moe_shared", "wide_trips", "narrow_trips",
                  "fc1", "fc2"):
        assert scope in text, scope
    # no array of L x L elements, of any dtype, forward or backward
    square = re.compile(r"\[(?:\d+,)*%d,%d\]" % (length, length))
    assert not square.search(text)
    # the peak leaves 1 GB of the described chip and is over a quarter of it
    rehearsal.fits_the_chip(compiled, CELL)
    # the state is donated: parameters, master and moments are updated in place
    assert m.alias_size_in_bytes > 7.9e9
