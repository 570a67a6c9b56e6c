"""The step of ``zaya1_8b.train_pack8k_x4`` compiled for a described v5e at
its real shapes (published widths, five layers as one scanned unit, four
rows of 8,192 tokens), the way ``test_compile_v5e_laguna.py`` does for the
cell before it: the proof that the chip's compiler takes the program (the
band kernels at 4 query heads on 1 KV head over four rows, the convolution
mix in front of them, the router network with its carried state, the gated
experts' wide and narrow loops at top-1, the tied head) and the record of
what it holds.  No chip, no chip time; a compile that passes is not a chip
run."""

import os
import re

import numpy as np

import test_compile_v5e as rehearsal
from bench_tiny import ROOT, load
from benchmark import harness
from test_compile_v5e import one_chip  # noqa: F401  (the module's fixture)

CELL = "zaya1_8b.train_pack8k_x4"


def packed_batch(cell, length):
    tok = np.full((int(cell.traffic["batch_size"]), length), 70, np.int64)
    return {"net_input": {"src_tokens": tok}, "target": tok}


def test_cell_step_compiles_for_v5e(one_chip, monkeypatch):  # noqa: F811
    monkeypatch.setattr(rehearsal, "example_batch", packed_batch)
    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    length = cell.traffic["task_args"]["tokens_per_sample"]
    assert (cell.traffic["batch_size"], length) == (4, 8192)
    compiled = rehearsal.compile_step(cell, length, one_chip, monkeypatch)
    text = compiled.as_text()
    m = compiled.memory_analysis()
    # one scanned unit: the blockwise kernels once a pass (forward, its
    # rematerialized copy, dq, dkv), not once a layer; the band is no
    # operand, so nothing asks for a bias gradient
    assert text.count("tpu_custom_call") >= 4
    assert "flash_bwd_dbias" not in text
    for scope in ("cca_down", "cca_mix", "rotary", "band_attn", "cca_up",
                  "moe_router", "router_down", "router_mlp", "router_choose",
                  "moe_routed", "wide_trips", "narrow_trips", "merge",
                  "lm_head"):
        assert scope in text, scope
    # the head is the embedding: no second vocabulary-sized parameter
    assert "lm_head" not in {
        p.split("'")[1] for p in re.findall(r"\['params'\]\['\w+'\]", text)}
    # no array of L x L elements, of any dtype, forward or backward
    square = re.compile(r"\[(?:\d+,)*%d,%d\]" % (length, length))
    assert not square.search(text)
    # the peak leaves 1 GB of the described chip and is over a quarter of it
    rehearsal.fits_the_chip(compiled, CELL)
    # the state is donated: parameters, master and moments are updated in place
    assert m.alias_size_in_bytes > 8.2e9
