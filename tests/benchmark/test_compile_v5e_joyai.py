"""The step of ``joyai_llm_flash.train_pack8k_x4`` compiled for a described
v5e at its real shapes (published widths, the dense layer, four sparse
layers as one scanned unit and the prediction module, four rows of 8,192
tokens), the way ``test_compile_v5e_zaya1.py`` does for the cell before it:
the proof that the chip's compiler takes the program (the band kernels at
8 heads with keys 192 wide and values padded to them, the low-rank
projections around them, the sigmoid router's balancing rule, the gated
experts' wide and narrow loops beside a shared expert, the dense MLP in row
chunks, the head's two passes) and the record of what it holds.  No chip,
no chip time; a compile that passes is not a chip run."""

import os
import re

import numpy as np

import test_compile_v5e as rehearsal
from bench_tiny import ROOT, load
from benchmark import harness
from test_compile_v5e import one_chip  # noqa: F401  (the module's fixture)

CELL = "joyai_llm_flash.train_pack8k_x4"


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
    # the blockwise kernels a pass (forward, its rematerialized copy, dq,
    # dkv) of the dense layer, the scanned unit and the module; the band is
    # no operand, so nothing asks for a bias gradient
    assert text.count("tpu_custom_call") >= 12
    assert "flash_bwd_dbias" not in text
    for scope in ("mla_q", "mla_latent", "mla_attn", "rotary", "out_proj",
                  "moe_router", "moe_routed", "wide_trips", "narrow_trips",
                  "moe_shared", "fc1", "fc2", "mtp", "mtp_eh", "lm_head",
                  "loss"):
        assert scope in text, scope
    # the module's head pass runs under its own scope AND the head's
    assert re.search(r"mtp/[^\"]*lm_head", text)
    # the kernels are handed keys and values 192 wide
    assert re.search(r"bf16\[4,8,8192,192\]", text)
    # no array of L x L elements, of any dtype, forward or backward
    square = re.compile(r"\[(?:\d+,)*%d,%d\]" % (length, length))
    assert not square.search(text)
    # the peak leaves 1 GB of the described chip and is over a quarter of it
    rehearsal.fits_the_chip(compiled, CELL)
    # the state is donated: parameters, master and moments are updated in place
    assert m.alias_size_in_bytes > 8.1e9
