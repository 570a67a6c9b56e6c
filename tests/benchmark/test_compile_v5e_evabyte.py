"""The step of ``evabyte.train_pack32k`` compiled for a described v5e at
its real shapes (published widths, four layers, one row of 32,768 bytes),
the way ``test_compile_v5e_nemotron.py`` does for the cell before it: the
record of how many heads are held (the configuration's file quotes these
bytes) and the proof that the chip's compiler takes the program.  No chip,
no chip time; a compile that passes is not a chip run.

The count the decision rests on is ``memory_analysis().peak_memory_in_bytes``,
the most the program holds at one time with buffers reused: at 16 of 32
heads 15,768,649,728 of 16,911,433,728 bytes, which leaves the 1 GB ISSUE 31
asks for (1.14); at 8 heads 14,610,534,400.  ``test_compile_v5e.py``'s
``total_bytes`` (arguments + outputs + temporaries - aliases) reads
18,997,351,936 and 17,680,603,648: it adds every temporary as if none
shared its bytes with another, and the chip's own compiler reports the
same 18,997,359,616 for the program that loads and runs there (PERF.md,
PR 31)."""

import os

import numpy as np

import test_compile_v5e as rehearsal
from bench_tiny import ROOT, load
from benchmark import harness
from test_compile_v5e import one_chip  # noqa: F401  (the module's fixture)

CELL = "evabyte.train_pack32k"


def packed_batch(cell, length):
    tok = np.full((int(cell.traffic["batch_size"]), length), 70, np.int64)
    return {"net_input": {"src_tokens": tok}, "target": tok}


def test_cell_step_compiles_for_v5e(one_chip, monkeypatch):  # noqa: F811
    monkeypatch.setattr(rehearsal, "example_batch", packed_batch)
    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    compiled = rehearsal.compile_step(
        cell, cell.traffic["task_args"]["tokens_per_sample"], one_chip,
        monkeypatch,
    )
    text = compiled.as_text()
    m = compiled.memory_analysis()
    # the blockwise attention kernels (forward, its rematerialized copy, dq,
    # dkv); the visibility mask is a constant and needs no bias gradient
    assert text.count("tpu_custom_call") >= 4
    assert "flash_bwd_dbias" not in text
    assert "eva_agg" in text and "eva_prep_kv" in text and "rotary" in text
    # bytes read when the heads were chosen: peak 15,768,649,728 (8 heads:
    # 14,610,534,400), total_bytes 18,997,351,936.  Held: the peak, with
    # 1 GB of the chip left; neither number is pinned to those readings (a
    # later PR that frees an activation may not edit this file)
    rehearsal.fits_the_chip(compiled, CELL)
    # the state is donated: parameters, master and moments are updated in place
    assert m.alias_size_in_bytes > 9.5e9
