"""Write a cell's train-step program as text, to compare two checkouts.

    JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0 python scripts/step_program.py <cell> <out> [<checkout>]

The step is the trainer's own, at the cell's real shapes, compiled for a
described v5e by ``tests/benchmark/test_compile_v5e.compile_step`` (no chip,
no chip time, nothing runs): ``<out>`` gets its optimized HLO with source
locations removed and ``op_name`` paths kept.  A change that is to leave a
cell's program as it is (a refactoring, a ``simplicity`` PR) runs this over
the parent's checkout (``git archive <commit> | tar -x -C <dir>``) and its
own, and ``cmp`` says whether the two texts are one.

With ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0`` JAX records no Python frame at
all; without it the text still differs between two checkouts that differ in
nothing else, because the Mosaic kernels' serialized bodies carry the
frames' file names and lines where no text filter reaches them.  What this
script removes itself is what is left: the header's frame tables and each
instruction's ``stack_frame_id`` / ``source_file`` / ``source_line``.
"""

import os
import re
import sys


def without_locations(text):
    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n(?:\d+ [^\n]*\n)*", "\n",
                  text, count=1, flags=re.S)
    text = re.sub(r' stack_frame_id=\d+| source_file="[^"]*"', "", text)
    return re.sub(r" source_(?:end_)?(?:line|column)=\d+", "", text)


def main(cell_name, out, root=None):
    root = os.path.abspath(root or os.path.join(os.path.dirname(__file__), ".."))
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "tests", "benchmark")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    import test_compile_v5e as rehearsal
    import unicore_tpu
    from bench_tiny import ROOT, load
    from benchmark import harness
    from unicore_tpu.ops import _pallas

    assert os.path.dirname(os.path.abspath(unicore_tpu.__file__)) == os.path.join(
        root, "unicore_tpu"), unicore_tpu.__file__

    class Setter:  # compile_step's ``monkeypatch``: this process ends with it
        setattr = staticmethod(setattr)

    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    _pallas.set_interpret(False)
    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), cell_name)
    if cell.config["task"] == "causal_lm":  # one packed batch, as the cells' tests
        length = cell.traffic["task_args"]["tokens_per_sample"]
        tok = np.full((int(cell.traffic["batch_size"]), length), 70, np.int64)
        rehearsal.example_batch = lambda cell, length: {
            "net_input": {"src_tokens": tok}, "target": tok}
    else:
        length = max(cell.traffic.get("pad_edges", [512]))
    compiled = rehearsal.compile_step(cell, length, device, Setter())
    text = without_locations(compiled.as_text())
    with open(out, "w") as f:
        f.write(text)
    print(f"{cell_name}: {len(text):,} characters, peak_memory_in_bytes "
          f"{compiled.memory_analysis().peak_memory_in_bytes:,}")


if __name__ == "__main__":
    main(*sys.argv[1:4])
