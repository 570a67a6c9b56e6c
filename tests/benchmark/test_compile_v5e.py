"""Compile-for-a-described-v5e rehearsal of each cell's program at its real
shapes: the trainer's own jitted step, at published widths and depth, at
the cell's batch.  No chip, no chip time; a compile that passes is not a
chip run.  ``memory_analysis()`` here is the record of how each batch was
chosen (the cell files quote these bytes).

What is HELD is what the chip holds: ``peak_memory_in_bytes``, the most the
program keeps at one time with buffers reused, under the described chip's
16,911,433,728 with 1 GB of room (:func:`fits_the_chip`), and over the
quarter of a chip a cell has to fill.  ``total_bytes`` (arguments + outputs
+ temporaries - aliases) counts every temporary as if none shared its
bytes with another: it reads 19.0 GB for a step that loads and runs on the
chip (PERF.md, PR 31), so it is printed and bounds nothing.  Neither number
is pinned to what it read when the batch was chosen: a later PR that keeps
an activation, or frees one, moves both and may not edit this file.

The topology is described inside a module-scoped fixture, never at import
(only one process may load libtpu, and every xdist worker imports every
test file); where it cannot be described the tests skip.
"""

import os
from argparse import Namespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench_tiny import manifest_with_candidates
from benchmark import harness
from benchmark.drivers import train

HBM = 16_911_433_728  # 15.75 GiB: what the v5e compiler allows a program


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from unicore_tpu.ops import _pallas

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    interpret_was = _pallas._override
    _pallas.set_interpret(False)
    try:
        yield topo.devices[0]
    finally:
        _pallas.set_interpret(interpret_was)
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


def example_batch(cell, length):
    """Shapes and dtypes of one collated batch of the cell at ``length``."""
    B, cfg = int(cell.traffic["batch_size"]), cell.config
    tok = np.full((B, length), 5, np.int64)
    if cfg["task"] == "bert":
        return {"net_input": {"src_tokens": tok}, "target": tok}
    pair = np.ones((B, length, length), np.float32)
    xyz = np.ones((B, length, 3), np.float32)
    return {
        "net_input": {"src_tokens": tok, "src_coord": xyz,
                      "src_distance": pair,
                      "src_edge_type": pair.astype(np.int64)},
        "target": {"tokens_target": tok, "coord_target": xyz,
                   "distance_target": pair},
    }


def compile_step(cell, length, device, monkeypatch):
    """The trainer's ``train_step`` program for one described chip."""
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models import ARCH_MODEL_REGISTRY
    from unicore_tpu.tasks.unicore_task import UnicoreTask
    from unicore_tpu.trainer import Trainer

    # the gates ask on_tpu() and the trainer lays its mesh over
    # jax.devices(): the test hands both the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [device])
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    monkeypatch.setattr(jax, "local_device_count", lambda *a: 1)
    one = SingleDeviceSharding(device)
    cfg = cell.config

    class Dict:
        def pad(self):
            return 1 if cfg["task"] == "bert" else 0

        def __len__(self):
            return cfg["vocab_size"]

    class Task(UnicoreTask):
        dictionary = Dict()

    args = train.trainer_args(cell, "/nonexistent", 1)
    task = Task(args)
    model = ARCH_MODEL_REGISTRY[cfg["arch"]].build_model(args, task)
    trainer = Trainer(args, task, model, LOSS_REGISTRY[cfg["loss"]](task))
    sample = example_batch(cell, length)

    def described(tree, floating=None):
        def one_leaf(a):
            dtype = a.dtype
            if floating is not None and jnp.issubdtype(dtype, jnp.floating):
                dtype = floating
            if dtype == np.int64:
                dtype = jnp.int32
            return jax.ShapeDtypeStruct(a.shape, dtype, sharding=one)
        return jax.tree_util.tree_map(one_leaf, tree)

    params = described(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), sample)
    ), jnp.bfloat16)
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one)
    state = {
        "params": params,
        "opt": described(jax.eval_shape(trainer.optimizer.init_state, params)),
        "loss_scale": scalar(jnp.float32), "since_overflow": scalar(jnp.int32),
        "since_rescale": scalar(jnp.int32),
        "overflows_since_rescale": scalar(jnp.int32),
    }
    scalars = jax.tree_util.tree_map(
        lambda a: scalar(a.dtype), trainer._step_scalars(0, 1.0)
    )
    return trainer._get_jit("train_step").lower(
        state, described(sample), scalars, None
    ).compile()


def total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def fits_the_chip(compiled, what, fills=True):
    """Hold the step to what the chip holds: its peak leaves 1 GB of the
    described chip's memory and (``fills``: at the cell's largest shape) is
    over the quarter a cell has to fill.  Prints the peak and
    ``total_bytes`` (``pytest -s`` shows them); returns the peak."""
    peak = compiled.memory_analysis().peak_memory_in_bytes
    print(f"{what}: peak_memory_in_bytes {peak:,} of {HBM:,}; "
          f"total_bytes {total_bytes(compiled):,} (no bound)")
    assert HBM - peak >= 1e9, peak
    if fills:
        assert peak > 0.25 * HBM, peak
    return peak


@pytest.mark.parametrize("name,length,kernels", [
    # read when the batch was chosen: total_bytes 7,439,044,608; since PR 38
    # keeps two activations a layer, peak 10,031,237,120 (total_bytes
    # 10,268,964,864)
    ("bert_base.train_mlm512", 512, 24),
    # total_bytes 8,307,357,696 at the largest edge when the batch was
    # chosen; the common edge has to fit too
    ("unimol.train_mol256", 256, 30),
    ("unimol.train_mol256", 128, 30),
])
def test_cell_step_compiles_for_v5e(name, length, kernels, one_chip,
                                    monkeypatch):
    cell = harness.Cell(manifest_with_candidates(), name)
    compiled = compile_step(cell, length, one_chip, monkeypatch)
    assert compiled.as_text().count("tpu_custom_call") >= kernels
    fits_the_chip(
        compiled, f"{name} at {length}",
        fills=length == max(cell.traffic.get("pad_edges", [length])),
    )
