"""Shared by the benchmark's tests (imported as ``bench_tiny``): a tiny copy of each cell (same files,
same code paths, sizes a CPU test run can hold) written into a temporary
checkout, and a stand-in for the harness's look for a chip."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "benchmark")

TINY = {
    "bert_base.train_mlm512": {
        "config": dict(encoder_layers=2, encoder_embed_dim=64,
                       encoder_ffn_embed_dim=128, encoder_attention_heads=4,
                       max_seq_len=128, vocab_size=200),
        "corpus": dict(vocab=200, n_docs=64, doc_words=[130, 160]),
        "traffic": dict(batch_size=4, reference_rows=2, warm_updates=1),
    },
    "unimol.train_mol256": {
        "config": dict(encoder_layers=2, encoder_embed_dim=64,
                       encoder_ffn_embed_dim=128, encoder_attention_heads=8,
                       max_seq_len=64, gaussian_kernels=16),
        "corpus": dict(n_records=256,
                       atoms=dict(median=12, sigma=0.5, min=8, max=60)),
        "traffic": dict(batch_size=4, reference_rows=2, warm_updates=1,
                        pad_edges=[16, 32, 48, 64]),
    },
}


#: limits of ``correct`` at the tiny size, read there the way PERF.md
#: section 2 reads them at the real size.  In float32 (program and reference
#: differ by summation order only) sound runs read at most 2e-6 / 2e-6 / 5e-7
#: over six seeds, and the control of a float32 configuration, the reference
#: in bfloat16, reads 2e-5..1e-4 / 2e-2..3e-1 / 1e-3..8e-3.  At widths of 64
#: bfloat16's own noise is as large as int8's, so a bfloat16 tiny run gets
#: wide limits: it is there to drive the bfloat16 path, not to separate.
LIMITS = {
    True: {"loss_rel_gap": 1e-5, "grad_norm_gap": 5e-4, "delta_norm_gap": 3e-4},
    False: {"loss_rel_gap": 5e-4, "grad_norm_gap": 1.0, "delta_norm_gap": 5e-2},
}


def manifest_with_candidates():
    """``BENCHMARK.json`` plus the cells whose files are in the tree but
    which the manifest does not name yet (``*.manifest.json`` beside the
    cell's file holds the entries a later PR adds; PERF.md says why each is
    waiting).  Their references, flops and step programs are tested all
    the same."""
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    folder = os.path.join(BENCH, "workloads")
    for name in sorted(os.listdir(folder)):
        if not name.endswith(".manifest.json"):
            continue
        extra = load(os.path.join(folder, name))
        if all(w["name"] != extra["workload"]["name"] for w in manifest["workloads"]):
            manifest["configs"].append(extra["config"])
            manifest["workloads"].append(extra["workload"])
            for m in manifest["end_to_end"]:
                if m["name"] == "train_tokens_per_s":
                    m["workloads"].append(extra["workload"]["name"])
    return manifest


def load(path):
    with open(path) as f:
        return json.load(f)


def fake_chip(chips):
    """What ``harness.require_chips`` returns, without the chip."""
    from unicore_tpu.platform_utils import describe_devices

    return describe_devices(), load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]


def tiny_checkout(tmp_path, cell_name, float32=False):
    """A checkout at ``tmp_path`` holding ``cell_name`` at its tiny size.
    Returns (root, base): what ``benchmark.run.run`` takes."""
    manifest = manifest_with_candidates()
    entry = next(w for w in manifest["workloads"] if w["name"] == cell_name)
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    tiny = TINY[cell_name]
    cfg = load(os.path.join(ROOT, cfg_entry["file"]))
    cfg.update(tiny["config"])
    if float32:
        cfg["train_args"]["bf16"] = False
    tr = load(os.path.join(BENCH, "workloads", cell_name + ".json"))
    tr["corpus"].update(tiny["corpus"])
    tr.update(tiny["traffic"])
    tr["limits"] = LIMITS[bool(float32)]
    root = str(tmp_path)
    base = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(base, "workloads"))
    os.makedirs(os.path.dirname(os.path.join(root, cfg_entry["file"])), exist_ok=True)
    with open(os.path.join(root, cfg_entry["file"]), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(base, "workloads", cell_name + ".json"), "w") as f:
        json.dump(tr, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    os.symlink(os.path.join(ROOT, "unicore_tpu"), os.path.join(root, "unicore_tpu"))
    return root, base



def tiny_epoch_rows(tmp_path, cell_name, seed=12345678901):
    """The rows of every batch of the tiny cell's first epoch and of the
    first batch of its second, as the driver's feed hands them out; checks
    on the way that the feed counts its epochs.  A tiny cell's epoch has to
    hold whole batches only (``assert_whole_batches``): a short last batch
    is a shape of its own, which a window that reaches it compiles
    (``recompiles_in_window`` 1, by the host's speed)."""
    from benchmark import harness, traffic
    from benchmark.drivers import train

    root, base = tiny_checkout(tmp_path, cell_name)
    cell = harness.Cell(harness.load_manifest(root), cell_name, base, root)
    feed = train.open_feed(cell, seed, os.path.join(root, "corpus"))[2]

    def rows_of_next():
        return len(traffic._get(next(feed), cell.traffic["token_key"]))

    rows = [rows_of_next()]  # the first batch opens the epoch: its length is known
    rows += [rows_of_next() for _ in range(feed.batches - 1)]
    assert (feed.epoch, feed.at) == (1, feed.batches)
    rows.append(rows_of_next())
    assert (feed.epoch, feed.at) == (2, 1)
    return rows


def assert_whole_batches(tmp_path, cell_name):
    rows = tiny_epoch_rows(tmp_path, cell_name)
    assert set(rows) == {TINY[cell_name]["traffic"]["batch_size"]}, rows
