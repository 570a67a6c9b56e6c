"""``evabyte`` and its cell through the benchmark's own code at a size a test
run can hold: the manifest's entries and the configuration file's
statements, the plain reference following the program over three updates,
a ``correct`` that notices mathematics left out, the operation counts and
the readers of the new per-layer metrics.  The step compiled for a
described v5e: ``test_compile_v5e_evabyte.py``.  (The cases a ``test_manifest.py``
or ``test_flops.py`` would hold for the new files are here: a PR that adds
a cell edits no benchmark file that is there.)"""

import json
import os

import numpy as np
import pytest

import bench_tiny
from bench_tiny import BENCH, ROOT, load, tiny_checkout
from benchmark import control, harness

CELL = "evabyte.train_pack32k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the cell at a tiny size: one of 2 shares of 8 heads of 8, 3 of 6 layers,
# windows of 32 bytes in chunks of 4, 3 bytes predicted, the feed-forward
# layer and the loss in chunks, 2 rows x 128 bytes (4 windows) an update;
# 7,680 words x 5 bytes are 300 blocks: every batch has both its rows
bench_tiny.TINY.setdefault(CELL, {
    "config": dict(
        hidden_size=64, num_hidden_layers=6, layers_held=3,
        num_attention_heads=8, num_key_value_heads=8, attention_shares=2,
        intermediate_size=96, window_size=32, chunk_size=4, num_pred_heads=3,
        loss_chunk=48, mlp_row_chunk=64,
    ),
    "corpus": dict(vocab=200, n_docs=64, doc_words=[40, 200]),
    "traffic": dict(
        batch_size=2, warm_updates=1, reference_rows=1,
        task_args=dict(mask_prob=1.0, tokens_per_sample=128, seq_pad_multiple=8),
    ),
})


def checks_of(out):
    return {c["name"]: c["value"] for c in out["checks"]}


# -- what the files state ---------------------------------------------------------

# ``checkout`` / ``manifest``: conftest.py's, the manifest as it is and with
# an append (what is asserted of it has to hold on both)

@pytest.fixture(scope="module")
def config():
    return load(os.path.join(BENCH, "configs", "evabyte.json"))


NEW = ["eva_agg_device_pct", "eva_agg_roofline_pct", "eva_prep_kv_device_pct",
       "eva_prep_kv_roofline_pct", "eva_keys_computed_over_visible"]


def test_the_cell_and_its_metrics_are_in_the_manifest(checkout):
    manifest = checkout.manifest
    cell = checkout.cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "train"
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW) | {
        "train_mfu_pct", "peak_hbm_gib", "train_step_ms", "data_wait_ms",
        "device_idle_pct", "pallas_device_pct",
        # the accepted readers that find something to read in the cell
        "attention_device_pct", "ffn_device_pct", "lm_head_loss_device_pct",
        "optimizer_share_pct", "unattributed_device_pct",
        "attn_kernel_fwd_device_pct", "attn_kernel_bwd_device_pct",
        "attn_kernel_fwd_roofline_pct", "attn_kernel_bwd_roofline_pct",
        "step_host_ms", "step_h2d_ms", "step_launch_ms",
        "data_buffer_depth", "data_produce_ms"} <= mine
    assert not mine & {"ssm_device_pct", "moe_device_pct",
                       "moe_load_max_over_mean"}
    # the new metrics are this cell's alone, each listed once, in the order
    # they were appended in and after the last the benchmark had then (all
    # found by name: whoever appends after them moves nothing asserted here)
    listed = [m["name"] for m in manifest["per_layer"]]
    at = [listed.index(name) for name in NEW]
    assert at == sorted(at) and listed.index("moe_load_max_over_mean") < at[0]
    assert all(listed.count(name) == 1 for name in NEW)
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == "evabyte")
    assert entry["config"] == "evabyte" and entry["traffic"] == "train_pack32k"
    # the cell and the configuration that were there last stand before it
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index("nemotron3_super_120b.train_pack8k") < cells.index(CELL)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("nemotron3_super_120b") < configs.index("evabyte")
    for name in mine:  # every reader is there, and finds nothing to read
        reader = harness.load_module("layer_metrics", name, checkout.base)
        assert reader.read({"peaks": {}, "base": BENCH}) is None or name in (
            "peak_hbm_gib",)
    for kind in ("reference", "flops"):
        harness.find(kind, cell.config[kind] + ".py")
    harness.find("flops", "evabyte_scopes.py")
    tr = cell.traffic
    assert tr["batch_size"] * tr["task_args"]["tokens_per_sample"] == 32768
    assert tr["corpus"] == {"kind": "text", "vocab": 16384, "n_docs": 1024,
                            "doc_words": [256, 16384]}
    assert tr["task_args"]["seq_pad_multiple"] == 128
    assert (tr["data_workers"], tr["data_buffer"]) == (2, 8)
    assert len(entry["why"]) <= 200
    assert len(cfg_entry["why"]) <= 200


def test_the_configuration_states_its_source_its_cuts_and_what_it_assumed(
        manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == "evabyte")
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/evabyte.json"
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "attention_shares", "layers_held"]
    for key in config["reduced"]:
        assert key in config and key in config["published"], key
    # the cut in depth and heads is stated by the program's own keys; the
    # published counts stay as published
    assert (config["num_hidden_layers"], config["num_attention_heads"],
            config["num_key_value_heads"], config["num_pred_heads"],
            config["vocab_size"]) == (32, 32, 32, 8, 320)
    assert config["layers_held"] >= 4          # the floor: four layers
    assert config["attention_shares"] in (2, 4)
    assert f"{config['attention_shares']} chips" in config["deployment"]
    assert config["deployment"] in config["reduced_why"]
    for stated in ("equations", "rotary", "tokenizer", "prediction heads",
                   "optimizer", "packing", "pooling vectors", "norm"):
        assert config["assumed"][stated]
    assert config["tokenizer"] == "bytes" and config["remat"] is True
    assert config["train_args"]["adam_betas"] == [0.9, 0.95]
    assert set(config["train_args"]["no_weight_decay_names"].split(",")) == {
        "norm", "adaptive_mu_k", "adaptive_phi"}


def test_no_key_differs_from_the_catalog_row(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the architectures catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    for key, value in row["config"].items():
        assert config[key] == value, key
    assert not set(config["reduced"]) & set(row["config"])


def test_the_share_counts_its_stated_parameters(config):
    import jax

    ref = harness.load_module("reference", "evabyte")
    shapes = ref.param_shapes(config, {"vocab_size": config["vocab_size"]})
    count = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    heads = 32 // config["attention_shares"]
    layer = (4 * 4096 * heads * 128 + 2 * heads * 128 + 3 * 4096 * 11008
             + 2 * 4096)
    assert count == 4 * layer + 320 * 4096 + 4096 * 8 * 320 + 4096
    stated = {2: "687.1 M", 4: "620.0 M"}[config["attention_shares"]]
    assert stated in config["reduced_why"], count
    assert 0.25 * 16e9 < 16 * count < 0.75 * 16e9  # 16 bytes a parameter


def test_the_program_builds_the_references_tree(config):
    """At the real widths, from shapes alone: the program's parameter tree
    is the one ``param_shapes`` states, leaf for leaf."""
    import jax

    from benchmark.drivers import train
    from unicore_tpu.models import ARCH_MODEL_REGISTRY
    from unicore_tpu.tasks.causal_lm import CausalLMTask

    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    args = train.trainer_args(cell, "/nonexistent", 1)
    task = CausalLMTask.setup_task(args)
    model = ARCH_MODEL_REGISTRY[config["arch"]].build_model(args, task)
    tok = np.zeros((1, 4096), np.int32)
    got = jax.eval_shape(lambda: model.init_params(
        jax.random.PRNGKey(0), {"net_input": {"src_tokens": tok}}))
    want = harness.load_module("reference", "evabyte").param_shapes(
        config, {"vocab_size": len(task.dictionary)})
    flat = lambda t: {
        jax.tree_util.keystr(p): tuple(x.shape)
        for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    assert flat(got) == flat(want)


# -- correct ----------------------------------------------------------------------

def test_reference_follows_the_program_in_float32(run_tiny):
    """Loss, first gradient and three updates: windows as batch rows under
    one grouped mask against a mask over the whole row, the chunked
    feed-forward layer and loss against whole rows, the trainer's Adam
    against the leaf-by-leaf follower."""
    out, last = run_tiny(CELL, float32=True)
    got = checks_of(out)
    assert last["correct"] is True and last["failed"] == 0, out["checks"]
    for step in (1, 2, 3):
        assert got[f"loss_rel_gap.step{step}"] < 2e-6
    assert got["first_grad_norm_gap.worst_leaf"] < 5e-5
    assert got["param_change_norm_gap.worst_leaf"] < 5e-5
    assert got["recompiles_in_window"] == 0
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # what the readers of a traced run would be handed
    line = json.loads(harness.result_line(
        harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL),
        out, trace=True))["metrics"]
    assert line["train_mfu_pct"]["value"] > 0
    assert not set(NEW) & set(line)  # no trace on a CPU: left out, not raised


def test_sound_bfloat16_run_is_correct_on_a_large_seed(run_tiny):
    out, last = run_tiny(CELL, seed=2 ** 31 + 977)
    assert last["correct"] is True, out["checks"]


def test_the_tiny_epoch_holds_whole_batches(tmp_path):
    """No batch of the tiny cell's epoch is short (a shape of its own, which
    a window that reaches it compiles: ROADMAP D18), and the feed counts
    its epochs."""
    bench_tiny.assert_whole_batches(tmp_path, CELL)


def _no_summaries(monkeypatch):
    from unicore_tpu.modules import eva_attention

    real = eva_attention.eva_prep_kv
    monkeypatch.setattr(  # the earlier windows' values are zero
        eva_attention, "eva_prep_kv",
        lambda *a: (lambda k, v: (k, v * 0.0))(*real(*a)),
    )


def _no_rotary(monkeypatch):
    from unicore_tpu.modules import eva_attention

    monkeypatch.setattr(eva_attention, "apply_rotary", lambda x, *_: x)


def _one_head_of_the_loss_short(monkeypatch):
    from unicore_tpu.losses import lm_cross_entropy

    real = lm_cross_entropy.shifted_targets
    monkeypatch.setattr(  # the last prediction head is never scored
        lm_cross_entropy, "shifted_targets",
        lambda target, heads, pad: real(target, heads, pad).at[..., -1].set(pad),
    )


@pytest.mark.parametrize("fault", [_no_summaries, _no_rotary,
                                   _one_head_of_the_loss_short])
def test_mathematics_left_out_is_not_correct(fault, run_tiny, monkeypatch):
    """The chunk summaries' values, the rotary term, or one prediction
    head's share of the loss left out of the timed path: ``correct`` comes
    out false."""
    fault(monkeypatch)
    out, last = run_tiny(CELL, float32=True)
    assert last["correct"] is False
    failed = {c["name"] for c in out["checks"] if not c["value"] <= c["limit"]}
    assert failed & {"first_grad_norm_gap.worst_leaf", "loss_rel_gap.step1"}, failed


def test_the_lower_precision_control_is_not_correct(tmp_path):
    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    c = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root)
    checks = control.control_checks(c, seed=2 ** 31 + 3, precision="bfloat16")
    assert harness.report_checks(checks) is False


def test_the_reference_notices_what_it_is_told_to_leave_out(tmp_path):
    from benchmark import weights

    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    cfg = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root).config
    ref = harness.load_module("reference", "evabyte")
    params = weights.make(ref.param_shapes(cfg, {"vocab_size": 320}), 5)
    tok = np.random.default_rng(0).integers(64, 320, (1, 100)).astype(np.int32)
    whole = np.asarray(ref.hidden(params, cfg, tok))
    assert np.abs(np.asarray(ref.hidden(params, cfg, tok, leave_out="summaries"))
                  - whole).max() > 1e-4
    # the first window sees no summary: leaving them out changes nothing there
    np.testing.assert_allclose(
        np.asarray(ref.hidden(params, cfg, tok[:, :32], leave_out="summaries")),
        np.asarray(ref.hidden(params, cfg, tok[:, :32])), atol=1e-6)


def test_reference_blocks_are_the_whole_computation(tmp_path, monkeypatch):
    """Query blocks and row blocks (with a padded last block) give what
    one block gives."""
    from benchmark import weights

    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    cfg = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root).config
    ref = harness.load_module("reference", "evabyte")
    params = weights.make(ref.param_shapes(cfg, {"vocab_size": 320}), 7)
    tok = np.random.default_rng(1).integers(64, 320, (2, 100)).astype(np.int32)
    batch = {"net_input": {"src_tokens": tok}, "target": tok}
    whole = float(ref.loss_sum(params, cfg, batch, 0))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 24)
    monkeypatch.setattr(ref, "ROW_BLOCK", 48)
    assert float(ref.loss_sum(params, cfg, batch, 0)) == pytest.approx(whole, rel=1e-6)
    assert ref.sample_size(batch, cfg, 0) == 2 * (99 + 98 + 97)


# -- counts ------------------------------------------------------------------------

def test_operation_counts_from_shapes(config):
    flops = harness.load_module("flops", "evabyte")
    heads = 32 // config["attention_shares"]
    inner = heads * 128
    body, head = flops.forward_per_token(config)
    assert head == 2 * 4096 * 8 * 320
    layer = (2 * 4096 * 3 * inner + 2 * inner * 4096 + 8 * inner
             + 2 * 4096 * 2 * 11008 + 2 * 11008 * 4096)
    assert body == 4 * layer
    n = 32768
    visible = 16 * 2048 * 2049 // 2 + 2048 * 128 * 120
    assert flops.visible_keys(n, 2048, 16) == visible
    # a short row: one whole window and half of the next
    assert flops.visible_keys(3072, 2048, 16) == (
        2048 * 2049 // 2 + 1024 * 1025 // 2 + 1024 * 128)
    total = flops.train_flops(config, 10 * n, 10 * n * n, 1.0)
    assert total == pytest.approx(
        3 * (10 * n * (body + head) + 10 * visible * 4 * 4 * inner))
    # about 6 x the matrices' parameters a byte, and the visible keys' share
    assert 4.0e9 < total / (10 * n) < 4.6e9
    run = {"config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * n, "updates": 10}
    scopes = harness.load_module("flops", "evabyte_scopes")
    ops, nbytes = scopes.eva_agg(run)
    assert ops == pytest.approx(3 * visible * 4 * 4 * inner)
    assert nbytes > 0 and ops / nbytes > 240   # the MXU bounds it on a v5e
    ops, nbytes = scopes.eva_prep_kv(run)
    assert ops == pytest.approx(3 * 4 * n * 8 * inner)
    row = 2 * inner
    assert nbytes == pytest.approx(4 * n * row * (2 + 1 / 8 + 4 + 1 / 8))
    assert ops / nbytes < 240                  # the bytes bound it


def test_readers_of_the_new_metrics():
    """On a run whose pass over the trace is already made: the two scopes'
    shares and roofline shares; the key counts from the marks; and nothing,
    not an error, from a program that leaves no such scope or mark (the
    parent commit)."""
    config = load(os.path.join(BENCH, "configs", "evabyte.json"))
    n = 32768
    passed = {"device_op_s": 10.0, "program_runs": 5,
              "seconds": {"eva_agg": 2.0, "eva_prep_kv": 0.1, "self_attn": 3.0},
              "inside": {}, "route": {}, "route_marks": 0}
    named = {"groups_s": {"rest": 1.0}, "host": {"updates": 5}}
    run = {"scope_pass": passed, "program_trace": named, "config": config,
           "base": BENCH, "sum_n": 10 * n, "sum_n2": 10 * n * n, "updates": 10,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "eva_key_marks": [
               {"keys_computed": 134217728, "keys_visible": 65028096},
               {"keys_computed": "134217728", "keys_visible": "65028096"}]}
    read = lambda name, r=run: harness.load_module("layer_metrics", name).read(r)
    assert read("eva_agg_device_pct") == pytest.approx(20.0)
    assert read("eva_prep_kv_device_pct") == pytest.approx(1.0)
    scopes = harness.load_module("flops", "evabyte_scopes")
    ops, _ = scopes.eva_agg(run)
    assert read("eva_agg_roofline_pct") == pytest.approx(
        100 * ops / 197e12 * 5 / 2.0)
    _, nbytes = scopes.eva_prep_kv(run)
    assert read("eva_prep_kv_roofline_pct") == pytest.approx(
        100 * nbytes / 819e9 * 5 / 0.1)
    assert 0 < read("eva_agg_roofline_pct") < 100
    assert read("eva_keys_computed_over_visible") == pytest.approx(
        134217728 / 65028096)
    # operations were named and none ran under the scopes: 0, as the other
    # scopes' readers say it
    bare = dict(run, scope_pass=dict(passed, seconds={"moe": 1.0}),
                eva_key_marks=[])
    assert read("eva_agg_device_pct", bare) == 0
    assert read("eva_agg_roofline_pct", bare) == 0
    assert read("eva_keys_computed_over_visible", bare) == 0
    # a program that writes no annotations at all: nothing to count
    assert read("eva_keys_computed_over_visible", dict(
        bare, program_trace=dict(named, host={}))) is None
    # no scope table, or no trace at all: nothing to read
    for name in NEW:
        assert read(name, {"program_trace": None, "peaks": {}, "base": BENCH}) is None
        assert read(name, {"peaks": {}, "base": BENCH}) is None
