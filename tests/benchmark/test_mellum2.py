"""``mellum2_12b`` and its cell through the benchmark's own code at a size a
test run can hold: the manifest's entries and the configuration file's
statements, the plain reference following the program over three updates,
a ``correct`` that notices a mechanism left out (a window, the rotary
scale), the operation counts and the readers of the new per-layer metrics.
The step compiled for a described v5e: ``test_compile_v5e_mellum2.py``.
(The cases a ``test_manifest.py`` or ``test_flops.py`` would hold for the
new files are here: a PR that adds a cell edits no benchmark file that is
there.)"""

import json
import os

import numpy as np
import pytest

import bench_tiny
from bench_tiny import BENCH, ROOT, load, tiny_checkout
from benchmark import control, harness

CELL = "mellum2_12b.train_pack32k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 100, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 4,
        "beta_slow": 1, "attention_factor": 1.1386294361119891},
    "sliding_attention": {"rope_type": "default", "rope_theta": 100},
}

# the cell at a tiny size: 3 of 6 layers (sliding, sliding, full), one of 2
# shares of 8 query heads on 4 KV heads of 16, a window of 16, a YaRN table
# whose original context is 32, 8 of 16 experts 4 a token, 2 rows x 128
# tokens an update (past the window and the original context); the groups
# as JSON text, which the train driver hands on to the program
bench_tiny.TINY.setdefault(CELL, {
    "config": dict(
        hidden_size=64, num_hidden_layers=6, layers_held=3,
        layer_types=json.dumps(
            ["sliding_attention", "sliding_attention", "full_attention"] * 2),
        mlp_layer_types=json.dumps(["sparse"] * 6),
        rope_parameters=json.dumps(TINY_ROPE),
        num_attention_heads=8, num_key_value_heads=4, head_dim=16,
        attention_shares=2, sliding_window=16, num_experts=16,
        num_experts_per_tok=4, num_experts_held=8, moe_intermediate_size=48,
        vocab_size=200, loss_chunk=48,
    ),
    # 64 documents of 40 .. 204 words are 62 blocks of 128 tokens, the same
    # for every seed: every batch of an epoch has both its rows
    "corpus": dict(vocab=200, n_docs=64, doc_words=[40, 204]),
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
    return load(os.path.join(BENCH, "configs", "mellum2_12b.json"))


NEW = ["band_attn_device_pct", "band_attn_roofline_pct",
       "band_keys_computed_over_visible", "moe_gated_roofline_pct",
       "rotary_device_pct", "band_window_keys_computed_over_visible",
       "band_full_keys_computed_over_visible"]
LISTED = ["attention_device_pct", "lm_head_loss_device_pct",
          "optimizer_share_pct", "unattributed_device_pct",
          "attn_kernel_fwd_device_pct", "attn_kernel_bwd_device_pct",
          "step_host_ms", "step_h2d_ms", "step_launch_ms",
          "data_buffer_depth", "data_produce_ms", "data_pack_ms",
          "moe_device_pct", "moe_routed_device_pct", "moe_load_max_over_mean",
          "xla_matmul_device_pct", "xla_matmul_roofline_pct",
          "attn_proj_roofline_pct", "optimizer_roofline_pct",
          "remat_device_pct"]


def test_the_cell_and_its_metrics_are_in_the_manifest(checkout):
    manifest = checkout.manifest
    cell = checkout.cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "train"
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW) | set(LISTED) | {
        "train_mfu_pct", "peak_hbm_gib", "train_step_ms", "data_wait_ms",
        "device_idle_pct", "pallas_device_pct"} <= mine
    # one keys_computed for two maps, a latent in the routed count, no
    # fc1 / fc2, no shared expert, no scan: not this cell's
    assert not mine & {
        "attn_kernel_fwd_roofline_pct", "attn_kernel_bwd_roofline_pct",
        "moe_routed_roofline_pct", "moe_shared_roofline_pct",
        "ffn_device_pct", "ffn_roofline_pct", "ssm_device_pct",
        "eva_agg_device_pct"}
    # the new metrics are this cell's alone, each listed once, in the order
    # they were appended in and after the last the benchmark had then
    listed = [m["name"] for m in manifest["per_layer"]]
    at = [listed.index(name) for name in NEW]
    assert at == sorted(at) and listed.index("data_pack_ms") < at[0]
    assert all(listed.count(name) == 1 for name in NEW)
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == "mellum2_12b")
    assert entry["config"] == "mellum2_12b" and entry["traffic"] == "train_pack32k"
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index("evabyte.train_pack32k") < cells.index(CELL)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("evabyte") < configs.index("mellum2_12b")
    for name in mine:  # every reader is there, and finds nothing to read
        reader = harness.load_module("layer_metrics", name, checkout.base)
        assert reader.read({"peaks": {}, "base": BENCH}) is None or name in (
            "peak_hbm_gib",)
    for kind in ("reference", "flops"):
        harness.find(kind, cell.config[kind] + ".py")
    harness.find("flops", "mellum2_scopes.py")
    tr = cell.traffic
    assert tr["batch_size"] * tr["task_args"]["tokens_per_sample"] == 32768
    assert tr["corpus"] == {"kind": "text", "vocab": 24576, "n_docs": 2048,
                            "doc_words": [512, 16384]}
    assert tr["task_args"]["seq_pad_multiple"] == 128
    assert (tr["data_workers"], tr["data_buffer"], tr["warm_updates"],
            tr["reference_rows"]) == (2, 8, 3, 1)
    assert len(entry["why"]) <= 200 and len(cfg_entry["why"]) <= 200


def test_the_configuration_states_its_source_its_cuts_and_what_it_assumed(
        manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == "mellum2_12b")
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/mellum2_12b.json"
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "attention_shares", "layers_held", "num_experts_held", "vocab_size"]
    for key in config["reduced"]:
        assert key in config and key in config["published"], key
    assert (config["num_hidden_layers"], config["num_attention_heads"],
            config["num_key_value_heads"], config["num_experts"],
            config["num_experts_per_tok"]) == (28, 32, 4, 64, 8)
    # the guide's floors: a whole period and four layers, 8 experts, an
    # eighth of the vocabulary
    assert config["layers_held"] >= 4
    assert config["layer_types"][:config["layers_held"]].count("full_attention") >= 1
    assert config["num_experts_held"] >= 8
    assert config["vocab_size"] * 8 >= 98304
    assert f"{config['attention_shares']} chips" in config["deployment"]
    assert config["deployment"] in config["reduced_why"]
    assert "531,452,160" in config["reduced_why"]
    for stated in ("equations", "q/k norm", "balancing loss",
                   "balancing rule", "MTP head", "experts", "router",
                   "rotary", "window", "optimizer", "packing"):
        assert config["assumed"][stated]
    # the one rule of training the cell adds to the published model
    assert config["router_balancing"] == "batch_bias"
    assert "2408.15664" in config["assumed"]["balancing rule"]
    assert "2408.15664" in config["papers"]
    assert config["remat"] is True
    assert config["train_args"]["adam_betas"] == [0.9, 0.95]


def test_no_key_differs_from_the_catalog_row(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the architectures catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert set(config["reduced"]) & set(row["config"]) == {"vocab_size"}
    assert row["config"]["vocab_size"] == 98304


def test_the_programs_defaults_are_the_files_groups(config):
    """The train driver hands the program the file's numbers and strings;
    its lists and groups reach the program as the program's own defaults,
    which are these very values."""
    from unicore_tpu.models import mellum

    assert json.loads(mellum.MELLUM2_LAYER_TYPES) == config["layer_types"]
    assert json.loads(mellum.MELLUM2_ROPE_PARAMETERS) == config["rope_parameters"]
    assert set(config["mlp_layer_types"]) == {"sparse"}
    fields = mellum.MellumModel.__dataclass_fields__
    for key, value in config.items():
        if key in fields and isinstance(value, (int, float, str)) and key not in (
                "name", "vocab_size", "router_balancing", *config["reduced"]):
            assert fields[key].default == value, key
    # the program's default is the published model: the top scores choose
    assert fields["router_balancing"].default == "none"


def test_the_share_counts_its_stated_parameters(config):
    import jax

    ref = harness.load_module("reference", "mellum2_12b")
    shapes = ref.param_shapes(config, {"vocab_size": config["vocab_size"]})
    count = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    layer = ((8 + 2 * 1) * 128 * 2304 + 8 * 128 * 2304 + 2304 * 64
             + 2 * 2304 + 16 * 3 * 2304 * 896)
    assert layer == 104_550_912
    assert count == 4 * layer + 2 * 24576 * 2304 + 2304 == 531_452_160
    assert 0.25 * 16.9e9 < 16 * count < 0.75 * 16.9e9  # 16 bytes a parameter


def test_the_program_builds_the_references_tree(config):
    """At the real widths, from shapes alone: the program's parameter tree
    is the one ``param_shapes`` states, leaf for leaf."""
    import jax

    from benchmark.drivers import train
    from unicore_tpu.models import ARCH_MODEL_REGISTRY

    class Dictionary:
        pad = staticmethod(lambda: 0)
        __len__ = lambda self: config["vocab_size"]

    class task:
        dictionary = Dictionary()

    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    args = train.trainer_args(cell, "/nonexistent", 1)
    model = ARCH_MODEL_REGISTRY[config["arch"]].build_model(args, task)
    assert model.pattern == "SRSRSRGR"
    tok = np.zeros((1, 256), np.int32)
    got = jax.eval_shape(lambda: model.init_params(
        jax.random.PRNGKey(0), {"net_input": {"src_tokens": tok}}))
    want = harness.load_module("reference", "mellum2_12b").param_shapes(
        config, {"vocab_size": len(task.dictionary)})
    flat = lambda t: {
        jax.tree_util.keystr(p): tuple(x.shape)
        for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    assert flat(got) == flat(want)


# -- correct ----------------------------------------------------------------------

def test_reference_follows_the_program_in_float32(run_tiny):
    """Loss, first gradient and three updates: the band as a mask of iotas
    against a mask over the whole row, two rotary tables, the sorted and
    tiled experts against dense products over all tokens, the chunked loss
    against row blocks, the trainer's Adam against the leaf-by-leaf
    follower."""
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
    bench_tiny.assert_whole_batches(tmp_path, CELL)


def _no_window(monkeypatch):
    from unicore_tpu.ops import flash_attention

    real = flash_attention.Band
    monkeypatch.setattr(  # the sliding layers see the whole row
        flash_attention, "Band", lambda window=None: real(None))


def _no_attention_factor(monkeypatch):
    from unicore_tpu.modules import rotary

    real = rotary.rope_table
    monkeypatch.setattr(  # YaRN's c left at 1
        rotary, "rope_table", lambda rp, D: (real(rp, D)[0], 1.0))


def _first_expert_left_out(monkeypatch):
    from unicore_tpu.modules import gated_moe

    real = gated_moe.top_k_set
    monkeypatch.setattr(  # expert 0 is never among the chosen
        gated_moe, "top_k_set",
        lambda x, k: (lambda idx, sel: (idx, sel.at[:, 0].set(False)))(
            *real(x, k)))


def _no_balancing(monkeypatch):
    from unicore_tpu.modules import gated_moe

    monkeypatch.setattr(  # the top logits choose: no noise, no bias
        gated_moe, "balanced_scores", lambda z, k: z)


@pytest.mark.parametrize("fault", [_no_window, _no_attention_factor,
                                   _first_expert_left_out, _no_balancing])
def test_a_mechanism_left_out_is_not_correct(fault, run_tiny, monkeypatch):
    """A layer's window, YaRN's attention factor, one held expert or the
    batch's bias left out of the timed path: ``correct`` comes out
    false (the comparison catches the mechanisms, not only the matmuls)."""
    fault(monkeypatch)
    out, last = run_tiny(CELL, float32=True)
    assert last["correct"] is False
    failed = {c["name"] for c in out["checks"] if not c["value"] <= c["limit"]}
    assert failed & {"first_grad_norm_gap.worst_leaf", "loss_rel_gap.step1"}, out["checks"]


def test_the_lower_precision_control_is_not_correct(tmp_path):
    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    c = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root)
    checks = control.control_checks(c, seed=2 ** 31 + 3, precision="bfloat16")
    assert harness.report_checks(checks) is False


def test_the_reference_notices_what_it_is_told_to_leave_out(tmp_path):
    from benchmark import weights

    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    cfg = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root).config
    ref = harness.load_module("reference", "mellum2_12b")
    params = weights.make(ref.param_shapes(cfg, {"vocab_size": 200}), 5)
    tok = np.random.default_rng(0).integers(5, 200, (1, 100)).astype(np.int32)
    whole = np.asarray(ref.hidden(params, cfg, tok))
    for what in ("window", "attention_factor"):
        assert np.abs(np.asarray(ref.hidden(params, cfg, tok, leave_out=what))
                      - whole).max() > 1e-5, what
    # inside the window nothing is hidden: leaving it out changes nothing
    np.testing.assert_allclose(
        np.asarray(ref.hidden(params, cfg, tok[:, :16], leave_out="window")),
        np.asarray(ref.hidden(params, cfg, tok[:, :16])), atol=1e-6)


def test_reference_blocks_are_the_whole_computation(tmp_path, monkeypatch):
    """Query blocks and row blocks (with a padded last block) give what
    one block gives."""
    from benchmark import weights

    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    cfg = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root).config
    ref = harness.load_module("reference", "mellum2_12b")
    params = weights.make(ref.param_shapes(cfg, {"vocab_size": 200}), 7)
    tok = np.random.default_rng(1).integers(5, 200, (2, 100)).astype(np.int32)
    batch = {"net_input": {"src_tokens": tok}, "target": tok}
    whole = float(ref.loss_sum(params, cfg, batch, 0))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 24)
    monkeypatch.setattr(ref, "ROW_BLOCK", 48)
    assert float(ref.loss_sum(params, cfg, batch, 0)) == pytest.approx(whole, rel=1e-6)


# -- counts ------------------------------------------------------------------------

def test_operation_counts_from_shapes(config):
    flops = harness.load_module("flops", "mellum2_12b")
    d, D, f, n = 2304, 128, 896, 32768
    body, head = flops.forward_per_token(config)
    assert head == 2 * d * 24576
    layer = (2 * d * (8 + 2) * D + 2 * 8 * D * d      # q, k, v; out
             + 2 * d * 64                              # the router, all 64
             + (8 * 16 / 64) * 3 * 2 * d * f)          # 2 pairs a token
    assert body == 4 * layer
    window = 1024 * 1025 // 2 + (n - 1024) * 1024
    full = n * (n + 1) // 2
    assert flops.visible_keys(n, 1024) == window and flops.visible_keys(n) == full
    assert flops.visible_keys(512, 1024) == 512 * 513 // 2
    assert flops.row_keys(config, n) == (3 * window, full)
    total = flops.train_flops(config, 10 * n, 10 * n * n, 1.0)
    assert total == pytest.approx(
        3 * (10 * n * (body + head) + 10 * (3 * window + full) * 4 * 8 * D))
    # ISSUE 40: about 1.0 GFLOP a token at 32,768
    assert 0.95e9 < total / (10 * n) < 1.05e9
    run = {"config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * n, "updates": 10}
    scopes = harness.load_module("flops", "mellum2_scopes")
    ops, nbytes = scopes.band_attn(run)
    assert ops == pytest.approx(3 * (3 * window + full) * 4 * 8 * D)
    assert nbytes == 4 * n * 2 * 8 * D * 12
    assert ops / nbytes > 240                      # the MXU bounds it on a v5e
    pairs = 4 * n * 2                              # an even load, four layers
    ops, nbytes = scopes.moe_gated(run, pairs)
    assert ops == pytest.approx(3 * pairs * 3 * 2 * d * f)
    assert nbytes == 4 * 3 * 16 * 3 * d * f * 2 + 4 * pairs * d * 2
    assert ops / nbytes > 240


def test_readers_of_the_new_metrics():
    """On a run whose pass over the trace is already made: the scopes'
    shares and roofline shares; the key counts from the marks; and nothing,
    not an error, from a program that leaves no such scope or mark."""
    config = load(os.path.join(BENCH, "configs", "mellum2_12b.json"))
    n = 32768
    passed = {"device_op_s": 10.0, "program_runs": 5,
              "seconds": {"band_attn": 4.0, "rotary": 0.2, "moe_routed": 2.5,
                          "self_attn": 5.0},
              "inside": {}, "route": {"pairs_here": 250000.0}, "route_marks": 5}
    named = {"groups_s": {"rest": 1.0}, "host": {"updates": 5},
             "device_op_s": 10.0, "program_runs": 5}
    mark = {"window_keys_computed": 148635648, "window_keys_visible": 99091968,
            "full_keys_computed": 545259520, "full_keys_visible": 536887296}
    work = {"device_op_s": 10.0, "program_runs": 5, "stated": True, "rows": [],
            "marks": {"attn_band": {"n": 2, "ms": [0.1, 0.1], "stats": {
                k: [v, str(v)] for k, v in mark.items()}}}}
    run = {"scope_pass": passed, "scope_work": work, "program_trace": named,
           "config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * n, "updates": 10,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name, r=run: harness.load_module("layer_metrics", name).read(r)
    assert read("band_attn_device_pct") == pytest.approx(40.0)
    assert read("rotary_device_pct") == pytest.approx(2.0)
    scopes = harness.load_module("flops", "mellum2_scopes")
    ops, _ = scopes.band_attn(run)
    assert read("band_attn_roofline_pct") == pytest.approx(
        100 * ops / 197e12 * 5 / 4.0)
    ops, _ = scopes.moe_gated(run, 250000.0)
    assert read("moe_gated_roofline_pct") == pytest.approx(
        100 * ops / 197e12 * 5 / 2.5)
    assert 0 < read("band_attn_roofline_pct") < 100
    assert 0 < read("moe_gated_roofline_pct") < 100
    assert read("band_keys_computed_over_visible") == pytest.approx(
        (148635648 + 545259520) / (99091968 + 536887296))
    # each kind by itself: the sum hides the sliding layers' half again
    assert read("band_window_keys_computed_over_visible") == pytest.approx(
        148635648 / 99091968)
    assert read("band_full_keys_computed_over_visible") == pytest.approx(
        545259520 / 536887296)
    # operations were named and none ran under the scopes: 0
    bare = dict(run, scope_pass=dict(passed, seconds={"moe": 1.0}, route={}),
                scope_work=dict(work, marks={}))
    for name in NEW:
        assert read(name, bare) == 0, name
    # a program that writes no annotations at all: nothing to count
    silent = dict(bare, program_trace=dict(named, host={}))
    assert read("band_keys_computed_over_visible", silent) is None
    assert read("moe_gated_roofline_pct", silent) is None
    # no scope table, or no trace at all: nothing to read
    for name in NEW:
        assert read(name, {"program_trace": None, "peaks": {}, "base": BENCH}) is None
        assert read(name, {"peaks": {}, "base": BENCH}) is None
