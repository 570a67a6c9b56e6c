"""``laguna_s_2_1`` and its cell through the benchmark's own code at a size a
test run can hold: the manifest's entries and the configuration file's
statements, the plain reference following the program over three updates,
a ``correct`` that notices a mechanism left out (the gate, the partial
rotation, the shared expert, the routed sum's scale), the operation counts
and the readers of the new per-layer metrics.  The step compiled for a
described v5e: ``test_compile_v5e_laguna.py``.  (The cases a
``test_manifest.py`` or ``test_flops.py`` would hold for the new files are
here: a PR that adds a cell edits no benchmark file that is there.)"""

import json
import os

import numpy as np
import pytest

import bench_tiny
from bench_tiny import BENCH, ROOT, load, tiny_checkout
from benchmark import control, harness

CELL = "laguna_s_2_1.train_pack32k"
CONFIG = "laguna_s_2_1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 100, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 4,
        "beta_slow": 1, "attention_factor": 1.1386294361119891,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                          "partial_rotary_factor": 1},
}
TINY_KINDS = ["full_attention"] + ["sliding_attention"] * 3

# the cell at a tiny size: 5 of 8 layers (dense + full; sparse + sliding
# x 3; sparse + full), one of 2 shares of 8 (full) or 12 (sliding) query
# heads on 4 KV heads of 16, a window of 16, a YaRN table over half a head
# whose original context is 32, a dense MLP of 96 in row chunks of 64, 8 of
# 16 experts 4 a token beside a shared expert of 40, the routed sum scaled
# by 2.5, 2 rows x 128 tokens an update (past the window and the original
# context); the groups as JSON text, which the train driver hands on
bench_tiny.TINY.setdefault(CELL, {
    "config": dict(
        hidden_size=64, intermediate_size=96, num_hidden_layers=8,
        layers_held=5, layer_types=json.dumps(TINY_KINDS * 2),
        mlp_layer_types=json.dumps(["dense"] + ["sparse"] * 7),
        gating_types=json.dumps(["per_head"] * 8),
        num_attention_heads_per_layer=json.dumps([8, 12, 12, 12] * 2),
        rope_parameters=json.dumps(TINY_ROPE),
        num_attention_heads=8, num_key_value_heads=4, head_dim=16,
        attention_shares=2, sliding_window=16, num_experts=16,
        num_experts_per_tok=4, num_experts_held=8, moe_intermediate_size=48,
        shared_expert_intermediate_size=40, vocab_size=200, loss_chunk=48,
        mlp_row_chunk=64,
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
    return load(os.path.join(BENCH, "configs", CONFIG + ".json"))


NEW = ["attn_gate_device_pct", "band_attn_heads_roofline_pct",
       "moe_gated_routed_roofline_pct"]
LISTED = ["attention_device_pct", "lm_head_loss_device_pct",
          "optimizer_share_pct", "unattributed_device_pct",
          "attn_kernel_fwd_device_pct", "attn_kernel_bwd_device_pct",
          "step_host_ms", "step_h2d_ms", "step_launch_ms",
          "data_buffer_depth", "data_produce_ms", "data_pack_ms",
          "moe_device_pct", "moe_routed_device_pct", "moe_load_max_over_mean",
          "xla_matmul_device_pct", "xla_matmul_roofline_pct",
          "attn_proj_roofline_pct", "optimizer_roofline_pct",
          "remat_device_pct", "ffn_device_pct", "ffn_roofline_pct",
          "moe_shared_roofline_pct"]
# ISSUE 42 also names these five; ``test_mellum2.py`` holds each of them to
# Mellum2's cell ALONE (``m["workloads"] == [CELL]``), a file this PR may
# not edit, so the cell is not on their lists (PERF.md section 7)
PINNED_TO_MELLUM2 = ["band_attn_device_pct", "band_keys_computed_over_visible",
                     "band_window_keys_computed_over_visible",
                     "band_full_keys_computed_over_visible",
                     "rotary_device_pct"]


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
    # one keys_computed for two maps, a latent in the routed count, one head
    # count for all layers and every held layer an expert layer
    # (flops/mellum2_scopes.py), no Mamba, no EVA: not this cell's
    assert not mine & {
        "attn_kernel_fwd_roofline_pct", "attn_kernel_bwd_roofline_pct",
        "moe_routed_roofline_pct", "band_attn_roofline_pct",
        "moe_gated_roofline_pct", "ssm_device_pct", "eva_agg_device_pct",
        *PINNED_TO_MELLUM2}
    # the new metrics are this cell's alone, each listed once, in the order
    # they were appended in and after the last the benchmark had then
    listed = [m["name"] for m in manifest["per_layer"]]
    at = [listed.index(name) for name in NEW]
    assert at == sorted(at)
    assert listed.index("band_full_keys_computed_over_visible") < at[0]
    assert all(name in listed for name in PINNED_TO_MELLUM2)
    assert all(listed.count(name) == 1 for name in NEW)
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
            assert m["unit"] == "%" and m["source"] == "device_trace"
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["config"] == CONFIG and entry["traffic"] == "train_pack32k"
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index("mellum2_12b.train_pack32k") < cells.index(CELL)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("mellum2_12b") < configs.index(CONFIG)
    for name in mine:  # every reader is there, and finds nothing to read
        reader = harness.load_module("layer_metrics", name, checkout.base)
        assert reader.read({"peaks": {}, "base": BENCH}) is None or name in (
            "peak_hbm_gib",)
    for kind in ("reference", "flops"):
        harness.find(kind, cell.config[kind] + ".py")
    harness.find("flops", "laguna_scopes.py")
    tr = cell.traffic
    assert tr["batch_size"] * tr["task_args"]["tokens_per_sample"] == 32768
    # ISSUE 42's traffic: 528 blocks an epoch (``corpus_why``)
    assert tr["corpus"] == {"kind": "text", "vocab": 12544, "n_docs": 2048,
                            "doc_words": [512, 16384]}
    assert tr["task_args"]["seq_pad_multiple"] == 128
    assert (tr["data_workers"], tr["data_buffer"], tr["warm_updates"],
            tr["reference_rows"]) == (2, 8, 3, 1)
    assert set(tr["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                 "delta_norm_gap"}
    assert len(entry["why"]) <= 200 and len(cfg_entry["why"]) <= 200


def test_the_configuration_states_its_source_its_cuts_and_what_it_assumed(
        manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "attention_shares", "layers_held", "num_experts_held", "vocab_size"]
    for key in config["reduced"]:
        assert key in config and key in config["published"], key
    assert (config["num_hidden_layers"], config["num_attention_heads"],
            config["num_key_value_heads"], config["num_experts"],
            config["num_experts_per_tok"]) == (48, 48, 8, 256, 10)
    # kept whole: 48 entries each
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert len(config[key]) == 48, key
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts, an eighth of the vocabulary
    held = config["layers_held"]
    assert config["mlp_layer_types"][:held] == ["dense"] + ["sparse"] * 4
    assert config["layer_types"][1:held] == (
        ["sliding_attention"] * 3 + ["full_attention"])
    assert config["num_experts_held"] >= 8
    assert config["vocab_size"] * 8 >= 100352
    assert "one of 32 chips" in config["deployment"]
    assert 256 // config["num_experts_held"] == 32
    assert "567,957,504" in config["reduced_why"]
    # no width among the cuts
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
    for stated in ("equations", "gate", "router", "shared expert", "q/k norm",
                   "balancing loss", "MTP module", "rotary", "experts",
                   "window", "balancing rule", "optimizer", "packing"):
        assert config["assumed"][stated]
    assert config["router_balancing"] == "batch_bias"
    assert "2505.06708" in config["assumed"]["gate"]
    assert "2505.06708" in config["papers"] and "2408.15664" in config["papers"]
    assert config["remat"] is True and config["mlp_row_chunk"] == 4096
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
    assert row["config"]["vocab_size"] == 100352


def test_the_programs_defaults_are_the_files_groups(config):
    """The train driver hands the program the file's numbers and strings;
    its lists and groups reach the program as the program's own defaults,
    which are these very values."""
    from unicore_tpu.models import laguna

    fields = laguna.LagunaModel.__dataclass_fields__
    for key in laguna.GROUPS:
        assert json.loads(fields[key].default) == config[key], key
    for key, value in config.items():
        if key in fields and isinstance(value, (int, float, str)) and key not in (
                "name", "vocab_size", "router_balancing", "mlp_row_chunk",
                *config["reduced"]):
            assert fields[key].default == value, key
    # the program's default is the published model: the top scores choose,
    # and nothing is held back
    assert fields["router_balancing"].default == "none"
    assert fields["vocab_size"].default == 100352
    assert fields["attention_shares"].default == 1


def test_the_share_counts_its_stated_parameters(config):
    import jax

    ref = harness.load_module("reference", CONFIG)
    shapes = ref.param_shapes(config, {"vocab_size": config["vocab_size"]})
    count = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    d = 3072
    attn = lambda H: (H + 2 * 1) * 128 * d + H * 128 * d + d * H
    assert (attn(6), attn(9)) == (5_523_456, 7_891_968)
    sparse = d * 256 + 3 * d * 1024 + 8 * 3 * d * 1024 + 2 * d
    layer0 = attn(6) + 3 * d * 12288 + 2 * d
    assert layer0 == 118_775_808
    assert attn(9) + sparse == 93_619_200 and attn(6) + sparse == 91_250_688
    assert count == (layer0 + 3 * (attn(9) + sparse) + attn(6) + sparse
                     + 2 * 12544 * d + d) == 567_957_504
    assert 0.25 * 16.9e9 < 16 * count < 0.75 * 16.9e9  # 16 bytes a parameter


def test_the_program_builds_the_references_tree(config):
    """At the real widths, from shapes alone: the program's parameter tree
    is the one ``param_shapes`` states, leaf for leaf, and counts the
    stated parameters."""
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
    assert model.pattern == "GFSRSRSRGR"
    assert (model.held_heads("full_attention"),
            model.held_heads("sliding_attention")) == (6, 9)
    tok = np.zeros((1, 256), np.int32)
    got = jax.eval_shape(lambda: model.init_params(
        jax.random.PRNGKey(0), {"net_input": {"src_tokens": tok}}))
    want = harness.load_module("reference", CONFIG).param_shapes(
        config, {"vocab_size": len(task.dictionary)})
    flat = lambda t: {
        jax.tree_util.keystr(p): tuple(x.shape)
        for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    assert flat(got) == flat(want)
    assert sum(int(np.prod(s)) for s in flat(got).values()) == 567_957_504


# -- correct ----------------------------------------------------------------------

def test_reference_follows_the_program_in_float32(run_tiny):
    """Loss, first gradient and three updates: the band as a mask of iotas
    against a mask over the whole row at each kind's own head count, the
    gate, a rotary table over half a head, the dense MLP's row chunks, the
    sorted and tiled experts against dense products over all tokens, the
    shared expert, the chunked loss against row blocks, the trainer's Adam
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
    # what the readers of a traced run would be handed: every listed reader
    # runs, none raises, and what needs a trace is left out on a CPU
    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    line = json.loads(harness.result_line(cell, out, trace=True))["metrics"]
    assert line["train_mfu_pct"]["value"] > 0
    assert not (set(NEW) | set(LISTED)) & set(line)


def test_sound_bfloat16_run_is_correct_on_a_large_seed(run_tiny):
    out, last = run_tiny(CELL, seed=2 ** 31 + 977)
    assert last["correct"] is True, out["checks"]


def test_the_tiny_epoch_holds_whole_batches(tmp_path):
    bench_tiny.assert_whole_batches(tmp_path, CELL)


def _no_gate(monkeypatch):
    import jax.numpy as jnp

    from unicore_tpu.modules import multihead_attention

    monkeypatch.setattr(  # every head's gate at one
        multihead_attention, "head_gates",
        lambda x, w_g: jnp.ones(x.shape[:2] + w_g.shape[1:], jnp.float32))


def _rotary_over_the_whole_head(monkeypatch):
    from unicore_tpu.modules import rotary

    real = rotary.rope_table
    monkeypatch.setattr(  # the factor of a half not read
        rotary, "rope_table",
        lambda rp, D: real(dict(rp, partial_rotary_factor=1), D))


def _no_shared_expert(monkeypatch):
    from unicore_tpu.modules import gated_moe

    real = gated_moe.silu_gate
    monkeypatch.setattr(  # its middle array zero: it adds nothing
        gated_moe, "silu_gate", lambda pre: real(pre) * 0.0)


def _routed_sum_unscaled(monkeypatch):
    from argparse import Namespace

    from unicore_tpu.models import laguna

    real = laguna.LagunaModel.build_model.__func__
    monkeypatch.setattr(  # the program is built as if the key read 1
        laguna.LagunaModel, "build_model", classmethod(
            lambda cls, args, task: real(cls, Namespace(**dict(
                vars(args), moe_routed_scaling_factor=1.0)), task)))


@pytest.mark.parametrize("fault", [_no_gate, _rotary_over_the_whole_head,
                                   _no_shared_expert, _routed_sum_unscaled])
def test_a_mechanism_left_out_is_not_correct(fault, run_tiny, monkeypatch):
    """The gate, the partial rotation, the shared expert or the routed
    sum's scale left out of the timed path: ``correct`` comes out false
    (the comparison catches the mechanisms, not only the matmuls)."""
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
    ref = harness.load_module("reference", CONFIG)
    params = weights.make(ref.param_shapes(cfg, {"vocab_size": 200}), 5)
    tok = np.random.default_rng(0).integers(5, 200, (1, 100)).astype(np.int32)
    whole = np.asarray(ref.hidden(params, cfg, tok))
    for what in ("window", "attention_factor", "gate", "partial_rotary",
                 "routed_scale", "shared_expert"):
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
    ref = harness.load_module("reference", CONFIG)
    params = weights.make(ref.param_shapes(cfg, {"vocab_size": 200}), 7)
    tok = np.random.default_rng(1).integers(5, 200, (2, 100)).astype(np.int32)
    batch = {"net_input": {"src_tokens": tok}, "target": tok}
    whole = float(ref.loss_sum(params, cfg, batch, 0))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 24)
    monkeypatch.setattr(ref, "ROW_BLOCK", 48)
    assert float(ref.loss_sum(params, cfg, batch, 0)) == pytest.approx(whole, rel=1e-6)


# -- counts ------------------------------------------------------------------------

def test_operation_counts_from_shapes(config):
    flops = harness.load_module("flops", CONFIG)
    d, D, n = 3072, 128, 32768
    body, head = flops.forward_per_token(config)
    assert head == 2 * d * 12544
    attn = lambda H: 2 * d * (H + 2) * D + 2 * d * H + 2 * H * D * d
    sparse = (2 * d * 256                       # the router, all 256
              + 3 * 2 * d * 1024                 # the shared expert
              + (10 * 8 / 256) * 3 * 2 * d * 1024)   # 0.3125 pairs a token
    assert body == (attn(6) + 3 * 2 * d * 12288
                    + 3 * (attn(9) + sparse) + attn(6) + sparse)
    # ISSUE 42's operations a token, forward: layer 0's MLP 238 M (with its
    # attention's 11 M), a sparse layer's attention 16 M, router 1.6 M,
    # shared 19 M, routed 5.9 M, head 77 M
    assert 3 * 2 * d * 12288 == pytest.approx(226e6, rel=0.01)
    assert attn(9) == pytest.approx(16e6, rel=0.02)
    assert 2 * d * 256 == pytest.approx(1.6e6, rel=0.02)
    assert 3 * 2 * d * 1024 == pytest.approx(19e6, rel=0.01)
    assert 0.3125 * 3 * 2 * d * 1024 == pytest.approx(5.9e6, rel=0.01)
    assert head == pytest.approx(77e6, rel=0.01)
    window = 512 * 513 // 2 + (n - 512) * 512
    full = n * (n + 1) // 2
    assert flops.visible_keys(n, 512) == window and flops.visible_keys(n) == full
    assert flops.visible_keys(256, 512) == 256 * 257 // 2
    assert flops.row_keys(config, n) == {
        "sliding_attention": 3 * window, "full_attention": 2 * full}
    assert flops.kind_heads(config) == {"full_attention": 6,
                                        "sliding_attention": 9}
    band = 3 * window * 4 * 9 * D + 2 * full * 4 * 6 * D
    total = flops.train_flops(config, 10 * n, 10 * n * n, 1.0)
    assert total == pytest.approx(3 * (10 * n * (body + head) + 10 * band))
    # the two full layers' triangle: 3.3 TFLOP forward, a tenth of the count
    assert 2 * full * 4 * 6 * D == pytest.approx(3.3e12, rel=0.01)
    assert 1.65e9 < total / (10 * n) < 1.8e9
    run = {"config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * n, "updates": 10}
    scopes = harness.load_module("flops", "laguna_scopes")
    ops, nbytes = scopes.band_attn(run)
    assert ops == pytest.approx(3 * band)
    assert nbytes == (2 * 6 + 3 * 9) * n * 2 * D * 12
    assert scopes.band_heads(run) == (9, 6)
    assert ops / nbytes > 240                      # the MXU bounds it on a v5e
    pairs = 4 * n * 0.3125                         # an even load, four layers
    ops, nbytes = scopes.moe_gated_routed(run, pairs)
    assert ops == pytest.approx(3 * pairs * 3 * 2 * d * 1024)
    # the held weights over the FOUR sparse layers, not the five held
    assert nbytes == 4 * 3 * 8 * 3 * d * 1024 * 2 + 4 * pairs * d * 2
    # at a quarter of a deployment's load the products still bound it:
    # 1,280 rows an expert against 18.9 MB of its weights three times
    assert ops / nbytes > 240


def test_counts_at_a_small_shape_by_hand():
    """A layer of each kind at sizes a hand count holds: 2 sliding heads
    and 1 full head on one KV head of 4, hidden 8, window 2, a row of 3."""
    cfg = dict(
        hidden_size=8, head_dim=4, intermediate_size=16, num_hidden_layers=2,
        layer_types=["full_attention", "sliding_attention"],
        mlp_layer_types=["dense", "sparse"],
        num_attention_heads_per_layer=[1, 2], num_key_value_heads=1,
        sliding_window=2, num_experts=4, num_experts_per_tok=2,
        num_experts_held=2, moe_intermediate_size=3,
        shared_expert_intermediate_size=5, vocab_size=10, flops=CONFIG)
    flops = harness.load_module("flops", CONFIG)
    body, head = flops.forward_per_token(cfg)
    full = 2 * 8 * 3 * 4 + 2 * 8 * 1 + 2 * 4 * 8 + 3 * 2 * 8 * 16
    sliding = (2 * 8 * 4 * 4 + 2 * 8 * 2 + 2 * 8 * 8          # q k v, gate, out
               + 2 * 8 * 4 + 3 * 2 * 8 * 5 + 1.0 * 3 * 2 * 8 * 3)
    assert (body, head) == (full + sliding, 160)
    # a row of 3: the full layer sees 1 + 2 + 3 keys, the window 1 + 2 + 2
    assert flops.row_keys(cfg, 3) == {"sliding_attention": 5, "full_attention": 6}
    band = 5 * 4 * 2 * 4 + 6 * 4 * 1 * 4
    assert flops.train_flops(cfg, 6, 18, 1.0) == 3 * (6 * (body + head) + 2 * band)
    run = {"config": cfg, "base": BENCH, "sum_n": 6, "sum_n2": 18, "updates": 2}
    scopes = harness.load_module("flops", "laguna_scopes")
    assert scopes.band_attn(run) == (3.0 * band, (1 + 2) * 3 * 2 * 4 * 12)
    assert scopes.band_heads(run) == (2, 1)
    assert scopes.moe_gated_routed(run, 7) == (
        3.0 * 7 * 3 * 2 * 8 * 3, 1 * 3 * (2 * 3 * 8 * 3 * 2) + 4 * 7 * 8 * 2)


def test_readers_of_the_new_metrics(config):
    """On a run whose pass over the trace is already made: the gate's share
    and the two roofline shares; and nothing, not an error, from a program
    that leaves no such scope or mark, or states other heads."""
    n = 32768
    passed = {"device_op_s": 10.0, "program_runs": 5,
              "seconds": {"band_attn": 4.0, "attn_gate": 0.1,
                          "moe_routed": 1.0, "self_attn": 5.0},
              "inside": {}, "route": {"pairs_here": 40960.0}, "route_marks": 5}
    named = {"groups_s": {"rest": 1.0}, "host": {"updates": 5},
             "device_op_s": 10.0, "program_runs": 5}
    mark = {"window_heads": 9, "full_heads": 6, "window_layers": 3,
            "full_layers": 2}
    stats = lambda m: {k: [v, v] for k, v in m.items()}
    work = {"device_op_s": 10.0, "program_runs": 5, "stated": True, "rows": [],
            "marks": {"attn_band": {"n": 2, "ms": [0.1, 0.1],
                                    "stats": stats(mark)}}}
    run = {"scope_pass": passed, "scope_work": work, "program_trace": named,
           "config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * n, "updates": 10,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name, r=run: harness.load_module("layer_metrics", name).read(r)
    assert read("attn_gate_device_pct") == pytest.approx(1.0)
    scopes = harness.load_module("flops", "laguna_scopes")
    ops, _ = scopes.band_attn(run)
    assert read("band_attn_heads_roofline_pct") == pytest.approx(
        100 * ops / 197e12 * 5 / 4.0)
    ops, nbytes = scopes.moe_gated_routed(run, 40960.0)
    assert ops / 197e12 > nbytes / 819e9
    assert read("moe_gated_routed_roofline_pct") == pytest.approx(
        100 * ops / 197e12 * 5 / 1.0)
    assert 0 < read("band_attn_heads_roofline_pct") < 100
    assert 0 < read("moe_gated_routed_roofline_pct") < 100
    # the mark states other heads than the count takes: not this reader's
    other = dict(run, scope_work=dict(work, marks={"attn_band": dict(
        work["marks"]["attn_band"], stats=stats(dict(mark, full_heads=9)))}))
    assert read("band_attn_heads_roofline_pct", other) is None
    # a mark without the heads (another program's): 0, not an error
    older = dict(run, scope_work=dict(work, marks={"attn_band": dict(
        work["marks"]["attn_band"], stats=stats({"window_layers": 3}))}))
    assert read("band_attn_heads_roofline_pct", older) == 0
    # a configuration without the keys the count reads
    mellum = load(os.path.join(BENCH, "configs", "mellum2_12b.json"))
    assert read("moe_gated_routed_roofline_pct", dict(run, config=mellum)) is None
    # operations were named and none ran under the scopes: 0
    bare = dict(run, scope_pass=dict(passed, seconds={"moe": 1.0}))
    for name in NEW:
        assert read(name, bare) == 0, name
    # a program that writes no annotations at all: nothing to count
    silent = dict(bare, program_trace=dict(named, host={}))
    assert read("band_attn_heads_roofline_pct", silent) is None
    assert read("moe_gated_routed_roofline_pct", silent) is None
    # no scope table, or no trace at all: nothing to read
    for name in NEW:
        assert read(name, {"program_trace": None, "peaks": {}, "base": BENCH}) is None
        assert read(name, {"peaks": {}, "base": BENCH}) is None
