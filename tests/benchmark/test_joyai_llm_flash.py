"""``joyai_llm_flash`` and its cell through the benchmark's own code at a
size a test run can hold: the manifest's entries and the configuration
file's statements, the counted parameters, the plain reference following
the program over three updates, a ``correct`` that notices a mechanism left
out (a latent norm, the interleaved pairing, the sigmoid score, the
module's shift by two), the operation counts and the readers of the new
per-layer metrics.  The step compiled for a described v5e:
``test_compile_v5e_joyai.py``; the model's own cases: ``tests/test_joyai.py``
and ``tests/test_mla.py``.  (The cases a ``test_manifest.py`` or
``test_flops.py`` would hold for the new files are here: a PR that adds a
cell edits no benchmark file that is there.)"""

import json
import os

import numpy as np
import pytest

import bench_tiny
from bench_tiny import BENCH, ROOT, load, tiny_checkout
from benchmark import control, harness

CELL = "joyai_llm_flash.train_pack8k_x4"
CONFIG = "joyai_llm_flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNT = 581_350_656

# the cell at a tiny size: a dense layer and two sparse ones (one scanned
# unit) of 6 layers, 2 of 4 heads whose keys are 16 + 8 wide and whose values
# 16 behind latents of 48 and 32, 4 of 8 sigmoid-scored experts 2 a token
# beside a shared one, the prediction module, 2 rows x 128 tokens an update
bench_tiny.TINY.setdefault(CELL, {
    "config": dict(
        hidden_size=64, intermediate_size=96, num_hidden_layers=6,
        layers_held=3, num_attention_heads=4, num_key_value_heads=4,
        attention_shares=2, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
        v_head_dim=16, head_dim=8, rope_theta=100.0, n_routed_experts=8,
        num_experts_per_tok=2, num_experts_held=4, moe_intermediate_size=48,
        vocab_size=200, loss_chunk=48, mlp_row_chunk=64,
    ),
    # 64 documents of 40 .. 204 words are 62 blocks of 128 tokens, the same
    # for every seed: every batch of an epoch has both its rows
    "corpus": dict(vocab=200, n_docs=64, doc_words=[40, 204]),
    "traffic": dict(
        batch_size=2, warm_updates=1, reference_rows=2,
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


NEW = ["mla_attn_device_pct", "mla_proj_device_pct", "mla_attn_roofline_pct",
       "mtp_device_pct", "mtp_loss_over_main"]
LISTED = ["attention_device_pct", "ffn_device_pct", "lm_head_loss_device_pct",
          "optimizer_share_pct", "unattributed_device_pct",
          "attn_kernel_fwd_device_pct", "attn_kernel_bwd_device_pct",
          "attn_kernel_fwd_roofline_pct", "attn_kernel_bwd_roofline_pct",
          "step_host_ms", "step_h2d_ms", "step_launch_ms",
          "data_buffer_depth", "data_produce_ms", "data_pack_ms",
          "moe_device_pct", "moe_routed_device_pct", "moe_load_max_over_mean",
          "moe_shared_roofline_pct", "xla_matmul_device_pct",
          "xla_matmul_roofline_pct", "ffn_roofline_pct",
          "attn_proj_roofline_pct", "optimizer_roofline_pct",
          "remat_device_pct"]
# held to one cell each by the files that brought them (``m["workloads"] ==
# [CELL]`` in test_mellum2.py, test_laguna_s_2_1.py, test_evabyte.py,
# test_zaya1_8b.py), files this PR may not edit: the cell is on none of
# their lists
PINNED = ["band_attn_device_pct", "band_attn_roofline_pct",
          "band_keys_computed_over_visible", "moe_gated_roofline_pct",
          "rotary_device_pct", "band_window_keys_computed_over_visible",
          "band_full_keys_computed_over_visible", "attn_gate_device_pct",
          "band_attn_heads_roofline_pct", "moe_gated_routed_roofline_pct",
          "cca_mix_device_pct", "cca_mix_roofline_pct",
          "zaya_router_device_pct", "moe_top1_routed_roofline_pct",
          "moe_skip_share"]


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
    # no latent in the routed count, no Mamba, no EVA, and what other files
    # pin to their cells: not this cell's
    assert not mine & {
        "moe_routed_roofline_pct", "ssm_device_pct", "eva_agg_device_pct",
        *PINNED}
    # the new metrics are this cell's alone, each listed once, in the order
    # they were appended in and after the last the benchmark had then
    listed = [m["name"] for m in manifest["per_layer"]]
    at = [listed.index(name) for name in NEW]
    assert at == sorted(at)
    assert listed.index("moe_skip_share") < at[0]
    assert all(listed.count(name) == 1 for name in NEW)
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
            assert m["layer"] == "modules (modules/, models/, losses/)"
        if m["name"] in LISTED:
            # appended: after every cell the list had
            older = [w for w in m["workloads"] if w in (
                "bert_base.train_mlm512", "laguna_s_2_1.train_pack32k",
                "zaya1_8b.train_pack8k_x4")]
            assert older and all(
                m["workloads"].index(w) < m["workloads"].index(CELL)
                for w in older)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert (by_name["mtp_loss_over_main"]["unit"],
            by_name["mtp_loss_over_main"]["source"]) == (
                "ratio", "program_counter")
    for name in NEW[:4]:
        assert (by_name[name]["unit"], by_name[name]["source"]) == (
            "%", "device_trace")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["config"] == CONFIG and entry["traffic"] == "train_pack8k_x4"
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index("zaya1_8b.train_pack8k_x4") < cells.index(CELL)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("zaya1_8b") < configs.index(CONFIG)
    for name in mine:  # every reader is there, and finds nothing to read
        reader = harness.load_module("layer_metrics", name, checkout.base)
        assert reader.read({"peaks": {}, "base": BENCH}) is None or name in (
            "peak_hbm_gib",)
    for kind in ("reference", "flops"):
        harness.find(kind, cell.config[kind] + ".py")
    harness.find("flops", "joyai_scopes.py")
    tr = cell.traffic
    # ISSUE 50's traffic: 4 rows x 8,192, 400 batches an epoch
    assert (tr["batch_size"], tr["task_args"]["tokens_per_sample"]) == (4, 8192)
    assert tr["corpus"] == {"kind": "text", "vocab": 16160, "n_docs": 2048,
                            "doc_words": [512, 12288]}
    assert tr["task_args"]["seq_pad_multiple"] == 128
    assert (tr["data_workers"], tr["data_buffer"], tr["warm_updates"],
            tr["reference_rows"]) == (2, 8, 3, 4)
    assert set(tr["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                 "delta_norm_gap"}
    assert len(entry["why"]) <= 200 and len(cfg_entry["why"]) <= 200
    assert "quarter" in entry["why"]  # an expert sees a quarter of its tokens


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
            config["n_routed_experts"], config["num_experts_per_tok"],
            config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["num_nextn_predict_layers"]) == (
                40, 32, 256, 8, 1536, 512, 128, 64, 128, 1)
    assert config["rope_scaling"] is None and config["rope_interleave"] is True
    assert (config["scoring_func"], config["topk_method"]) == (
        "sigmoid", "noaux_tc")
    # the guide's floors: four sparse layers after the dense one, 8 experts,
    # an eighth of the vocabulary
    assert config["layers_held"] - config["first_k_dense_replace"] >= 4
    assert config["num_experts_held"] >= 8
    assert config["vocab_size"] * 8 >= 129280
    assert "one of 16 chips" in config["deployment"]
    assert "four data-parallel groups of four" in config["deployment"]
    assert (config["attention_shares"], config["num_experts_held"]) == (4, 16)
    assert f"{COUNT:,}" in config["reduced_why"]
    # no width among the cuts
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
    for stated in ("equations", "mtp_loss_weight", "prediction module",
                   "rotary", "latents", "selection", "balancing loss",
                   "experts", "head", "optimizer", "packing"):
        assert config["assumed"][stated]
    assert config["router_balancing"] == "batch_bias"
    assert config["mtp_loss_weight"] == 0.3
    assert "2405.04434" in config["papers"] and "2412.19437" in config["papers"]
    assert (config["remat"], config["loss_chunk"], config["mlp_row_chunk"]) == (
        True, 1024, 4096)
    assert config["train_args"]["adam_betas"] == [0.9, 0.95]
    assert config["train_args"]["no_weight_decay_names"] == "norm"
    # the compiled peak the depth was chosen by is in the file
    assert "peak_memory_in_bytes" in config["layers_held_why"]


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
    assert row["config"]["vocab_size"] == 129280 == 8 * config["vocab_size"]


def test_the_programs_defaults_are_the_published_keys(config):
    """The train driver hands the program the file's numbers and strings;
    every published key is a field of the model under its own name, whose
    default is the published value; ``rope_scaling`` null is its empty
    text."""
    from unicore_tpu.models import joyai

    fields = joyai.JoyAIModel.__dataclass_fields__
    for key, value in config.items():
        if key in fields and isinstance(value, (int, float, str)) and key not in (
                "name", "vocab_size", "router_balancing", "loss_chunk",
                "mlp_row_chunk", *config["reduced"]):
            assert fields[key].default == value, key
    published = set(config) - {
        "name", "source", "papers", "arch", "task", "loss", "reference",
        "flops", "precision", "deployment", "train_args",
        "weight_decay_rule", "reduced", "published", "reduced_why",
        "layers_held_why", "assumed", "keys_why", "model_type"}
    assert published <= set(fields), published - set(fields)
    assert fields["rope_scaling"].default == ""
    assert fields["router_balancing"].default == "none"
    assert fields["vocab_size"].default == 129280
    assert fields["attention_shares"].default == 1


def test_the_share_counts_its_stated_parameters(config):
    import jax

    ref = harness.load_module("reference", CONFIG)
    shapes = ref.param_shapes(config, {"vocab_size": config["vocab_size"]})
    count = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    d, V = 2048, 16160
    mla = (d * 1536 + 1536 + 1536 * 8 * 192      # q_a, its norm, q_b
           + d * 576 + 512 + 512 * 8 * 256       # kv_a, its norm, kv_b
           + 8 * 128 * d)                        # o
    assert mla == 9_832_448
    expert = 3 * d * 768
    router = d * 256 + 256
    sparse = mla + 16 * expert + expert + router + 2 * d
    assert (expert, router, sparse) == (4_718_592, 524_544, 90_577_152)
    dense = mla + 3 * d * 7168 + 2 * d
    assert dense == 53_876_736
    module = sparse + 2 * d * d + 3 * d
    assert module == 98_971_904
    assert count == dense + 4 * sparse + module + 2 * V * d + d == COUNT
    assert 0.25 * 16.9e9 < 16 * count < 0.75 * 16.9e9  # 16 bytes a parameter
    # the one lever ISSUE 50 leaves, 4 heads a share: 564,835,584
    half = mla - 1536 * 4 * 192 - 512 * 4 * 256 - 4 * 128 * d
    assert count - 6 * (mla - half) == 564_835_584


def test_the_program_builds_the_references_tree(config):
    """At the real widths, from shapes alone: the program's parameter tree
    is the one ``param_shapes`` states, leaf for leaf, and counts the stated
    parameters."""
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
    assert model.pattern == "LF" + "LR" * 4 and model.mtp_pattern == "LR"
    assert model.ahead == (("mtp", 0.3),) and not model.tied
    sizes = model.layers()["sizes"]
    assert (sizes["L"]["num_heads"], sizes["L"]["q_lora_rank"],
            sizes["L"]["kv_lora_rank"], sizes["L"]["rope"]["rope_theta"]) == (
                8, 1536, 512, 32000000)
    assert (sizes["R"]["n_held"], sizes["R"]["n_routed"], sizes["R"]["top_k"],
            sizes["R"]["balancing"], sizes["R"]["scoring"],
            sizes["R"]["routed_scale"], sizes["R"]["shared_dim"]) == (
                16, 256, 8, "batch_bias", "sigmoid", 2.5, 768)
    assert sizes["F"] == dict(ffn_dim=7168, row_chunk=4096)
    tok = np.zeros((1, 256), np.int32)
    got = jax.eval_shape(lambda: model.init_params(
        jax.random.PRNGKey(0), {"net_input": {"src_tokens": tok}}))
    want = harness.load_module("reference", CONFIG).param_shapes(
        config, {"vocab_size": len(task.dictionary)})
    flat = lambda t: {
        jax.tree_util.keystr(p): tuple(x.shape)
        for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    assert flat(got) == flat(want)
    assert sum(int(np.prod(s)) for s in flat(got).values()) == COUNT
    assert set(got["params"]) == {"embed_tokens", "decoder", "lm_head", "mtp"}
    # the even load: exactly one wide trip an expert
    from unicore_tpu.modules import latent_moe

    assert latent_moe.wide_rows(32768, 8, 256) == 1024 == 32768 * 8 // 256


# -- correct ----------------------------------------------------------------------

def test_reference_follows_the_program_in_float32(run_tiny):
    """Loss, first gradient and three updates: the rotary columns read even
    channels first against the interleaved rotation, the band as a mask of
    iotas against a mask over the whole row, the values padded to the keys'
    width against values 16 wide, the sorted and tiled experts against a
    dense loop, the two products under ``W_eh`` against one, the head's two
    passes in loss chunks against row blocks, the trainer's Adam (with the
    vectors the scan gives a second axis left undecayed and the selection
    bias left alone) against the leaf-by-leaf follower."""
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


def _no_query_norm(monkeypatch):
    from unicore_tpu.modules import mla

    real = mla.RMSNorm.__call__
    # the gain is made (the tree stays whole) and the latent goes unnormed
    monkeypatch.setattr(mla.RMSNorm, "__call__", lambda self, x: (
        x + 0 * real(self, x) if self.name == "q_norm" else real(self, x)))


def _rotate_half(monkeypatch):
    from unicore_tpu.modules import mla

    monkeypatch.setattr(mla, "evens_first", lambda n: np.arange(n))


def _shift_by_one(monkeypatch):
    from unicore_tpu.losses import lm_cross_entropy

    real, calls = lm_cross_entropy.shifted_targets, []

    def once(target, heads, pad):  # the module's targets not shifted again
        calls.append(1)
        return real(target, heads, pad) if len(calls) % 2 else target

    monkeypatch.setattr(lm_cross_entropy, "shifted_targets", once)


def _unweighted_module(monkeypatch):
    from unicore_tpu.models import joyai

    monkeypatch.setattr(joyai.JoyAIModel, "ahead", (("mtp", 1.0),))


@pytest.mark.parametrize("fault", [_no_query_norm, _rotate_half,
                                   _shift_by_one, _unweighted_module])
def test_a_mechanism_left_out_is_not_correct(fault, run_tiny, monkeypatch):
    """The query latent's norm, the interleaved pairing, the module's shift
    by two or its weight left out of the timed path: ``correct`` comes out
    false (the comparison catches the mechanisms, not only the matmuls)."""
    fault(monkeypatch)
    out, last = run_tiny(CELL, float32=True)
    assert last["correct"] is False
    failed = {c["name"] for c in out["checks"] if not c["value"] <= c["limit"]}
    assert failed & {"first_grad_norm_gap.worst_leaf", "loss_rel_gap.step1",
                     "param_change_norm_gap.worst_leaf"}, out["checks"]


def test_the_lower_precision_control_is_not_correct(tmp_path):
    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    c = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root)
    checks = control.control_checks(c, seed=2 ** 31 + 3, precision="bfloat16")
    assert harness.report_checks(checks) is False


@pytest.mark.parametrize("float32", [True, False], ids=["float32", "bfloat16"])
def test_the_whole_tree_follower_is_the_leaf_by_leaf_one(
        tmp_path, monkeypatch, float32):
    """The reference's own follower (one compiled update over the tree, the
    parameters rounded by ``reduce_precision``) against the decoder cells'
    (an Adam, two scalings and two casts a leaf): the same three losses,
    first-gradient norms and parameter-change norms."""
    import shutil
    import tempfile

    from benchmark.drivers import train
    from benchmark.reference import nemotron3_super_120b as leafwise

    root, base = tiny_checkout(tmp_path, CELL, float32=float32)
    cell = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root)
    work = tempfile.mkdtemp(prefix="unicore_bench_")
    try:
        _args, task, batches, shaped, _ = train.open_feed(cell, 7, work)
        kept = [shaped(next(batches))[0] for _ in range(3)]
        hyper = train.hyper_of(cell.config, task)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    assert bool(hyper["bf16"]) is not float32
    ref = harness.load_module("reference", CONFIG)
    mine = ref.train_check(cell.config, hyper, kept, 7, 2)
    monkeypatch.setattr(ref, "follow", leafwise.follow)
    theirs = ref.train_check(cell.config, hyper, kept, 7, 2)
    assert mine["names"] == theirs["names"]
    np.testing.assert_allclose(mine["loss"], theirs["loss"], rtol=1e-6)
    for key in ("grad_norms", "delta_norms"):
        np.testing.assert_allclose(mine[key], theirs[key], rtol=2e-5,
                                   atol=1e-9)


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
    d, n, L = 2048, 32768, 8192
    assert flops.held(config) == dict(
        attention=6, dense=1, sparse=5, module=1, heads=8, experts=16)
    body, head = flops.forward_per_token(config)
    assert head == 2 * d * 16160
    mla = (2 * d * 1536 + 2 * 1536 * 8 * 192 + 2 * d * 576
           + 2 * 512 * 8 * 256 + 2 * 8 * 128 * d)
    assert flops.mla_per_token(config) == mla == 19_660_800
    routed = 0.5 * 3 * 2 * d * 768             # 16 x 8 / 256 pairs a token
    sparse = 2 * d * 256 + 3 * 2 * d * 768 + routed
    assert flops.sparse_per_token(config) == sparse
    assert body == pytest.approx(
        6 * mla + 3 * 2 * d * 7168 + 5 * sparse + 2 * 2 * d * d)
    full = L * (L + 1) // 2
    assert flops.visible_keys(L) == full
    # the EQUATIONS' pair: keys 192 wide, values 128 (the kernels run 2 x 192)
    assert flops.forward_per_key(config) == 2 * 8 * (192 + 128)
    band = 6 * full * 2 * 8 * 320
    total = flops.train_flops(config, 10 * n, 10 * n * L, 1.0)
    assert total == pytest.approx(3 * (10 * n * (body + 2 * head) + 40 * band))
    # at 8k rows the equations' visible pairs are 23% of the count, the
    # head's two passes 24%, the module (block, projection, head pass) 25%
    assert 0.21 < 3 * 40 * band / total < 0.24
    assert 0.22 < 3 * 10 * n * 2 * head / total < 0.26
    module = n * (mla + sparse + 4 * d * d + head) + 4 * band / 6
    assert 0.23 < 3 * 10 * module / total < 0.27
    assert 1.6e9 < total / (10 * n) < 1.75e9
    run = {"config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * L, "updates": 10}
    scopes = harness.load_module("flops", "joyai_scopes")
    ops, nbytes = scopes.mla_attn(run)
    assert ops == pytest.approx(3 * 4 * band)
    # a head's token: q, k, v, o forward 1,280 bytes; those and do read and
    # dq, dk, dv written backward 2,560
    assert nbytes == 6 * 8 * n * (1280 + 2560)
    assert ops / nbytes > 240     # compute-bound by far


def test_counts_at_a_small_shape_by_hand():
    """One dense layer and the module at sizes a hand count holds: 2 heads
    with keys 3 + 2 wide and values 3, latents 4 and 3, hidden 8, an MLP of
    6, 4 experts of 5 two a token of which 1 is held, a row of 3."""
    cfg = dict(
        hidden_size=8, intermediate_size=6, num_hidden_layers=2,
        layers_held=1, first_k_dense_replace=1, num_attention_heads=2,
        q_lora_rank=4, kv_lora_rank=3, qk_nope_head_dim=3,
        qk_rope_head_dim=2, v_head_dim=3, n_routed_experts=4,
        n_shared_experts=1, num_experts_per_tok=2, num_experts_held=1,
        moe_intermediate_size=5, num_nextn_predict_layers=1, vocab_size=10,
        flops=CONFIG)
    flops = harness.load_module("flops", CONFIG)
    assert flops.held(cfg) == dict(
        attention=2, dense=1, sparse=1, module=1, heads=2, experts=1)
    mla = 2 * 8 * 4 + 2 * 4 * 2 * 5 + 2 * 8 * 5 + 2 * 3 * 2 * 6 + 2 * 2 * 3 * 8
    sparse = 2 * 8 * 4 + 3 * 2 * 8 * 5 + 0.5 * 3 * 2 * 8 * 5
    body, head = flops.forward_per_token(cfg)
    assert (body, head) == (
        2 * mla + 3 * 2 * 8 * 6 + sparse + 2 * 2 * 8 * 8, 160)
    # a row of 3 sees 1 + 2 + 3 keys; two attention sublayers
    band = 2 * 6 * 2 * 2 * (5 + 3)
    assert flops.train_flops(cfg, 6, 18, 1.0) == pytest.approx(
        3 * (6 * (body + 2 * head) + 2 * band))
    run = {"config": cfg, "base": BENCH, "sum_n": 6, "sum_n2": 18, "updates": 2}
    scopes = harness.load_module("flops", "joyai_scopes")
    ops, nbytes = scopes.mla_attn(run)
    assert ops == 3.0 * band
    assert nbytes == 2 * 2 * 3 * (32 + 64)


def test_readers_of_the_new_metrics(config):
    """On a run whose pass over the trace is already made: the three shares
    of device time, the roofline share and the loss ratio; and nothing, not
    an error, from a run that was not traced or a program without a scope
    table (0 from one that names its operations and has no such scope)."""
    n, L = 32768, 8192
    passed = {"device_op_s": 10.0, "program_runs": 5,
              "seconds": {"mla_attn": 4.0, "mtp": 1.8, "self_attn": 5.5,
                          "moe_routed": 1.0},
              "inside": {}, "route": {"pairs_here": 77000.0}, "route_marks": 5}
    named = {"groups_s": {"rest": 1.0}, "host": {"updates": 5},
             "device_op_s": 10.0, "program_runs": 5}
    pre = "jit(train_step)/JoyAI/decoder/units/layer_0/self_attn/"
    row = lambda path, s: {"path": path, "flops": 1e9, "bytes": 1e6,
                           "pass": "forward", "calls": 50, "seconds": s}
    rows = [row(pre + "mla_q/dot_general", 0.5),
            row(pre + "mla_latent/dot_general", 0.4),
            row(pre + "out_proj/dot_general", 0.3),
            row(pre + "mla_attn/rotary/mul", 0.2),
            # another decoder's attention: an out_proj with no mla_q beside it
            row("jit(train_step)/JoyAI/decoder/layers_1/mlp/fc1/dot_general", 0.6)]
    work = {"device_op_s": 10.0, "program_runs": 5, "stated": True,
            "rows": rows, "marks": {"mtp_loss": {
                "n": 2, "ms": [0.1, 0.1],
                "stats": {"mtp": [9.8, 9.7], "main": [9.7, 9.6]}}}}
    run = {"scope_pass": passed, "scope_work": work, "program_trace": named,
           "config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * L, "updates": 10,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name, r=run: harness.load_module("layer_metrics", name).read(r)
    assert read("mla_attn_device_pct") == pytest.approx(40.0)
    assert read("mtp_device_pct") == pytest.approx(18.0)
    assert read("mla_proj_device_pct") == pytest.approx(12.0)
    scopes = harness.load_module("flops", "joyai_scopes")
    ops, nbytes = scopes.mla_attn(run)
    assert ops / 197e12 > nbytes / 819e9
    assert read("mla_attn_roofline_pct") == pytest.approx(
        100 * ops / 197e12 * 5 / 4.0)
    assert 0 < read("mla_attn_roofline_pct") < 100
    assert read("mtp_loss_over_main") == pytest.approx(19.5 / 19.3)
    # a configuration without the keys the count reads
    bert = load(os.path.join(BENCH, "configs", "bert_base.json"))
    assert read("mla_attn_roofline_pct", dict(run, config=bert)) is None
    # operations were named and none ran under the scopes (another
    # decoder's out_proj is not latent attention's), no such mark: 0
    bare = dict(run, scope_pass=dict(passed, seconds={"moe": 1.0}),
                scope_work=dict(work, rows=rows[2:3] + rows[4:], marks={}))
    for name in NEW:
        assert read(name, bare) == 0, name
    # a program that writes no annotations at all: nothing to count
    silent = dict(bare, program_trace=dict(named, host={}))
    assert read("mtp_loss_over_main", silent) is None
    # no scope table, or no trace at all: nothing to read
    for name in NEW:
        assert read(name, {"program_trace": None, "peaks": {}, "base": BENCH}) is None
        assert read(name, {"peaks": {}, "base": BENCH}) is None
