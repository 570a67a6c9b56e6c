"""``zaya1_8b`` and its cell through the benchmark's own code at a size a
test run can hold: the manifest's entries and the configuration file's
statements, the counted parameters, the plain reference following the
program over three updates, a ``correct`` that notices a mechanism left out
(the q-k mean, the late value, the carried router state, a router in
bfloat16), the operation counts and the readers of the new per-layer
metrics.  The step compiled for a described v5e:
``test_compile_v5e_zaya1.py``; the model's own cases: ``tests/test_zaya.py``.
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

CELL = "zaya1_8b.train_pack8k_x4"
CONFIG = "zaya1_8b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNT = 587_806_725

TINY_ROPE = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 100,
                        "rope_type": "default"}}

# the cell at a tiny size: 3 of 6 layers as one scanned unit, one of 2
# shares of 4 query heads on 2 KV heads of 16 (KV head 1: the late value), a
# rotary table over half a head, 4 of 8 experts behind a router 24 wide with
# its skip column, 2 rows x 128 tokens an update; the groups as JSON text,
# which the train driver hands on
bench_tiny.TINY.setdefault(CELL, {
    "config": dict(
        hidden_size=64, num_hidden_layers=6, layers_held=3,
        layer_types=json.dumps(["hybrid"] * 6),
        rope_parameters=json.dumps(TINY_ROPE),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        attention_shares=2, first_kv_head_held=1, num_experts=8,
        num_experts_held=4, moe_intermediate_size=48, router_hidden_size=24,
        vocab_size=200, loss_chunk=48,
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


NEW = ["cca_mix_device_pct", "cca_mix_roofline_pct", "zaya_router_device_pct",
       "moe_top1_routed_roofline_pct", "moe_skip_share"]
LISTED = ["attention_device_pct", "lm_head_loss_device_pct",
          "optimizer_share_pct", "unattributed_device_pct",
          "attn_kernel_fwd_device_pct", "attn_kernel_bwd_device_pct",
          "attn_kernel_fwd_roofline_pct", "attn_kernel_bwd_roofline_pct",
          "step_host_ms", "step_h2d_ms", "step_launch_ms",
          "data_buffer_depth", "data_produce_ms", "data_pack_ms",
          "moe_device_pct", "moe_routed_device_pct", "moe_load_max_over_mean",
          "xla_matmul_device_pct", "xla_matmul_roofline_pct",
          "attn_proj_roofline_pct", "optimizer_roofline_pct",
          "remat_device_pct"]
# held to one cell each by the files that brought them (``m["workloads"] ==
# [CELL]`` in test_mellum2.py, test_laguna_s_2_1.py, test_evabyte.py), files
# this PR may not edit: the cell is on none of their lists
PINNED = ["band_attn_device_pct", "band_attn_roofline_pct",
          "band_keys_computed_over_visible", "moe_gated_roofline_pct",
          "rotary_device_pct", "band_window_keys_computed_over_visible",
          "band_full_keys_computed_over_visible", "attn_gate_device_pct",
          "band_attn_heads_roofline_pct", "moe_gated_routed_roofline_pct"]


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
    # no dense layer, no shared expert, no latent in the routed count, no
    # Mamba, no EVA, and what other files pin to their cells: not this cell's
    assert not mine & {
        "ffn_device_pct", "ffn_roofline_pct", "moe_shared_roofline_pct",
        "moe_routed_roofline_pct", "ssm_device_pct", "eva_agg_device_pct",
        *PINNED}
    # the new metrics are this cell's alone, each listed once, in the order
    # they were appended in and after the last the benchmark had then
    listed = [m["name"] for m in manifest["per_layer"]]
    at = [listed.index(name) for name in NEW]
    assert at == sorted(at)
    assert listed.index("moe_gated_routed_roofline_pct") < at[0]
    assert all(listed.count(name) == 1 for name in NEW)
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
            assert m["layer"] == "modules (modules/, models/, losses/)"
        if m["name"] in LISTED:
            # appended: after every cell the list had
            older = [w for w in m["workloads"] if w in (
                "bert_base.train_mlm512", "laguna_s_2_1.train_pack32k")]
            assert older and all(
                m["workloads"].index(w) < m["workloads"].index(CELL)
                for w in older)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert (by_name["moe_skip_share"]["unit"],
            by_name["moe_skip_share"]["source"]) == ("ratio", "program_counter")
    for name in NEW[:4]:
        assert (by_name[name]["unit"], by_name[name]["source"]) == (
            "%", "device_trace")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["config"] == CONFIG and entry["traffic"] == "train_pack8k_x4"
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index("laguna_s_2_1.train_pack32k") < cells.index(CELL)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("laguna_s_2_1") < configs.index(CONFIG)
    for name in mine:  # every reader is there, and finds nothing to read
        reader = harness.load_module("layer_metrics", name, checkout.base)
        assert reader.read({"peaks": {}, "base": BENCH}) is None or name in (
            "peak_hbm_gib",)
    for kind in ("reference", "flops"):
        harness.find(kind, cell.config[kind] + ".py")
    harness.find("flops", "zaya_scopes.py")
    tr = cell.traffic
    # ISSUE 46's traffic: 4 rows x 8,192, 400 batches an epoch
    assert (tr["batch_size"], tr["task_args"]["tokens_per_sample"]) == (4, 8192)
    assert tr["corpus"] == {"kind": "text", "vocab": 32784, "n_docs": 2048,
                            "doc_words": [512, 12288]}
    assert tr["task_args"]["seq_pad_multiple"] == 128
    assert (tr["data_workers"], tr["data_buffer"], tr["warm_updates"],
            tr["reference_rows"]) == (2, 8, 3, 4)
    assert set(tr["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                 "delta_norm_gap"}
    assert len(entry["why"]) <= 200 and len(cfg_entry["why"]) <= 200
    assert "quarter" in entry["why"]  # the head sees a quarter of its tokens


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
            config["num_experts_per_tok"], config["router_hidden_size"]) == (
                40, 8, 2, 16, 1, 256)
    assert config["layer_types"] == ["hybrid"] * 40   # kept whole
    assert config["sliding_window"] is None
    assert config["tie_word_embeddings"] is True
    # the guide's floors: four layers, 8 experts, an eighth of the vocabulary
    assert config["layers_held"] >= 4
    assert config["num_experts_held"] >= 8
    assert config["vocab_size"] * 8 >= 262272
    assert "one of 8 chips" in config["deployment"]
    assert (config["attention_shares"], config["first_kv_head_held"]) == (2, 1)
    assert f"{COUNT:,}" in config["reduced_why"]
    # no width among the cuts
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
    for stated in ("equations", "switches", "skip column", "depth averaging",
                   "router network", "merge", "convolutions", "q-k mean",
                   "value", "temperature", "rotary", "experts", "selection",
                   "balancing loss", "head", "optimizer", "packing"):
        assert config["assumed"][stated]
    assert config["router_balancing"] == "batch_bias"
    assert "2510.04476" in config["papers"] and "2511.17127" in config["papers"]
    assert config["remat"] is True
    assert config["train_args"]["adam_betas"] == [0.9, 0.95]
    assert config["train_args"]["no_weight_decay_names"] == "norm,scale"
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
    assert row["config"]["vocab_size"] == 262272 == 8 * config["vocab_size"]


def test_the_programs_defaults_are_the_files_groups(config):
    """The train driver hands the program the file's numbers and strings;
    its list and its group reach the program as the program's own defaults,
    which are these very values; ``sliding_window`` null is its 0."""
    from unicore_tpu.models import zaya

    fields = zaya.ZayaModel.__dataclass_fields__
    for key in zaya.ZayaModel.GROUPS:
        assert json.loads(fields[key].default) == config[key], key
    for key, value in config.items():
        if key in fields and isinstance(value, (int, float, str)) and key not in (
                "name", "vocab_size", "router_balancing", "first_kv_head_held",
                *config["reduced"]):
            assert fields[key].default == value, key
    assert fields["sliding_window"].default == 0
    assert fields["router_balancing"].default == "none"
    assert fields["vocab_size"].default == 262272
    assert fields["attention_shares"].default == 1


def test_the_share_counts_its_stated_parameters(config):
    import jax

    ref = harness.load_module("reference", CONFIG)
    shapes = ref.param_shapes(config, {"vocab_size": config["vocab_size"]})
    count = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    d, D, R, f = 2048, 128, 256, 2048
    cca = (d * 4 * D + 2 * d * D            # W_q, W_k and one value projection
           + 3 * 5 * D + 5 * D              # the depthwise taps, both biases
           + 2 * 5 * D * D + 1              # the per-head taps, one temperature
           + 4 * D * d)                     # W_o
    assert cca == 2_787_841
    router = d * R + 3 * R + 2 * (R * R + R) + R * 17
    assert router == 660_992
    experts = 8 * 3 * d * f
    assert experts == 100_663_296
    layer = cca + router + experts + 2 * d + 2 * 4 * d
    assert layer == 104_132_609
    assert count == 5 * layer + 32784 * d + d == COUNT
    assert 0.25 * 16.9e9 < 16 * count < 0.75 * 16.9e9  # 16 bytes a parameter
    # four layers, the floor, would be 483.7 M
    assert 4 * layer + 32784 * d + d == 483_674_116


def test_the_program_builds_the_references_tree(config):
    """At the real widths, from shapes alone: the program's parameter tree
    is the one ``param_shapes`` states, leaf for leaf, counts the stated
    parameters and has no ``lm_head``."""
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
    assert model.pattern == "CZ" * 5 and model.tied
    sizes = model.layers()["sizes"]
    assert (sizes["C"]["num_heads"], sizes["C"]["num_kv_heads"],
            sizes["C"]["first_kv_head"]) == (4, 1, 1)
    assert (sizes["Z"]["n_held"], sizes["Z"]["n_routed"],
            sizes["Z"]["balancing"]) == (8, 16, "batch_bias")
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
    assert set(got["params"]) == {"embed_tokens", "decoder"}
    # the even load: one wide trip and seven tiles an expert
    from unicore_tpu.modules import latent_moe

    assert latent_moe.wide_rows(32768, 1, 17) == 1024
    assert 32768 // 17 == 1927 == 1024 + 7 * 128 + 7


# -- correct ----------------------------------------------------------------------

def test_reference_follows_the_program_in_float32(run_tiny):
    """Loss, first gradient and three updates: the fused down projection
    and the one product over both taps against the equations' sums over two
    positions, the band as a mask of iotas against a mask over the whole
    row, the router's carried state through the scanned unit, the sorted
    and tiled experts against a dense loop over the columns, the tied head
    in loss chunks against row blocks, the trainer's Adam (with the vectors
    the scan gives a second axis left undecayed) against the leaf-by-leaf
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


def _no_qk_mean(monkeypatch):
    import jax.numpy as jnp

    from unicore_tpu.modules import cca

    monkeypatch.setattr(cca, "qk_mean", lambda q, k: jnp.zeros_like(q))


def _no_late_value(monkeypatch):
    from unicore_tpu.models import zaya

    real = zaya.ZayaModel.build_model.__func__
    from argparse import Namespace

    monkeypatch.setattr(  # built as if it held KV head 0, the prompt one
        zaya.ZayaModel, "build_model", classmethod(
            lambda cls, args, task: real(cls, Namespace(**dict(
                vars(args), first_kv_head_held=0)), task)))


def _no_depth_state(monkeypatch):
    import jax.numpy as jnp

    from unicore_tpu.modules.zaya_moe import ZayaMoE

    real = ZayaMoE.__call__
    monkeypatch.setattr(  # every layer's router starts from zeros
        ZayaMoE, "__call__", lambda self, h, r_prev: real(
            self, h, jnp.zeros_like(r_prev)))


def _bf16_router(monkeypatch):
    import jax.numpy as jnp

    from unicore_tpu.modules import zaya_moe

    monkeypatch.setattr(
        zaya_moe, "_product", lambda x, w: jnp.dot(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32))


@pytest.mark.parametrize("fault", [_no_qk_mean, _no_late_value,
                                   _no_depth_state, _bf16_router])
def test_a_mechanism_left_out_is_not_correct(fault, run_tiny, monkeypatch):
    """The q-k mean, the late value or the carried router state left out of
    the timed path, or its router computed in bfloat16: ``correct`` comes
    out false (the comparison catches the mechanisms, not only the
    matmuls)."""
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
    d, D, n, L = 2048, 128, 32768, 8192
    body, head = flops.forward_per_token(config)
    assert head == 2 * d * 32784
    cca = 2 * d * 6 * D + 2 * 5 * 2 * D * D + 2 * 4 * D * d
    router = 2 * d * 256 + 2 * 2 * 256 * 256 + 2 * 256 * 17
    assert flops.router_per_token(config) == router
    routed = (8 / 17) * 3 * 2 * d * 2048        # 0.47 pairs a token
    assert body == pytest.approx(5 * (cca + router + routed))
    # a token, forward: the latent's projections 5.2 M and its convolution
    # 0.3 M a layer, the router 1.3 M, the routed experts 11.8 M, head 134 M
    assert 2 * d * 6 * D + 2 * 4 * D * d == pytest.approx(5.2e6, rel=0.01)
    assert 2 * 5 * 2 * D * D == 327_680
    assert router == pytest.approx(1.32e6, rel=0.01)
    assert routed == pytest.approx(11.8e6, rel=0.01)
    assert head == pytest.approx(134e6, rel=0.01)
    full = L * (L + 1) // 2
    assert flops.visible_keys(L) == full
    assert flops.forward_per_key(config) == 4 * 4 * D
    band = 5 * full * 4 * 4 * D
    total = flops.train_flops(config, 10 * n, 10 * n * L, 1.0)
    assert total == pytest.approx(3 * (10 * n * (body + head) + 40 * band))
    # at 8k rows the band kernels' visible work is 16% of the count, the
    # tied head (an eighth of the vocabulary) half of it
    assert 0.14 < 3 * 40 * band / total < 0.17
    assert 0.48 < 3 * 10 * n * head / total < 0.52
    assert 0.78e9 < total / (10 * n) < 0.84e9
    run = {"config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * L, "updates": 10}
    scopes = harness.load_module("flops", "zaya_scopes")
    pairs = 5 * n * 8 / 17                         # an even load, five layers
    ops, nbytes = scopes.moe_top1_routed(run, pairs)
    assert ops == pytest.approx(3 * pairs * 3 * 2 * d * 2048)
    assert nbytes == pytest.approx(
        5 * 3 * 8 * 3 * d * 2048 * 2 + 4 * pairs * d * 2)
    # at a deployment's load the products bound it: 1,927 rows an expert
    # against 25 MB of its weights three times
    assert ops / nbytes > 240


def test_counts_at_a_small_shape_by_hand():
    """One layer at sizes a hand count holds: 2 query heads on one KV head
    of 4, hidden 8, a router 3 wide over 2 experts and the skip column, one
    expert of width 5 held, a row of 3."""
    cfg = dict(
        hidden_size=8, head_dim=4, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, num_experts=2,
        num_experts_held=1, moe_intermediate_size=5, router_hidden_size=3,
        vocab_size=10, flops=CONFIG)
    flops = harness.load_module("flops", CONFIG)
    body, head = flops.forward_per_token(cfg)
    cca = 2 * 8 * 4 * 4 + 2 * 3 * 8 * 4 + 2 * 2 * 4 * 8   # down, taps, up
    router = 2 * 8 * 3 + 2 * 2 * 3 * 3 + 2 * 3 * 3
    assert (body, head) == (cca + router + (1 / 3) * 3 * 2 * 8 * 5, 160)
    # a row of 3 sees 1 + 2 + 3 keys
    band = 6 * 4 * 2 * 4
    assert flops.train_flops(cfg, 6, 18, 1.0) == pytest.approx(
        3 * (6 * (body + head) + 2 * band))
    run = {"config": cfg, "base": BENCH, "sum_n": 6, "sum_n2": 18, "updates": 2}
    scopes = harness.load_module("flops", "zaya_scopes")
    assert scopes.moe_top1_routed(run, 7) == (
        3.0 * 7 * 3 * 2 * 8 * 5, 1 * 3 * (1 * 3 * 8 * 5 * 2) + 4 * 7 * 8 * 2)


def test_readers_of_the_new_metrics(config):
    """On a run whose pass over the trace is already made: the two shares
    of device time, the two roofline shares and the skip share; and
    nothing, not an error, from a program that leaves no such scope or
    mark."""
    n, L = 32768, 8192
    passed = {"device_op_s": 10.0, "program_runs": 5,
              "seconds": {"cca_mix": 0.5, "moe_router": 0.4,
                          "moe_routed": 1.0, "self_attn": 5.0},
              "inside": {}, "route": {"pairs_here": 77000.0}, "route_marks": 5}
    named = {"groups_s": {"rest": 1.0}, "host": {"updates": 5},
             "device_op_s": 10.0, "program_runs": 5}
    rows = [
        # a fusion of the mix: 40 MB in 80 us; its product: 1 GFLOP in 10 us
        {"path": "jit(train_step)/Zaya/decoder/units/layer_0/self_attn/cca_mix/mul",
         "flops": 0, "bytes": 40e6, "pass": "forward", "calls": 50,
         "seconds": 50 * 80e-6},
        {"path": "jit(train_step)/Zaya/decoder/units/layer_0/self_attn/cca_mix/dot_general",
         "flops": 1e9, "bytes": 1e6, "pass": "forward", "calls": 50,
         "seconds": 50 * 10e-6},
        {"path": "jit(train_step)/Zaya/decoder/units/layer_0/self_attn/cca_up/dot_general",
         "flops": 1e9, "bytes": 1e6, "pass": "forward", "calls": 50,
         "seconds": 50 * 10e-6},
    ]
    work = {"device_op_s": 10.0, "program_runs": 5, "stated": True,
            "rows": rows, "marks": {"moe_skip": {
                "n": 2, "ms": [0.1, 0.1],
                "stats": {"skipped": [9000, 10000], "tokens": [163840, 163840]}}}}
    run = {"scope_pass": passed, "scope_work": work, "program_trace": named,
           "config": config, "base": BENCH, "sum_n": 10 * n,
           "sum_n2": 10 * n * L, "updates": 10,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name, r=run: harness.load_module("layer_metrics", name).read(r)
    assert read("cca_mix_device_pct") == pytest.approx(5.0)
    assert read("zaya_router_device_pct") == pytest.approx(4.0)
    least = 40e6 / 819e9 + 1e9 / 197e12
    assert read("cca_mix_roofline_pct") == pytest.approx(100 * least / 90e-6)
    scopes = harness.load_module("flops", "zaya_scopes")
    ops, nbytes = scopes.moe_top1_routed(run, 77000.0)
    assert ops / 197e12 > nbytes / 819e9
    assert read("moe_top1_routed_roofline_pct") == pytest.approx(
        100 * ops / 197e12 * 5 / 1.0)
    assert 0 < read("moe_top1_routed_roofline_pct") < 100
    assert 0 < read("cca_mix_roofline_pct") < 100
    assert read("moe_skip_share") == pytest.approx(19000 / 327680)
    # a configuration without the keys the count reads
    bert = load(os.path.join(BENCH, "configs", "bert_base.json"))
    assert read("moe_top1_routed_roofline_pct", dict(run, config=bert)) is None
    # operations were named and none ran under the scopes, no such mark: 0
    bare = dict(run, scope_pass=dict(passed, seconds={"moe": 1.0}),
                scope_work=dict(work, rows=rows[2:], marks={}))
    for name in NEW:
        assert read(name, bare) == 0, name
    # a program that writes no annotations at all: nothing to count
    silent = dict(bare, program_trace=dict(named, host={}))
    assert read("moe_top1_routed_roofline_pct", silent) is None
    assert read("moe_skip_share", silent) is None
    # no scope table, or no trace at all: nothing to read
    for name in NEW:
        assert read(name, {"program_trace": None, "peaks": {}, "base": BENCH}) is None
        assert read(name, {"peaks": {}, "base": BENCH}) is None
