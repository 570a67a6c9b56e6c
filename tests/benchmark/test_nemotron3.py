"""``nemotron3_super_120b`` and its cell through the benchmark's own code at
a size a test run can hold: the manifest's entries and the configuration
file's statements, the plain reference following the program over three
updates, a ``correct`` that notices mathematics left out, the operation
counts, and the reduction the new per-layer metrics
read.  The step compiled for a described v5e: ``test_compile_v5e_nemotron.py``."""

import json
import os

import numpy as np
import pytest

import bench_tiny
from bench_tiny import BENCH, ROOT, load, tiny_checkout
from benchmark import control, harness, scope_shares

CELL = "nemotron3_super_120b.train_pack8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the cell at a tiny size: one of 2 shares of the mixers' heads (4 Mamba
# heads in 2 groups, 4 query heads on 1 KV head), 16 experts of which 8 are
# held, the layers *EMEM of M*EMEM (so the scanned unit repeats), 2 x 64 tokens an
# update
bench_tiny.TINY.setdefault(CELL, {
    "config": dict(
        hidden_size=64, hybrid_override_pattern="M*EMEM", pattern_held="*EMEM",
        num_hidden_layers=6, mixer_shares=2,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=4, ssm_state_size=16,
        chunk_size=16, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, n_routed_experts=16, n_routed_experts_held=8,
        first_routed_expert_held=4, num_experts_per_tok=4, moe_latent_size=32,
        moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
        loss_chunk=48, vocab_size=200,
    ),
    # 64 documents of 32 .. 200 words are 118 blocks of 64 tokens, the same
    # for every seed: every batch of an epoch has both its rows (at 30 ..
    # 200 they were 117, and a window that reached the epoch's last, single
    # row compiled that shape: ``test_every_tiny_epoch_holds_whole_batches``)
    "corpus": dict(vocab=200, n_docs=64, doc_words=[32, 200]),
    "traffic": dict(
        batch_size=2, warm_updates=1, reference_rows=1,
        task_args=dict(mask_prob=1.0, tokens_per_sample=64, seq_pad_multiple=8),
    ),
})


def checks_of(out):
    return {c["name"]: c["value"] for c in out["checks"]}


# -- what the files state ---------------------------------------------------------

# ``checkout`` / ``manifest``: conftest.py's, the manifest as it is and with
# an append (what is asserted of it has to hold on both)

@pytest.fixture(scope="module")
def config():
    return load(os.path.join(BENCH, "configs", "nemotron3_super_120b.json"))


def test_the_cell_and_its_metrics_are_in_the_manifest(checkout):
    cell = checkout.cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "train"
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert {"ssm_device_pct", "ssd_scan_device_pct", "ssd_scan_roofline_pct",
            "moe_device_pct", "moe_routed_device_pct",
            "moe_routed_roofline_pct", "moe_load_max_over_mean",
            "train_mfu_pct", "peak_hbm_gib",
            # the accepted readers that find something to read in the cell
            "attention_device_pct", "lm_head_loss_device_pct",
            "optimizer_share_pct", "unattributed_device_pct",
            "attn_kernel_fwd_device_pct", "attn_kernel_bwd_device_pct",
            "attn_kernel_fwd_roofline_pct", "attn_kernel_bwd_roofline_pct",
            "step_host_ms", "step_h2d_ms", "step_launch_ms",
            "data_buffer_depth", "data_produce_ms"} <= mine
    assert "ffn_device_pct" not in mine  # no fc1 / fc2 in this model
    for name in mine:  # every reader is there, and finds nothing to read
        reader = harness.load_module("layer_metrics", name, checkout.base)
        assert reader.read({"peaks": {}, "base": BENCH}) is None or name in (
            "peak_hbm_gib",)
    for kind in ("reference", "flops"):
        harness.find(kind, cell.config[kind] + ".py")
    tr = cell.traffic
    assert tr["batch_size"] * tr["task_args"]["tokens_per_sample"] == 8192
    assert tr["corpus"] == {"kind": "text", "vocab": 16384, "n_docs": 1024,
                            "doc_words": [512, 12288]}
    assert tr["task_args"]["mask_prob"] == 1.0


def test_the_configuration_states_its_source_its_cuts_and_what_it_assumed(
        manifest, config):
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "nemotron3_super_120b")
    assert entry["source"] == config["source"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    for key in config["reduced"]:
        assert key in config and key in config["published"], key
        assert config[key] != config["published"][key]
    # the cut in depth and heads is stated by the program's own keys; the
    # published counts stay as published
    assert (config["mamba_num_heads"], config["n_groups"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["num_hidden_layers"]) == (128, 8, 32, 2, 88)
    assert config["pattern_held"] in config["hybrid_override_pattern"]
    assert "64 chips" in config["deployment"]
    assert config["deployment"] in config["reduced_why"]
    for stated in ("no rotary embedding", "optimizer", "routing",
                   "routing_bias_update", "packing"):
        assert config["assumed"][stated]
    # one whole period, 8 experts, an eighth of the vocabulary: the floors
    assert config["pattern_held"] == "*EMEMEMEMEM" and config["mixer_shares"] == 8
    assert config["n_routed_experts_held"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]


def test_no_width_differs_from_the_catalog_row(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the architectures catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["reduced"]) & set(row["config"]) == {
        "vocab_size", "num_nextn_predict_layers"}


def test_the_share_counts_701_million_parameters(config):
    import jax

    ref = harness.load_module("reference", "nemotron3_super_120b")
    shapes = ref.param_shapes(config, {"vocab_size": config["vocab_size"]})
    count = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 700e6 < count < 702e6
    assert 0.25 * 16e9 < 16 * count < 0.75 * 16e9  # 16 bytes a parameter


# -- correct ----------------------------------------------------------------------

def test_reference_follows_the_program_in_float32(run_tiny):
    """Loss, first gradient and three updates: the chunked scan against the
    token-by-token recurrence, the sorted, tiled dispatch against the loop over
    experts, the kernels' layout against plain softmax attention, the
    trainer's Adam against the leaf-by-leaf follower."""
    out, last = run_tiny(CELL, float32=True)
    got = checks_of(out)
    assert last["correct"] is True and last["failed"] == 0, out["checks"]
    for step in (1, 2, 3):
        assert got[f"loss_rel_gap.step{step}"] < 2e-6
    assert got["first_grad_norm_gap.worst_leaf"] < 5e-5
    assert got["param_change_norm_gap.worst_leaf"] < 5e-5
    assert got["recompiles_in_window"] == 0
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_sound_bfloat16_run_is_correct_on_a_large_seed(run_tiny):
    out, last = run_tiny(CELL, seed=2 ** 31 + 977)
    assert last["correct"] is True, out["checks"]

def test_the_tiny_epoch_holds_whole_batches(tmp_path):
    """No batch of the tiny cell's epoch is short (a shape of its own, which
    a window that reaches it compiles: ROADMAP D18), and the feed counts
    its epochs."""
    bench_tiny.assert_whole_batches(tmp_path, CELL)


def _no_skip_term(monkeypatch):
    from unicore_tpu.modules import mamba2

    real = mamba2.ssd_scan
    monkeypatch.setattr(
        mamba2, "ssd_scan",
        lambda x, dt, A, B, C, D, chunk: real(x, dt, A, B, C, None, chunk=chunk),
    )


def _one_expert_silent(monkeypatch):
    from unicore_tpu.modules import latent_moe

    real = latent_moe._grouped_ffn
    monkeypatch.setattr(  # the first held expert's output projection is zero
        latent_moe, "_grouped_ffn",
        lambda x, w1, w2, *tiles: real(x, w1, w2.at[0].set(0.0), *tiles),
    )


@pytest.mark.parametrize("fault", [_no_skip_term, _one_expert_silent])
def test_mathematics_left_out_is_not_correct(fault, run_tiny, monkeypatch):
    """The ``D x_t`` term of the scan, or one held expert's output, left
    out of the timed path: ``correct`` comes out false."""
    fault(monkeypatch)
    out, last = run_tiny(CELL, float32=True)
    assert last["correct"] is False
    failed = {c["name"] for c in out["checks"] if not c["value"] <= c["limit"]}
    assert "first_grad_norm_gap.worst_leaf" in failed, failed


def test_the_lower_precision_control_is_not_correct(tmp_path):
    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    c = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root)
    checks = control.control_checks(c, seed=2 ** 31 + 3, precision="bfloat16")
    assert harness.report_checks(checks) is False


def test_the_reference_notices_what_it_is_told_to_leave_out(tmp_path):
    """The reference's own ``leave_out`` switches (what the tests above
    break in the program) change its numbers: they are not dead."""
    from benchmark import weights

    root, base = tiny_checkout(tmp_path, CELL, float32=True)
    cfg = harness.Cell(load(root + "/BENCHMARK.json"), CELL, base, root).config
    ref = harness.load_module("reference", "nemotron3_super_120b")
    params = weights.make(ref.param_shapes(cfg, {"vocab_size": 200}), 5)
    tok = np.random.default_rng(0).integers(4, 200, (1, 40)).astype(np.int32)
    whole = np.asarray(ref.hidden(params, cfg, tok))
    for what in ("skip", "expert"):
        assert np.abs(np.asarray(ref.hidden(params, cfg, tok, leave_out=what))
                      - whole).max() > 1e-4


# -- counts and the reduction ---------------------------------------------------------

def test_operation_counts_from_shapes(config):
    flops = harness.load_module("flops", "nemotron3_super_120b")
    body, head = flops.forward_per_token(config)
    assert head == 2 * 4096 * 16384
    d, lat, f, fs = 4096, 1024, 2688, 5376
    moe = 2 * d * 512 + 4 * d * lat + 4 * d * fs + 22 * 8 / 512 * 4 * lat * f
    attn = 2 * d * (4 + 2) * 128 + 2 * 4 * 128 * d
    mamba = (2 * d * (2 * 1024 + 2 * 128 + 16) + 2 * 1024 * d
             + flops.scan_per_token(16, 64, 1, 128, 128))
    assert body == pytest.approx(5 * moe + attn + 5 * mamba)
    n = 8192
    total = flops.train_flops(config, n, n * n, 1.0)
    pairs = n * (n + 1) / 2
    assert total == pytest.approx(
        3 * (n * (body + head) + pairs * 2 * 2 * 4 * 128))
    assert 2.4e9 < total / n < 2.7e9  # about 6 x the 420 M active parameters
    run = {"config": config, "base": BENCH, "sum_n": 10 * n, "updates": 10}
    scopes = harness.load_module("flops", "nemotron_scopes")
    ops, nbytes = scopes.ssd_scan(run)
    assert ops == pytest.approx(
        3 * 5 * n * flops.scan_per_token(16, 64, 1, 128, 128))
    assert nbytes > 0 and ops / nbytes < 240  # the bytes bound it on a v5e
    # the routed experts: the pairs an update really routed here, all
    # layers together, not the even share ``train_flops`` counts
    ops, nbytes = scopes.moe_routed(run, pairs=2000)
    assert ops == pytest.approx(3 * 2000 * 4 * lat * f)
    weights = 5 * 3 * 8 * 2 * lat * f * 2
    assert nbytes == pytest.approx(weights + 4 * 2000 * lat * 2)
    assert scopes.moe_routed(run, pairs=0) == (0, weights)


def test_scope_reduction_credits_every_scope_on_an_operations_path():
    """A hand-built trace: two runs of a program on one device, four
    operations each, one of them a while loop's wrapper (not counted)."""
    table = {"module": "jit_train_step", "instructions": {
        "fusion.1": "jit(train_step)/forward/Model/decoder/units/while/body/layer_1/mamba/ssd_scan/dot_general",
        "fusion.2": "jit(train_step)/forward/Model/decoder/units/while/body/layer_0/moe/moe_routed/dot_general",
        "fusion.3": "jit(train_step)/optimizer/mul",
        "fusion.4": "",
        "fusion.5": "jit(train_step)/forward/Model/decoder/units/while/body/layer_0/add",
    }}
    ms = 1e6
    events, modules = [], []
    for run in range(2):
        t = run * 100 * ms
        modules.append((t, t + 50 * ms, "jit_train_step"))
        modules.append((t + 60 * ms, t + 61 * ms, "jit__lambda_"))
        events += [
            (t, t + 40 * ms, "%while.9 = (f32[]) while(%x)"),
            (t, t + 10 * ms, "%fusion.1 = f32[8] fusion(%a), kind=kLoop"),
            (t + 10 * ms, t + 30 * ms, "%fusion.2 = f32[8] fusion(%a), kind=kLoop"),
            (t + 30 * ms, t + 35 * ms, "%fusion.3 = f32[8] fusion(%a), kind=kLoop"),
            (t + 35 * ms, t + 40 * ms, "%fusion.4 = f32[8] fusion(%a), kind=kLoop"),
            (t + 40 * ms, t + 42 * ms, "%fusion.5 = f32[8] fusion(%a), kind=kLoop"),
        ]
    marks = {("host", 0, "main"): [
        (0.0, 1.0, "unicore:moe_route",
         {"pairs_here": 100, "load_max": 30.0, "load_mean": 12.5}),
        (5.0, 6.0, "unicore:moe_route",
         {"pairs_here": 120, "load_max": 50.0, "load_mean": 15.0}),
    ]}
    got = scope_shares.reduce_scopes(
        {"/device:TPU:0": events}, {"/device:TPU:0": modules}, [table], marks
    )
    assert got["program_runs"] == 2
    assert got["device_op_s"] == pytest.approx(0.084)
    assert got["seconds"]["mamba"] == pytest.approx(0.020)
    assert got["seconds"]["ssd_scan"] == pytest.approx(0.020)
    assert got["seconds"]["moe"] == got["seconds"]["moe_routed"] == pytest.approx(0.040)
    assert got["seconds"]["optimizer"] == pytest.approx(0.010)
    # what no scope owns is left to ``trace_scopes``' own group
    assert "" not in got["seconds"] and "remainder_s" not in got
    assert got["inside"] == {
        "moe_routed": [["dot_general", pytest.approx(0.040)]],
        "ssd_scan": [["dot_general", pytest.approx(0.020)]],
    }
    assert got["route"] == {"pairs_here": 110, "load_max": 40.0,
                            "load_mean": 13.75}
    # the readers, on a run whose pass over the trace is already made
    named = {"groups_s": {"rest": 1.0}, "host": {"updates": 2}}
    run = {"scope_pass": got, "program_trace": named,
           "peaks": {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}}
    assert scope_shares.scope_pct(run, "moe") == pytest.approx(100 * 40 / 84)
    # operations were named and none ran under that scope
    assert scope_shares.scope_pct(run, "self_attn") == 0
    assert scope_shares.scope_roofline_pct(
        run, "ssd_scan", lambda r: (0.5, 0.02)) == pytest.approx(50.0)  # 2 x 5 ms least of 20
    assert harness.load_module(
        "layer_metrics", "moe_load_max_over_mean").read(run) == pytest.approx(40 / 13.75)
    # the routed roofline counts the pairs the marks report: 110 an update
    run.update(config=load(os.path.join(BENCH, "configs", "nemotron3_super_120b.json")),
               base=BENCH)
    ops, nbytes = harness.load_module("flops", "nemotron_scopes").moe_routed(run, 110)
    assert harness.load_module(
        "layer_metrics", "moe_routed_roofline_pct").read(run) == pytest.approx(
            100 * max(ops / 100.0, nbytes / 10.0) * 2 / 0.040)
    # a program that leaves no scope table (or an untraced run): nothing to read
    assert scope_shares.scope_pct({"program_trace": None}, "moe") is None
    assert scope_shares.route_stat(
        {"program_trace": dict(named, groups_s={})}, "pairs_here") is None
    # annotations, but none of routing (the parent commit): nothing to count
    assert harness.load_module("layer_metrics", "moe_routed_roofline_pct").read(
        {"program_trace": dict(named, host={}), "scope_pass": got}) is None

# -- the four-chip candidate ----------------------------------------------------------

def test_dp4_candidate_is_the_one_chip_cell_at_the_same_per_chip_batch():
    """``bert_base.train_dp4`` waits outside the manifest (PERF.md, PR 27:
    its runs spread too widely); its files are data only and load through
    the candidates' manifest like ``unimol.train_mol256``'s."""
    merged = bench_tiny.manifest_with_candidates()
    dp4 = harness.Cell(merged, "bert_base.train_dp4")
    one = harness.Cell(merged, "bert_base.train_mlm512")
    assert dp4.chips == 4 and one.chips == 1
    assert dp4.traffic["driver"] == "train" and dp4.config == one.config
    for same in ("batch_size", "task_args", "limits", "token_key"):
        assert dp4.traffic[same] == one.traffic[same], same
    # 128 sequences an update, and an epoch that outlasts a run on either:
    # 192 batches for its 80 updates of 250 ms (PERF.md section 5), 512 for
    # the one-chip cell's 260 of 92 ms and twice that rate (PR 39)
    assert dp4.traffic["corpus"]["n_docs"] // (dp4.traffic["batch_size"] * 4) == 192
    assert one.traffic["corpus"]["n_docs"] // one.traffic["batch_size"] == 512
    real = load(os.path.join(ROOT, "BENCHMARK.json"))
    assert all(w["name"] != "bert_base.train_dp4" for w in real["workloads"])
