"""``joyai``: latent attention with a query latent, sigmoid-scored experts
under a selection bias beside a shared expert, and a multi-token-prediction
module that trains with the model, at sizes a CPU test holds.  The model is
held to ``benchmark/reference/joyai_llm_flash.py`` (float32, plain
``jax.numpy``); the attention module's own cases are in
``tests/test_mla.py``, the benchmark cell's in
``tests/benchmark/test_joyai_llm_flash.py``."""

import os
import sys
from argparse import Namespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.modules import latent_moe, mla, mtp
from unicore_tpu.modules.gated_moe import GatedMoE
from unicore_tpu.modules.hybrid_decoder import KINDS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the reference's keys of the tiny preset
KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
        "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_theta", "rope_interleave", "n_routed_experts",
        "n_shared_experts", "num_experts_per_tok", "moe_intermediate_size",
        "norm_topk_prob", "routed_scaling_factor", "num_nextn_predict_layers",
        "mtp_loss_weight", "rms_norm_eps", "router_balancing", "layers_held",
        "attention_shares", "num_experts_held", "first_expert_held")
V = 120


class _Dictionary:
    pad = staticmethod(lambda: 0)
    __len__ = lambda self: V


class _Task:
    dictionary = _Dictionary()
    args = None


def tiny_model(**over):
    from unicore_tpu.models import ARCH_CONFIG_REGISTRY, ARCH_MODEL_REGISTRY

    args = Namespace(**over)
    ARCH_CONFIG_REGISTRY["joyai_tiny"](args)
    return args, ARCH_MODEL_REGISTRY["joyai_tiny"].build_model(args, _Task())


def reference():
    from benchmark.reference import joyai_llm_flash

    return joyai_llm_flash


def seeded(args, seed=11, scale=3.0):
    """The reference's tree for ``args``, seeded by the benchmark's rules
    (kernels and the selection bias N(0, 0.02), norm gains 1 + N(0, 0.02)),
    then everything times ``scale``: a sharper softmax, a router that
    spreads and a selection bias that decides."""
    from benchmark import weights

    cfg = {k: getattr(args, k) for k in KEYS}
    params = weights.make(reference().param_shapes(cfg, {"vocab_size": V}), seed)
    return cfg, jax.tree_util.tree_map(lambda a: scale * a, params)


def batch_of(rows=2, length=96, seed=0):
    tok = np.random.default_rng(seed).integers(1, V, (rows, length)).astype(np.int32)
    return {"net_input": {"src_tokens": tok}, "target": tok}


def loss_and_gradients(model, params, sample):
    from unicore_tpu.losses.lm_cross_entropy import LMCrossEntropyLoss

    loss = LMCrossEntropyLoss(_Task())
    (value, log), grads = jax.value_and_grad(
        lambda p: loss.forward(model, p, sample)[::2], has_aux=True)(params)
    return value, grads, log


def reference_loss_and_gradients(cfg, params, sample, leave_out=None):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: reference().loss_sum(
            p, cfg, sample, 0, leave_out=leave_out))(params)


def worst_leaf(got, want):
    """The largest gap of a gradient leaf over that leaf's largest entry in
    the reference, and the leaf's path."""
    gaps = {
        jax.tree_util.keystr(path): float(jnp.abs(a - b).max())
        / (float(jnp.abs(b).max()) + 1e-9)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                jax.tree_util.tree_leaves(want))}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


#: float32 against float32: the program and the reference differ in the
#: order of their sums only (the rotary columns read even channels first,
#: two products under W_eh for one, sorted tiles of pairs against dense
#: products over all tokens, loss chunks against row blocks)
LOSS_RTOL = 2e-6
LEAF_TOL = 5e-5


# -- the model against the reference ---------------------------------------------

@pytest.mark.parametrize("wide", [0, 32], ids=["tiles", "wide-trips"])
@pytest.mark.parametrize("balancing", ["none", "batch_bias"])
def test_tiny_model_is_the_plain_reference_loss_logits_and_gradients(
        monkeypatch, balancing, wide):
    """A dense layer, two sparse layers as one scanned unit and the
    prediction module on seeded weights, one share of two of the heads and
    experts 2 .. 5 of 8: the weighted loss, the logits and every gradient
    leaf against the reference, with the published choice of experts (the
    bias leaf read) and with the batch's bias; through the loop over the
    tiles and, with the tile at 8 rows and the wide trip at 32, through the
    wide and narrow loops the benchmark's cell runs."""
    if wide:
        monkeypatch.setattr(latent_moe, "TILE", 8)
        monkeypatch.setattr(latent_moe, "WIDE", wide)
    args, model = tiny_model(
        attention_shares=2, num_experts_held=4, first_expert_held=2,
        router_balancing=balancing)
    assert model.pattern == "LFLRLR" and set(model.pattern) <= set(KINDS)
    assert model.mtp_pattern == "LR" and model.ahead == (("mtp", 0.3),)
    cfg, params = seeded(args)
    sample = batch_of(4, 96)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0), sample))
    assert (jax.tree_util.tree_structure(shapes)
            == jax.tree_util.tree_structure(params))
    assert jax.tree_util.tree_map(lambda a: a.shape, shapes) == (
        jax.tree_util.tree_map(lambda a: a.shape, params))
    value, grads, log = loss_and_gradients(model, params, sample)
    want = reference_loss_and_gradients(cfg, params, sample)
    assert float(value) == pytest.approx(float(want[0]), rel=LOSS_RTOL)
    gap, leaf = worst_leaf(grads, want[1])
    assert gap < LEAF_TOL, leaf
    # the parts: the main pass's NLL, the module's scaled to its sample size
    with jax.default_matmul_precision("highest"):
        _, nll, nll2, n, n2 = reference().loss_parts(params, cfg, sample, 0)
    assert (n, n2) == (4 * 95, 4 * 94) and log["sample_size"] == n
    assert float(log["nll_loss"]) == pytest.approx(float(nll), rel=LOSS_RTOL)
    assert float(log["mtp_loss"]) == pytest.approx(
        float(nll2) * n / n2, rel=LOSS_RTOL)
    assert float(log["loss"]) == pytest.approx(
        float(log["nll_loss"]) + 0.3 * float(log["mtp_loss"]), rel=1e-6)
    # no gradient reaches the selection bias
    for leaf_ in (grads["params"]["decoder"]["units"]["layer_1"]["moe"],
                  grads["params"]["mtp"]["layers_1"]["moe"]):
        assert not np.asarray(leaf_["router_bias"]).any()
    # full logits: the head over the reference's hidden states
    tok = sample["net_input"]["src_tokens"]
    with jax.default_matmul_precision("highest"):
        hidden = reference().hidden(params, cfg, tok)
        logits = hidden @ params["params"]["lm_head"]
        np.testing.assert_allclose(model.apply(params, tok), logits, atol=2e-5)
    # 2 of 8 experts a token on 3 expert layers (the module's one of them),
    # 4 held: under the batch's bias each near its share
    n_tokens = 4 * 96
    assert log["moe_layers"] == 3
    assert (log["moe_rows_wide"] > 0) == bool(wide)
    if balancing == "batch_bias":
        assert abs(log["moe_pairs_here"] - 3 * n_tokens) < 0.1 * 3 * n_tokens
    assert log["mla_heads"] / log["mla_rows"] == 2
    assert log["mla_layers"] / log["mla_rows"] == 4
    assert log["mla_latent_dim"] / log["mla_rows"] == 32 + 8


@pytest.mark.parametrize("what", reference().LEAVE_OUT)
def test_the_reference_notices_what_it_is_told_to_leave_out(what):
    """Every ``leave_out`` switch moves the reference's weighted loss by far
    more than the tolerance the sound program keeps (the selection bias is
    read under the published choice of experts)."""
    args, _ = tiny_model()
    cfg, params = seeded(args)
    sample = batch_of(2, 64)
    ref = reference()
    with jax.default_matmul_precision("highest"):
        whole = float(ref.loss_sum(params, cfg, sample, 0))
        left = float(ref.loss_sum(params, cfg, sample, 0, leave_out=what))
    assert abs(left / whole - 1) > 100 * LOSS_RTOL


def _no_query_norm(monkeypatch):
    real = mla.RMSNorm.__call__
    monkeypatch.setattr(mla.RMSNorm, "__call__", lambda self, x: (
        x if self.name == "q_norm" else real(self, x)))


def _no_key_norm(monkeypatch):
    real = mla.RMSNorm.__call__
    monkeypatch.setattr(mla.RMSNorm, "__call__", lambda self, x: (
        x if self.name == "kv_norm" else real(self, x)))


def _no_rotary(monkeypatch):
    monkeypatch.setattr(mla, "apply_rotary", lambda x, positions, table: x)


def _rotate_half(monkeypatch):
    monkeypatch.setattr(mla, "evens_first", lambda n: np.arange(n))


def _softmax_router(monkeypatch):
    from unicore_tpu.models.joyai import JoyAIModel

    real = JoyAIModel.layers

    def layers(self):  # built with the other decoders' softmax scores
        out = real(self)
        out["sizes"]["R"]["scoring"] = "softmax"
        return out

    monkeypatch.setattr(JoyAIModel, "layers", layers)


def _module_final_norm(monkeypatch):
    real = mtp.RMSNorm.__call__
    monkeypatch.setattr(mtp.RMSNorm, "__call__", lambda self, x: (
        x if self.name == "final_norm" else real(self, x)))


def _halves_swapped(monkeypatch):
    real = mtp._Join.__call__
    monkeypatch.setattr(mtp._Join, "__call__",
                        lambda self, x, e: real(self, e.astype(x.dtype), x))


def _shift_by_one(monkeypatch):
    from unicore_tpu.losses import lm_cross_entropy

    real, calls = lm_cross_entropy.shifted_targets, []

    def once(target, heads, pad):  # the module's targets not shifted again
        calls.append(1)
        return real(target, heads, pad) if len(calls) % 2 else target

    monkeypatch.setattr(lm_cross_entropy, "shifted_targets", once)


@pytest.mark.parametrize("fault", [
    _no_query_norm, _no_key_norm, _no_rotary, _rotate_half, _softmax_router,
    _module_final_norm, _halves_swapped, _shift_by_one])
def test_a_mechanism_left_out_of_the_program_fails_the_comparison(
        fault, monkeypatch):
    """A latent norm, the rotation, the interleaved pairing, the sigmoid
    score, the module's final norm, the order of the halves under ``W_eh``
    or the shift by two left out of the PROGRAM: the loss or a gradient
    leaf is out of the tolerance the sound program keeps."""
    args, model = tiny_model(router_balancing="batch_bias")
    cfg, params = seeded(args)
    sample = batch_of(2, 64)
    want = reference_loss_and_gradients(cfg, params, sample)
    value, grads, _ = loss_and_gradients(model, params, sample)
    assert (abs(float(value) / float(want[0]) - 1) < LOSS_RTOL
            and worst_leaf(grads, want[1])[0] < LEAF_TOL)
    fault(monkeypatch)
    if fault is _softmax_router:  # builds no bias leaf: the loss alone
        strip = lambda t: {k: (strip(v) if isinstance(v, dict) else v)
                           for k, v in t.items() if k != "router_bias"}
        value = loss_and_gradients(model, strip(params), sample)[0]
        assert abs(float(value) / float(want[0]) - 1) > LOSS_RTOL
        return
    value, grads, _ = loss_and_gradients(model, params, sample)
    assert (abs(float(value) / float(want[0]) - 1) > LOSS_RTOL
            or worst_leaf(grads, want[1])[0] > LEAF_TOL)


# -- the shares add up ---------------------------------------------------------------

@pytest.mark.parametrize("balancing", ["none", "batch_bias"])
def test_expert_shares_add_up_with_router_and_shared_expert_once(balancing):
    """Eight shares of an expert sublayer, one of 8 experts each with the
    whole router, its bias and the shared expert (as 16 of 256 sixteen
    times over): every share chooses the same set, and their results, the
    shared expert's counted once, add up to the uncut reference layer's."""
    args, _ = tiny_model()
    cfg, params = seeded(args, 5)
    cfg = dict(cfg, router_balancing=balancing)
    p = params["params"]["mtp"]["layers_1"]["moe"]
    h = jax.random.normal(jax.random.key(2), (2, 50, 64))
    with jax.default_matmul_precision("highest"):
        want = reference().experts(h, p, cfg, "float32")
        shared = reference().experts(
            h, dict(p, experts_fc1=0 * p["experts_fc1"]), cfg, "float32")
    total, pairs = 0.0, 0
    for j in range(8):
        part = GatedMoE(
            64, expert_dim=48, n_routed=8, top_k=2, n_held=1, first_held=j,
            balancing=balancing, routed_scale=2.5, shared_dim=48,
            scoring="sigmoid")
        f, stats = part.apply({"params": dict(
            p, experts_fc1=p["experts_fc1"][j:j + 1],
            experts_fc2=p["experts_fc2"][j:j + 1])}, h)
        total = total + (f - shared)
        pairs += float(stats[0])
    np.testing.assert_allclose(total + shared, want, atol=3e-5)
    assert pairs == 2 * 100  # every token's two experts, each on one share


def test_vocabulary_slices_are_the_uncut_head_and_embedding():
    """Eight slices of the vocabulary, each a model of its own over ``V /
    8`` ids with its rows of the embedding and its columns of the head: on
    ids drawn from its slice a slice computes the uncut model's hidden
    states (the decoder's and the module's), and its logits are the uncut
    head's columns."""
    args, whole = tiny_model()
    _, params = seeded(args)
    E, W = params["params"]["embed_tokens"]["embedding"], params["params"]["lm_head"]
    n = V // 8
    local = np.random.default_rng(4).integers(0, n, (2, 32)).astype(np.int32)
    for j in (0, 3, 7):
        tok = local + j * n
        (want_x, want_z), _ = whole.apply(params, tok, features_only=True)
        want = whole.apply(params, tok)
        part = whole.clone(vocab_size=n)
        sliced = {"params": dict(
            params["params"], lm_head=W[:, j * n:(j + 1) * n],
            embed_tokens={"embedding": E[j * n:(j + 1) * n]})}
        (got_x, got_z), _ = part.apply(sliced, local, features_only=True)
        np.testing.assert_array_equal(got_x, want_x)
        np.testing.assert_array_equal(got_z, want_z)
        np.testing.assert_allclose(
            part.apply(sliced, local), want[..., j * n:(j + 1) * n], atol=5e-6)


# -- the prediction module ------------------------------------------------------------

def test_the_modules_targets_are_two_ahead_and_nothing_leaks_back():
    """Position ``i`` of the module reads tokens ``0 .. i + 1`` and is
    scored against ``t_{i+2}``: changing token ``j`` moves the module's
    stream at positions ``j - 1 ..`` and nowhere before, so ``t_{i+3}``
    reaches no loss term at position ``i``; the targets' last two positions
    count nowhere."""
    from unicore_tpu.losses.lm_cross_entropy import shifted_targets

    args, model = tiny_model()
    _, params = seeded(args)
    tok = batch_of(2, 48)["net_input"]["src_tokens"]
    (x, z), _ = model.apply(params, tok, features_only=True)
    j = 30
    other = tok.copy()
    other[:, j] = (other[:, j] + 7) % (V - 1) + 1
    (x2, z2), _ = model.apply(params, other, features_only=True)
    np.testing.assert_array_equal(x2[:, :j], x[:, :j])
    assert np.abs(np.asarray(x2[:, j] - x[:, j])).max() > 1e-3
    np.testing.assert_array_equal(z2[:, :j - 1], z[:, :j - 1])
    assert np.abs(np.asarray(z2[:, j - 1] - z[:, j - 1])).max() > 1e-3
    # position i is scored against t_{i+2}: token j is the target of
    # position j - 2, whose stream it does not move
    ahead = np.asarray(shifted_targets(
        shifted_targets(jnp.asarray(tok), 1, 0), 1, 0))
    np.testing.assert_array_equal(ahead[:, :-2], tok[:, 2:])
    assert not ahead[:, -2:].any()
    assert ahead[0, j - 2] == tok[0, j] and j - 2 < j - 1


def test_the_heads_gradient_is_the_sum_of_both_passes():
    """The head's kernel (and the embedding) receive the main pass's
    gradient and the module's at its weight: the gradient is linear in the
    weight, and the module's part is not nothing."""
    sample = batch_of(2, 48)
    grads = {}
    for weight in (0.0, 0.3, 0.6):
        args, model = tiny_model(mtp_loss_weight=weight)
        _, params = seeded(args)
        grads[weight] = loss_and_gradients(model, params, sample)[1]["params"]
    for leaf in ("lm_head",):
        g0, g3, g6 = (np.asarray(grads[w][leaf]) for w in (0.0, 0.3, 0.6))
        np.testing.assert_allclose(g0 + g6, 2 * g3, atol=1e-5 * np.abs(g3).max())
        assert np.abs(g3 - g0).max() > 1e-2 * np.abs(g0).max()
    e0, e3 = (np.asarray(grads[w]["embed_tokens"]["embedding"])
              for w in (0.0, 0.3))
    assert np.abs(e3 - e0).max() > 1e-3 * np.abs(e0).max()
    # at weight 0 nothing reaches the module
    assert not any(np.asarray(a).any() for a in jax.tree_util.tree_leaves(
        grads[0.0]["mtp"]))


def test_a_model_without_the_module_builds_none():
    args, model = tiny_model(num_nextn_predict_layers=0)
    sample = batch_of(1, 32)
    params = model.init_params(jax.random.key(0), sample)
    assert "mtp" not in params["params"] and model.ahead == ()
    x, _ = model.apply(params, sample["net_input"]["src_tokens"],
                       features_only=True)
    assert x.shape == (1, 32, 64)
    _, _, log = loss_and_gradients(model, params, sample)
    assert "mtp_loss" not in log and "nll_loss" not in log


# -- arguments, the normal path ------------------------------------------------------

@pytest.mark.parametrize("over,said", [
    (dict(n_group=8), "n_group"),
    (dict(topk_group=4), "topk_group"),
    (dict(rope_scaling='{"type": "yarn", "factor": 40}'), "rope_scaling"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(num_nextn_predict_layers=2), "num_nextn_predict_layers"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(num_key_value_heads=2), "num_key_value_heads"),
    (dict(qk_head_dim=32), "qk_head_dim"),
    (dict(layers_held=4), "layers_held"),
    (dict(attention_shares=3), "attention-shares"),
])
def test_what_is_not_built_is_refused(over, said):
    with pytest.raises(ValueError, match=said):
        tiny_model(**over)


def test_the_published_model_is_the_default_and_a_share_is_stated():
    from unicore_tpu.models.joyai import JoyAIModel

    fields = JoyAIModel.__dataclass_fields__
    assert (fields["vocab_size"].default, fields["num_hidden_layers"].default,
            fields["q_lora_rank"].default, fields["kv_lora_rank"].default,
            fields["n_routed_experts"].default,
            fields["scoring_func"].default) == (
                129280, 40, 1536, 512, 256, "sigmoid")
    assert fields["router_balancing"].default == "none"
    _, model = tiny_model(layers_held=2, attention_shares=2,
                          num_experts_held=3, first_expert_held=5)
    assert model.pattern == "LFLR" and model.mtp_pattern == "LR"
    sizes = model.layers()["sizes"]
    assert sizes["L"]["num_heads"] == 2 and sizes["L"]["rope_interleave"]
    assert (sizes["R"]["n_held"], sizes["R"]["first_held"],
            sizes["R"]["scoring"], sizes["R"]["shared_dim"]) == (
                3, 5, "sigmoid", 48)


def test_tiny_model_trains_through_parser_task_and_trainer(tmp_path):
    """``unicore-tpu-train DATA --task causal_lm --arch joyai_tiny`` as its
    parser and task build it, one share of two, through
    ``Trainer.train_step``: a falling loss, the module's loss and the
    routing in the step's sums, what decays, and the marks a profiler
    capture would be told."""
    from unicore_tpu import options, tasks
    from unicore_tpu.data.indexed_dataset import make_builder
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models import build_model
    from unicore_tpu.ops.flash_attention import Band, band_counts
    from unicore_tpu.trainer import Trainer

    words = [f"w{a}{b}" for a in "abcdefgh" for b in "abcdefgh"]
    (tmp_path / "dict.txt").write_text(
        "\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]"] + words) + "\n")
    rng = np.random.default_rng(0)
    builder = make_builder(str(tmp_path / "train"))
    for n in rng.integers(20, 200, 80):
        builder.add_item(" ".join(rng.choice(words, n)))
    builder.finalize()
    args = options.parse_args_and_arch(options.get_training_parser(), [
        str(tmp_path), "--task", "causal_lm", "--loss", "lm_cross_entropy",
        "--arch", "joyai_tiny", "--tokens-per-sample", "64",
        "--attention-shares", "2", "--num-experts-held", "4",
        "--router-balancing", "batch_bias",
        "--optimizer", "adam", "--lr-scheduler", "fixed", "--lr", "3e-3",
        "--weight-decay", "0.1", "--no-weight-decay-names", "norm",
        "--batch-size", "1", "--max-update", "20", "--seed", "1"])
    task = tasks.setup_task(args)
    task.load_dataset("train")
    model = build_model(args, task)
    loss = LOSS_REGISTRY[args.loss](task)
    trainer = Trainer(args, task, model, loss)
    batches = task.get_batch_iterator(
        task.datasets["train"], batch_size=4, seed=1, epoch=1,
    ).next_epoch_itr(shuffle=True)
    sums = []
    for _, batch in zip(range(8), batches):
        trainer.train_step([batch])
        sums.append({k: float(v) for k, v in jax.device_get(trainer._macc).items()})
    per_update = np.diff([0.0] + [s["loss"] for s in sums])
    assert per_update[-1] < per_update[0]
    params = trainer.state["params"]["params"]
    assert set(params) == {"embed_tokens", "decoder", "lm_head", "mtp"}
    assert set(params["mtp"]) == {"join", "layers_0", "layers_1", "final_norm"}
    # what decays: the optimizer's mask leaves every vector alone though
    # the scanned unit gives it a second axis
    from unicore_tpu.optim.unicore_optimizer import make_decay_mask

    mask = make_decay_mask(trainer.state["params"],
                           ("bias", "layer_norm", "layernorm", "norm"))
    flat = {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(mask)[0]}
    assert {k.rsplit("'", 2)[-2] for k, v in flat.items() if v} == {
        "kernel", "embedding", "router", "experts_fc1", "experts_fc2",
        "lm_head"}
    last = sums[-1]
    assert last["moe_layers"] == 8 * 3
    assert 0 < last["mtp_loss"] and 0 < last["nll_loss"] < last["loss"]
    assert last["loss"] == pytest.approx(
        last["nll_loss"] + 0.3 * last["mtp_loss"], rel=1e-5)
    one = {k: v / 8 for k, v in last.items()}
    marks = loss.trace_marks(one)
    assert set(marks) == {"moe_route", "attn_band", "attn_band_call", "mla",
                          "mtp_loss"}
    # the module's mean NLL and the model's, nats a target
    assert marks["mtp_loss"]["main"] == pytest.approx(
        last["nll_loss"] / last["sample_size"])
    assert 0.8 < marks["mtp_loss"]["mtp"] / marks["mtp_loss"]["main"] < 1.25
    assert marks["mla"] == dict(heads=2, layers=4, qk_dim=24, v_dim=16,
                                latent_dim=40)
    computed, visible = band_counts(Band(None), 128, 128)
    assert marks["attn_band_call"] == {"keys_computed": 4 * computed,
                                       "keys_visible": 4 * visible}
    assert marks["attn_band"]["full_layers"] == 4
