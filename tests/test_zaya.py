"""``zaya``: attention in a compressed, convolution-mixed latent (CCA), an
expert sublayer whose router is a network with a state carried from layer
to layer and a skip expert, the scaled residual merge and the tied head, at
sizes a CPU test holds.  The model is held to ``benchmark/reference/
zaya1_8b.py`` (float32, plain ``jax.numpy``); the benchmark cell's own cases
are in ``tests/benchmark/test_zaya1_8b.py``."""

import json
import os
import sys
from argparse import Namespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.modules import cca, hybrid_decoder, latent_moe, zaya_moe
from unicore_tpu.modules.cca import CompressedConvAttention
from unicore_tpu.modules.hybrid_decoder import (
    KINDS, TABLE, HybridBlock, HybridDecoder, stat_names,
)
from unicore_tpu.modules.zaya_moe import MORE_STATS, ZayaMoE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the reference's keys of the tiny preset
KEYS = ("hidden_size", "num_hidden_layers", "layer_types", "rope_parameters",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_experts", "moe_intermediate_size", "router_hidden_size",
        "rms_norm_eps", "router_balancing", "layers_held", "attention_shares",
        "first_kv_head_held", "num_experts_held", "first_expert_held")
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
    ARCH_CONFIG_REGISTRY["zaya_tiny"](args)
    return args, ARCH_MODEL_REGISTRY["zaya_tiny"].build_model(args, _Task())


def reference():
    from benchmark.reference import zaya1_8b

    return zaya1_8b


def seeded(args, seed=11, scale=3.0):
    """The reference's tree for ``args``, seeded by the benchmark's rules:
    kernels N(0, 0.02), norm gains, merge scales, ``tau`` and ``gamma`` 1 +
    N(0, 0.02), every bias (the merges', both convolutions', the router's)
    N(0, 0.02) and not zero; then everything times ``scale`` (a sharper
    softmax and a router that spreads)."""
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
#: order of their sums only (one product over ``[z1_{t-1} | z1_t]`` against
#: two, sorted tiles of pairs against dense products over all tokens, loss
#: chunks against row blocks).  Six seeds read at most 4e-7 on the loss and
#: 2e-5 on the worst gradient leaf; any mechanism left out reads 1e-3 or
#: more on one of the two
LOSS_RTOL = 2e-6
LEAF_TOL = 5e-5


# -- the model against the reference ---------------------------------------------

@pytest.mark.parametrize("wide", [0, 32], ids=["tiles", "wide-trips"])
@pytest.mark.parametrize("balancing", ["none", "batch_bias"])
def test_tiny_model_is_the_plain_reference_loss_logits_and_gradients(
        monkeypatch, balancing, wide):
    """Three layers as one scanned unit on seeded weights with the merge
    vectors, ``tau``, ``gamma`` and every bias perturbed, one share of two
    (KV head 1: the late value; experts 2 .. 5 of 8 and the skip column):
    loss, logits and every gradient leaf against the reference, with the
    published choice of expert and with the batch's bias; through the loop
    over the tiles and, with the tile at 8 rows and the wide trip at 32,
    through the wide and narrow loops the benchmark's cell runs."""
    if wide:
        monkeypatch.setattr(latent_moe, "TILE", 8)
        monkeypatch.setattr(latent_moe, "WIDE", wide)
    args, model = tiny_model(
        attention_shares=2, first_kv_head_held=1, num_experts_held=4,
        first_expert_held=2, router_balancing=balancing)
    assert model.pattern == "CZCZCZ" and set(model.pattern) <= set(KINDS)
    cfg, params = seeded(args)
    sample = batch_of(4, 96)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0), sample))
    assert (jax.tree_util.tree_structure(shapes)
            == jax.tree_util.tree_structure(params))
    assert "lm_head" not in params["params"]
    value, grads, log = loss_and_gradients(model, params, sample)
    want = reference_loss_and_gradients(cfg, params, sample)
    assert float(value) == pytest.approx(float(want[0]), rel=LOSS_RTOL)
    gap, leaf = worst_leaf(grads, want[1])
    assert gap < LEAF_TOL, leaf
    # full logits: the tied head over the reference's hidden states
    tok = sample["net_input"]["src_tokens"]
    with jax.default_matmul_precision("highest"):
        hidden = reference().hidden(params, cfg, tok)
        logits = hidden @ params["params"]["embed_tokens"]["embedding"].T
        np.testing.assert_allclose(model.apply(params, tok), logits, atol=2e-5)
    # top-1 with a skip column: fewer pairs than tokens, and under the
    # batch's bias each of the 4 held experts and the skip column near n / 9
    n = 3 * 4 * 96
    assert log["moe_tokens"] == n and log["moe_pairs_here"] < n
    assert (log["moe_rows_wide"] > 0) == bool(wide)
    if balancing == "batch_bias":
        assert abs(log["moe_skipped"] - n / 9) < 0.2 * n / 9
        assert abs(log["moe_pairs_here"] - 4 * n / 9) < 0.2 * 4 * n / 9


def _bf16_router(monkeypatch):
    monkeypatch.setattr(  # the router's own products with bfloat16 operands
        zaya_moe, "_product", lambda x, w: jnp.dot(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32))


def _no_qk_mean(monkeypatch):
    monkeypatch.setattr(cca, "qk_mean", lambda q, k: jnp.zeros_like(q))


def _no_late_value(monkeypatch):
    from unicore_tpu.models.zaya import ZayaModel

    real = ZayaModel.layers

    def layers(self):  # built as if it held KV head 0, the prompt one
        out = real(self)
        out["sizes"]["C"]["first_kv_head"] = 0
        return out

    monkeypatch.setattr(ZayaModel, "layers", layers)


def _padded_between_the_convolutions(monkeypatch):
    real = cca.late
    monkeypatch.setattr(  # zeros, not the bias, before the row's first
        cca, "late", lambda x, first=None: real(x))


def _no_depth_state(monkeypatch):
    monkeypatch.setattr(
        zaya_moe, "side_start", lambda x, sizes: None)
    real = ZayaMoE.__call__
    monkeypatch.setattr(  # every layer's router starts from zeros
        ZayaMoE, "__call__", lambda self, h, r_prev: real(
            self, h, jnp.zeros(h.shape[:-1] + (self.router_dim,), jnp.float32)))


def _plain_merge(monkeypatch):
    monkeypatch.setattr(
        hybrid_decoder.ScaledMerge, "__call__", lambda self, x, f: x + f)


@pytest.mark.parametrize("fault", [
    _bf16_router, _no_qk_mean, _no_late_value,
    _padded_between_the_convolutions, _no_depth_state, _plain_merge])
def test_a_mechanism_left_out_of_the_program_fails_the_comparison(
        fault, monkeypatch):
    """A router in bfloat16, the q-k mean, the late value, the bias at the
    position before the row, the carried router state or the merge's
    vectors left out of the PROGRAM: the loss or a gradient leaf is out of
    the tolerance the sound program keeps."""
    args, model = tiny_model(attention_shares=2, first_kv_head_held=1,
                             router_balancing="batch_bias")
    cfg, params = seeded(args)
    sample = batch_of(2, 64)
    want = reference_loss_and_gradients(cfg, params, sample)
    value, grads, _ = loss_and_gradients(model, params, sample)
    sound = (abs(float(value) / float(want[0]) - 1) < LOSS_RTOL
             and worst_leaf(grads, want[1])[0] < LEAF_TOL)
    assert sound
    fault(monkeypatch)
    if fault is _plain_merge:
        # the merge's parameters are not read: gradients of what is left
        value = loss_and_gradients(model, params, sample)[0]
        assert abs(float(value) / float(want[0]) - 1) > 100 * LOSS_RTOL
        return
    value, grads, _ = loss_and_gradients(model, params, sample)
    assert (abs(float(value) / float(want[0]) - 1) > LOSS_RTOL
            or worst_leaf(grads, want[1])[0] > LEAF_TOL)


@pytest.mark.parametrize("what", [
    "qk_mean", "late_value", "first_tap", "temperature", "depth_state",
    "skip_column", "merge"])
def test_the_reference_notices_what_it_is_told_to_leave_out(what):
    args, model = tiny_model()
    cfg, params = seeded(args)
    tok = batch_of(2, 64)["net_input"]["src_tokens"]
    ref = reference()
    with jax.default_matmul_precision("highest"):
        whole = ref.hidden(params, cfg, tok)
        assert float(jnp.abs(
            ref.hidden(params, cfg, tok, leave_out=what) - whole).max()) > 1e-4


# -- the shares add up ---------------------------------------------------------------

def _layer_params(seed=5):
    """One whole layer's seeded parameters (the tiny preset, one layer) and
    its configuration."""
    args, model = tiny_model(num_hidden_layers=1,
                             layer_types=json.dumps(["hybrid"]))
    cfg, params = seeded(args, seed)
    dec = params["params"]["decoder"]
    return cfg, dec["layers_0"], dec["layers_1"]


def test_cca_head_shares_add_up_to_the_uncut_reference_layer():
    """Two shares of the attention sublayer, each one KV head with its two
    query heads, its columns of ``W_q``, ``W_k``, ``W_v``, its channels of
    both convolutions, its ``tau`` and its rows of ``W_o`` (share 1 reads
    its value one position late, share 0 does not): their ``f`` summed is
    the uncut reference layer's."""
    cfg, attn, _ = _layer_params()
    p = attn["self_attn"]
    H, KV, D, d = 4, 2, 16, 64
    h = jax.random.normal(jax.random.key(1), (3, 40, d))
    with jax.default_matmul_precision("highest"):
        want = reference().cca(h, p, cfg, "float32")
    rope = json.loads(cfg["rope_parameters"])["hybrid"]
    total = 0.0
    for j in range(KV):
        q = slice(j * 2 * D, (j + 1) * 2 * D)              # its query columns
        k = slice(j * D, (j + 1) * D)
        heads = np.r_[2 * j:2 * j + 2, H + j]              # its conv channels
        part = CompressedConvAttention(
            d, num_heads=2, num_kv_heads=1, head_dim=D, kv_heads_model=KV,
            first_kv_head=j, rope=rope)
        total = total + part.apply({"params": {
            "q_proj": {"kernel": p["q_proj"]["kernel"][:, q]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, k]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, k]},
            "conv0": {"kernel": p["conv0"]["kernel"][:, heads]},
            "conv0_bias": {"bias": p["conv0_bias"]["bias"][heads]},
            "conv1": {"kernel": p["conv1"]["kernel"][:, heads]},
            "conv1_bias": {"bias": p["conv1_bias"]["bias"][heads]},
            "temperature": {"scale": p["temperature"]["scale"][j:j + 1]},
            "out_proj": {"kernel": p["out_proj"]["kernel"][q]},
        }}, h)
    # float32 sums in another order (two products of 32 rows of W_o for
    # one of 64)
    np.testing.assert_allclose(total, want, atol=2e-5)


@pytest.mark.parametrize("balancing", ["none", "batch_bias"])
def test_expert_shares_add_up_with_the_skip_column_once(balancing):
    """Two shares of the expert sublayer, 4 of 8 experts each and the whole
    router: their ``f`` summed is the uncut reference layer's, every share
    hands on the same router state and counts the same tokens on the skip
    column, which is counted once."""
    cfg, _, moe = _layer_params()
    cfg = dict(cfg, router_balancing=balancing)
    p = moe["moe"]
    h = jax.random.normal(jax.random.key(2), (2, 50, 64))
    r_prev = jax.random.normal(jax.random.key(3), (2, 50, 24))
    with jax.default_matmul_precision("highest"):
        want, r_want = reference().experts(h, r_prev, p, cfg, "float32")
    total, skipped, pairs = 0.0, set(), 0
    for j in range(2):
        held = slice(4 * j, 4 * j + 4)
        part = ZayaMoE(64, expert_dim=48, n_routed=8, router_dim=24, n_held=4,
                       first_held=4 * j, balancing=balancing)
        f, stats, r = part.apply({"params": dict(
            p, experts_fc1=p["experts_fc1"][held],
            experts_fc2=p["experts_fc2"][held])}, h, r_prev)
        total = total + f
        np.testing.assert_allclose(r, r_want, atol=1e-5)
        skipped.add(float(stats[len(latent_moe.STATS)]))
        pairs += float(stats[0])
    np.testing.assert_allclose(total, want, atol=2e-5)
    (skip,) = skipped
    assert pairs + skip == 100 and (skip > 0 or balancing == "none")


def test_vocabulary_slices_are_the_uncut_head_and_embedding():
    """Eight slices of the tied embedding, each a model of its own over
    ``V / 8`` ids: on ids drawn from its slice a slice computes the uncut
    model's hidden states, and the slices' logits side by side are the
    uncut head's ``x E^T``."""
    args, whole = tiny_model()
    cfg, params = seeded(args)
    E = params["params"]["embed_tokens"]["embedding"]
    n = V // 8
    local = np.random.default_rng(4).integers(0, n, (2, 32)).astype(np.int32)
    for j in (0, 3, 7):
        tok = local + j * n
        want_x, _ = whole.apply(params, tok, features_only=True)
        want = whole.apply(params, tok)
        part = whole.clone(vocab_size=n)
        sliced = {"params": dict(params["params"], embed_tokens={
            "embedding": E[j * n:(j + 1) * n]})}
        got_x, _ = part.apply(sliced, local, features_only=True)
        np.testing.assert_array_equal(got_x, want_x)
        np.testing.assert_allclose(
            part.apply(sliced, local), want[..., j * n:(j + 1) * n], atol=1e-6)
    with jax.default_matmul_precision("highest"):
        tok = local + 2 * n
        ref_logits = reference().hidden(params, cfg, tok) @ E.T
    np.testing.assert_allclose(whole.apply(params, tok), ref_logits, atol=2e-5)


def test_the_tied_heads_gradient_is_the_sum_of_both_uses():
    """The embedding is read twice, by the gather in front and by the head
    behind: its gradient under the tied loss is the gradient of the gather
    with the head held fixed plus the gradient of the head with the gather
    held fixed."""
    from unicore_tpu.losses.lm_cross_entropy import (
        LMCrossEntropyLoss, chunked_lm_nll, shifted_targets)

    args, model = tiny_model()
    _, params = seeded(args)
    sample = batch_of(2, 48)
    tied = jax.grad(lambda p: LMCrossEntropyLoss(_Task()).forward(
        model, p, sample)[0])(params)["params"]["embed_tokens"]["embedding"]
    E = params["params"]["embed_tokens"]["embedding"]

    def two_uses(gathered, head):
        tree = {"params": dict(params["params"],
                               embed_tokens={"embedding": gathered})}
        x, _ = model.apply(tree, sample["net_input"]["src_tokens"],
                           features_only=True)
        target = shifted_targets(sample["target"], 1, 0).reshape(-1)
        return chunked_lm_nll(x.reshape(-1, x.shape[-1]), head.T,
                              jnp.where(target != 0, target, 0), target != 0, 32)

    by_gather, by_head = jax.grad(two_uses, argnums=(0, 1))(E, E)
    assert float(jnp.abs(by_gather).max()) > 0 < float(jnp.abs(by_head).max())
    np.testing.assert_allclose(
        tied, by_gather + by_head, atol=1e-5 * float(jnp.abs(tied).max()))


# -- rows, the side stream, the table --------------------------------------------------

@pytest.mark.parametrize("first_kv_head", [0, 1])
def test_rows_do_not_leak(first_kv_head):
    """Row 1's first position reads no ``h_{-1}`` and no convolution tap
    from row 0: a row of the batch computes what it computes alone, and
    changing another row changes nothing of it."""
    layer = CompressedConvAttention(
        64, num_heads=2, num_kv_heads=1, head_dim=16, kv_heads_model=2,
        first_kv_head=first_kv_head,
        rope={"rope_theta": 100, "partial_rotary_factor": 0.5})
    h = jax.random.normal(jax.random.key(1), (3, 24, 64))
    params = layer.init(jax.random.key(2), h)
    params = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(3), a.shape), params)
    whole = layer.apply(params, h)
    for row in range(3):
        np.testing.assert_allclose(
            layer.apply(params, h[row:row + 1])[0], whole[row], atol=1e-6)
    other = layer.apply(params, h.at[0].set(7.0))
    np.testing.assert_array_equal(other[1:], whole[1:])
    # and the late value is read: the first position of a row whose second
    # half of KV heads is held differs from the same layer told it holds
    # the first half
    early = layer.clone(first_kv_head=1 - first_kv_head).apply(params, h)
    assert float(jnp.abs(early - whole).max()) > 1e-3


def test_the_side_stream_through_the_scanned_unit_is_the_unrolled_layers(
        monkeypatch):
    """Three layers as one scanned unit (its parameters stacked) against
    the same layers one after another, each handed the router state of the
    one before: the hidden states and the stats agree, and a model whose
    layers start from zeros each does not."""
    args, model = tiny_model(router_balancing="batch_bias")
    _, params = seeded(args)
    tok = batch_of(2, 40)["net_input"]["src_tokens"]
    want, logged = model.apply(params, tok, features_only=True)
    units = params["params"]["decoder"]["units"]
    unrolled = {f"layers_{2 * i + j}": jax.tree_util.tree_map(
        lambda a: a[i], units[f"layer_{j}"]) for i in range(3) for j in range(2)}
    tree = {"params": dict(params["params"], decoder=dict(
        unrolled, final_norm=params["params"]["decoder"]["final_norm"]))}
    monkeypatch.setattr(hybrid_decoder, "split_pattern", lambda p: (p, "", 0))
    got, logged_unrolled = model.apply(tree, tok, features_only=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for key in logged:
        assert float(logged[key]) == float(logged_unrolled[key]), key
    _no_depth_state(monkeypatch)
    apart, _ = model.apply(tree, tok, features_only=True)
    assert float(jnp.abs(apart - want).max()) > 1e-3


def test_the_table_says_which_kinds_carry_a_side_stream_and_more_stats():
    assert TABLE["Z"].side is zaya_moe.side_start
    assert [k for k, row in TABLE.items() if row.side is not None] == ["Z"]
    assert [k for k, row in TABLE.items() if row.more_stats] == ["Z"]
    # every pattern the benchmark had returns the stats it returned
    for pattern in ("*EMEMEMEMEM", "AFAFAFAF", "SRSRSRGR", "GFSRSRSRGR"):
        assert stat_names(pattern) == latent_moe.STATS
        assert hybrid_decoder.side_start(pattern, None, {}) is None
    assert stat_names("CZCZ") == latent_moe.STATS + MORE_STATS
    x = jnp.zeros((2, 5, 8), jnp.bfloat16)
    side = hybrid_decoder.side_start("CZ", x, {"Z": {"router_dim": 3}})
    assert side.shape == (2, 5, 3) and side.dtype == jnp.float32
    assert not side.any()


def test_a_kind_with_stats_beside_one_with_more_places_each():
    """``R`` and ``Z`` in one pattern: the decoder's stats are the wider
    tuple, ``R``'s six land in the first six places."""
    sizes = {"R": dict(expert_dim=8, n_routed=4, top_k=2),
             "Z": dict(expert_dim=8, n_routed=4, router_dim=6)}
    dec = HybridDecoder(pattern="RZ", embed_dim=16, norm_eps=1e-5, sizes=sizes,
                        remat=False)
    x = jax.random.normal(jax.random.key(0), (1, 12, 16))
    params = dec.init(jax.random.key(1), x)
    _, stats = dec.apply(params, x)
    named = dict(zip(stat_names("RZ"), np.asarray(stats)))
    assert named["layers"] == 2 and named["tokens"] == 12
    assert named["pairs_here"] == 24 + 12 - named["skipped"]


def test_the_skip_column_takes_tokens_and_adds_nothing():
    """With every expert held, pairs and skipped tokens are all tokens; a
    token on the skip column gets ``f`` = 0 exactly."""
    layer = ZayaMoE(32, expert_dim=16, n_routed=4, router_dim=8,
                    balancing="batch_bias")
    h = jax.random.normal(jax.random.key(0), (2, 40, 32))
    r = jnp.zeros((2, 40, 8))
    params = layer.init(jax.random.key(1), h, r)
    f, stats, _ = layer.apply(params, h, r)
    named = dict(zip(latent_moe.STATS + MORE_STATS, np.asarray(stats)))
    assert named["pairs_here"] + named["skipped"] == named["tokens"] == 80
    assert named["skipped"] > 0
    assert int((jnp.abs(f).max(axis=-1) == 0).sum()) == named["skipped"]


# -- arguments, the normal path ------------------------------------------------------

@pytest.mark.parametrize("over,said", [
    (dict(layer_types=json.dumps(["hybrid", "hybrid_sliding", "hybrid"])),
     "layer_types"),
    (dict(sliding_window=64), "sliding_window"),
    (dict(layer_types=json.dumps(["hybrid"] * 2)), "layer_types"),
    (dict(cca_time0=3), "cca_time0"),
    (dict(cca_time1=4), "cca_time1"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(lm_head_bias=True), "lm_head_bias"),
    (dict(num_experts_per_tok=2), "num_experts_per_tok"),
    (dict(partial_rotary_factor=1.0), "partial_rotary_factor"),
    (dict(attention_shares=3), "attention-shares"),
    (dict(attention_shares=4), "attention-shares"),
    (dict(attention_shares=2, first_kv_head_held=2), "attention-shares"),
])
def test_what_is_not_built_is_refused(over, said):
    with pytest.raises(ValueError, match=said):
        tiny_model(**over)


def test_the_published_model_is_the_default_and_a_share_is_stated():
    from unicore_tpu.models.zaya import ZayaModel

    fields = ZayaModel.__dataclass_fields__
    assert (fields["vocab_size"].default, fields["num_hidden_layers"].default,
            fields["tie_word_embeddings"].default) == (262272, 40, True)
    assert fields["router_balancing"].default == "none"
    _, model = tiny_model(layers_held=2, attention_shares=2,
                          first_kv_head_held=1, num_experts_held=3,
                          first_expert_held=5)
    assert model.pattern == "CZCZ"
    sizes = model.layers()["sizes"]
    assert (sizes["C"]["num_heads"], sizes["C"]["num_kv_heads"],
            sizes["C"]["first_kv_head"], sizes["C"]["kv_heads_model"]) == (2, 1, 1, 2)
    assert (sizes["Z"]["n_held"], sizes["Z"]["first_held"]) == (3, 5)
    assert model.layers()["scaled_merge"] is True
    # an untied head builds too: then the loss reads lm_head
    _, untied = tiny_model(tie_word_embeddings=False)
    params = untied.init_params(jax.random.key(0), batch_of(1, 16))
    assert "lm_head" in params["params"] and not untied.tied


def test_tiny_model_trains_through_parser_task_and_trainer(tmp_path):
    """``unicore-tpu-train DATA --task causal_lm --arch zaya_tiny`` as its
    parser and task build it, one share of two, through
    ``Trainer.train_step``: a falling loss, no ``lm_head`` in the state,
    the skip column and the routing in the step's sums, and the marks a
    profiler capture would be told."""
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
        "--arch", "zaya_tiny", "--tokens-per-sample", "64",
        "--attention-shares", "2", "--first-kv-head-held", "1",
        "--num-experts-held", "4", "--router-balancing", "batch_bias",
        "--optimizer", "adam", "--lr-scheduler", "fixed", "--lr", "3e-3",
        "--weight-decay", "0.1", "--no-weight-decay-names", "norm,scale",
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
    assert set(params) == {"embed_tokens", "decoder"}
    unit = params["decoder"]["units"]
    assert set(unit["layer_0"]) == {"norm", "self_attn", "merge"}
    assert set(unit["layer_1"]) == {"norm", "moe", "merge"}
    # what decays: the optimizer's mask leaves every vector alone though
    # the scanned unit gives it a second axis
    from unicore_tpu.optim.unicore_optimizer import make_decay_mask

    mask = make_decay_mask(trainer.state["params"],
                           ("bias", "layer_norm", "layernorm", "norm", "scale"))
    flat = {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(mask)[0]}
    assert {k.rsplit("'", 2)[-2] for k, v in flat.items() if v} == {
        "kernel", "embedding", "experts_fc1", "experts_fc2"}
    last = sums[-1]
    tokens = 8 * 4 * 64 * 3
    assert last["moe_tokens"] == tokens and last["moe_layers"] == 8 * 3
    assert 0 < last["moe_skipped"] < 0.25 * tokens
    assert last["moe_pairs_here"] < tokens
    one = {k: v / 8 for k, v in last.items()}
    marks = loss.trace_marks(one)
    assert set(marks) == {"moe_route", "moe_skip", "attn_band", "attn_band_call"}
    assert marks["moe_skip"]["tokens"] == tokens // 8
    computed, visible = band_counts(Band(None), 128, 128)
    assert marks["attn_band_call"] == {"keys_computed": 4 * computed,
                                       "keys_visible": 4 * visible}
    assert marks["attn_band"]["full_layers"] == 3
    assert marks["attn_band"]["window_layers"] == 0
