"""``mellum``: grouped-KV attention under a band the kernels mask
themselves (a sliding window, or the whole row), two rotary tables (default
and YaRN), routed gated experts under a softmax router, at sizes a CPU test
holds.  The kernels' own cases are in ``test_flash_attention.py``; the
benchmark cell's in ``tests/benchmark/test_mellum2.py``."""

import json
import math
import os
import re
import sys
from argparse import Namespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.modules.gated_moe import (
    BIAS_GAIN, BIAS_ROUNDS, NOISE, GatedMoE, balanced_scores, noise_table,
)
from unicore_tpu.modules.hybrid_decoder import KINDS
from unicore_tpu.modules.latent_moe import STATS, routed_experts, silu_gate
from unicore_tpu.modules.multihead_attention import GroupedQueryAttention
from unicore_tpu.modules.rotary import apply_rotary, rope_table

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MELLUM2_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
    "attention_factor": 1.2772588722239782,
}


# -- rotary tables ------------------------------------------------------------------

def test_yarn_table_is_the_equations_in_float64():
    """``rope_table`` against the published form written out in float64:
    the pairs below ``low`` untouched, above ``high`` divided by the
    factor, a linear ramp between; ``c`` the stated attention factor."""
    D = 128
    inv_freq, c = rope_table(MELLUM2_YARN, D)
    i = np.arange(64, dtype=np.float64)
    e = 500000.0 ** (-2 * i / D)
    dim = lambda r: D * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000))
    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), D - 1)
    assert (low, high) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = e / 16 * ramp + e * (1 - ramp)
    assert inv_freq.dtype == np.float32 and inv_freq.shape == (64,)
    np.testing.assert_allclose(inv_freq, want, rtol=1e-7)
    np.testing.assert_array_equal(inv_freq[:low + 1], e[:low + 1].astype(np.float32))
    np.testing.assert_allclose(inv_freq[high:], e[high:] / 16, rtol=1e-7)
    assert abs(c - MELLUM2_YARN["attention_factor"]) < 1e-12
    assert abs(c - (0.1 * math.log(16) + 1)) < 1e-12
    # without a stated factor it is computed; the default table is plain
    unstated = {k: v for k, v in MELLUM2_YARN.items() if k != "attention_factor"}
    assert abs(rope_table(unstated, D)[1] - c) < 1e-12
    plain, one = rope_table({"rope_type": "default", "rope_theta": 500000}, D)
    np.testing.assert_allclose(plain, e, rtol=1e-7)
    assert one == 1.0
    with pytest.raises(ValueError, match="rope_type"):
        rope_table({"rope_type": "llama3", "rope_theta": 1e4}, D)


def test_a_table_turns_what_theta_turns_and_scales_by_c():
    x = jax.random.normal(jax.random.key(0), (2, 3, 40, 16))
    pos = jnp.arange(40)
    by_theta = apply_rotary(x, pos, 100.0)
    table = rope_table({"rope_type": "default", "rope_theta": 100.0}, 16)
    np.testing.assert_allclose(apply_rotary(x, pos, table=table), by_theta,
                               atol=1e-5)
    np.testing.assert_allclose(
        apply_rotary(x, pos, table=(table[0], 1.5)), 1.5 * by_theta, atol=1e-5)


# -- the banded attention layer -------------------------------------------------------

def banded_by_hand(p, x, H, KV, D, window, rope):
    """The layer as the equations read: rotate-half rotary from a float64
    table, a dense mask ``0 <= i - j < window``, one softmax."""
    B, L, _ = x.shape
    inv_freq, c = rope_table(rope, D)
    angle = np.arange(L)[:, None] * inv_freq.astype(np.float64)[None]
    cos, sin = np.cos(angle) * c, np.sin(angle) * c

    def rot(t):
        t1, t2 = t[..., :D // 2], t[..., D // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    heads = lambda t, n: t.reshape(B, L, n, D).transpose(0, 2, 1, 3)
    q = rot(heads(x @ p["q_proj"]["kernel"], H))
    k = jnp.repeat(rot(heads(x @ p["k_proj"]["kernel"], KV)), H // KV, axis=1)
    v = jnp.repeat(heads(x @ p["v_proj"]["kernel"], KV), H // KV, axis=1)
    ahead = np.arange(L)[:, None] - np.arange(L)[None, :]
    seen = (ahead >= 0) & (ahead < (window or L))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
    o = jnp.einsum("bhqk,bhkd->bhqd",
                   jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)
    return o.transpose(0, 2, 1, 3).reshape(B, L, H * D) @ p["out_proj"]["kernel"]


TINY_YARN = {"rope_type": "yarn", "rope_theta": 100.0, "factor": 4,
             "original_max_position_embeddings": 32, "beta_fast": 4,
             "beta_slow": 1}


@pytest.mark.parametrize("window,rope", [
    (0, TINY_YARN), (24, {"rope_type": "default", "rope_theta": 100.0}),
    (150, TINY_YARN),
])
@pytest.mark.parametrize("kernels", [True, False])
def test_banded_layer_is_the_equations_on_both_routes(window, rope, kernels):
    """``GroupedQueryAttention(banded=True)`` at ``L`` = 200 (not a
    multiple of the kernels' 128 tile: the router pads, and the band's
    positions are the padded row's) through the blockwise kernels in
    interpret mode and through XLA's softmax, output and gradients."""
    from unicore_tpu.ops import _pallas

    _pallas.set_interpret(kernels)
    H, KV, D, d, L = 4, 2, 16, 32, 200
    layer = GroupedQueryAttention(d, num_heads=H, num_kv_heads=KV, head_dim=D,
                                  banded=True, window=window, rope=rope)
    x = jax.random.normal(jax.random.key(1), (2, L, d))
    params = layer.init(jax.random.key(2), x)
    params = jax.tree_util.tree_map(lambda a: 10.0 * a, params)  # sharp softmax
    w = jnp.cos(jnp.arange(2 * L * d, dtype=jnp.float32)).reshape(2, L, d)
    got = jax.value_and_grad(lambda p: jnp.sum(layer.apply(p, x) * w))(params)
    want = jax.value_and_grad(lambda p: jnp.sum(banded_by_hand(
        p["params"], x, H, KV, D, window, rope) * w))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))


def test_the_default_layer_is_untouched_and_a_window_needs_the_band():
    x = jnp.zeros((1, 8, 16))
    with pytest.raises(ValueError, match="band"):
        GroupedQueryAttention(16, 2, 1, 8, window=4).init(jax.random.key(0), x)
    plain = GroupedQueryAttention(16, 2, 1, 8)
    text = jax.jit(lambda p: plain.apply(p, x)).lower(
        plain.init(jax.random.key(0), x)).as_text()
    assert "band_attn" not in text and "rotary" not in text


def test_banded_head_shares_add_up_to_the_uncut_layer():
    """8 query heads on 4 KV heads over 4 shares (2 on 1 each): the shares'
    results add up to the whole layer's."""
    H, KV, D, d, L = 8, 4, 16, 32, 48
    kw = dict(head_dim=D, banded=True, window=20, rope=TINY_YARN)
    whole = GroupedQueryAttention(d, num_heads=H, num_kv_heads=KV, **kw)
    x = jax.random.normal(jax.random.key(1), (2, L, d))
    params = whole.init(jax.random.key(2), x)
    p = params["params"]
    want = whole.apply(params, x)
    part = GroupedQueryAttention(d, num_heads=2, num_kv_heads=1, **kw)
    total = 0.0
    for j in range(4):
        qs, ks = slice(j * 2 * D, (j + 1) * 2 * D), slice(j * D, (j + 1) * D)
        total = total + part.apply({"params": {
            "q_proj": {"kernel": p["q_proj"]["kernel"][:, qs]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, ks]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, ks]},
            "out_proj": {"kernel": p["out_proj"]["kernel"][qs]},
        }}, x)
    np.testing.assert_allclose(total, want, atol=2e-5)


# -- the routed gated experts -----------------------------------------------------------

MOE = dict(expert_dim=24, n_routed=16, top_k=4)


def moe_layer_and_params(d=32, n=48, balancing="none"):
    whole = GatedMoE(d, balancing=balancing, **MOE)
    h = jax.random.normal(jax.random.key(1), (2, n // 2, d))
    params = whole.init(jax.random.key(2), h)
    params = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(3), a.shape), params
    )
    return whole, params, h


def scores_by_hand(z, top_k):
    """``balanced_scores`` as its equations read, float64 on the host (the
    noise is the layer's own fixed table: data, not arithmetic)."""
    n, E = z.shape
    u = (z - z.mean(axis=0)) / (z.std(axis=0) + 1e-6) + NOISE * np.asarray(
        noise_table(n, E), np.float64)
    b = np.zeros(E)
    for _ in range(BIAS_ROUNDS):
        chosen = np.argsort(-(u + b), axis=1, kind="stable")[:, :top_k]
        c = np.bincount(chosen.ravel(), minlength=E)
        b = b - BIAS_GAIN * np.log((c + 1.0) / (n * top_k / E + 1.0))
    return u + b


def moe_token_by_token(p, h, top_k=MOE["top_k"], balancing="none"):
    """The layer as a loop over the tokens, the oracle: each token's
    softmax, its ``top_k`` largest (of the batch's balanced scores with
    ``batch_bias``), their renormalised weights, each chosen expert
    applied to that token alone.  Returns ``(y, weights (n, k))``."""
    tokens = np.asarray(h.reshape(-1, h.shape[-1]), np.float64)
    w1, w2 = (np.asarray(p[k], np.float64) for k in ("experts_fc1", "experts_fc2"))
    f = w2.shape[1]
    out, weights = np.zeros_like(tokens), []
    scores = None
    if balancing == "batch_bias":
        scores = scores_by_hand(
            tokens @ np.asarray(p["router"], np.float64), top_k)
    for t, x in enumerate(tokens):
        logits = x @ np.asarray(p["router"], np.float64)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        chosen = np.argsort(
            -(probs if scores is None else scores[t]), kind="stable")[:top_k]
        w = probs[chosen] / probs[chosen].sum()
        weights.append(w)
        for e, w_e in zip(chosen, w):
            pre = x @ w1[e]
            gate = pre[:f] / (1 + np.exp(-pre[:f]))
            out[t] += w_e * ((gate * pre[f:]) @ w2[e])
    return out, np.asarray(weights)


@pytest.mark.parametrize("balancing", ["none", "batch_bias"])
def test_routed_layer_is_a_loop_over_tokens_and_a_tokens_weights_sum_to_one(
        balancing):
    whole, params, h = moe_layer_and_params(balancing=balancing)
    y, stats = whole.apply(params, h)
    want, weights = moe_token_by_token(
        params["params"], h, balancing=balancing)
    np.testing.assert_allclose(y.reshape(want.shape), want, atol=2e-4)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-12)
    n = h.shape[0] * h.shape[1]
    assert float(stats[STATS.index("pairs_here")]) == n * MOE["top_k"]
    assert float(stats[STATS.index("layers")]) == 1
    # the weights the layer itself uses: what reaches the experts for a
    # token whose experts all return the same vector is that vector
    ones = jax.tree_util.tree_map(jnp.zeros_like, params)
    ones["params"]["router"] = params["params"]["router"]
    same, _ = GatedMoE(32, **MOE).apply(ones, h)
    assert float(jnp.abs(same).max()) == 0.0  # zero experts: a zero result


@pytest.mark.parametrize("own", [0.3, 0.003])
def test_balanced_scores_even_what_the_top_scores_lump(own):
    """A seeded router at the benchmark's widths: most of a logit is the
    same for every token (sd 0.9 against 0.3, PERF.md PR 40), so the top
    scores send nearly every token to the same experts; a few updates into
    training nine tokens in ten have all but the same logits (the second
    case).  Under the balanced scores every expert gets its share to
    within a tenth, any quarter of the experts theirs to within a
    hundredth, and a router that lowers some experts' logits for all
    tokens alike changes no choice."""
    n, E, k = 4096, 64, 8
    common, mine, few = jax.random.split(jax.random.key(7), 3)
    z = 0.9 * jax.random.normal(common, (E,)) + own * jax.random.normal(
        mine, (n, E)) * jnp.where(jax.random.uniform(few, (n, 1)) < 0.1,
                                  0.3 / own, 1.0)

    def counts(scores):
        chosen = jax.lax.top_k(scores, k)[1]
        return np.bincount(np.asarray(chosen).ravel(), minlength=E)

    share = n * k / E
    assert counts(z).max() > 5 * share
    u = balanced_scores(z, k)
    np.testing.assert_allclose(
        u, scores_by_hand(np.asarray(z, np.float64), k), atol=2e-2)
    c = counts(u)
    assert abs(c / share - 1).max() < 0.1
    for first in range(0, E, 16):
        assert abs(c[first:first + 16].sum() / (16 * share) - 1) < 0.01
    lowered = z - 5.0 * (jnp.arange(E) < 16)
    assert abs(counts(balanced_scores(lowered, k)) - c).max() <= 2


def test_gated_body_and_its_written_out_backward_are_autodiffs():
    """``routed_experts(act="silu_gate")`` against the plain sum over the
    pairs, with every gradient the written-out backward gives."""
    n, d, f, Eh = 40, 16, 12, 3
    keys = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(keys[0], (n, d))
    w1 = jax.random.normal(keys[1], (Eh, d, 2 * f)) * 0.3
    w2 = jax.random.normal(keys[2], (Eh, f, d)) * 0.3
    pair = jax.random.uniform(keys[3], (n, Eh)) < 0.5
    w_held = jnp.where(pair, jax.random.uniform(keys[4], (n, Eh)), 0.0)
    cot = jnp.sin(jnp.arange(n * d, dtype=jnp.float32)).reshape(n, d)

    def plain(x, w_held, w1, w2):
        y = sum(w_held[:, e:e + 1] * (silu_gate(x @ w1[e]) @ w2[e])
                for e in range(Eh))
        return jnp.sum(y * cot)

    def tiled(x, w_held, w1, w2):
        return jnp.sum(routed_experts(
            x, w_held, w1, w2, 4 * 128, pair, "silu_gate") * cot)

    got = jax.value_and_grad(tiled, argnums=(0, 1, 2, 3))(x, w_held, w1, w2)
    want = jax.value_and_grad(plain, argnums=(0, 1, 2, 3))(x, w_held, w1, w2)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip(("x", "w_held", "w1", "w2"), got[1], want[1]):
        if name == "w_held":  # off the pairs no weight reaches the sum
            a, b = jnp.where(pair, a, 0.0), jnp.where(pair, b, 0.0)
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("balancing", ["none", "batch_bias"])
def test_expert_shares_add_up_to_the_uncut_layer(balancing):
    """16 experts over 4 shares: the held parts of all shares (the router
    whole on each, and with it the batch's bias) are the uncut layer's
    output, and every token's chosen experts fall in exactly one share
    each."""
    d = 32
    whole, params, h = moe_layer_and_params(d, balancing=balancing)
    p = params["params"]
    want, _ = whole.apply(params, h)
    total, pairs = 0.0, 0.0
    for j in range(4):
        held = slice(4 * j, 4 * j + 4)
        share = dict(p, experts_fc1=p["experts_fc1"][held],
                     experts_fc2=p["experts_fc2"][held])
        y, st = GatedMoE(d, n_held=4, first_held=4 * j, balancing=balancing,
                         **MOE).apply({"params": share}, h)
        total = total + y
        pairs += float(st[STATS.index("pairs_here")])
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert pairs == h.shape[0] * h.shape[1] * MOE["top_k"]
    with pytest.raises(ValueError, match="not among"):
        GatedMoE(d, n_held=4, first_held=14, **MOE).init(jax.random.key(0), h)
    with pytest.raises(ValueError, match="balancing"):
        GatedMoE(d, balancing="sinkhorn", **MOE).init(jax.random.key(0), h)


# -- the model --------------------------------------------------------------------------

class _Dictionary:
    def pad(self):
        return 0

    def __len__(self):
        return 120


class _Task:
    dictionary = _Dictionary()
    args = None


def tiny_model(**over):
    from unicore_tpu.models import ARCH_CONFIG_REGISTRY, ARCH_MODEL_REGISTRY

    args = Namespace(**over)
    ARCH_CONFIG_REGISTRY["mellum_tiny"](args)
    return args, ARCH_MODEL_REGISTRY["mellum_tiny"].build_model(args, _Task())


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layers():
    """One sliding layer and its experts, whole and as 2 x 2 shares (the
    heads two ways, the experts two ways; the tiny preset has 2 KV heads):
    attention parts and expert parts summed, norms and router counted
    once, give the uncut layers' residual stream."""
    kinds = json.dumps(["sliding_attention"])
    _, whole = tiny_model(num_hidden_layers=1, layer_types=kinds)
    tok = np.random.default_rng(0).integers(1, 120, (2, 40)).astype(np.int32)
    params = whole.init_params(jax.random.key(0), {"net_input": {"src_tokens": tok}})
    params = jax.tree_util.tree_map(
        lambda a: a + 0.2 * jax.random.normal(jax.random.key(3), a.shape), params)
    want, _ = whole.apply(params, tok, features_only=True)

    dec = params["params"]["decoder"]
    attn, moe = dec["layers_0"], dec["layers_1"]
    from unicore_tpu.modules.layer_norm import RMSNorm

    norm = lambda p, x: RMSNorm(64, eps=1e-6).apply({"params": p}, x)
    x = params["params"]["embed_tokens"]["embedding"][tok]
    rope = json.loads(whole.rope_parameters)["sliding_attention"]
    part = GroupedQueryAttention(64, num_heads=2, num_kv_heads=1, head_dim=16,
                                 banded=True, window=16, rope=rope)
    h = norm(attn["norm"], x)
    for j in range(2):
        qs, ks = slice(32 * j, 32 * j + 32), slice(16 * j, 16 * j + 16)
        a = attn["self_attn"]
        x = x + part.apply({"params": {
            "q_proj": {"kernel": a["q_proj"]["kernel"][:, qs]},
            "k_proj": {"kernel": a["k_proj"]["kernel"][:, ks]},
            "v_proj": {"kernel": a["v_proj"]["kernel"][:, ks]},
            "out_proj": {"kernel": a["out_proj"]["kernel"][qs]},
        }}, h)
    h = norm(moe["norm"], x)
    for j in range(2):
        held = slice(4 * j, 4 * j + 4)
        y, _ = GatedMoE(64, expert_dim=48, n_routed=8, top_k=2, n_held=4,
                        first_held=4 * j).apply({"params": dict(
                            moe["moe"], experts_fc1=moe["moe"]["experts_fc1"][held],
                            experts_fc2=moe["moe"]["experts_fc2"][held])}, h)
        x = x + y
    x = norm(dec["final_norm"], x)
    np.testing.assert_allclose(x, want, atol=5e-5)


@pytest.mark.parametrize("wide", [0, 32], ids=["tiles", "wide-trips"])
@pytest.mark.parametrize("balancing", ["none", "batch_bias"])
def test_tiny_model_is_the_plain_reference_loss_and_gradients(
        monkeypatch, balancing, wide):
    """The tiny preset (two sliding layers and a full one) on seeded
    weights at ``L`` = 96, past the window (16) and the YaRN table's
    original context (32): loss and every gradient against
    ``benchmark/reference/mellum2_12b.py``, with the published choice of
    experts and with the batch's bias; through the loop over the tiles
    (192 tokens on 2 of 8 experts are an even load of 48, under ``WIDE``)
    and, with the tile at 8 and the wide trip at 32 rows, through the wide
    and the narrow loop the benchmark's cell runs."""
    sys.path.insert(0, ROOT)
    from benchmark import weights
    from benchmark.reference import mellum2_12b as ref
    from unicore_tpu.losses.lm_cross_entropy import LMCrossEntropyLoss
    from unicore_tpu.modules import latent_moe

    if wide:
        monkeypatch.setattr(latent_moe, "TILE", 8)
        monkeypatch.setattr(latent_moe, "WIDE", wide)
    args, model = tiny_model(num_experts_held=4, first_expert_held=2,
                             router_balancing=balancing)
    assert model.pattern == "SRSRGR" and set(model.pattern) <= set(KINDS)
    cfg = {k: getattr(args, k) for k in (
        "router_balancing",
        "hidden_size", "num_hidden_layers", "layer_types", "rope_parameters",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "sliding_window", "num_experts", "num_experts_per_tok",
        "num_experts_held", "first_expert_held", "moe_intermediate_size",
        "norm_topk_prob", "rms_norm_eps")}
    tok = np.random.default_rng(0).integers(1, 120, (2, 96)).astype(np.int32)
    sample = {"net_input": {"src_tokens": tok}, "target": tok}
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0), sample))
    params = weights.make(shapes, 11)
    params = jax.tree_util.tree_map(lambda a: 3.0 * a, params)
    want_shapes = ref.param_shapes(cfg, {"vocab_size": 120})
    assert (jax.tree_util.tree_structure(shapes)
            == jax.tree_util.tree_structure(want_shapes))
    loss = LMCrossEntropyLoss(_Task())
    (value, log), grads = jax.value_and_grad(
        lambda p: loss.forward(model, p, sample)[::2], has_aux=True)(params)
    got = (value, grads)
    # three expert layers: under the batch's bias every held expert makes a
    # wide trip of its 48 pairs or so; with no rule at least one does
    assert (log["moe_rows_wide"] > 0) == bool(wide)
    if wide and balancing == "batch_bias":
        assert log["moe_rows_wide"] == 3 * 4 * wide < log["moe_pairs_here"]
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(
            lambda p: ref.loss_sum(p, cfg, sample, 0))(params)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got[1])[0],
                            jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(
            a, b, atol=5e-5 * float(jnp.abs(b).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_the_compiled_step_holds_no_array_of_l_by_l_elements():
    """Loss and gradient of the tiny model through the kernels (interpret
    mode), compiled: no array has ``L x L`` elements in its last two axes,
    forward or backward; the default layer's dense triangle is what such
    a search finds."""
    from unicore_tpu.losses.lm_cross_entropy import LMCrossEntropyLoss

    L = 384
    _, model = tiny_model()
    tok = np.ones((1, L), np.int32)
    sample = {"net_input": {"src_tokens": tok}, "target": tok}
    params = model.init_params(jax.random.key(0), sample)
    loss = LMCrossEntropyLoss(_Task())
    text = jax.jit(jax.value_and_grad(
        lambda p: loss.forward(model, p, sample)[0])).lower(params).compile().as_text()
    square = re.compile(r"\[(?:\d+,)*%d,%d\]" % (L, L))
    assert "flash_fwd" in text and not square.search(text)
    plain = GroupedQueryAttention(64, 4, 2, 16)
    x = jnp.zeros((1, L, 64))
    dense = jax.jit(lambda p: plain.apply(p, x)).lower(
        plain.init(jax.random.key(0), x)).compile().as_text()
    assert square.search(dense)


def test_the_loss_states_the_bands_counts():
    from unicore_tpu.losses.lm_cross_entropy import LMCrossEntropyLoss
    from unicore_tpu.ops.flash_attention import Band, band_counts

    _, model = tiny_model()
    counts = {k: float(v) for k, v in model.band_counts(3, 200).items()}
    marks = LMCrossEntropyLoss.trace_marks(counts)
    (mark,) = marks.values()
    assert list(marks) == ["attn_band"] and "keys_computed" not in mark
    window, full = band_counts(Band(16), 256, 256), band_counts(Band(), 256, 256)
    assert mark == {
        "window_keys_computed": 2 * window[0], "window_keys_visible": 2 * window[1],
        "window_layers": 2, "full_keys_computed": full[0],
        "full_keys_visible": full[1], "full_layers": 1}
    assert LMCrossEntropyLoss.trace_marks({"loss": 1.0}) == {}


def test_what_is_not_built_is_refused():
    for over, said in [
        (dict(tie_word_embeddings=True), "tie_word_embeddings"),
        (dict(hidden_act="gelu"), "hidden_act"),
        (dict(mlp_layer_types=json.dumps(["dense"] * 3)), "mlp_layer_types"),
        (dict(layer_types=json.dumps(["sliding_attention"] * 2)), "layer_types"),
        (dict(attention_shares=3), "attention-shares"),
    ]:
        with pytest.raises(ValueError, match=said):
            tiny_model(**over)
    _, model = tiny_model(layers_held=2, attention_shares=2)
    assert model.pattern == "SRSR"
