"""``laguna``: head-gated grouped-KV attention whose sliding and full layers
hold different numbers of query heads, a rotary table over part of a head,
a dense layer ahead of sparse ones, routed gated experts beside a shared
expert with the routed sum scaled, at sizes a CPU test holds.  What the
layers share with ``mellum`` has its cases in ``test_mellum.py``; the
benchmark cell's are in ``tests/benchmark/test_laguna_s_2_1.py``."""

import json
import math
import os
import sys
from argparse import Namespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.modules.gated_moe import GatedMoE
from unicore_tpu.modules.hybrid_decoder import KINDS, HybridDecoder
from unicore_tpu.modules.multihead_attention import GroupedQueryAttention
from unicore_tpu.modules.rotary import apply_rotary, rope_table

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LAGUNA_YARN = {
    "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
    "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
    "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5,
}
TINY_YARN = {"rope_type": "yarn", "rope_theta": 100.0, "factor": 4,
             "original_max_position_embeddings": 32, "beta_fast": 4,
             "beta_slow": 1}


# -- partial rotary ----------------------------------------------------------------

def test_partial_yarn_table_is_the_equations_on_half_a_head():
    """``rope_table`` of Laguna-S-2.1's full layers against the published
    form written out in float64 with ``R = 128 x 0.5`` in the head size's
    place: 32 pairs, the stated factor."""
    inv_freq, c = rope_table(LAGUNA_YARN, 128)
    R = 64
    assert inv_freq.shape == (R // 2,) and inv_freq.dtype == np.float32
    i = np.arange(R // 2, dtype=np.float64)
    e = 500000.0 ** (-2 * i / R)
    dim = lambda r: R * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(5e5))
    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), R - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(
        inv_freq, e / 128 * ramp + e * (1 - ramp), rtol=1e-7)
    assert c == 1.4852030263919618
    assert abs(c - (0.1 * math.log(128) + 1)) < 1e-12
    # the fastest pairs keep their frequency, the slowest are divided by 128
    assert inv_freq[0] == 1.0 and 0 < low < high < R // 2
    np.testing.assert_allclose(inv_freq[-1], e[-1] / 128, rtol=1e-6)


@pytest.mark.parametrize("group", [
    {"rope_type": "default", "rope_theta": 1e4},
    dict(TINY_YARN, attention_factor=1.25),
])
def test_a_factor_of_one_is_todays_table_and_rotation_bit_for_bit(group):
    """``partial_rotary_factor`` 1 (as the sliding layers state it) gives
    the table and the rotation of a group that states none."""
    stated = dict(group, partial_rotary_factor=1)
    for D in (16, 128):
        a, b = rope_table(group, D), rope_table(stated, D)
        assert a[1] == b[1] and np.array_equal(a[0], b[0])
    x = jax.random.normal(jax.random.key(0), (2, 3, 40, 16), jnp.bfloat16)
    pos = jnp.arange(40)
    assert np.array_equal(
        apply_rotary(x, pos, table=rope_table(stated, 16)),
        apply_rotary(x, pos, table=rope_table(group, 16)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_partial_rotary_is_the_rotation_written_out(dtype):
    """Half of a head of 16: channels 0 .. 7 are rotated, channel ``i`` with
    ``i + 4``, by the table made for 8; channels 8 .. 15 come back as they
    went in, neither rotated nor scaled by ``c``."""
    group = dict(TINY_YARN, partial_rotary_factor=0.5, attention_factor=1.5)
    inv_freq, c = rope_table(group, 16)
    assert inv_freq.shape == (4,) and c == 1.5
    whole, _ = rope_table(dict(group, partial_rotary_factor=1), 8)
    assert np.array_equal(inv_freq, whole)   # the table of a head of 8
    x = jax.random.normal(jax.random.key(1), (2, 3, 50, 16), dtype)
    got = np.asarray(apply_rotary(x, jnp.arange(50), table=(inv_freq, c)),
                     np.float64)
    xf = np.asarray(x, np.float64)
    want = xf.copy()
    angle = np.arange(50)[:, None] * np.asarray(inv_freq, np.float64)
    for i in range(4):
        cos, sin = c * np.cos(angle[:, i]), c * np.sin(angle[:, i])
        want[..., i] = xf[..., i] * cos - xf[..., i + 4] * sin
        want[..., i + 4] = xf[..., i + 4] * cos + xf[..., i] * sin
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[..., :8], want[..., :8], atol=tol)
    assert np.array_equal(got[..., 8:], xf[..., 8:])     # bit for bit


# -- the gate ------------------------------------------------------------------------

def attention(gate, rope=None, H=6, **kw):
    return GroupedQueryAttention(
        32, num_heads=H, num_kv_heads=2, head_dim=16, banded=True,
        rope=rope or dict(TINY_YARN, partial_rotary_factor=0.5), gate=gate,
        **kw)


def test_a_gate_at_zero_halves_the_ungated_layers_result():
    """``W_g = 0`` makes every gate ``sigmoid(0)``: the gated layer gives
    half of what the ungated layer gives from the same projections; and a
    gate of its own for every head and token scales that head alone."""
    x = jax.random.normal(jax.random.key(1), (2, 40, 32))
    gated, plain = attention(True, window=16), attention(False, window=16)
    params = gated.init(jax.random.key(2), x)
    p = dict(params["params"])
    assert p["gate_proj"]["kernel"].shape == (32, 6)
    w_g = p.pop("gate_proj")
    assert (jax.tree_util.tree_structure({"params": p})
            == jax.tree_util.tree_structure(plain.init(jax.random.key(2), x)))
    want = plain.apply({"params": p}, x)
    zero = dict(p, gate_proj={"kernel": jnp.zeros((32, 6))})
    np.testing.assert_allclose(
        gated.apply({"params": zero}, x), 0.5 * want, atol=1e-6)
    # a large gate on head 3 alone, nothing on the others: that head's
    # rows of out_proj times its ungated result
    big = jnp.zeros((32, 6)).at[:, 3].set(50.0 * jnp.sign(x[0, 0]))
    only = gated.apply({"params": dict(p, gate_proj={"kernel": big})}, x[:1, :1])
    one_head = dict(p, out_proj={"kernel": p["out_proj"]["kernel"].at[:48].set(
        0.0).at[64:].set(0.0)})
    rest = plain.apply({"params": dict(p, out_proj={
        "kernel": p["out_proj"]["kernel"].at[48:64].set(0.0)})}, x[:1, :1])
    np.testing.assert_allclose(
        only, plain.apply({"params": one_head}, x[:1, :1]) + 0.5 * rest,
        atol=1e-5)
    assert w_g["kernel"].dtype == jnp.float32


def test_the_gate_runs_under_a_scope_of_its_own():
    x = jnp.zeros((1, 32, 32))
    layer = attention(True)
    text = jax.jit(lambda p: layer.apply(p, x)).lower(
        layer.init(jax.random.key(0), x)).as_text(debug_info=True)
    assert "attn_gate" in text and "band_attn" in text
    plain = attention(False)
    text = jax.jit(lambda p: plain.apply(p, x)).lower(
        plain.init(jax.random.key(0), x)).as_text(debug_info=True)
    assert "attn_gate" not in text


def test_gated_head_shares_add_up_to_the_uncut_layer():
    """12 query heads on 4 KV heads over 4 shares (3 on 1 each), gates and
    all: the shares' results add up to the whole layer's."""
    D, d, L = 16, 32, 48
    kw = dict(head_dim=D, banded=True, window=20, gate=True,
              rope=dict(TINY_YARN, partial_rotary_factor=0.5))
    whole = GroupedQueryAttention(d, num_heads=12, num_kv_heads=4, **kw)
    x = jax.random.normal(jax.random.key(1), (2, L, d))
    params = whole.init(jax.random.key(2), x)
    p = jax.tree_util.tree_map(lambda a: 5.0 * a, params["params"])
    want = whole.apply({"params": p}, x)
    part = GroupedQueryAttention(d, num_heads=3, num_kv_heads=1, **kw)
    total = 0.0
    for j in range(4):
        qs, ks = slice(j * 3 * D, (j + 1) * 3 * D), slice(j * D, (j + 1) * D)
        total = total + part.apply({"params": {
            "q_proj": {"kernel": p["q_proj"]["kernel"][:, qs]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, ks]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, ks]},
            "gate_proj": {"kernel": p["gate_proj"]["kernel"][:, 3 * j:3 * j + 3]},
            "out_proj": {"kernel": p["out_proj"]["kernel"][qs]},
        }}, x)
    np.testing.assert_allclose(total, want, atol=2e-5)


# -- what mellum's layers are: the new fields at their defaults ---------------------

def test_no_gate_no_shared_expert_and_scale_one_are_mellums_layer_bit_for_bit():
    """``HybridDecoder`` over ``SR`` with the new fields stated at their
    defaults (``gate`` off, a ``partial_rotary_factor`` of 1, ``shared_dim``
    0, ``routed_scale`` 1) and without them: the same parameters, the same
    bits, forward and gradient."""
    rope = {"rope_type": "default", "rope_theta": 100.0}
    heads = dict(num_heads=4, num_kv_heads=2, head_dim=16)
    moe = dict(expert_dim=24, n_routed=8, top_k=2, balancing="batch_bias")
    old = HybridDecoder(
        pattern="SRGR", embed_dim=32, norm_eps=1e-6, remat=False, sizes={
            "S": dict(heads, window=16, rope=rope),
            "G": dict(heads, rope=TINY_YARN), "R": moe})
    new = HybridDecoder(
        pattern="SRGR", embed_dim=32, norm_eps=1e-6, remat=False, sizes={
            "S": dict(heads, window=16, gate=False,
                      rope=dict(rope, partial_rotary_factor=1)),
            "G": dict(heads, gate=False,
                      rope=dict(TINY_YARN, partial_rotary_factor=1)),
            "R": dict(moe, routed_scale=1.0, shared_dim=0)})
    x = jax.random.normal(jax.random.key(3), (2, 48, 32), jnp.bfloat16)
    params = old.init(jax.random.key(4), x)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(new.init(jax.random.key(4), x)))
    f = lambda m: jax.value_and_grad(
        lambda p: jnp.sum(m.apply(p, x)[0].astype(jnp.float32) ** 2))(params)
    (a, da), (b, db) = f(old), f(new)
    assert np.array_equal(a, b)
    for u, v in zip(jax.tree_util.tree_leaves(da), jax.tree_util.tree_leaves(db)):
        assert np.array_equal(u, v)


def test_shared_expert_and_scale_are_the_equations():
    """``GatedMoE(shared_dim=, routed_scale=)`` against the same layer
    without them: the routed sum times the scale, plus the gated body of
    the shared kernels over every token, unweighted."""
    d, n = 32, 40
    base = dict(expert_dim=24, n_routed=8, top_k=2)
    full = GatedMoE(d, shared_dim=20, routed_scale=2.5, **base)
    h = jax.random.normal(jax.random.key(5), (2, n, d))
    params = full.init(jax.random.key(6), h)
    p = jax.tree_util.tree_map(lambda a: 4.0 * a, dict(params["params"]))
    assert p["shared_fc1"]["kernel"].shape == (d, 40)
    assert p["shared_fc2"]["kernel"].shape == (20, d)
    got, stats = full.apply({"params": p}, h)
    routed_only = {k: v for k, v in p.items() if not k.startswith("shared")}
    routed, stats0 = GatedMoE(d, **base).apply({"params": routed_only}, h)
    pre = h @ p["shared_fc1"]["kernel"]
    shared = (jax.nn.silu(pre[..., :20]) * pre[..., 20:]) @ p["shared_fc2"]["kernel"]
    np.testing.assert_allclose(got, 2.5 * routed + shared, atol=2e-5)
    assert np.array_equal(stats, stats0)
    text = jax.jit(lambda q: full.apply(q, h)).lower(
        {"params": p}).as_text(debug_info=True)
    assert "moe_shared" in text and "moe_routed" in text


def test_the_rules_rounds_as_one_loop_are_the_rounds_written_out():
    """``balanced_scores`` runs its eight rounds under one ``fori_loop``:
    the scores the equations give written out round by round here (to the
    last bits: the loop's body is compiled as one program, the copies one
    operation at a time), the same chosen set, and a layer's program with
    fewer sorts than rounds."""
    from unicore_tpu.modules import gated_moe
    from unicore_tpu.modules.gated_moe import balanced_scores
    from unicore_tpu.modules.latent_moe import top_k_set

    n, E, k = 192, 16, 4
    z = 0.3 * jax.random.normal(jax.random.key(7), (n, E)) + jnp.linspace(
        -1.0, 1.0, E)
    mean = jnp.mean(z, axis=0)
    spread = jnp.sqrt(jnp.mean(jnp.square(z - mean), axis=0))
    u = (z - mean) / (spread + 1e-6) + gated_moe.NOISE * gated_moe.noise_table(n, E)
    b = jnp.zeros((E,))
    for _ in range(gated_moe.BIAS_ROUNDS):
        count = jnp.sum(top_k_set(u + b, k)[1], axis=0).astype(jnp.float32)
        b = b - gated_moe.BIAS_GAIN * jnp.log((count + 1.0) / (n * k / E + 1.0))
    loop = balanced_scores(z, k)
    np.testing.assert_allclose(loop, u + b, atol=2e-6)
    assert np.array_equal(top_k_set(loop, k)[1], top_k_set(u + b, k)[1])
    h = jax.random.normal(jax.random.key(8), (2, 48, 32))
    layer = GatedMoE(32, expert_dim=24, n_routed=E, top_k=k,
                     balancing="batch_bias")
    params = layer.init(jax.random.key(9), h)
    sorts = jax.jit(lambda p: layer.apply(p, h)).lower(
        params).as_text().count("top_k")
    assert 0 < sorts < gated_moe.BIAS_ROUNDS


# -- the band at the published window -------------------------------------------------

def test_the_bands_map_at_a_window_of_one_key_block():
    """Window 512 at 32,768 positions under the kernels' ``(256, 512)``
    blocks: a query block sees its own key block and the one before, so
    254 of 8,192 blocks are visited and every second pair scored is one no
    query may see; the full layer's map is Mellum2's."""
    from unicore_tpu.ops.flash_attention import (
        Band, band_block_map, band_counts)

    L = 32768
    visits = band_block_map(Band(512), L, L).kv_counts
    assert visits.shape == (1, 128)
    assert visits[0, :2].tolist() == [1, 1] and set(visits[0, 2:].tolist()) == {2}
    computed, visible = band_counts(Band(512), L, L)
    assert computed == 254 * 256 * 512
    assert visible == 512 * 513 // 2 + (L - 512) * 512
    assert computed / visible == pytest.approx(2.0, abs=1e-3)
    full = band_counts(Band(), L, L)
    assert full == (4160 * 256 * 512, L * (L + 1) // 2)


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
    ARCH_CONFIG_REGISTRY["laguna_tiny"](args)
    return args, ARCH_MODEL_REGISTRY["laguna_tiny"].build_model(args, _Task())


def config_of(args):
    """The keys the plain reference reads, as a configuration file has
    them."""
    return {k: getattr(args, k) for k in (
        "router_balancing", "hidden_size", "intermediate_size",
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "rope_parameters",
        "num_key_value_heads", "head_dim", "sliding_window", "num_experts",
        "num_experts_per_tok", "num_experts_held", "first_expert_held",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "moe_routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
        "layers_held", "attention_shares")}


def test_the_tiny_preset_has_every_mechanism():
    args, model = tiny_model()
    assert model.pattern == "GFSRSRGR" and set(model.pattern) <= set(KINDS)
    assert (model.held_heads("full_attention"),
            model.held_heads("sliding_attention")) == (4, 6)
    rope = json.loads(args.rope_parameters)
    assert rope["full_attention"]["partial_rotary_factor"] == 0.5
    assert (args.sliding_window, args.head_dim) == (16, 16)
    assert rope["full_attention"]["original_max_position_embeddings"] == 32
    assert (args.num_experts, args.num_experts_per_tok) == (8, 2)
    assert args.shared_expert_intermediate_size > 0
    assert args.moe_routed_scaling_factor == 2.5
    _, share = tiny_model(layers_held=3, attention_shares=2, num_experts_held=4)
    assert share.pattern == "GFSRSR"
    assert (share.held_heads("full_attention"),
            share.held_heads("sliding_attention")) == (2, 3)


@pytest.mark.parametrize("balancing,dtype,shared", [
    ("none", "float32", 40), ("none", "bfloat16", 40),
    ("batch_bias", "float32", 40), ("batch_bias", "bfloat16", 40),
    # a shared expert of the routed ones' width: the cell's, which the
    # reference runs as one more trip of the experts' loop
    ("batch_bias", "float32", 48),
])
def test_tiny_model_is_the_plain_reference_loss_and_gradients(
        balancing, dtype, shared):
    """The tiny preset (a full layer with the dense MLP, two sliding layers
    and a full one with experts) on seeded weights at ``L`` = 96, past the
    window (16) and the YaRN table's original context (32): loss and every
    gradient leaf against ``benchmark/reference/laguna_s_2_1.py``, with the
    published choice of experts and with the batch's bias; half of the
    experts held.

    Tolerances.  float32 (weights scaled by 3 for sharp softmaxes): program
    and reference differ by summation order alone, loss 2e-6, every
    gradient element 5e-5 of its leaf's largest.  bfloat16 parameters and
    activations against the float32 reference, on the seeded weights as
    they are (read here: loss 1.6e-5 / 2.6e-6, a leaf's gradient as a vector
    at most 3.3e-2 of its norm under the top scores' choice): loss 2e-4,
    each leaf's gradient 6e-2 of its norm; under the batch's bias a count
    that differs by one in bfloat16 moves many tokens' choice, so there the
    leaf's NORM is held (what ``correct`` compares on the chip; read 2.1e-2
    at most) to 6e-2."""
    from benchmark import weights
    from benchmark.reference import laguna_s_2_1 as ref
    from unicore_tpu.losses.lm_cross_entropy import LMCrossEntropyLoss

    args, model = tiny_model(num_experts_held=4, first_expert_held=2,
                             router_balancing=balancing,
                             shared_expert_intermediate_size=shared)
    cfg = config_of(args)
    tok = np.random.default_rng(0).integers(1, 120, (2, 96)).astype(np.int32)
    sample = {"net_input": {"src_tokens": tok}, "target": tok}
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0), sample))
    params = weights.make(shapes, 11)
    if dtype == "float32":
        params = jax.tree_util.tree_map(lambda a: 3.0 * a, params)
    want_shapes = ref.param_shapes(cfg, {"vocab_size": 120})
    flat = lambda t: {jax.tree_util.keystr(p): x.shape for p, x in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    assert flat(shapes) == flat(want_shapes)
    loss = LMCrossEntropyLoss(_Task())
    run = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    (value, log), grads = jax.value_and_grad(
        lambda p: loss.forward(model, p, sample)[::2], has_aux=True)(run)
    assert log["moe_layers"] == 3 and log["band_window_heads"] == 2 * 6
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(
            lambda p: ref.loss_sum(p, cfg, sample, 0))(params)
    assert float(value) == pytest.approx(
        float(want[0]), rel=2e-6 if dtype == "float32" else 2e-4)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want[1])):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        name = jax.tree_util.keystr(path)
        if dtype == "float32":
            np.testing.assert_allclose(
                a, b, atol=5e-5 * float(np.abs(b).max()) + 1e-9, err_msg=name)
        elif balancing == "none":
            assert np.linalg.norm(a - b) < 6e-2 * np.linalg.norm(b), name
        else:
            assert abs(np.linalg.norm(a) - np.linalg.norm(b)) < (
                6e-2 * np.linalg.norm(b)), name


@pytest.mark.parametrize("layer", [0, 1], ids=["full-dense", "sliding-sparse"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference_layer(layer):
    """One layer, whole in the plain reference and as 2 attention shares x
    4 expert shares in the program (the tiny preset has 2 KV heads and 8
    experts): the heads' ``out_proj`` parts and the routed parts summed,
    norms, the shared expert and the dense MLP counted ONCE, give the uncut
    reference layer's residual stream."""
    from benchmark import weights
    from benchmark.reference import laguna_s_2_1 as ref
    from unicore_tpu.modules.gated_mlp import GatedMLP
    from unicore_tpu.modules.layer_norm import RMSNorm

    tiny = tiny_model()[0]
    kinds, mlps, heads = (json.loads(getattr(tiny, key)) for key in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"))
    one = dict(num_hidden_layers=1, layer_types=json.dumps([kinds[layer]]),
               mlp_layer_types=json.dumps([mlps[layer]]),
               mlp_only_layers=json.dumps([0] if mlps[layer] == "dense" else []),
               num_attention_heads_per_layer=json.dumps([heads[layer]]),
               gating_types=json.dumps(["per_head"]), mlp_row_chunk=40)
    args, whole = tiny_model(**one)
    H, D = heads[layer], 16
    tok = np.random.default_rng(0).integers(1, 120, (2, 40)).astype(np.int32)
    shapes = jax.eval_shape(lambda: whole.init_params(
        jax.random.key(0), {"net_input": {"src_tokens": tok}}))
    params = jax.tree_util.tree_map(
        lambda a: 5.0 * a, weights.make(shapes, 3))
    with jax.default_matmul_precision("highest"):
        want = ref.hidden(params, config_of(args), tok)

    dec = params["params"]["decoder"]
    attn, body = dec["layers_0"], dec["layers_1"]
    norm = lambda p, x: RMSNorm(64, eps=1e-6).apply({"params": p}, x)
    x = params["params"]["embed_tokens"]["embedding"][tok]
    rope = json.loads(whole.rope_parameters)[kinds[layer]]
    part = GroupedQueryAttention(
        64, num_heads=H // 2, num_kv_heads=1, head_dim=D, banded=True,
        gate=True, rope=rope,
        window=16 if kinds[layer] == "sliding_attention" else 0)
    h = norm(attn["norm"], x)
    a = attn["self_attn"]
    for j in range(2):
        qs = slice(j * H // 2 * D, (j + 1) * H // 2 * D)
        ks = slice(j * D, (j + 1) * D)
        x = x + part.apply({"params": {
            "q_proj": {"kernel": a["q_proj"]["kernel"][:, qs]},
            "k_proj": {"kernel": a["k_proj"]["kernel"][:, ks]},
            "v_proj": {"kernel": a["v_proj"]["kernel"][:, ks]},
            "gate_proj": {"kernel": a["gate_proj"]["kernel"][
                :, j * H // 2:(j + 1) * H // 2]},
            "out_proj": {"kernel": a["out_proj"]["kernel"][qs]},
        }}, h)
    h = norm(body["norm"], x)
    if mlps[layer] == "dense":  # every share computes it alike: once
        x = x + GatedMLP(64, 96).apply({"params": body["mlp"]}, h)
    else:
        moe = body["moe"]
        shared = 0.0
        for j in range(4):
            held = slice(2 * j, 2 * j + 2)
            mine = dict(moe, experts_fc1=moe["experts_fc1"][held],
                        experts_fc2=moe["experts_fc2"][held])
            kw = dict(expert_dim=48, n_routed=8, top_k=2, n_held=2,
                      first_held=2 * j, routed_scale=2.5)
            with_shared, _ = GatedMoE(64, shared_dim=40, **kw).apply(
                {"params": mine}, h)
            routed, _ = GatedMoE(64, **kw).apply({"params": {
                k: v for k, v in mine.items() if not k.startswith("shared")}}, h)
            x = x + routed
            shared = with_shared - routed   # alike on every share
        x = x + shared
    x = norm(dec["final_norm"], x)
    np.testing.assert_allclose(x, want, atol=1e-4)


def test_the_loss_states_the_bands_counts_and_each_kinds_heads():
    from unicore_tpu.losses.lm_cross_entropy import LMCrossEntropyLoss
    from unicore_tpu.ops.flash_attention import Band, band_counts

    _, model = tiny_model(attention_shares=2)
    counts = {k: float(v) for k, v in model.band_counts(3, 200).items()}
    marks = LMCrossEntropyLoss.trace_marks(counts)
    (mark,) = marks.values()
    assert list(marks) == ["attn_band"] and "keys_computed" not in mark
    window, full = band_counts(Band(16), 256, 256), band_counts(Band(), 256, 256)
    assert mark == {
        "window_keys_computed": 2 * window[0], "window_keys_visible": 2 * window[1],
        "window_layers": 2, "window_heads": 3,
        "full_keys_computed": 2 * full[0], "full_keys_visible": 2 * full[1],
        "full_layers": 2, "full_heads": 2}
    # a model that states no heads (mellum) keeps the mark it had
    older = {k: v for k, v in counts.items() if not k.endswith("_heads")}
    assert set(LMCrossEntropyLoss.trace_marks(older)["attn_band"]) == {
        f"{kind}_{stat}" for kind in ("window", "full")
        for stat in ("keys_computed", "keys_visible", "layers")}


@pytest.mark.parametrize("over,said", [
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(gating="per-element"), "gating"),
    (dict(gating_types=json.dumps(["per_head", "none"] * 2)), "gating_types"),
    (dict(moe_router_logit_softcapping=30.0), "moe_router_logit_softcapping"),
    (dict(moe_apply_router_weight_on_input=True),
     "moe_apply_router_weight_on_input"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(mlp_only_layers="[0, 1]"), "mlp_only_layers"),
    (dict(mlp_layer_types=json.dumps(["dense"] * 3)), "mlp_layer_types"),
    (dict(layer_types=json.dumps(["sliding_attention"] * 2)), "layer_types"),
    (dict(num_attention_heads_per_layer=json.dumps([4, 6, 8, 4])),
     "query heads"),
    (dict(attention_shares=4), "attention-shares"),
])
def test_what_is_not_built_is_refused(over, said):
    with pytest.raises(ValueError, match=said):
        tiny_model(**over)


def test_the_command_line_takes_the_published_keys():
    """``--arch laguna`` through the trainer's own parser: the published
    keys are options, unset ones default to Laguna-S-2.1's, and the held
    share is stated by the program's own four."""
    from unicore_tpu import options

    parser = options.get_training_parser()
    args = options.parse_args_and_arch(parser, [
        "/nonexistent", "--task", "causal_lm", "--arch", "laguna", "--loss",
        "lm_cross_entropy", "--layers-held", "5", "--attention-shares", "8",
        "--num-experts-held", "8", "--moe-routed-scaling-factor", "2.5",
        "--router-balancing", "batch_bias", "--mlp-row-chunk", "4096",
    ])
    assert (args.hidden_size, args.num_experts, args.sliding_window,
            args.intermediate_size) == (3072, 256, 512, 12288)
    assert (args.layers_held, args.attention_shares, args.num_experts_held,
            args.first_expert_held) == (5, 8, 8, 0)
    assert json.loads(args.num_attention_heads_per_layer)[:5] == [48, 72, 72, 72, 48]
    assert json.loads(args.rope_parameters)["full_attention"][
        "partial_rotary_factor"] == 0.5
