"""``modules/mla.py``: latent attention with a query latent against the
equations as ``benchmark/reference/joyai_llm_flash.py`` writes them (a mask
over the row, keys 24 wide and values 16, no padding), through the blockwise
kernels in interpret mode and through XLA's softmax."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.modules import mla
from unicore_tpu.modules.mla import LatentAttention, evens_first

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

d, H, Cq, C, N, R, Dv = 64, 4, 48, 32, 16, 8, 16
CFG = dict(num_attention_heads=H, q_lora_rank=Cq, kv_lora_rank=C,
           qk_nope_head_dim=N, qk_rope_head_dim=R, v_head_dim=Dv,
           rope_theta=100.0, rope_interleave=True, rms_norm_eps=1e-6,
           num_hidden_layers=1, first_k_dense_replace=1, n_routed_experts=8)
ROPE = {"rope_type": "default", "rope_theta": 100.0}


def layer(heads=H, **over):
    return LatentAttention(
        d, num_heads=heads, q_lora_rank=Cq, kv_lora_rank=C,
        qk_nope_head_dim=N, qk_rope_head_dim=R, v_head_dim=Dv,
        **dict(dict(rope=ROPE, rope_interleave=True, norm_eps=1e-6), **over))


def seeded(module, x, scale=6.0):
    """Seeded kernels times ``scale`` (a sharp softmax), gains near 1."""
    params = module.init(jax.random.key(2), x)["params"]
    return {name: ({"weight": 1.0 + 0.1 * jax.random.normal(
        jax.random.key(len(name)), leaf["weight"].shape)}
        if "norm" in name else {"kernel": scale * leaf["kernel"]})
        for name, leaf in params.items()}


def by_the_equations(p, x, cfg=CFG, leave_out=None):
    from benchmark.reference import joyai_llm_flash as ref

    with jax.default_matmul_precision("highest"):
        return ref.mla(x.astype(jnp.float32), p, cfg, "float32", leave_out)


@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "softmax"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 4e-2)])
def test_layer_is_the_equations_values_and_every_gradient(dtype, tol, kernels):
    """At ``L`` = 200 (not a multiple of the kernels' tile: the router
    pads): the output and the gradient of every leaf and of the input, in
    float32 to the order of the sums and in bfloat16 to its rounding."""
    from unicore_tpu.ops import _pallas

    _pallas.set_interpret(kernels)
    L = 200
    module = layer()
    x = jax.random.normal(jax.random.key(1), (2, L, d))
    p = seeded(module, x)
    w = jnp.cos(jnp.arange(2 * L * d, dtype=jnp.float32)).reshape(2, L, d)
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    got = jax.value_and_grad(lambda p, x: jnp.sum(module.apply(
        {"params": cast(p)}, x.astype(dtype)).astype(jnp.float32) * w),
        argnums=(0, 1))(p, x)
    want = jax.value_and_grad(
        lambda p, x: jnp.sum(by_the_equations(p, x) * w), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(got[0], want[0], rtol=tol,
                               atol=tol * float(jnp.abs(want[0])))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got[1])[0],
                            jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(
            a, b, atol=tol * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("what", ["q_norm", "kv_norm", "shared_rope_key",
                                  "rotary", "scale"])
def test_each_mechanism_is_in_the_layer(what):
    """The layer is NOT the equations with a latent norm, the shared rotary
    key, the rotation or the scale by ``sqrt(N + R)`` left out."""
    module = layer()
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    p = seeded(module, x)
    got = module.apply({"params": p}, x)
    np.testing.assert_allclose(got, by_the_equations(p, x), atol=2e-4)
    left = by_the_equations(p, x, leave_out=what)
    assert float(jnp.abs(got - left).max()) > 1e-2


@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "softmax"])
def test_the_padded_value_channels_are_exact_zeros(monkeypatch, kernels):
    """The kernels are handed values padded from 16 to the keys' 24
    channels: the weighted sum's padded channels are exact zeros, and so is
    the padded channels' gradient, whatever flows back into them."""
    from unicore_tpu.ops import _pallas

    _pallas.set_interpret(kernels)
    seen = {}
    real = mla._attend

    def attend(module, q, k, v, *rest, **kw):
        seen.update(q=q, k=k, v=v)
        out = real(module, q, k, v, *rest, **kw)
        seen["o"] = out[0]
        return out

    monkeypatch.setattr(mla, "_attend", attend)
    module = layer()
    x = jax.random.normal(jax.random.key(1), (2, 128, d))
    p = seeded(module, x)
    module.apply({"params": p}, x)
    q, k, v, o = (seen[n] for n in "qkvo")
    assert q.shape == k.shape == v.shape == o.shape == (2, H, 128, N + R)
    assert not np.asarray(v[..., Dv:]).any()
    assert not np.asarray(o[..., Dv:]).any()
    assert np.asarray(o[..., :Dv]).any()
    # every head reads ONE rotary key
    np.testing.assert_array_equal(k[:, 0, :, N:], k[:, 3, :, N:])
    # a cotangent on every channel of the weighted sum, the padded ones too
    g = jax.random.normal(jax.random.key(5), o.shape)
    band = mla.Band(None)
    dv = jax.grad(lambda v: jnp.sum(real(
        module, q, k, v, None, None, 0.0, False, False, True, band=band
    )[0][..., :Dv] * g[..., :Dv]))(v)
    assert not np.asarray(dv[..., Dv:]).any() and np.asarray(dv[..., :Dv]).any()


def test_interleaved_pairs_are_rotate_half_under_a_permutation():
    """``rope_interleave`` pairs channels ``2i`` and ``2i + 1``: the layer
    with it is the layer without it whose rotary columns of ``W_qb`` and
    ``W_kva`` are permuted even channels first, and it is not the layer
    without it on the same weights."""
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    p = seeded(layer(), x)
    got = layer().apply({"params": p}, x)
    turn = evens_first(R)
    assert list(turn) == [0, 2, 4, 6, 1, 3, 5, 7]
    w_qb = p["q_b_proj"]["kernel"].reshape(Cq, H, N + R)
    w_qb = jnp.concatenate([w_qb[..., :N], w_qb[..., N:][..., turn]], axis=-1)
    w_kva = p["kv_a_proj"]["kernel"]
    w_kva = jnp.concatenate([w_kva[:, :C], w_kva[:, C:][:, turn]], axis=1)
    permuted = dict(p, q_b_proj={"kernel": w_qb.reshape(Cq, H * (N + R))},
                    kv_a_proj={"kernel": w_kva})
    half = layer(rope_interleave=False)
    np.testing.assert_allclose(
        half.apply({"params": permuted}, x), got, atol=1e-6)
    assert float(jnp.abs(half.apply({"params": p}, x) - got).max()) > 1e-2
    # and the reference's interleaved rotation is what both compute
    np.testing.assert_allclose(got, by_the_equations(p, x), atol=2e-4)
    np.testing.assert_allclose(
        half.apply({"params": p}, x),
        by_the_equations(p, x, dict(CFG, rope_interleave=False)), atol=2e-4)


def test_head_shares_add_up_to_the_uncut_layer():
    """4 shares of one head each: their columns of ``q_b_proj`` and
    ``kv_b_proj`` and their rows of ``out_proj``, with ``q_a_proj``,
    ``kv_a_proj`` and both latent norms every share's alike; the shares'
    ``f`` add up to the whole layer's, which is the equations'."""
    x = jax.random.normal(jax.random.key(1), (2, 96, d))
    p = seeded(layer(), x)
    want = layer().apply({"params": p}, x)
    np.testing.assert_allclose(want, by_the_equations(p, x), atol=2e-4)
    part = layer(heads=1)
    total = 0.0
    for j in range(H):
        mine = dict(
            p,
            q_b_proj={"kernel": p["q_b_proj"]["kernel"][
                :, j * (N + R):(j + 1) * (N + R)]},
            kv_b_proj={"kernel": p["kv_b_proj"]["kernel"][
                :, j * (N + Dv):(j + 1) * (N + Dv)]},
            out_proj={"kernel": p["out_proj"]["kernel"][j * Dv:(j + 1) * Dv]})
        total = total + part.apply({"params": mine}, x)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_rows_do_not_leak_and_the_future_is_not_seen():
    module = layer()
    x = jax.random.normal(jax.random.key(1), (2, 128, d))
    p = seeded(module, x)
    got = module.apply({"params": p}, x)
    other = x.at[1].set(0.0).at[0, 100:].set(1.0)
    again = module.apply({"params": p}, other)
    np.testing.assert_array_equal(again[0, :100], got[0, :100])
    assert float(jnp.abs(again[0, 100:] - got[0, 100:]).max()) > 1e-3


def test_values_wider_than_keys_are_refused_and_the_latents_are_named():
    x = jnp.zeros((1, 16, d))
    wide = LatentAttention(d, 2, Cq, C, 8, 8, 32)
    with pytest.raises(ValueError, match="pad"):
        wide.init(jax.random.key(0), x)
    text = jax.make_jaxpr(lambda p: layer().apply(p, x))(
        layer().init(jax.random.key(0), x))
    for name in mla.KEPT:
        assert name in str(text)
    assert mla.mla_mark({}) == {} and mla.mla_mark(
        {k: float(v) for k, v in mla.mla_log(4, 8, 6, 192, 128, 576).items()}
    ) == {"mla": dict(heads=8, layers=6, qk_dim=192, v_dim=128,
                      latent_dim=576)}
