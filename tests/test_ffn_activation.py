"""The feed-forward layer's kept activation (``keep_ffn_activation``): what
``fc2`` reads passes through a forward-only ``optimization_barrier`` where
the activation is dear to evaluate, so XLA makes it once under ``fc1``'s
product.  The same operations on the same values: everything here is held
to the bare composition bit for bit, on the CPU.  What the chip's compiler
makes of it: ``tests/test_tpu_compile.py``.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.modules import (
    TransformerDecoderLayer,
    TransformerEncoderLayer,
    transformer_decoder,
    transformer_encoder,
)
from unicore_tpu.modules.transformer_encoder import (
    KEPT_ACTIVATIONS,
    keep_ffn_activation,
)
from unicore_tpu.utils import get_activation_fn

B, L, E, H, F = 2, 16, 32, 4, 64
LAYERS = {"encoder": TransformerEncoderLayer, "decoder": TransformerDecoderLayer}


def _loss_and_grads(layer, params, x, key):
    def loss(params, x):
        out = layer.apply(params, x, train=True, rngs={"dropout": key})
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (value, out), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(params, x)
    return out, value, grads


@pytest.mark.parametrize("kind", sorted(LAYERS))
@pytest.mark.parametrize("activation_dropout", [0.0, 0.1])
@pytest.mark.parametrize(
    "activation_fn", ["gelu", "gelu_fast", "tanh", "relu", "silu"])
def test_kept_form_equals_the_bare_composition(
        activation_fn, activation_dropout, kind, monkeypatch):
    layer = LAYERS[kind](
        embed_dim=E, ffn_embed_dim=F, attention_heads=H, dropout=0.0,
        attention_dropout=0.0, activation_dropout=activation_dropout,
        activation_fn=activation_fn,
    )
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (B, L, E), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), x)
    kept = _loss_and_grads(layer, params, x, key)
    for module in (transformer_encoder, transformer_decoder):
        monkeypatch.setattr(module, "keep_ffn_activation", lambda x, _: x)
    bare = _loss_and_grads(layer, params, x, key)
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(bare)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("activation_fn", [
    "gelu", "gelu_fast", "gelu_accurate", "tanh",
    "relu", "silu", "swish", "linear"])
def test_only_a_dear_activation_traces_a_barrier(activation_fn):
    layer = TransformerEncoderLayer(
        embed_dim=E, ffn_embed_dim=F, attention_heads=H,
        activation_fn=activation_fn)
    x = jnp.zeros((B, L, E), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(layer.apply(p, x).astype(jnp.float32))
    ))(params, x))
    # forward only: the backward rule hands the cotangent through
    assert text.count("optimization_barrier") == (
        1 if activation_fn in KEPT_ACTIVATIONS else 0)


def _under_vmap(fn):
    return lambda w, x: jnp.sum(jax.vmap(fn, in_axes=(None, 0))(w, x))


def _under_scan(fn):
    class Body(nn.Module):
        @nn.compact
        def __call__(self, carry, x):
            w = self.param("w", nn.initializers.ones, (F, F), jnp.float32)
            return carry + fn(w, x), None

    scanned = nn.scan(Body, variable_broadcast="params",
                      split_rngs={"params": False})()

    def run(w, x):
        out, _ = scanned.apply({"params": {"w": w}}, jnp.float32(0.0), x)
        return out

    return run


@pytest.mark.parametrize(
    "wrap", [jax.checkpoint, _under_scan, _under_vmap],
    ids=["checkpoint", "nn.scan", "vmap"])
def test_helper_traces_and_differentiates_under(wrap):
    act = get_activation_fn("gelu")
    w = jax.random.normal(jax.random.PRNGKey(0), (F, F), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (3, L, F), jnp.float32)

    def ffn(keep):
        def one(w, x):
            h = keep(act(x @ w))
            return jnp.sum((h @ w.T) ** 2)
        return wrap(one)

    kept = jax.jit(jax.value_and_grad(
        ffn(lambda h: keep_ffn_activation(h, "gelu")), argnums=(0, 1)))(w, x)
    bare = jax.jit(jax.value_and_grad(ffn(lambda h: h), argnums=(0, 1)))(w, x)
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
