"""A gated feed-forward layer: ``fc2(act(gate) * up)`` with ``[gate | up] =
fc1(x)``, no biases (SwiGLU with ``act = silu``: Shazeer, "GLU Variants
Improve Transformer", arXiv:2002.05202).  ``fc1`` is the fused
gate-and-up product: its kernel's first ``ffn_dim`` columns are the gate's,
the next ``ffn_dim`` the up projection's.

With ``row_chunk`` the layer runs over that many rows (tokens) at a time,
each chunk rematerialized in the backward pass, as the chunked loss runs
(``losses/lm_cross_entropy.py``): the ``2 x ffn_dim`` wide intermediates of
a long row (1.4 GB in bfloat16 at 32,768 x 22,016, and as much again for
their gradient) are alive for one chunk only.  The arithmetic is that of
the whole row; the kernels' cotangents are summed over the chunks in
float32.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.utils import get_activation_fn


class _Kernel(nn.Module):
    """A bias-free product's ``kernel``, under the product's name."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.normal(0.02), self.shape, jnp.float32
        )


def gated_rows(x, w1, w2, act):
    """``x`` (..., d), ``w1`` (d, 2 f), ``w2`` (f, d), both in ``x``'s
    dtype.  The gate's activation and its product with ``up`` are float32,
    rounded once."""
    f = w2.shape[0]
    with jax.named_scope("fc1"):
        h = jnp.dot(x, w1)
        g = act(h[..., :f].astype(jnp.float32)) * h[..., f:].astype(jnp.float32)
    with jax.named_scope("fc2"):
        return jnp.dot(g.astype(x.dtype), w2)


def gated_mlp(x, w1, w2, act, row_chunk=0):
    lead, d = x.shape[:-1], x.shape[-1]
    x = x.reshape(-1, d)
    n = x.shape[0]
    if not row_chunk or n <= row_chunk:
        y = gated_rows(x, w1.astype(x.dtype), w2.astype(x.dtype), act)
        return y.reshape(lead + (w2.shape[1],))
    if n % row_chunk:
        raise ValueError(
            f"{n} rows are not whole chunks of {row_chunk} (row_chunk)"
        )
    # float32 outside the loop, the activations' dtype inside: the kernels'
    # cotangents are then summed over the chunks in float32
    w1, w2 = w1.astype(jnp.float32), w2.astype(jnp.float32)

    @jax.checkpoint
    def one(xc):
        return gated_rows(xc, w1.astype(xc.dtype), w2.astype(xc.dtype), act)

    y = jax.lax.map(one, x.reshape(n // row_chunk, row_chunk, d))
    return y.reshape(lead + (w2.shape[1],))


class GatedMLP(nn.Module):
    embed_dim: int
    ffn_dim: int
    activation: str = "silu"
    row_chunk: int = 0

    @nn.compact
    def __call__(self, x):
        w1 = _Kernel((self.embed_dim, 2 * self.ffn_dim), name="fc1")()
        w2 = _Kernel((self.ffn_dim, self.embed_dim), name="fc2")()
        return gated_mlp(
            x, w1, w2, get_activation_fn(self.activation), self.row_chunk
        )
