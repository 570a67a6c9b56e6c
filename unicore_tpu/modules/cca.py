"""Compressed Convolutional Attention (Zyphra, arXiv:2510.04476; ``zaya``'s
attention sublayer): the whole attention runs in a latent narrower than the
stream.  The block's normed input ``h`` (L, d) is projected DOWN to ``H``
query heads and ``KV`` key/value heads of ``D`` channels (``H D < d``),
queries and keys are mixed over two positions and over each head's
channels by two causal convolutions, and the heads' weighted sums are
projected UP from ``H D`` to ``d``:

    q~ = h W_q  (L, H D),   k~ = h W_k  (L, KV D)
    v_j = h W_v,j                       for the first half of the KV heads
    v_j = (h W_v,j) one position late   for the second half (``h_{-1}`` = 0)
    z  = [q~ ; k~], as H + KV heads of D
    z1_t = a0 * z_{t-1} + a1 * z_t + c0             depthwise, kernel 2
    z2_t[head] = z1_{t-1}[head] A0[head] + z1_t[head] A1[head] + c1[head]
    m_q[i] = (q~[i] + k~[i // G]) / 2,  m_k[j] = mean of m_q over j's G heads
    q = z2_q + m_q,   k = z2_k + m_k
    q = sqrt(D) q / |q|,   k = tau_j sqrt(D) k / |k|         float32
    rotary on q, k;  softmax(q k^T / sqrt(D)) v, causal, head i on i // G
    f = concat(heads) W_o

The row is padded in front ONCE, by the two kernels' ``2 + 2 - 2`` zero
positions, and not again between the convolutions: ``z_{-1} = z_{-2} = 0``,
so ``z1_{-1} = c0`` (the first convolution's bias, not zero) is what
``z2_0`` reads as its earlier position.  Rows are independent: no tap and no
shifted value crosses from one row of the batch into the next.

Which KV heads are held is stated (``first_kv_head`` of ``kv_heads_model``):
a share of the layer owns whole KV heads with their ``G`` query heads, their
columns of ``W_q``, ``W_k``, ``W_v``, their channels of both convolutions,
their ``tau`` and their rows of ``W_o``, and whether a held head's value is
the late one follows from WHICH head of the model it is.  Every term above
stays inside one KV head's group, so the shares' ``f`` add up to the whole
layer's (``tests/test_zaya.py``).

The kernels are the banded blockwise ones every grouped-KV layer here runs
(``_attend`` with a causal ``Band``: no ``(L, L)`` array); what is new is
the memory-bound work in front of them, under the scope ``cca_mix`` (both
convolutions, the mean, the late values, the norms and the temperature),
between ``cca_down`` and ``cca_up``.  Stream dtype operands with float32
accumulators in the products; the depthwise taps, the mean, the L2 norms,
``tau`` and the rotation are float32.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.ops.flash_attention import Band
from .gated_mlp import _Kernel
from .multihead_attention import _attend
from .rotary import apply_rotary, rope_table


class _Vector(nn.Module):
    """A learned vector under a leaf name the optimizer's and the
    benchmark's rules know: ``scale`` (ones) or ``bias`` (zeros)."""

    shape: tuple
    leaf: str = "bias"

    @nn.compact
    def __call__(self):
        init = (nn.initializers.ones if self.leaf == "scale"
                else nn.initializers.zeros)
        return self.param(self.leaf, init, self.shape, jnp.float32)


def late(x, first=None):
    """``x`` (B, L, ...) one position late along ``L``: position ``t`` holds
    ``x_{t-1}``, position 0 holds ``first`` (zeros unless given), in every
    row of the batch on its own."""
    if first is None:
        first = jnp.zeros_like(x[:, :1])
    else:
        first = jnp.broadcast_to(first.astype(x.dtype), x[:, :1].shape)
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def mix(z, a, c0, A, c1):
    """Both convolutions over ``z`` (B, L, heads, D): the depthwise taps
    ``a`` (2, heads, D) with bias ``c0`` (heads, D) in float32, then the
    per-head ``A`` (2, heads, D, D) with bias ``c1`` (heads, D) as ONE
    product over ``[z1_{t-1} | z1_t]`` (2 D deep), its result in ``z``'s
    dtype as every projection's is; the bias is added in float32, which is
    what is returned."""
    f32 = jnp.float32
    zf = z.astype(f32)
    z1 = a[0] * late(zf) + a[1] * zf + c0
    # the position before the row's first holds the bias alone
    both = jnp.concatenate([late(z1, c0), z1], axis=-1).astype(z.dtype)
    taps = jnp.concatenate([A[0], A[1]], axis=1).astype(z.dtype)
    return jnp.einsum("blhc,hcd->blhd", both, taps).astype(f32) + c1


def qk_mean(q, k):
    """``m_q`` (B, L, KV, G, D): each query head's pre-convolution latent
    averaged with its KV head's, ``q`` (B, L, KV, G, D), ``k`` (B, L, KV,
    D); its mean over ``G`` is ``m_k``."""
    return 0.5 * (q + k[:, :, :, None])


def unit(x, gain=1.0):
    """``gain * x / |x|_2`` over the last axis, float32."""
    return x * (gain * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-12))


class CompressedConvAttention(nn.Module):
    embed_dim: int
    num_heads: int            # query heads held
    num_kv_heads: int         # KV heads held
    head_dim: int
    kv_heads_model: int = 0   # the whole layer's KV heads (0: all are held)
    first_kv_head: int = 0    # which of them the first held one is
    rope: Optional[dict] = None

    @nn.compact
    def __call__(self, h, train: bool = False):
        B, L, d = h.shape
        H, KV, D = self.num_heads, self.num_kv_heads, self.head_dim
        whole = self.kv_heads_model or KV
        if H % KV or not 0 <= self.first_kv_head <= whole - KV:
            raise ValueError(
                f"{H} query heads on KV heads {self.first_kv_head}.."
                f"{self.first_kv_head + KV - 1} of {whole}")
        G = H // KV
        dtype, f32 = h.dtype, jnp.float32

        with jax.named_scope("cca_down"):
            w = jnp.concatenate([
                _Kernel((d, n * D), name=name)().astype(dtype)
                for name, n in (("q_proj", H), ("k_proj", KV), ("v_proj", KV))
            ], axis=1)
            down = jnp.dot(h, w).reshape(B, L, H + 2 * KV, D)
            z, v = down[:, :, :H + KV], down[:, :, H + KV:]

        with jax.named_scope("cca_mix"):
            a = _Kernel((2, H + KV, D), name="conv0")()
            c0 = _Vector((H + KV, D), name="conv0_bias")()
            A = _Kernel((2, H + KV, D, D), name="conv1")()
            c1 = _Vector((H + KV, D), name="conv1_bias")()
            tau = _Vector((KV,), "scale", name="temperature")()
            z2 = mix(z, a, c0, A, c1)
            m_q = qk_mean(z[:, :, :H].astype(f32).reshape(B, L, KV, G, D),
                          z[:, :, H:].astype(f32))
            q = z2[:, :, :H] + m_q.reshape(B, L, H, D)
            k = z2[:, :, H:] + jnp.mean(m_q, axis=3)
            q = unit(q, D ** 0.5)
            k = unit(k, D ** 0.5 * tau[:, None])
            # the model's second half of KV heads read the value of the
            # position before
            lates = [self.first_kv_head + j >= (whole + 1) // 2
                     for j in range(KV)]
            if all(lates):
                v = late(v)
            elif any(lates):
                v = jnp.where(jnp.asarray(lates)[:, None], late(v), v)
            # (B, L, heads, D) -> the kernels' (B, heads, L, D)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

        if self.rope is not None:
            table = rope_table(self.rope, D)
            positions = jnp.arange(L)
            q = apply_rotary(q, positions, table=table)
            k = apply_rotary(k, positions, table=table)
        q, k = (q * D ** -0.5).astype(dtype), k.astype(dtype)

        with jax.named_scope("band_attn"):
            if G > 1:
                k = jnp.repeat(k, G, axis=1)
                v = jnp.repeat(v, G, axis=1)
            o, _, _ = _attend(self, q, k, v, None, None, 0.0, train, False,
                              True, band=Band(None))

        with jax.named_scope("cca_up"):
            w_o = _Kernel((H * D, d), name="out_proj")().astype(dtype)
            return jnp.einsum("bhld,hde->ble", o, w_o.reshape(H, D, d))
