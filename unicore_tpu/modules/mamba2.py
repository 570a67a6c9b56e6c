"""Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060) as ``nemotron_h``
builds it: one input projection to ``[z | x B C | dt]``, a causal depthwise
convolution over ``x B C``, the state-space scan (``ops/ssd_scan.py``), a
gate, an RMSNorm per group, and the output projection.

    z, xBC, dt = split(in_proj(u))
    xBC        = silu(conv1d_causal_depthwise(xBC) + b)
    x, B, C    = split(xBC)
    dt         = softplus(dt + dt_bias);   A = -exp(A_log)
    y          = ssd_scan(x, dt, A, B, C, D)
    out        = out_proj(RMSNorm_group(y * silu(z)))

``num_heads`` and ``n_groups`` are the heads and groups HELD here.  A model
published with 128 heads in 8 groups divides over 8 tensor-parallel ranks
as 16 heads and 1 group each: a group's ``B, C`` and its slice of the gated
norm (``d_inner / n_groups`` wide) belong to that group's heads alone, so
each rank's ``out_proj`` output is its exact part of the whole mixer's and
the parts add up (``tests/test_hybrid_lm.py`` holds it to that).

For a rematerializing caller the mixer names (``checkpoint_name``) one
array, :data:`KEPT`; a name changes no value and no dtype, and without
such a caller it does nothing.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from unicore_tpu.ops.ssd_scan import ssd_scan
from unicore_tpu.quant.dense import QuantDense

_init = nn.initializers.normal(0.02)

#: the array :meth:`Mamba2Mixer.__call__` names for a rematerializing caller
#: (``modules/hybrid_decoder.py``) to keep across the forward pass:
#: ``in_proj``'s result ``[z | x B C | dt]``, the mixer's largest product
#: (38 MB a layer at the benchmark's 8,192 tokens x 2,320).  The
#: convolution, the scan and the gated norm are made again from its slices
#: (they are memory-bound), and the mixer's own result is not kept: it is
#: what the NEXT layer's second forward starts from (``latent_moe.KEPT``
#: says why)
KEPT = ("mamba_in_proj",)


def _a_log_init(key, shape, dtype=jnp.float32):
    # A in [1, 16), the reference implementation's default range
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(dt_min, dt_max, floor):
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(
            jax.random.uniform(key, shape, dtype)
            * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min)
        )
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1

    return init


def causal_depthwise_conv(x, kernel, bias):
    """``x`` (B, L, C), ``kernel`` (K, C): ``out_t = sum_k kernel[k] *
    x_{t-(K-1)+k} + bias`` with zeros before the first token, as a sum of
    ``K`` shifted products (K = 4: cheaper on the chip than a convolution
    with one input channel per group)."""
    K = kernel.shape[0]
    L = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(padded[:, k:k + L] * kernel[k] for k in range(K))
    return out + bias


class GatedGroupRMSNorm(nn.Module):
    """``RMSNorm(y * silu(z))`` with one mean square per group of
    ``dim / n_groups`` channels; float32 statistics."""

    dim: int
    n_groups: int = 1
    eps: float = 1e-5

    @nn.compact
    def __call__(self, y, z):
        weight = self.param("weight", nn.initializers.ones, (self.dim,),
                            jnp.float32)
        dtype = y.dtype
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        grouped = g.reshape(g.shape[:-1] + (self.n_groups, -1))
        ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        g = (grouped * jax.lax.rsqrt(ms + self.eps)).reshape(g.shape)
        return (g * weight).astype(dtype)


class Mamba2Mixer(nn.Module):
    embed_dim: int
    num_heads: int            # held here
    head_dim: int = 64
    n_groups: int = 1         # held here
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @nn.compact
    def __call__(self, u):
        H, P, G, N = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        d_inner, bc = H * P, G * N
        b, L, _ = u.shape
        dense = lambda name, features: QuantDense(
            features, use_bias=False, name=name, kernel_init=_init,
            dtype=u.dtype, param_dtype=jnp.float32,
        )
        zxbcdt = checkpoint_name(
            dense("in_proj", 2 * d_inner + 2 * bc + H)(u), "mamba_in_proj")
        z, xBC, dt = jnp.split(
            zxbcdt, [d_inner, 2 * d_inner + 2 * bc], axis=-1
        )
        conv_w = self.param("conv_kernel", _init,
                            (self.conv_kernel, d_inner + 2 * bc), jnp.float32)
        conv_b = self.param("conv_bias", nn.initializers.zeros,
                            (d_inner + 2 * bc,), jnp.float32)
        xBC = jax.nn.silu(causal_depthwise_conv(
            xBC, conv_w.astype(u.dtype), conv_b.astype(u.dtype)
        ))
        x, B, C = jnp.split(xBC, [d_inner, d_inner + bc], axis=-1)

        dt_bias = self.param(
            "dt_bias", _dt_bias_init(self.dt_min, self.dt_max, self.dt_floor),
            (H,), jnp.float32,
        )
        A_log = self.param("A_log", _a_log_init, (H,), jnp.float32)
        D = self.param("D_skip", nn.initializers.ones, (H,), jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        y = ssd_scan(
            x.reshape(b, L, H, P), dt, -jnp.exp(A_log.astype(jnp.float32)),
            B.reshape(b, L, G, N), C.reshape(b, L, G, N), D,
            chunk=self.chunk_size,
        ).reshape(b, L, d_inner)
        y = GatedGroupRMSNorm(d_inner, G, self.norm_eps, name="norm")(y, z)
        return dense("out_proj", self.embed_dim)(y)
