"""The EVA attention layer of ``evabyte`` (``attention_class: eva``):
projections without bias, rotary positions over the whole head, the two
learned pooling vectors per head (``adaptive_mu_k``, ``adaptive_phi``), and
``ops/eva_attention.py``'s chunk summaries and joint softmax.

``num_heads`` is the heads HELD here: the shares of a tensor-parallel split
each own whole heads (their ``q_proj`` / ``k_proj`` / ``v_proj`` columns,
their rows of ``adaptive_mu_k`` / ``adaptive_phi`` and of ``out_proj``),
and their ``out_proj`` outputs add up to the whole layer's.
"""

import flax.linen as nn
import jax.numpy as jnp

from unicore_tpu.ops.eva_attention import eva_agg, eva_prep_kv, uses_kernel
from unicore_tpu.quant.dense import QuantDense

from .rotary import apply_rotary

_init = nn.initializers.normal(0.02)


class EvaAttention(nn.Module):
    embed_dim: int
    num_heads: int
    head_dim: int
    window_size: int
    chunk_size: int
    rope_theta: float

    @nn.compact
    def __call__(self, x):
        bsz, seq_len, _ = x.shape
        H, D = self.num_heads, self.head_dim
        if seq_len % self.window_size or self.window_size % self.chunk_size:
            raise ValueError(
                f"a row of {seq_len} positions is not whole windows of "
                f"{self.window_size} in chunks of {self.chunk_size}"
            )
        fused = uses_kernel(self.window_size, D, x.dtype)
        dense = lambda name, features, **heads: QuantDense(
            features, use_bias=False, name=name, kernel_init=_init,
            dtype=x.dtype, param_dtype=jnp.float32, heads_fused=fused, **heads,
        )
        (q,) = dense("q_proj", H * D, heads_out=(1, H))(x)
        (k,) = dense("k_proj", H * D, heads_out=(1, H))(x)
        (v,) = dense("v_proj", H * D, heads_out=(1, H))(x)
        mu = self.param("adaptive_mu_k", _init, (H, D), jnp.float32)
        phi = self.param("adaptive_phi", _init, (H, D), jnp.float32)
        positions = jnp.arange(seq_len)
        q = apply_rotary(q, positions, self.rope_theta)
        k = apply_rotary(k, positions, self.rope_theta)
        scale = D ** -0.5
        k_sum, v_sum = eva_prep_kv(k, v, mu, phi, self.chunk_size, scale)
        o = eva_agg(q, k, v, k_sum, v_sum, self.window_size,
                    self.chunk_size, scale)
        return dense("out_proj", self.embed_dim, heads_in=H)(o)
