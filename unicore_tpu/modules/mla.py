"""Multi-head latent attention with a query latent (DeepSeek-V2,
arXiv:2405.04434 section 2.1; ``joyai``'s attention sublayer): queries,
keys and values are expanded from two latents a token, each narrower than
the heads together and each normed before it is expanded, beside ONE rotary
key a token that every head shares.  Per held head, with ``h`` (L, d) the
block's normed input and both norms RMSNorm:

    cq = RMSNorm(h W_qa)                       (Cq)   the query latent
    [q_nope (N) ; q_rope (R)] = cq W_qb        per head
    [c' (C) ; k_rope (R)] = h W_kva            c = RMSNorm(c')
    [k_nope (N) ; v (Dv)] = c W_kvb            per head
    q_rope, k_rope rotated at positions 0 .. L-1, all R channels
    p = causal softmax(([q_nope ; q_rope] . [k_nope ; k_rope]) / sqrt(N + R))
    f = W_o concat_heads(p v)

``Cq`` is ``q_lora_rank``, ``C`` ``kv_lora_rank`` (``C + R`` is what a
serving cache would hold a token), ``N`` / ``R`` / ``Dv``
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``.

``rope_interleave`` pairs ADJACENT rotary channels (``2i`` with ``2i + 1``)
where ``modules/rotary.py`` pairs channel ``i`` with ``i + R / 2``.  A
score sums over the rotary channels of ``q`` and ``k`` alike, so it does not
change when both are permuted alike: the rotary COLUMNS of ``W_qb`` and
``W_kva`` are read even channels first (a gather of two kernels, not of an
activation), and the rotate-half rotation of the result is the interleaved
rotation of the columns as published (``tests/test_mla.py``).

A share of the layer holds whole heads: their columns of ``W_qb`` and
``W_kvb`` and their rows of ``W_o``.  ``W_qa``, ``W_kva`` and the two latent
norms are every share's alike (a latent is a token's, not a head's), and
since the heads' sums meet only in ``W_o`` the shares' ``f`` add up to the
whole layer's.

The kernels are the banded blockwise ones every banded layer here runs
(``_attend`` under a causal ``Band``: no ``(L, L)`` array).  They read ONE
width for ``q``, ``k`` and ``v``; keys are ``N + R`` wide and values
``Dv``, so ``v`` is padded with ``N + R - Dv`` zero channels and the
weighted sum cut back to ``Dv``.  That is MORE work than the equations ask
for, never less: at 192 and 128 the second product of a (query, key) pair
runs 192 wide, 1.2 x the pair's operations (``benchmark/flops`` counts the
equations').  The padding's channels are exact zeros in the output and in
``dv``.  A value width of their own in the kernels is PERF.md section 7's.

Scopes: ``mla_q`` (both query products and the norm between them),
``mla_latent`` (down, norm, up), ``mla_attn`` (rotary, the shared key's
broadcast, the padding, the kernels), ``out_proj``.  Named for a
rematerializing caller (:data:`KEPT`): the two normed latents and the
rotary key, ``Cq + C + R`` channels a token (2,112 against the 2 x 2,048 x
2,112 multiply-adds a token that make them again).
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from unicore_tpu.ops.flash_attention import Band
from .gated_mlp import _Kernel
from .layer_norm import RMSNorm
from .multihead_attention import _attend
from .rotary import apply_rotary, rope_table

#: what the layer names for a rematerializing caller
KEPT = ("mla_q_latent", "mla_kv_latent", "mla_k_rope")


def evens_first(n):
    """The channels ``0, 2, .., 1, 3, ..`` of ``n``: adjacent pairs as
    rotate-half's pairs."""
    return np.concatenate([np.arange(0, n, 2), np.arange(1, n, 2)])


def mla_log(rows, heads, layers, qk_dim, v_dim, latent_dim):
    """What a model with such layers logs of an update of ``rows`` rows:
    the heads held a layer, the layers, the widths of keys and values and
    what a serving cache would hold a token, each times the rows (the sums
    over an update's rows divide by ``mla_rows`` again)."""
    out = dict(mla_rows=1, mla_heads=heads, mla_layers=layers,
               mla_qk_dim=qk_dim, mla_v_dim=v_dim, mla_latent_dim=latent_dim)
    return {k: jnp.asarray(rows * v, jnp.float32) for k, v in out.items()}


def mla_mark(sums):
    """One ``unicore:mla`` mark an update, from that update's summed
    logging output; nothing where no row was logged."""
    rows = sums.get("mla_rows", 0)
    if not rows:
        return {}
    return {"mla": {
        stat: int(sums[f"mla_{stat}"] / rows)
        for stat in ("heads", "layers", "qk_dim", "v_dim", "latent_dim")}}


class LatentAttention(nn.Module):
    embed_dim: int
    num_heads: int            # heads held
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope: Optional[dict] = None   # a rope_parameters group over R channels
    rope_interleave: bool = True
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, h, train: bool = False):
        B, L, d = h.shape
        H, Cq, C = self.num_heads, self.q_lora_rank, self.kv_lora_rank
        N, R, Dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                    self.v_head_dim)
        D = N + R
        if Dv > D:
            raise ValueError(f"values {Dv} wide do not pad to keys {D} wide")
        dtype = h.dtype
        # adjacent pairs read as rotate-half's: on the kernels' columns
        turn = (evens_first(R) if self.rope_interleave and self.rope is not None
                else None)

        with jax.named_scope("mla_q"):
            cq = jnp.dot(h, _Kernel((d, Cq), name="q_a_proj")().astype(dtype))
            cq = checkpoint_name(
                RMSNorm(Cq, eps=self.norm_eps, name="q_norm")(cq),
                "mla_q_latent")
            w_qb = _Kernel((Cq, H * D), name="q_b_proj")()
            if turn is not None:
                w_qb = w_qb.reshape(Cq, H, D)[
                    :, :, np.concatenate([np.arange(N), N + turn])
                ].reshape(Cq, H * D)
            q = jnp.dot(cq, w_qb.astype(dtype))
            q = q.reshape(B, L, H, D).transpose(0, 2, 1, 3)

        with jax.named_scope("mla_latent"):
            w_kva = _Kernel((d, C + R), name="kv_a_proj")()
            if turn is not None:
                w_kva = w_kva[:, np.concatenate([np.arange(C), C + turn])]
            kva = jnp.dot(h, w_kva.astype(dtype))
            c = checkpoint_name(
                RMSNorm(C, eps=self.norm_eps, name="kv_norm")(kva[..., :C]),
                "mla_kv_latent")
            k_rope = checkpoint_name(kva[..., C:], "mla_k_rope")  # (B, L, R)
            kv = jnp.dot(
                c, _Kernel((C, H * (N + Dv)), name="kv_b_proj")()
                .astype(dtype)).reshape(B, L, H, N + Dv).transpose(0, 2, 1, 3)

        with jax.named_scope("mla_attn"):
            q_rope, k_rope = q[..., N:], k_rope[:, None]    # (B, 1, L, R)
            if self.rope is not None:
                table = rope_table(self.rope, R)
                positions = jnp.arange(L)
                q_rope = apply_rotary(q_rope, positions, table=table)
                k_rope = apply_rotary(k_rope, positions, table=table)
            q = jnp.concatenate(
                [q[..., :N], q_rope], axis=-1) * jnp.asarray(D ** -0.5, dtype)
            # ONE rotary key a token, every head's alike
            k = jnp.concatenate(
                [kv[..., :N], jnp.broadcast_to(k_rope, (B, H, L, R))],
                axis=-1)
            # the kernels read one width: the values padded to the keys'
            v = jnp.pad(kv[..., N:], ((0, 0),) * 3 + ((0, D - Dv),))
            o, _, _ = _attend(self, q, k, v, None, None, 0.0, train, False,
                              True, band=Band(None))
            o = o[..., :Dv]

        with jax.named_scope("out_proj"):
            w_o = _Kernel((H * Dv, d), name="out_proj")().astype(dtype)
            return jnp.einsum("bhld,hde->ble", o, w_o.reshape(H, Dv, d))
