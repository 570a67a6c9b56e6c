"""Transformer decoder stack
(reference /root/reference/unicore/modules/transformer_decoder.py,
transformer_decoder_layer.py): self-attention (optionally causal) +
cross-attention + FFN, pre-/post-LN, bucketed rel-pos bias.
"""

from functools import partial
from typing import Optional

import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu import utils
from .layer_norm import LayerNorm
from .multihead_attention import CrossMultiheadAttention, SelfMultiheadAttention
from .transformer_encoder import (
    bert_init,
    keep_ffn_activation,
    make_rp_bucket,
)


class TransformerDecoderLayer(nn.Module):
    embed_dim: int = 768
    ffn_embed_dim: int = 3072
    attention_heads: int = 8
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation_dropout: float = 0.0
    activation_fn: str = "gelu"
    post_ln: bool = False

    @nn.compact
    def __call__(
        self,
        x,
        encoder_out: Optional[jnp.ndarray] = None,
        attn_bias: Optional[jnp.ndarray] = None,
        padding_mask: Optional[jnp.ndarray] = None,
        encoder_attn_bias: Optional[jnp.ndarray] = None,
        encoder_padding_mask: Optional[jnp.ndarray] = None,
        train: bool = False,
        cache_kv=None,
        cache_positions: Optional[jnp.ndarray] = None,
        kv_scales=None,
        return_kv: bool = False,
    ):
        act = utils.get_activation_fn(self.activation_fn)
        dropout = partial(nn.Dropout(rate=self.dropout), deterministic=not train)
        act_dropout = partial(
            nn.Dropout(rate=self.activation_dropout), deterministic=not train
        )
        incremental = cache_kv is not None
        if incremental:
            # decoder-only serving: the decode cache covers self-attention
            # only (docs/serving.md names cross-attention decode as
            # unsupported)
            assert encoder_out is None, (
                "incremental decode does not support cross-attention"
            )

        residual = x
        ln_self = LayerNorm(self.embed_dim, name="self_attn_layer_norm")
        if not self.post_ln:
            x = ln_self(x)
        attn_out = SelfMultiheadAttention(
            self.embed_dim,
            self.attention_heads,
            dropout=self.attention_dropout,
            name="self_attn",
        )(x, key_padding_mask=padding_mask, attn_bias=attn_bias, train=train,
          cache_kv=cache_kv, cache_positions=cache_positions,
          kv_scales=kv_scales, return_kv=return_kv)
        kv = None
        if incremental or return_kv:
            x, kv = attn_out
        else:
            x = attn_out
        x = dropout(x)
        x = residual + x
        if self.post_ln:
            x = ln_self(x)

        ln_enc = LayerNorm(self.embed_dim, name="encoder_attn_layer_norm")
        cross = CrossMultiheadAttention(
            self.embed_dim,
            self.attention_heads,
            dropout=self.attention_dropout,
            name="encoder_attn",
        )
        if encoder_out is not None:
            residual = x
            if not self.post_ln:
                x = ln_enc(x)
            x = cross(
                x,
                encoder_out,
                encoder_out,
                key_padding_mask=encoder_padding_mask,
                attn_bias=encoder_attn_bias,
                train=train,
            )
            x = dropout(x)
            x = residual + x
            if self.post_ln:
                x = ln_enc(x)

        residual = x
        ln_final = LayerNorm(self.embed_dim, name="final_layer_norm")
        if not self.post_ln:
            x = ln_final(x)
        x = nn.Dense(
            self.ffn_embed_dim, name="fc1", kernel_init=bert_init,
            dtype=x.dtype, param_dtype=jnp.float32,
        )(x)
        x = act(x)
        x = act_dropout(x)
        x = keep_ffn_activation(x, self.activation_fn)
        x = nn.Dense(
            self.embed_dim, name="fc2", kernel_init=bert_init,
            dtype=x.dtype, param_dtype=jnp.float32,
        )(x)
        x = dropout(x)
        x = residual + x
        if self.post_ln:
            x = ln_final(x)
        if incremental or return_kv:
            return x, kv
        return x


class TransformerDecoder(nn.Module):
    decoder_layers: int = 6
    embed_dim: int = 768
    ffn_embed_dim: int = 3072
    attention_heads: int = 8
    emb_dropout: float = 0.1
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation_dropout: float = 0.0
    max_seq_len: int = 256
    activation_fn: str = "gelu"
    rel_pos: bool = True
    rel_pos_bins: int = 32
    max_rel_pos: int = 128
    post_ln: bool = False
    auto_regressive: bool = True

    def setup(self):
        self.emb_layer_norm = LayerNorm(self.embed_dim, name="emb_layer_norm")
        self.emb_dropout_module = nn.Dropout(rate=self.emb_dropout)
        if not self.post_ln:
            self.final_layer_norm = LayerNorm(self.embed_dim, name="final_layer_norm")
        self.layers = [
            TransformerDecoderLayer(
                embed_dim=self.embed_dim,
                ffn_embed_dim=self.ffn_embed_dim,
                attention_heads=self.attention_heads,
                dropout=self.dropout,
                attention_dropout=self.attention_dropout,
                activation_dropout=self.activation_dropout,
                activation_fn=self.activation_fn,
                post_ln=self.post_ln,
                name=f"layers_{i}",
            )
            for i in range(self.decoder_layers)
        ]
        if self.rel_pos:
            assert self.rel_pos_bins % 2 == 0
            self.relative_attention_bias = nn.Embed(
                self.rel_pos_bins,
                self.attention_heads,
                embedding_init=bert_init,
                name="relative_attention_bias",
                param_dtype=jnp.float32,
            )
            self._rp_bucket = make_rp_bucket(
                self.max_seq_len, self.rel_pos_bins, self.max_rel_pos
            )

    def get_rel_pos_bias(self, seq_len):
        rp_bucket = jnp.asarray(self._rp_bucket[:seq_len, :seq_len])
        values = self.relative_attention_bias(rp_bucket)
        return values.transpose(2, 0, 1)

    def get_rel_pos_bias_row(self, positions, seq_len):
        """The bias ROW each decoding sequence needs: query at
        ``positions[b]`` against keys ``0..seq_len-1`` — a per-row
        dynamic slice of the same ``_rp_bucket`` table the full forward
        reads, so decode and full-forward biases agree exactly.
        Returns (B, H, seq_len)."""
        rp = jnp.asarray(self._rp_bucket)[:, :seq_len]
        rows = jax.vmap(
            lambda p: jax.lax.dynamic_slice(rp, (p, 0), (1, seq_len))
        )(positions.astype(jnp.int32))[:, 0]  # (B, seq_len)
        values = self.relative_attention_bias(rows)  # (B, seq_len, H)
        return values.transpose(0, 2, 1)

    def __call__(
        self,
        emb,
        encoder_out: Optional[jnp.ndarray] = None,
        padding_mask: Optional[jnp.ndarray] = None,
        encoder_padding_mask: Optional[jnp.ndarray] = None,
        attn_mask: Optional[jnp.ndarray] = None,
        encoder_attn_mask: Optional[jnp.ndarray] = None,
        train: bool = False,
        return_kv: bool = False,
    ) -> jnp.ndarray:
        bsz, seq_len, _ = emb.shape
        x = self.emb_layer_norm(emb)
        x = self.emb_dropout_module(x, deterministic=not train)

        if padding_mask is not None:
            x = x * (1 - padding_mask[..., None].astype(x.dtype))

        rel_pos_bias = self.get_rel_pos_bias(seq_len) if self.rel_pos else None
        if attn_mask is None:
            attn_bias = rel_pos_bias
        elif rel_pos_bias is not None:
            attn_bias = attn_mask + rel_pos_bias
        else:
            attn_bias = attn_mask

        if self.auto_regressive:
            # additive causal mask (reference builds a -inf triu buffer);
            # NEG_INF-style finite value keeps softmax rescans NaN-free
            causal = jnp.triu(jnp.full((seq_len, seq_len), -1e30), 1)
            attn_bias = causal if attn_bias is None else attn_bias + causal

        # key-padding mask passes through separately (see encoder note)

        kv_layers = []
        for layer in self.layers:
            x = layer(
                x,
                encoder_out=encoder_out,
                padding_mask=padding_mask,
                attn_bias=attn_bias,
                encoder_padding_mask=encoder_padding_mask,
                encoder_attn_bias=encoder_attn_mask,
                train=train,
                return_kv=return_kv,
            )
            if return_kv:
                x, kv = x
                kv_layers.append(kv)

        if not self.post_ln:
            x = self.final_layer_norm(x)
        if return_kv:
            # prefill cache seed: (n_layers, B, H, L, D) each
            return x, (
                jnp.stack([k for k, _ in kv_layers]),
                jnp.stack([v for _, v in kv_layers]),
            )
        return x

    def decode_step(
        self,
        emb_t,
        caches,
        positions,
        kv_scales=None,
    ):
        """One incremental decode step: ``emb_t`` (B, 1, E) is the
        current token's embedding, ``caches = (k, v)`` the gathered
        per-layer caches ((n_layers, B, H, L, D) each, fp32 or int8),
        ``positions`` (B,) int32 each sequence's current row.  Each
        layer writes its new K/V row before attending (the token sees
        itself, matching the causal full forward row-for-row) and the
        NEW rows return for the caller's page scatter — the gathered
        view is ephemeral.  Returns ``(x, (k_rows, v_rows))`` with rows
        (n_layers, B, H, D) in the cache dtype."""
        k_caches, v_caches = caches
        seq_len = k_caches.shape[3]
        x = self.emb_layer_norm(emb_t)

        bias_row = (
            self.get_rel_pos_bias_row(positions, seq_len)
            if self.rel_pos else None
        )
        # causality is positional here: rows beyond each sequence's
        # position are masked inside ops/decode_attention — no triu

        k_rows, v_rows = [], []
        for i, layer in enumerate(self.layers):
            scales_i = (
                None if kv_scales is None
                else (kv_scales[0][i], kv_scales[1][i])
            )
            x, (k_t, v_t) = layer(
                x,
                attn_bias=bias_row,
                cache_kv=(k_caches[i], v_caches[i]),
                cache_positions=positions,
                kv_scales=scales_i,
                train=False,
            )
            k_rows.append(k_t)
            v_rows.append(v_t)

        if not self.post_ln:
            x = self.final_layer_norm(x)
        return x, (jnp.stack(k_rows), jnp.stack(v_rows))
