"""EvaByte (``model_type: evabyte``, ``attention_class: eva``): a
tokenizer-free byte-level causal LM.  A byte embedding, pre-norm layers of
EVA attention (exact inside a window, one learned-pooled key/value per
chunk of every earlier window, one softmax; rotary positions) and a gated
SiLU feed-forward layer, RMSNorm with a unit offset, and an untied head
that predicts the next ``--num-pred-heads`` bytes at every position
(``lm_cross_entropy`` sums the cross-entropy over the shifted targets).

The layers run through :class:`~unicore_tpu.modules.hybrid_decoder.
HybridDecoder` as the pattern ``AF`` x layers: one scanned body, each
rematerialized in the backward pass, one final norm.

Arguments carry the names of the published ``config.json`` keys and state
the MODEL.  Two more say what of it is HELD in this process, the whole
model by default, or one chip's share of a deployment: ``--layers-held``
(a stretch of the stack; the rest lies on further pipeline stages) and
``--attention-shares`` (the attention heads divided that many ways, this
process holding one share: its columns of ``q_proj`` / ``k_proj`` /
``v_proj``, its rows of the pooling vectors and of ``out_proj``; the
feed-forward layers, norms, embedding and head are whole).
"""

import flax.linen as nn
import jax.numpy as jnp

from unicore_tpu.models import register_model
from unicore_tpu.models.hybrid_lm import (
    HybridLM,
    register_architecture,
    shares_divide,
)
from unicore_tpu.ops.eva_attention import keys_log


@register_model("evabyte")
class EvaByteModel(HybridLM):
    vocab_size: int = 320
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    layers_held: int = 0
    num_attention_heads: int = 32
    attention_shares: int = 1
    intermediate_size: int = 11008
    hidden_act: str = "silu"
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rms_norm_eps: float = 1e-5
    norm_add_unit_offset: bool = True
    rope_theta: float = 1e5
    # memory
    mlp_row_chunk: int = 0

    HELP = dict(
        window_size="positions attended exactly; earlier windows are seen "
                    "through their chunks' summaries",
        chunk_size="positions pooled into one key/value summary",
        num_pred_heads="bytes predicted at every position (targets t+1 .. "
                       "t+N)",
    )
    # (B, L, num_pred_heads * vocab): head m's logits for byte t + m
    logits_dtype = jnp.float32

    def check(self):
        n = self.attention_shares
        if not shares_divide(n, self.num_attention_heads):
            raise ValueError(
                f"--attention-shares {n} does not divide "
                f"{self.num_attention_heads} heads"
            )
        if not 0 <= self.layers_held <= self.num_hidden_layers:
            raise ValueError(
                f"--layers-held {self.layers_held} of "
                f"{self.num_hidden_layers} layers"
            )
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} heads do not divide a hidden "
                f"size of {self.hidden_size}"
            )

    @property
    def heads_held(self):
        return self.num_attention_heads // self.attention_shares

    @property
    def head_columns(self):
        return self.num_pred_heads * self.vocab_size

    @property
    def pattern(self):
        return "AF" * (self.layers_held or self.num_hidden_layers)

    def layers(self):
        return dict(
            norm_eps=self.rms_norm_eps,
            norm_unit_offset=self.norm_add_unit_offset,
            sizes={
                "A": dict(
                    num_heads=self.heads_held,
                    head_dim=self.hidden_size // self.num_attention_heads,
                    window_size=self.window_size, chunk_size=self.chunk_size,
                    rope_theta=self.rope_theta,
                ),
                "F": dict(ffn_dim=self.intermediate_size,
                          activation=self.hidden_act,
                          row_chunk=self.mlp_row_chunk),
            })

    @nn.nowrap
    def logged(self, stats, rows, length):
        return keys_log(rows, length, self.window_size, self.chunk_size)


#: unset sizes default to EvaByte's (6.5 B), whole
evabyte_base_architecture = register_architecture("evabyte", "evabyte")

#: every mechanism at a size a CPU test holds: three layers, windows of 32
#: positions in chunks of 4, four heads of 16, three bytes predicted
evabyte_tiny_architecture = register_architecture(
    "evabyte", "evabyte_tiny", dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
        intermediate_size=96, window_size=32, chunk_size=4, num_pred_heads=3,
        loss_chunk=48, mlp_row_chunk=64,
    ))
