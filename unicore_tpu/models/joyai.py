"""JoyAI-LLM-Flash (``model_type: joyai_llm_flash``, jdopensource; key for
key a DeepSeek-V3 configuration): a decoder whose every layer is multi-head
latent attention with a query latent (DeepSeek-V2, arXiv:2405.04434 section
2.1; ``modules/mla.py``), then a feed-forward layer that is a dense gated
SiLU MLP on the first ``first_k_dense_replace`` layers and, on the others,
``n_routed_experts`` gated experts ``num_experts_per_tok`` a token under a
sigmoid router with a bias on the scores that choose (DeepSeek-V3,
arXiv:2412.19437 section 2.1.2; ``modules/gated_moe.py`` with
``scoring="sigmoid"``) beside ``n_shared_experts`` shared ones; an untied
head; and ``num_nextn_predict_layers`` multi-token-prediction modules that
train with the model (section 2.2; ``modules/mtp.py``).

    x = x + MLA(RMSNorm(x));   x = x + MLP(RMSNorm(x))
    loss = nll(head(x_final), t+1) + mtp_loss_weight mean-nll(head(MTP(x_final)), t+2)

The layers run through :class:`~unicore_tpu.modules.hybrid_decoder.
HybridDecoder` as two characters a layer, ``L`` then ``F`` / ``R``; the
sparse layers, built alike, are one repeated unit under ``nn.scan``.  The
prediction module is one more ``L`` and one more ``R`` (the model's last
layer's kinds) behind its two norms and its ``2d -> d`` projection; it reads
the model's embedding and is scored by the model's head, so it is held where
both are.

Arguments carry the names of the published ``config.json`` keys and state
the MODEL.  What is HELD in this process, the whole model by default or one
chip's share of a deployment, is said as ``laguna`` says it:
``--layers-held``, ``--attention-shares`` (the heads divided that many ways:
their columns of ``q_b_proj`` and ``kv_b_proj`` and their rows of
``o_proj``; both down-projections and both latent norms are every share's
alike), ``--num-experts-held`` with ``--first-expert-held``.  The router,
the shared expert and a dense layer's MLP are never divided.
``--router-balancing batch_bias`` is a rule of TRAINING the published keys
do not state (``modules/gated_moe.py``): the published recipe moves the
selection bias between updates, which the trainer here does not carry
(ROADMAP R8), so the leaf is read as loaded under ``none`` and stood in for
under ``batch_bias``.  ``--mtp-loss-weight`` is the module's weight in the
loss (``config.json`` has none; 0.3 is DeepSeek-V3's first phase).

What the keys can say and the program does not build raises
(:meth:`JoyAIModel.check`): routing limited to groups of experts
(``n_group`` / ``topk_group`` over 1), a ``rope_scaling``, an
``attention_bias``, more than one prediction module, another
``topk_method`` or ``scoring_func`` than ``noaux_tc`` and ``sigmoid``,
another activation, experts on other than every layer after the dense ones.
"""

import flax.linen as nn

from unicore_tpu.models import register_model
from unicore_tpu.models.hybrid_lm import (
    HybridLM,
    register_architecture,
    shares_divide,
)
from unicore_tpu.modules.latent_moe import route_log
from unicore_tpu.modules.mla import mla_log
from unicore_tpu.ops.flash_attention import band_log


@register_model("joyai")
class JoyAIModel(HybridLM):
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    qk_head_dim: int = 192
    v_head_dim: int = 128
    head_dim: int = 64                 # published: the rotary channels
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rope_scaling: str = ""             # published null
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # published and not used here: the largest context, the checkpoint's
    # expert-parallel degree
    max_position_embeddings: int = 131072
    ep_size: int = 1
    # the prediction module's weight in the loss (not a published key)
    mtp_loss_weight: float = 0.3
    # what is held
    layers_held: int = 0
    attention_shares: int = 1
    num_experts_held: int = 0
    first_expert_held: int = 0
    # training's load-balancing rule (modules/gated_moe.BALANCINGS)
    router_balancing: str = "none"
    # memory
    mlp_row_chunk: int = 0

    HELP = dict(
        first_k_dense_replace="the first layers whose feed-forward layer "
                              "is a dense MLP of --intermediate-size",
        num_nextn_predict_layers="multi-token-prediction modules trained "
                                 "with the model (0 or 1)",
        mtp_loss_weight="the prediction module's mean loss is added to the "
                        "model's at this weight",
        first_expert_held="the first expert of --num-experts-held",
    )

    def check(self):
        not_built = dict(
            n_group=self.n_group != 1, topk_group=self.topk_group != 1,
            rope_scaling=bool(self.rope_scaling),
            attention_bias=self.attention_bias,
            num_nextn_predict_layers=not 0 <= self.num_nextn_predict_layers <= 1,
            topk_method=self.topk_method != "noaux_tc",
            scoring_func=self.scoring_func != "sigmoid",
            hidden_act=self.hidden_act != "silu",
            moe_layer_freq=self.moe_layer_freq != 1,
            num_key_value_heads=(
                self.num_key_value_heads != self.num_attention_heads),
            qk_head_dim=self.qk_head_dim != (
                self.qk_nope_head_dim + self.qk_rope_head_dim),
        )
        if any(not_built.values()):
            raise ValueError(
                "joyai is built with one group of experts (no group limit), "
                "no rope_scaling, no attention bias, at most one prediction "
                "module, topk_method noaux_tc over sigmoid scores, silu, "
                "experts on every layer after the dense ones, one key/value "
                "head a query head and qk_head_dim the sum of its two parts; "
                f"asked otherwise: {[k for k, v in not_built.items() if v]}"
            )
        held = self.layers_held or self.num_hidden_layers
        if not 0 < held <= self.num_hidden_layers:
            raise ValueError(
                f"layers_held {self.layers_held} of num_hidden_layers "
                f"{self.num_hidden_layers}")
        if not shares_divide(self.attention_shares, self.num_attention_heads):
            raise ValueError(
                f"--attention-shares {self.attention_shares} does not "
                f"divide num_attention_heads {self.num_attention_heads}")

    @property
    def pattern(self):
        """The held layers in ``HybridDecoder``'s characters."""
        return "".join(
            "L" + ("F" if i < self.first_k_dense_replace else "R")
            for i in range(self.layers_held or self.num_hidden_layers))

    @property
    def mtp_pattern(self):
        """The prediction module's block: the model's last layer's kinds."""
        if not self.num_nextn_predict_layers:
            return ""
        dense = self.num_hidden_layers <= self.first_k_dense_replace
        return "L" + ("F" if dense else "R")

    @property
    def ahead(self):
        return (("mtp", self.mtp_loss_weight),) if self.mtp_pattern else ()

    @property
    def held_heads(self):
        return self.num_attention_heads // self.attention_shares

    def layers(self):
        return dict(norm_eps=self.rms_norm_eps, sizes={
            "L": dict(
                num_heads=self.held_heads, q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim,
                rope={"rope_type": "default", "rope_theta": self.rope_theta},
                rope_interleave=self.rope_interleave,
                norm_eps=self.rms_norm_eps,
            ),
            "F": dict(ffn_dim=self.intermediate_size,
                      row_chunk=self.mlp_row_chunk),
            "R": dict(
                expert_dim=self.moe_intermediate_size,
                n_routed=self.n_routed_experts,
                top_k=self.num_experts_per_tok,
                n_held=self.num_experts_held,
                first_held=self.first_expert_held,
                norm_topk_prob=self.norm_topk_prob,
                balancing=self.router_balancing,
                routed_scale=self.routed_scaling_factor,
                shared_dim=self.n_shared_experts * self.moe_intermediate_size,
                scoring=self.scoring_func,
            ),
        })

    @nn.nowrap
    def logged(self, stats, rows, length):
        both = self.pattern + self.mtp_pattern
        layers = {"window": 0, "full": both.count("L")}
        return {
            **route_log(stats), **band_log(rows, length, None, layers),
            **mla_log(rows, self.held_heads, both.count("L"),
                      self.qk_head_dim, self.v_head_dim,
                      self.kv_lora_rank + self.qk_rope_head_dim),
        }


#: unset sizes default to JoyAI-LLM-Flash's, whole
joyai_llm_flash_architecture = register_architecture(
    "joyai", "joyai_llm_flash")

#: every mechanism at a size a CPU test holds: a dense layer and two sparse
#: ones (one scanned unit), four heads whose keys are 16 + 8 wide and whose
#: values 16, a query latent of 48 and a key/value latent of 32, eight
#: sigmoid-scored experts two a token beside a shared one, of which any
#: number may be held, and the prediction module
joyai_tiny_architecture = register_architecture(
    "joyai", "joyai_tiny", dict(
        hidden_size=64, intermediate_size=96, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        qk_head_dim=24, v_head_dim=16, head_dim=8, rope_theta=100.0,
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=48,
        loss_chunk=32, mlp_row_chunk=32,
    ))
