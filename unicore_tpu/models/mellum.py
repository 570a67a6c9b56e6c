"""Mellum 2 (``model_type: mellum``, e.g. JetBrains/Mellum2-12B-A2.5B-
Instruct): a decoder whose every layer is grouped-KV attention with rotary
positions, then routed gated experts.

    x = x + Attn(RMSNorm(x));   x = x + MoE(RMSNorm(x))

``layer_types`` says each layer's attention: ``sliding_attention`` (the
last ``sliding_window`` positions up to the query's own, rotary
``rope_parameters.sliding_attention``) or ``full_attention`` (the whole
row, ``rope_parameters.full_attention``: YaRN), three to one.  Both run
under a band the blockwise kernels mask themselves
(``modules/multihead_attention.GroupedQueryAttention(banded=True)``), so no
``(L, L)`` mask exists at any length.  Every ``mlp_layer_types`` entry is
``sparse``: ``num_experts`` gated SiLU experts of ``moe_intermediate_size``,
``num_experts_per_tok`` a token by a softmax router whose chosen scores are
renormalised (``norm_topk_prob``); no shared expert (``modules/
gated_moe.py``).  An untied head.  The layers run through
:class:`~unicore_tpu.modules.hybrid_decoder.HybridDecoder` as the pattern
``SR`` / ``GR`` per layer, each block rematerialized in the backward pass.

Arguments carry the names of the published ``config.json`` keys and state
the MODEL (the list and the group among them, ``layer_types`` and
``rope_parameters``, as JSON text; unset, they are Mellum2-12B-A2.5B's).
Four more say what of it is HELD in this process, the whole model by
default, or one chip's share of a deployment: ``--layers-held`` (the first
layers of the stack; the rest lie on further pipeline stages),
``--attention-shares`` (query heads divided that many ways with their KV
heads, at least one), ``--num-experts-held`` with ``--first-expert-held``
(the layer routes over all experts and computes the held ones' part).
``--router-balancing batch_bias`` is a rule of TRAINING the published keys do
not state (``modules/gated_moe.py``): without it the chosen set is the top
scores', as the published model computes it.

Embedding, head, building and the memory arguments are ``models/
hybrid_lm.py``'s; the model logs its routing stats and the bands' key
counts.
"""

import json

import flax.linen as nn

from unicore_tpu.models import register_model
from unicore_tpu.models.hybrid_lm import (
    HybridLM,
    held_attention,
    parsed,
    register_architecture,
    shares_divide,
)
from unicore_tpu.modules.latent_moe import route_log

#: Mellum2-12B-A2.5B-Instruct's 28 layers and its two rotary tables
MELLUM2_LAYER_TYPES = json.dumps(
    (["sliding_attention"] * 3 + ["full_attention"]) * 7)
MELLUM2_ROPE_PARAMETERS = json.dumps({
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
})


@register_model("mellum")
class MellumModel(HybridLM):
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    layer_types: str = MELLUM2_LAYER_TYPES
    mlp_layer_types: str = ""          # empty: every layer "sparse"
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_parameters: str = MELLUM2_ROPE_PARAMETERS
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    use_sliding_window: bool = True
    # published and not used here: the dense width no layer has, the
    # largest context, the window layers' count in another family's sense
    intermediate_size: int = 7168
    max_position_embeddings: int = 131072
    max_window_layers: int = 0
    # what is held
    layers_held: int = 0
    attention_shares: int = 1
    num_experts_held: int = 0
    first_expert_held: int = 0
    # training's load-balancing rule (modules/gated_moe.BALANCINGS)
    router_balancing: str = "none"

    GROUPS = ("layer_types", "mlp_layer_types", "rope_parameters")
    HELP = dict(mlp_layer_types="JSON list; every entry has to be sparse")

    def check(self):
        kinds = parsed(self.layer_types)
        layers = self.layers_held or self.num_hidden_layers
        if (len(kinds) != self.num_hidden_layers or layers > len(kinds)
                or set(kinds) - {"sliding_attention", "full_attention"}):
            raise ValueError(
                f"layer_types names {len(kinds)} layers of kinds "
                f"{sorted(set(kinds))}; the model has "
                f"{self.num_hidden_layers}, of which {layers} are held"
            )
        not_built = dict(
            mlp_layer_types=set(parsed(self.mlp_layer_types or "[]"))
            - {"sparse"},
            hidden_act=self.hidden_act != "silu",
            attention_bias=self.attention_bias,
            tie_word_embeddings=self.tie_word_embeddings,
            use_sliding_window=not self.use_sliding_window,
        )
        if any(not_built.values()):
            raise ValueError(
                "mellum is built with sparse layers, silu, no attention "
                "bias, an untied head and its sliding window; asked "
                f"otherwise: {[k for k, v in not_built.items() if v]}"
            )
        if not shares_divide(self.attention_shares, self.num_attention_heads,
                             self.num_key_value_heads):
            raise ValueError(
                f"--attention-shares {self.attention_shares} does not "
                f"divide {self.num_attention_heads} query heads on "
                f"{self.num_key_value_heads} KV heads"
            )

    @property
    def pattern(self):
        """The held layers in ``HybridDecoder``'s characters."""
        kinds = parsed(self.layer_types)
        held = kinds[:self.layers_held or self.num_hidden_layers]
        return "".join(
            ("S" if k == "sliding_attention" else "G") + "R" for k in held)

    def layers(self):
        rope = parsed(self.rope_parameters)
        attention = held_attention(
            self.num_attention_heads, self.num_key_value_heads,
            self.attention_shares, head_dim=self.head_dim)
        return dict(norm_eps=self.rms_norm_eps, sizes={
            "S": dict(attention, window=self.sliding_window,
                      rope=rope["sliding_attention"]),
            "G": dict(attention, rope=rope["full_attention"]),
            "R": dict(
                expert_dim=self.moe_intermediate_size,
                n_routed=self.num_experts, top_k=self.num_experts_per_tok,
                n_held=self.num_experts_held,
                first_held=self.first_expert_held,
                norm_topk_prob=self.norm_topk_prob,
                balancing=self.router_balancing,
            ),
        })

    @nn.nowrap
    def logged(self, stats, rows, length):
        return {**route_log(stats), **self.band_counts(rows, length)}


#: unset sizes default to Mellum2-12B-A2.5B-Instruct's, whole
mellum_base_architecture = register_architecture("mellum", "mellum")

#: every mechanism at a size a CPU test holds: two sliding layers and a
#: full one, a window of 16, a YaRN table whose original context is 32
#: positions, four query heads on two KV heads of 16, eight experts two a
#: token, of which any number may be held
mellum_tiny_architecture = register_architecture(
    "mellum", "mellum_tiny", dict(
        hidden_size=64, num_hidden_layers=3,
        layer_types=json.dumps(["sliding_attention"] * 2 + ["full_attention"]),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=48, loss_chunk=32,
        rope_parameters=json.dumps({
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 100, "factor": 4,
                "original_max_position_embeddings": 32, "beta_fast": 4,
                "beta_slow": 1},
            "sliding_attention": {"rope_type": "default", "rope_theta": 100},
        }),
    ))
