"""Laguna (``model_type: laguna``, e.g. poolside/Laguna-S-2.1): a decoder
whose every layer is head-gated grouped-KV attention with rotary positions,
then a feed-forward layer that is dense on the layers ``mlp_layer_types``
/ ``mlp_only_layers`` say and routed gated experts beside a shared expert
on the others.

    x = x + Attn(RMSNorm(x));   x = x + MLP(RMSNorm(x))

``layer_types`` says each layer's attention: ``sliding_attention`` (the
last ``sliding_window`` positions up to the query's own) or
``full_attention`` (the whole row), each with its own
``rope_parameters`` group, which may rotate part of a head only
(``partial_rotary_factor``), and each with ITS OWN NUMBER of query heads
(``num_attention_heads_per_layer``) on the same ``num_key_value_heads``.
Each head's weighted sum is scaled by a gate of its own for every token
(``gating: per-head``; ``modules/multihead_attention.
GroupedQueryAttention(gate=True)``).  Both kinds run under a band the
blockwise kernels mask themselves, so no ``(L, L)`` mask exists at any
length.  A ``dense`` layer is a gated SiLU MLP of ``intermediate_size``; a
``sparse`` one ``num_experts`` gated SiLU experts of
``moe_intermediate_size``, ``num_experts_per_tok`` a token by a softmax
router whose chosen scores are renormalised (``norm_topk_prob``) and then
scaled by ``moe_routed_scaling_factor``, plus one shared expert of
``shared_expert_intermediate_size`` that every token passes, unweighted
(``modules/gated_moe.py``).  An untied head.  The layers run through
:class:`~unicore_tpu.modules.hybrid_decoder.HybridDecoder` as two
characters a layer, ``S`` / ``G`` then ``F`` / ``R``, each block
rematerialized in the backward pass.

Arguments carry the names of the published ``config.json`` keys and state
the MODEL (lists and the group as JSON text; unset, they are
Laguna-S-2.1's).  Four more say what of it is HELD in this process, the
whole model by default, or one chip's share of a deployment, as ``mellum``
has them: ``--layers-held``, ``--attention-shares`` (every layer's query
heads divided that many ways with their KV heads, at least one),
``--num-experts-held`` with ``--first-expert-held``.  The shared expert and
a dense layer's MLP are never divided: every share computes them alike.
``--router-balancing batch_bias`` is a rule of TRAINING the published keys
do not state (``modules/gated_moe.py``).

Embedding, head, building and the memory arguments are ``models/
hybrid_lm.py``'s; the model logs its routing stats, the bands' key counts
and, because its two kinds of layer differ there, each kind's held heads.
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

#: Laguna-S-2.1's 48 layers: a full layer, then three sliding ones; the
#: first layer dense; 48 query heads on a full layer, 72 on a sliding one
_PERIOD = ["full_attention"] + ["sliding_attention"] * 3
LAGUNA_LAYER_TYPES = json.dumps(_PERIOD * 12)
LAGUNA_MLP_LAYER_TYPES = json.dumps(["dense"] + ["sparse"] * 47)
LAGUNA_HEADS_PER_LAYER = json.dumps([48, 72, 72, 72] * 12)
LAGUNA_GATING_TYPES = json.dumps(["per_head"] * 48)
LAGUNA_ROPE_PARAMETERS = json.dumps({
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
})

#: the arguments that are lists or groups, given as JSON text
GROUPS = ("layer_types", "mlp_layer_types", "mlp_only_layers",
          "gating_types", "num_attention_heads_per_layer", "rope_parameters")


@register_model("laguna")
class LagunaModel(HybridLM):
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    layer_types: str = LAGUNA_LAYER_TYPES
    mlp_layer_types: str = LAGUNA_MLP_LAYER_TYPES
    mlp_only_layers: str = "[0]"
    decoder_sparse_step: int = 1
    num_attention_heads: int = 48
    num_attention_heads_per_layer: str = LAGUNA_HEADS_PER_LAYER
    num_key_value_heads: int = 8
    head_dim: int = 128
    gating: str = "per-head"
    gating_types: str = LAGUNA_GATING_TYPES
    sliding_window: int = 512
    rope_parameters: str = LAGUNA_ROPE_PARAMETERS
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    moe_router_logit_softcapping: float = 0.0
    moe_apply_router_weight_on_input: bool = False
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # published and not used here: the largest context
    max_position_embeddings: int = 1048576
    # what is held
    layers_held: int = 0
    attention_shares: int = 1
    num_experts_held: int = 0
    first_expert_held: int = 0
    # training's load-balancing rule (modules/gated_moe.BALANCINGS)
    router_balancing: str = "none"
    # memory
    mlp_row_chunk: int = 0

    GROUPS = GROUPS
    HELP = dict(
        mlp_layer_types="JSON list, one of dense / sparse a layer",
        mlp_only_layers="JSON list of the dense layers' indices (has to "
                        "agree with --mlp-layer-types)",
        num_attention_heads_per_layer="JSON list of each layer's query "
                                      "heads; the layers of one kind have "
                                      "to agree",
        gating_types="JSON list; every entry has to be per_head",
    )

    def check(self):
        self.held  # raises on what the program does not build

    @property
    def held(self):
        """The held layers: ``(attention kinds, mlp kinds, {attention kind:
        query heads of the whole layer})``, checked against everything
        the arguments state."""
        kinds = parsed(self.layer_types)
        mlps = parsed(self.mlp_layer_types)
        per_layer = parsed(self.num_attention_heads_per_layer)
        n_layers = self.num_hidden_layers
        held = self.layers_held or n_layers
        if (not len(kinds) == len(mlps) == len(per_layer) == n_layers
                or held > n_layers
                or set(kinds) - {"sliding_attention", "full_attention"}
                or set(mlps) - {"dense", "sparse"}):
            raise ValueError(
                f"layer_types ({len(kinds)}: {sorted(set(kinds))}), "
                f"mlp_layer_types ({len(mlps)}: {sorted(set(mlps))}) and "
                f"num_attention_heads_per_layer ({len(per_layer)}) each "
                f"name every one of {n_layers} layers, of which {held} "
                "are held"
            )
        dense = sorted(i for i, m in enumerate(mlps) if m == "dense")
        not_built = dict(
            mlp_only_layers=sorted(parsed(self.mlp_only_layers)) != dense,
            decoder_sparse_step=self.decoder_sparse_step != 1,
            gating=self.gating != "per-head",
            gating_types=set(parsed(self.gating_types)) - {"per_head"},
            moe_router_logit_softcapping=self.moe_router_logit_softcapping,
            moe_apply_router_weight_on_input=(
                self.moe_apply_router_weight_on_input),
            attention_bias=self.attention_bias,
            tie_word_embeddings=self.tie_word_embeddings,
        )
        if any(not_built.values()):
            raise ValueError(
                "laguna is built with mlp_only_layers the dense entries of "
                "mlp_layer_types, a sparse step of 1, a per-head gate on "
                "every layer, router weights on the output with no soft "
                "cap, no attention bias and an untied head; asked "
                f"otherwise: {[k for k, v in not_built.items() if v]}"
            )
        heads = {}
        n, KV = self.attention_shares, self.num_key_value_heads
        for kind in sorted(set(kinds[:held])):
            counts = {h for k, h in zip(kinds[:held], per_layer) if k == kind}
            (H,) = counts if len(counts) == 1 else (0,)
            if not H or not shares_divide(n, H, KV):
                raise ValueError(
                    f"the held {kind} layers have {sorted(counts)} query "
                    f"heads on {KV} KV heads: one count a kind, which "
                    f"--attention-shares {n} has to divide"
                )
            heads[kind] = H
        return kinds[:held], mlps[:held], heads

    @property
    def pattern(self):
        """The held layers in ``HybridDecoder``'s characters."""
        kinds, mlps, _ = self.held
        return "".join(
            ("S" if k == "sliding_attention" else "G")
            + ("F" if m == "dense" else "R") for k, m in zip(kinds, mlps))

    def held_heads(self, kind):
        """The query heads this process holds on a layer of ``kind`` (0
        where it holds no such layer)."""
        return self.held[2].get(kind, 0) // self.attention_shares

    def layers(self):
        rope = parsed(self.rope_parameters)
        _, _, heads = self.held  # a whole layer's (no layer of a kind: 0)
        attention = lambda kind: held_attention(
            heads.get(kind, 0), self.num_key_value_heads,
            self.attention_shares, head_dim=self.head_dim, rope=rope[kind],
            gate=True)
        return dict(norm_eps=self.rms_norm_eps, sizes={
            "S": dict(attention("sliding_attention"),
                      window=self.sliding_window),
            "G": attention("full_attention"),
            "F": dict(ffn_dim=self.intermediate_size,
                      row_chunk=self.mlp_row_chunk),
            "R": dict(
                expert_dim=self.moe_intermediate_size,
                n_routed=self.num_experts, top_k=self.num_experts_per_tok,
                n_held=self.num_experts_held,
                first_held=self.first_expert_held,
                norm_topk_prob=self.norm_topk_prob,
                balancing=self.router_balancing,
                routed_scale=self.moe_routed_scaling_factor,
                shared_dim=self.shared_expert_intermediate_size,
            ),
        })

    def band_heads(self):
        return {"window": self.held_heads("sliding_attention"),
                "full": self.held_heads("full_attention")}

    @nn.nowrap
    def logged(self, stats, rows, length):
        return {**route_log(stats), **self.band_counts(rows, length)}


#: unset sizes default to Laguna-S-2.1's, whole
laguna_base_architecture = register_architecture("laguna", "laguna")

_TINY_KINDS = ["full_attention", "sliding_attention", "sliding_attention",
               "full_attention"]

#: every mechanism at a size a CPU test holds: a full layer with a dense
#: MLP, two sliding layers and a full one with experts; 4 query heads on a
#: full layer and 6 on a sliding one, on two KV heads of 16; a window of
#: 16; a YaRN table over half of each head whose original context is 32
#: positions; eight experts two a token, of which any number may be held,
#: beside a shared expert; the routed sum scaled by 2.5
laguna_tiny_architecture = register_architecture(
    "laguna", "laguna_tiny", dict(
        hidden_size=64, intermediate_size=96, num_hidden_layers=4,
        layer_types=json.dumps(_TINY_KINDS),
        mlp_layer_types=json.dumps(["dense"] + ["sparse"] * 3),
        gating_types=json.dumps(["per_head"] * 4),
        num_attention_heads=4,
        num_attention_heads_per_layer=json.dumps(
            [4 if k == "full_attention" else 6 for k in _TINY_KINDS]),
        num_key_value_heads=2, head_dim=16, sliding_window=16,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=48,
        shared_expert_intermediate_size=40, loss_chunk=32, mlp_row_chunk=32,
        rope_parameters=json.dumps({
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 100, "factor": 4,
                "original_max_position_embeddings": 32, "beta_fast": 4,
                "beta_slow": 1, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                                  "partial_rotary_factor": 1},
        }),
    ))
