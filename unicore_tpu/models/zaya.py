"""ZAYA1 (``model_type: zaya``, e.g. Zyphra/ZAYA1-8B): a decoder whose every
``hybrid`` layer is an attention sublayer in a compressed,
convolution-mixed latent (CCA, arXiv:2510.04476) and an expert sublayer
whose router is a small network with a state carried from layer to layer
and a skip expert (arXiv:2511.17127), each merged into the stream by
learned per-channel vectors, under a head that is the embedding.

    x = s_x (x + b_x) + s_f (CCA(RMSNorm(x)) + b_f)
    x, r = s_x' (x + b_x') + s_f' (MoE(RMSNorm(x), r) + b_f'),  r the router's state

``modules/cca.py`` and ``modules/zaya_moe.py`` state the two sublayers.
The layers run through :class:`~unicore_tpu.modules.hybrid_decoder.
HybridDecoder` as the pattern ``CZ`` per layer: ``Z`` carries the side
stream, the merge is ``ScaledMerge``, and the held layers are one repeated
unit, so a step holds one body of each kind under ``nn.scan``.  The head
is tied (``tie_word_embeddings``): the logits are ``x E^T`` and the loss
reads the embedding, whose gradient is the sum of both uses.

Arguments carry the names of the published ``config.json`` keys and state
the MODEL (``layer_types`` and ``rope_parameters`` as JSON text; unset,
they are ZAYA1-8B's).  What is HELD in this process, the whole model by
default or one chip's share of a deployment, is said as ``mellum`` says
it: ``--layers-held``, ``--attention-shares`` with ``--first-kv-head-held``
(the KV heads are divided that many ways with their query heads; WHICH are
held decides whose value is read one position late), ``--num-experts-held``
with ``--first-expert-held``.  ``--router-balancing batch_bias`` is a rule
of TRAINING the published keys do not state (``modules/gated_moe.py``),
here over the 17 columns: the published model adds a selection bias its
recipe's optimiser moves, which is not built.

What the keys can say and the program does not build raises: a
``layer_types`` entry other than ``hybrid`` or a ``sliding_window`` (the
``hybrid_sliding`` rotary table is then read by no layer), convolutions
over other than two positions, another activation, more than one expert a
token, a bias on attention or head.
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
from unicore_tpu.modules.zaya_moe import skip_log
from unicore_tpu.ops.flash_attention import band_log

#: ZAYA1-8B's 40 layers and its rotary tables (no layer reads the second)
ZAYA1_LAYER_TYPES = json.dumps(["hybrid"] * 40)
ZAYA1_ROPE_PARAMETERS = json.dumps({
    "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
               "rope_type": "default"},
    "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                       "rope_type": "default"},
    "rope_type": "default",
})


@register_model("zaya")
class ZayaModel(HybridLM):
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: str = ZAYA1_LAYER_TYPES
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_parameters: str = ZAYA1_ROPE_PARAMETERS
    sliding_window: int = 0            # published null
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    attention_bias: bool = False
    lm_head_bias: bool = False
    tie_word_embeddings: bool = True
    # published and not used here: the largest context
    max_position_embeddings: int = 131072
    # what is held
    layers_held: int = 0
    attention_shares: int = 1
    first_kv_head_held: int = 0
    num_experts_held: int = 0
    first_expert_held: int = 0
    # training's load-balancing rule (modules/gated_moe.BALANCINGS)
    router_balancing: str = "none"

    GROUPS = ("layer_types", "rope_parameters")
    HELP = dict(
        layer_types="JSON list; every entry has to be hybrid",
        rope_parameters="JSON group whose hybrid table every layer reads",
        first_kv_head_held="the first KV head of this process's share of "
                           "--attention-shares (a whole multiple of the "
                           "share's KV heads); the model's second half of "
                           "KV heads read their value one position late",
        first_expert_held="the first expert of --num-experts-held",
    )

    def check(self):
        kinds = parsed(self.layer_types)
        layers = self.layers_held or self.num_hidden_layers
        if (len(kinds) != self.num_hidden_layers or layers > len(kinds)
                or set(kinds) - {"hybrid"} or self.sliding_window):
            raise ValueError(
                f"layer_types names {len(kinds)} layers of kinds "
                f"{sorted(set(kinds))} with sliding_window "
                f"{self.sliding_window}; the model has "
                f"{self.num_hidden_layers} layers, of which {layers} are "
                "held, and only hybrid layers over the whole row are built"
            )
        rope = parsed(self.rope_parameters).get("hybrid", {})
        not_built = dict(
            cca_time0=self.cca_time0 != 2, cca_time1=self.cca_time1 != 2,
            hidden_act=self.hidden_act != "silu",
            attention_bias=self.attention_bias,
            lm_head_bias=self.lm_head_bias,
            num_experts_per_tok=self.num_experts_per_tok != 1,
            rope_parameters=rope.get("rope_type", "default") != "default"
            or "rope_theta" not in rope,
            partial_rotary_factor=rope.get(
                "partial_rotary_factor", 1.0) != self.partial_rotary_factor,
        )
        if any(not_built.values()):
            raise ValueError(
                "zaya is built with convolutions over two positions, silu, "
                "one expert a token, no bias on attention or head and a "
                "default hybrid rotary table that states the model's "
                "partial_rotary_factor; asked otherwise: "
                f"{[k for k, v in not_built.items() if v]}"
            )
        n, KV = self.attention_shares, self.num_key_value_heads
        if (not shares_divide(n, self.num_attention_heads, KV) or KV % n
                or self.first_kv_head_held % (KV // n)
                or not 0 <= self.first_kv_head_held < KV):
            raise ValueError(
                f"--attention-shares {n} from KV head "
                f"{self.first_kv_head_held} does not divide "
                f"{self.num_attention_heads} query heads on {KV} KV heads "
                "into whole KV heads"
            )

    @property
    def pattern(self):
        """The held layers in ``HybridDecoder``'s characters."""
        return "CZ" * (self.layers_held or self.num_hidden_layers)

    def layers(self):
        return dict(norm_eps=self.rms_norm_eps, scaled_merge=True, sizes={
            "C": held_attention(
                self.num_attention_heads, self.num_key_value_heads,
                self.attention_shares, head_dim=self.head_dim,
                kv_heads_model=self.num_key_value_heads,
                first_kv_head=self.first_kv_head_held,
                rope=parsed(self.rope_parameters)["hybrid"]),
            "Z": dict(
                expert_dim=self.moe_intermediate_size,
                n_routed=self.num_experts,
                router_dim=self.router_hidden_size,
                n_held=self.num_experts_held,
                first_held=self.first_expert_held,
                norm_eps=self.rms_norm_eps,
                balancing=self.router_balancing,
            ),
        })

    @nn.nowrap
    def logged(self, stats, rows, length):
        layers = {"window": 0, "full": self.pattern.count("C")}
        return {**route_log(stats), **skip_log(stats),
                **band_log(rows, length, None, layers)}


#: unset sizes default to ZAYA1-8B's, whole
zaya1_8b_architecture = register_architecture("zaya", "zaya1_8b")

#: every mechanism at a size a CPU test holds: three layers, four query
#: heads on two KV heads of 16 (the second's value one position late), a
#: rotary table over half a head, eight experts and the skip column behind
#: a router 24 wide, of which any number may be held
zaya_tiny_architecture = register_architecture(
    "zaya", "zaya_tiny", dict(
        hidden_size=64, num_hidden_layers=3,
        layer_types=json.dumps(["hybrid"] * 3),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, moe_intermediate_size=48, router_hidden_size=24,
        loss_chunk=32,
        rope_parameters=json.dumps({
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 100,
                       "rope_type": "default"}}),
    ))
