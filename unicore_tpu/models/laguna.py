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

The loss does not need all logits at once: ``features_only=True`` returns
the final hidden states with the routing stats and the band's key and
head counts, and ``lm_cross_entropy`` runs head and loss over
``--loss-chunk`` tokens at a time.
"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu import utils
from unicore_tpu.models import register_model, register_model_architecture
from unicore_tpu.models.unicore_model import (
    BaseUnicoreModel,
    strip_diagnostic_collections,
)
from unicore_tpu.modules.gated_moe import BALANCINGS
from unicore_tpu.modules.hybrid_decoder import HybridDecoder
from unicore_tpu.modules.latent_moe import STATS
from unicore_tpu.ops.flash_attention import Band, band_counts

_init = nn.initializers.normal(0.02)

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


def _parsed(value):
    """A list or group given as such, or as JSON text (the command line's
    and the benchmark's argument namespaces carry text)."""
    return json.loads(value) if isinstance(value, str) else value


@register_model("laguna")
class LagunaModel(BaseUnicoreModel):
    vocab_size: int = 100352
    padding_idx: int = 0
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
    remat: bool = True
    loss_chunk: int = 1024
    mlp_row_chunk: int = 0

    @classmethod
    def add_args(cls, parser):
        add = parser.add_argument
        for name in ("hidden-size", "intermediate-size", "num-hidden-layers",
                     "decoder-sparse-step", "num-attention-heads",
                     "num-key-value-heads", "head-dim", "sliding-window",
                     "num-experts", "num-experts-per-tok",
                     "moe-intermediate-size",
                     "shared-expert-intermediate-size",
                     "max-position-embeddings"):
            add("--" + name, type=int)
        add("--layer-types", type=str,
            help="JSON list, one of sliding_attention / full_attention a "
                 "layer")
        add("--mlp-layer-types", type=str,
            help="JSON list, one of dense / sparse a layer")
        add("--mlp-only-layers", type=str,
            help="JSON list of the dense layers' indices (has to agree with "
                 "--mlp-layer-types)")
        add("--num-attention-heads-per-layer", type=str,
            help="JSON list of each layer's query heads; the layers of one "
                 "kind have to agree")
        add("--gating", type=str)
        add("--gating-types", type=str,
            help="JSON list; every entry has to be per_head")
        add("--rope-parameters", type=str,
            help="JSON group with a full_attention and a sliding_attention "
                 "rotary table (rope_type default or yarn, each with an "
                 "optional partial_rotary_factor)")
        add("--norm-topk-prob", type=utils.str_to_bool)
        add("--moe-routed-scaling-factor", type=float)
        add("--moe-router-logit-softcapping", type=float)
        add("--moe-apply-router-weight-on-input", type=utils.str_to_bool)
        add("--rms-norm-eps", type=float)
        add("--attention-bias", type=utils.str_to_bool)
        add("--tie-word-embeddings", type=utils.str_to_bool)
        add("--layers-held", type=int,
            help="layers held here, from the first (0: all)")
        add("--attention-shares", type=int,
            help="every layer's query heads are divided this many ways, "
                 "with their KV heads (at least one), and this process "
                 "holds one share")
        add("--num-experts-held", type=int,
            help="experts held here (0: all): a sparse layer routes over "
                 "all of them and computes the held ones' part")
        add("--first-expert-held", type=int)
        add("--router-balancing", type=str, choices=BALANCINGS,
            help="how the chosen set is balanced over the experts: none "
                 "(the top scores, as published) or batch_bias "
                 "(modules/gated_moe.py)")
        add("--remat", type=utils.str_to_bool,
            help="rematerialize each layer in the backward pass")
        add("--loss-chunk", type=int,
            help="tokens per chunk of the output head and loss (0: all "
                 "logits at once)")
        add("--mlp-row-chunk", type=int,
            help="rows per chunk of a dense layer's MLP (0: the whole row)")

    @classmethod
    def build_model(cls, args, task):
        laguna_base_architecture(args)
        for key in GROUPS:
            value = getattr(args, key)
            if not isinstance(value, str):  # a namespace made from a config
                setattr(args, key, json.dumps(value))
        fields = {f: getattr(args, f) for f in cls.__dataclass_fields__
                  if hasattr(args, f) and f not in ("name", "parent")}
        fields.update(vocab_size=len(task.dictionary),
                      padding_idx=task.dictionary.pad())
        model = cls(**fields)
        model.held  # raises on what the program does not build
        return model

    @property
    def held(self):
        """The held layers: ``(attention kinds, mlp kinds, {attention kind:
        query heads of the whole layer})``, checked against everything
        the arguments state."""
        kinds = _parsed(self.layer_types)
        mlps = _parsed(self.mlp_layer_types)
        per_layer = _parsed(self.num_attention_heads_per_layer)
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
            mlp_only_layers=sorted(_parsed(self.mlp_only_layers)) != dense,
            decoder_sparse_step=self.decoder_sparse_step != 1,
            gating=self.gating != "per-head",
            gating_types=set(_parsed(self.gating_types)) - {"per_head"},
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
            if n < 1 or not H or H % n or (H // n) % max(1, KV // n):
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

    def setup(self):
        self.embed_tokens = nn.Embed(
            self.vocab_size, self.hidden_size, embedding_init=_init,
            name="embed_tokens", param_dtype=jnp.float32,
        )
        rope = _parsed(self.rope_parameters)
        attention = lambda kind: dict(
            num_heads=self.held_heads(kind),
            # fewer KV heads than shares: the shares of one KV head's
            # query heads each hold a copy of it
            num_kv_heads=max(
                1, self.num_key_value_heads // self.attention_shares),
            head_dim=self.head_dim, rope=rope[kind], gate=True,
        )
        self.decoder = HybridDecoder(
            pattern=self.pattern,
            embed_dim=self.hidden_size,
            norm_eps=self.rms_norm_eps,
            window_attention=dict(
                attention("sliding_attention"), window=self.sliding_window),
            full_attention=attention("full_attention"),
            mlp=dict(ffn_dim=self.intermediate_size,
                     row_chunk=self.mlp_row_chunk),
            gated_moe=dict(
                expert_dim=self.moe_intermediate_size,
                n_routed=self.num_experts, top_k=self.num_experts_per_tok,
                n_held=self.num_experts_held,
                first_held=self.first_expert_held,
                norm_topk_prob=self.norm_topk_prob,
                balancing=self.router_balancing,
                routed_scale=self.moe_routed_scaling_factor,
                shared_dim=self.shared_expert_intermediate_size,
            ),
            remat=self.remat,
            name="decoder",
        )
        self.lm_head = self.param(
            "lm_head", _init, (self.hidden_size, self.vocab_size), jnp.float32
        )

    def __call__(self, src_tokens, train: bool = False,
                 features_only: bool = False, **kwargs):
        x, stats = self.decoder(self.embed_tokens(src_tokens))
        if features_only:
            extra = {"moe_" + k: stats[i] for i, k in enumerate(STATS)}
            extra.update(self.band_counts(*src_tokens.shape))
            return x, extra
        with jax.named_scope("lm_head"):
            return x @ self.lm_head.astype(x.dtype)

    def band_counts(self, rows, length):
        """What the loss logs of the two bands' work, from shapes and the
        maps the kernels are handed (``models/mellum.py`` has the same
        stats, per row and HEAD); and, because the two kinds differ in
        their heads here, the query heads held on a layer of each kind."""
        padded = length + (-length) % 128
        out = {"band_rows": 1}
        for name, kind, window in (
                ("window", "sliding_attention", self.sliding_window),
                ("full", "full_attention", None)):
            layers = self.held[0].count(kind)
            computed, visible = band_counts(Band(window), padded, padded)
            out.update({
                f"band_{name}_keys_computed": layers * computed,
                f"band_{name}_keys_visible": layers * visible,
                f"band_{name}_layers": layers,
                f"band_{name}_heads": self.held_heads(kind),
            })
        return {k: jnp.asarray(rows * v, jnp.float32) for k, v in out.items()}

    def init_params(self, rng, sample):
        src_tokens = jnp.asarray(sample["net_input"]["src_tokens"])
        return strip_diagnostic_collections(
            self.init({"params": rng}, src_tokens, train=False)
        )


@register_model_architecture("laguna", "laguna")
def laguna_base_architecture(args):
    """Unset sizes default to Laguna-S-2.1's, whole."""
    for field, default in LagunaModel.__dataclass_fields__.items():
        if field in ("name", "parent", "vocab_size", "padding_idx"):
            continue
        if getattr(args, field, None) is None:
            setattr(args, field, default.default)


@register_model_architecture("laguna", "laguna_tiny")
def laguna_tiny_architecture(args):
    """Every mechanism at a size a CPU test holds: a full layer with a
    dense MLP, two sliding layers and a full one with experts; 4 query
    heads on a full layer and 6 on a sliding one, on two KV heads of 16; a
    window of 16; a YaRN table over half of each head whose original
    context is 32 positions; eight experts two a token, of which any
    number may be held, beside a shared expert; the routed sum scaled by
    2.5."""
    kinds = ["full_attention", "sliding_attention", "sliding_attention",
             "full_attention"]
    tiny = dict(
        hidden_size=64, intermediate_size=96, num_hidden_layers=4,
        layer_types=json.dumps(kinds),
        mlp_layer_types=json.dumps(["dense"] + ["sparse"] * 3),
        gating_types=json.dumps(["per_head"] * 4),
        num_attention_heads=4,
        num_attention_heads_per_layer=json.dumps(
            [4 if k == "full_attention" else 6 for k in kinds]),
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
    )
    for field, value in tiny.items():
        if getattr(args, field, None) is None:
            setattr(args, field, value)
    laguna_base_architecture(args)
