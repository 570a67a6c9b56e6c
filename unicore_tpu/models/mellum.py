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

The loss does not need all logits at once: ``features_only=True`` returns
the final hidden states with the routing stats and the band's key counts,
and ``lm_cross_entropy`` runs head and loss over ``--loss-chunk`` tokens at
a time.
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


def _parsed(value):
    """A list or group given as such, or as JSON text (the command line's
    and the benchmark's argument namespaces carry text)."""
    return json.loads(value) if isinstance(value, str) else value


@register_model("mellum")
class MellumModel(BaseUnicoreModel):
    vocab_size: int = 98304
    padding_idx: int = 0
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
    # memory
    remat: bool = True
    loss_chunk: int = 1024

    @classmethod
    def add_args(cls, parser):
        add = parser.add_argument
        for name in ("hidden-size", "num-hidden-layers", "num-attention-heads",
                     "num-key-value-heads", "head-dim", "sliding-window",
                     "num-experts", "num-experts-per-tok",
                     "moe-intermediate-size", "intermediate-size",
                     "max-position-embeddings", "max-window-layers"):
            add("--" + name, type=int)
        add("--layer-types", type=str,
            help="JSON list, one of sliding_attention / full_attention a "
                 "layer")
        add("--mlp-layer-types", type=str,
            help="JSON list; every entry has to be sparse")
        add("--rope-parameters", type=str,
            help="JSON group with a full_attention and a sliding_attention "
                 "rotary table (rope_type default or yarn)")
        add("--norm-topk-prob", type=utils.str_to_bool)
        add("--rms-norm-eps", type=float)
        add("--hidden-act", type=str)
        add("--attention-bias", type=utils.str_to_bool)
        add("--tie-word-embeddings", type=utils.str_to_bool)
        add("--use-sliding-window", type=utils.str_to_bool)
        add("--layers-held", type=int,
            help="layers held here, from the first (0: all)")
        add("--attention-shares", type=int,
            help="the query heads are divided this many ways, with their KV "
                 "heads (at least one), and this process holds one share")
        add("--num-experts-held", type=int,
            help="experts held here (0: all): the layer routes over all of "
                 "them and computes the held ones' part")
        add("--first-expert-held", type=int)
        add("--router-balancing", type=str, choices=BALANCINGS,
            help="how the chosen set is balanced over the experts: none "
                 "(the top scores, as published) or batch_bias (loss-free "
                 "balancing's bias on the scores that choose, solved anew "
                 "on every batch; the weights still the scores')")
        add("--remat", type=utils.str_to_bool,
            help="rematerialize each layer in the backward pass")
        add("--loss-chunk", type=int,
            help="tokens per chunk of the output head and loss (0: all "
                 "logits at once)")

    @classmethod
    def build_model(cls, args, task):
        mellum_base_architecture(args)
        for key in ("layer_types", "mlp_layer_types", "rope_parameters"):
            value = getattr(args, key)
            if not isinstance(value, str):  # a namespace made from a config
                setattr(args, key, json.dumps(value))
        kinds = _parsed(args.layer_types)
        layers = args.layers_held or args.num_hidden_layers
        if (len(kinds) != args.num_hidden_layers or layers > len(kinds)
                or set(kinds) - {"sliding_attention", "full_attention"}):
            raise ValueError(
                f"layer_types names {len(kinds)} layers of kinds "
                f"{sorted(set(kinds))}; the model has "
                f"{args.num_hidden_layers}, of which {layers} are held"
            )
        not_built = dict(
            mlp_layer_types=set(_parsed(args.mlp_layer_types or "[]"))
            - {"sparse"},
            hidden_act=args.hidden_act != "silu",
            attention_bias=args.attention_bias,
            tie_word_embeddings=args.tie_word_embeddings,
            use_sliding_window=not args.use_sliding_window,
        )
        if any(not_built.values()):
            raise ValueError(
                "mellum is built with sparse layers, silu, no attention "
                "bias, an untied head and its sliding window; asked "
                f"otherwise: {[k for k, v in not_built.items() if v]}"
            )
        n = args.attention_shares
        if n < 1 or args.num_attention_heads % n or (
                args.num_attention_heads // n) % max(
                    1, args.num_key_value_heads // n):
            raise ValueError(
                f"--attention-shares {n} does not divide "
                f"{args.num_attention_heads} query heads on "
                f"{args.num_key_value_heads} KV heads"
            )
        fields = {f: getattr(args, f) for f in cls.__dataclass_fields__
                  if hasattr(args, f) and f not in ("name", "parent")}
        fields.update(vocab_size=len(task.dictionary),
                      padding_idx=task.dictionary.pad())
        return cls(**fields)

    @property
    def pattern(self):
        """The held layers in ``HybridDecoder``'s characters."""
        kinds = _parsed(self.layer_types)
        held = kinds[:self.layers_held or self.num_hidden_layers]
        return "".join(
            ("S" if k == "sliding_attention" else "G") + "R" for k in held)

    def setup(self):
        self.embed_tokens = nn.Embed(
            self.vocab_size, self.hidden_size, embedding_init=_init,
            name="embed_tokens", param_dtype=jnp.float32,
        )
        n = self.attention_shares
        rope = _parsed(self.rope_parameters)
        attention = dict(
            num_heads=self.num_attention_heads // n,
            # fewer KV heads than shares: the shares of one KV head's
            # query heads each hold a copy of it
            num_kv_heads=max(1, self.num_key_value_heads // n),
            head_dim=self.head_dim,
        )
        self.decoder = HybridDecoder(
            pattern=self.pattern,
            embed_dim=self.hidden_size,
            norm_eps=self.rms_norm_eps,
            window_attention=dict(
                attention, window=self.sliding_window,
                rope=rope["sliding_attention"]),
            full_attention=dict(attention, rope=rope["full_attention"]),
            gated_moe=dict(
                expert_dim=self.moe_intermediate_size,
                n_routed=self.num_experts, top_k=self.num_experts_per_tok,
                n_held=self.num_experts_held,
                first_held=self.first_expert_held,
                norm_topk_prob=self.norm_topk_prob,
                balancing=self.router_balancing,
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
        maps the kernels are handed (the row padded to the kernels' 128
        tile, their default blocks): summed over the batch's rows and over
        the layers of each kind, per head, the (query, key) pairs the
        kernels score and the pairs a query may see."""
        padded = length + (-length) % 128
        out = {"band_rows": 1}
        for name, kind, window in (("window", "S", self.sliding_window),
                                   ("full", "G", None)):
            layers = self.pattern.count(kind)
            computed, visible = band_counts(Band(window), padded, padded)
            out.update({
                f"band_{name}_keys_computed": layers * computed,
                f"band_{name}_keys_visible": layers * visible,
                f"band_{name}_layers": layers,
            })
        return {k: jnp.asarray(rows * v, jnp.float32) for k, v in out.items()}

    def init_params(self, rng, sample):
        src_tokens = jnp.asarray(sample["net_input"]["src_tokens"])
        return strip_diagnostic_collections(
            self.init({"params": rng}, src_tokens, train=False)
        )


@register_model_architecture("mellum", "mellum")
def mellum_base_architecture(args):
    """Unset sizes default to Mellum2-12B-A2.5B-Instruct's, whole."""
    for field, default in MellumModel.__dataclass_fields__.items():
        if field in ("name", "parent", "vocab_size", "padding_idx"):
            continue
        if getattr(args, field, None) is None:
            setattr(args, field, default.default)


@register_model_architecture("mellum", "mellum_tiny")
def mellum_tiny_architecture(args):
    """Every mechanism at a size a CPU test holds: two sliding layers and
    a full one, a window of 16, a YaRN table whose original context is 32
    positions, four query heads on two KV heads of 16, eight experts two a
    token, of which any number may be held."""
    tiny = dict(
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
    )
    for field, value in tiny.items():
        if getattr(args, field, None) is None:
            setattr(args, field, value)
    mellum_base_architecture(args)
