"""Hybrid state-space / attention / mixture-of-experts causal LM
(``model_type: nemotron_h``, e.g. NVIDIA-Nemotron-3-Super-120B-A12B): a
token embedding, :class:`~unicore_tpu.modules.hybrid_decoder.HybridDecoder`
over ``--hybrid-override-pattern``, and an untied output head.  No
positional term anywhere: the Mamba layers carry order, and
``nemotron_h``'s attention applies no rotary embedding.

Arguments carry the names of the published ``config.json`` keys and state
the MODEL.  Three more say what of it is HELD in this process, the whole
model by default, or one chip's share of a deployment (docs/hybrid_lm.md
says how a share maps to one): ``--pattern-held`` (the layers),
``--mixer-shares`` (the mixers' heads divided that many ways) and
``--n-routed-experts-held``.

The loss does not need all logits at once: ``features_only=True`` returns
the final hidden states and the routing stats, and ``lm_cross_entropy``
runs head and loss over ``--loss-chunk`` tokens at a time.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu import utils
from unicore_tpu.models import register_model, register_model_architecture
from unicore_tpu.models.unicore_model import (
    BaseUnicoreModel,
    strip_diagnostic_collections,
)
from unicore_tpu.modules.hybrid_decoder import KINDS, HybridDecoder
from unicore_tpu.modules.latent_moe import STATS

_init = nn.initializers.normal(0.02)

#: NVIDIA-Nemotron-3-Super-120B-A12B's 88 layers
SUPER_120B_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
)


@register_model("nemotron_h")
class NemotronHModel(BaseUnicoreModel):
    vocab_size: int = 131072
    padding_idx: int = 0
    hidden_size: int = 4096
    hybrid_override_pattern: str = SUPER_120B_PATTERN
    pattern_held: str = ""
    mixer_shares: int = 1
    layer_norm_epsilon: float = 1e-5
    # Mamba-2 mixers
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # LatentMoE
    n_routed_experts: int = 512
    n_routed_experts_held: int = 0
    first_routed_expert_held: int = 0
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    # memory
    remat: bool = True
    loss_chunk: int = 1024

    @classmethod
    def add_args(cls, parser):
        add = parser.add_argument
        add("--hidden-size", type=int)
        add("--hybrid-override-pattern", type=str,
            help="one character per layer: M Mamba-2, * attention, "
                 "E LatentMoE")
        add("--pattern-held", type=str,
            help="the layers held here, in the pattern's characters (a "
                 "stretch of --hybrid-override-pattern; empty: all of it)")
        add("--mixer-shares", type=int,
            help="the mixers' heads are divided this many ways and this "
                 "process holds one share: 1/N of the Mamba-2 heads and "
                 "B/C groups and of the query heads, with their KV heads "
                 "(at least one)")
        add("--layer-norm-epsilon", type=float)
        add("--mamba-num-heads", type=int)
        add("--mamba-head-dim", type=int)
        add("--n-groups", type=int, help="Mamba-2 B/C groups")
        add("--ssm-state-size", type=int)
        add("--conv-kernel", type=int)
        add("--chunk-size", type=int, help="tokens per chunk of the scan")
        add("--num-attention-heads", type=int)
        add("--num-key-value-heads", type=int)
        add("--head-dim", type=int)
        add("--n-routed-experts", type=int,
            help="routed experts of the model (the router's width)")
        add("--n-routed-experts-held", type=int,
            help="routed experts held here (0: all): the layer routes over "
                 "all of them and computes the held ones' part")
        add("--first-routed-expert-held", type=int)
        add("--num-experts-per-tok", type=int)
        add("--moe-latent-size", type=int)
        add("--moe-intermediate-size", type=int)
        add("--moe-shared-expert-intermediate-size", type=int)
        add("--routed-scaling-factor", type=float)
        add("--remat", type=utils.str_to_bool,
            help="rematerialize each layer in the backward pass")
        add("--loss-chunk", type=int,
            help="tokens per chunk of the output head and loss (0: all "
                 "logits at once)")

    @classmethod
    def build_model(cls, args, task):
        nemotron_h_base_architecture(args)
        bad = set(args.hybrid_override_pattern + args.pattern_held) - set(KINDS)
        if bad:
            raise ValueError(
                f"the layer pattern holds {sorted(bad)}; layer kinds are "
                f"{KINDS!r}"
            )
        n = args.mixer_shares
        if (n < 1 or args.mamba_num_heads % n or args.n_groups % n
                or args.num_attention_heads % n):
            raise ValueError(
                f"--mixer-shares {n} does not divide {args.mamba_num_heads} "
                f"Mamba heads in {args.n_groups} groups and "
                f"{args.num_attention_heads} query heads"
            )
        fields = {f: getattr(args, f) for f in cls.__dataclass_fields__
                  if hasattr(args, f) and f not in ("name", "parent")}
        fields.update(vocab_size=len(task.dictionary),
                      padding_idx=task.dictionary.pad())
        return cls(**fields)

    def setup(self):
        self.embed_tokens = nn.Embed(
            self.vocab_size, self.hidden_size, embedding_init=_init,
            name="embed_tokens", param_dtype=jnp.float32,
        )
        n = self.mixer_shares
        self.decoder = HybridDecoder(
            pattern=self.pattern_held or self.hybrid_override_pattern,
            embed_dim=self.hidden_size,
            norm_eps=self.layer_norm_epsilon,
            mamba=dict(
                num_heads=self.mamba_num_heads // n,
                head_dim=self.mamba_head_dim, n_groups=self.n_groups // n,
                state_size=self.ssm_state_size,
                conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
                norm_eps=self.layer_norm_epsilon,
            ),
            attention=dict(
                num_heads=self.num_attention_heads // n,
                # fewer KV heads than shares: the shares of one KV head's
                # query heads each hold a copy of it
                num_kv_heads=max(1, self.num_key_value_heads // n),
                head_dim=self.head_dim,
            ),
            moe=dict(
                latent_dim=self.moe_latent_size,
                expert_dim=self.moe_intermediate_size,
                shared_dim=self.moe_shared_expert_intermediate_size,
                n_routed=self.n_routed_experts,
                top_k=self.num_experts_per_tok,
                n_held=self.n_routed_experts_held,
                first_held=self.first_routed_expert_held,
                routed_scale=self.routed_scaling_factor,
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
            return x, {"moe_" + k: stats[i] for i, k in enumerate(STATS)}
        with jax.named_scope("lm_head"):
            return x @ self.lm_head.astype(x.dtype)

    def init_params(self, rng, sample):
        src_tokens = jnp.asarray(sample["net_input"]["src_tokens"])
        return strip_diagnostic_collections(
            self.init({"params": rng}, src_tokens, train=False)
        )


@register_model_architecture("nemotron_h", "nemotron_h")
def nemotron_h_base_architecture(args):
    """Unset sizes default to NVIDIA-Nemotron-3-Super-120B-A12B's, whole."""
    for field, default in NemotronHModel.__dataclass_fields__.items():
        if field in ("name", "parent", "vocab_size", "padding_idx"):
            continue
        if getattr(args, field, None) is None:
            setattr(args, field, default.default)


@register_model_architecture("nemotron_h", "nemotron_h_tiny")
def nemotron_h_tiny_architecture(args):
    """Every mechanism at a size a CPU test holds: attention, two repeats
    of an ``EM`` unit, 16 experts of which any number may be held."""
    tiny = dict(
        hidden_size=64, hybrid_override_pattern="*EMEM",
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=16, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
        moe_latent_size=32, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, loss_chunk=32,
    )
    for field, value in tiny.items():
        if getattr(args, field, None) is None:
            setattr(args, field, value)
    nemotron_h_base_architecture(args)
