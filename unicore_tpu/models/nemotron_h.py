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

Embedding, head, building and the memory arguments are ``models/
hybrid_lm.py``'s; the model logs its expert layers' routing stats.

The published model's multi-token-prediction module
(``num_nextn_predict_layers``, ``mtp_hybrid_override_pattern``) is left out:
neither key is a field here.  ``modules/mtp.py`` is such a module (``joyai``
trains one), and the skeleton builds it for a decoder that states
``mtp_pattern`` and ``ahead``.
"""

import flax.linen as nn

from unicore_tpu.models import register_model
from unicore_tpu.models.hybrid_lm import (
    HybridLM,
    held_attention,
    register_architecture,
)
from unicore_tpu.modules.hybrid_decoder import KINDS
from unicore_tpu.modules.latent_moe import route_log

#: NVIDIA-Nemotron-3-Super-120B-A12B's 88 layers
SUPER_120B_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
)


@register_model("nemotron_h")
class NemotronHModel(HybridLM):
    vocab_size: int = 131072
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

    HELP = dict(
        hybrid_override_pattern="one character per layer: M Mamba-2, "
                                "* attention, E LatentMoE",
        pattern_held="the layers held here, in the pattern's characters (a "
                     "stretch of --hybrid-override-pattern; empty: all of "
                     "it)",
        mixer_shares="the mixers' heads are divided this many ways and this "
                     "process holds one share: 1/N of the Mamba-2 heads and "
                     "B/C groups and of the query heads, with their KV "
                     "heads (at least one)",
        n_groups="Mamba-2 B/C groups",
        chunk_size="tokens per chunk of the scan",
        n_routed_experts="routed experts of the model (the router's width)",
        n_routed_experts_held="routed experts held here (0: all): the layer "
                              "routes over all of them and computes the "
                              "held ones' part",
    )

    def check(self):
        bad = set(self.hybrid_override_pattern + self.pattern_held) - set(KINDS)
        if bad:
            raise ValueError(
                f"the layer pattern holds {sorted(bad)}; layer kinds are "
                f"{KINDS!r}"
            )
        n = self.mixer_shares
        if (n < 1 or self.mamba_num_heads % n or self.n_groups % n
                or self.num_attention_heads % n):
            raise ValueError(
                f"--mixer-shares {n} does not divide {self.mamba_num_heads} "
                f"Mamba heads in {self.n_groups} groups and "
                f"{self.num_attention_heads} query heads"
            )

    @property
    def pattern(self):
        return self.pattern_held or self.hybrid_override_pattern

    def layers(self):
        n = self.mixer_shares
        return dict(norm_eps=self.layer_norm_epsilon, sizes={
            "M": dict(
                num_heads=self.mamba_num_heads // n,
                head_dim=self.mamba_head_dim, n_groups=self.n_groups // n,
                state_size=self.ssm_state_size,
                conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
                norm_eps=self.layer_norm_epsilon,
            ),
            "*": held_attention(
                self.num_attention_heads, self.num_key_value_heads, n,
                head_dim=self.head_dim),
            "E": dict(
                latent_dim=self.moe_latent_size,
                expert_dim=self.moe_intermediate_size,
                shared_dim=self.moe_shared_expert_intermediate_size,
                n_routed=self.n_routed_experts,
                top_k=self.num_experts_per_tok,
                n_held=self.n_routed_experts_held,
                first_held=self.first_routed_expert_held,
                routed_scale=self.routed_scaling_factor,
            ),
        })

    @nn.nowrap
    def logged(self, stats, rows, length):
        return route_log(stats)


#: unset sizes default to NVIDIA-Nemotron-3-Super-120B-A12B's, whole
nemotron_h_base_architecture = register_architecture(
    "nemotron_h", "nemotron_h")

#: every mechanism at a size a CPU test holds: attention, two repeats of an
#: ``EM`` unit, 16 experts of which any number may be held
nemotron_h_tiny_architecture = register_architecture(
    "nemotron_h", "nemotron_h_tiny", dict(
        hidden_size=64, hybrid_override_pattern="*EMEM",
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=16, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
        moe_latent_size=32, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, loss_chunk=32,
    ))
