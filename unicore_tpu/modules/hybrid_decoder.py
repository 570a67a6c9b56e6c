"""A decoder whose layers follow a pattern string (``nemotron_h``'s
``hybrid_override_pattern``): ``M`` a Mamba-2 mixer, ``*`` grouped-KV
causal attention, ``E`` a LatentMoE with a shared expert, ``A`` EVA
attention (a window's keys plus the earlier windows' chunk summaries, with
rotary positions), ``F`` a gated feed-forward layer, ``S`` and ``G``
grouped-KV attention with rotary positions under a band the kernels mask
themselves (``S`` over a sliding window, ``G`` over the whole row; each
with its own rotary table), ``R`` routed gated experts with a softmax
router and no shared expert.  Every layer is

    x = x + mixer(RMSNorm(x))

with one mixer per layer, a final RMSNorm after the last, no biases (the
convolution's apart) and no dropout.  A transformer layer of the usual
kind is two of these: ``AF``.

A pattern whose tail repeats (``*EMEMEMEMEM`` = ``*`` + 5 x ``EM``) runs
the repeated unit as ONE traced body under ``nn.scan``, its parameters
stacked on a leading axis (``units/layer_<j>/...`` with shape ``(repeats,
...)``): the step program then holds one ``E`` and one ``M`` body instead
of five of each, which is what keeps its compilation inside a benchmark
run's time limit.  Layers before the repeated tail are ``layers_<i>``.
Each layer (each unit, under the scan) is rematerialized in the backward
pass when ``remat`` is set.  Kept across the forward pass are the residual
stream and the arrays a layer kind NAMES (``jax.ad_checkpoint.
checkpoint_name``) because they are cheap to hold and dear to make again:
one policy for every pattern (:func:`_remat`), and what it keeps in a layer
is that layer kind's to say, which knows its shapes.  ``E`` names the
router's scores, ``top_k``'s choice, both latent arrays, the layout, the
shared expert's result and its ``shared_fc1`` product (``latent_moe.KEPT``:
211 MB a layer at 8,192 tokens, for which the backward pass runs no second
router product, ``top_k``, ``latent_down``, routed forward loop,
``shared_fc1`` or ``shared_fc2``); ``R`` names the same router, ``top_k``
and layout arrays, its routed sum and, with a shared expert, that expert's
two products (``gated_moe.py``); ``M`` names ``in_proj``'s result
(``mamba2.KEPT``: 38 MB a layer, no second ``in_proj``; convolution, scan
and gated norm are made again from it); ``*``, ``A``, ``F``, ``S`` and
``G`` name nothing and are made again whole.  What a name is worth is the
chip's to say: with ``in_proj``'s result kept the compiler lays the scanned
backward loop out against the forward loop's and copies two saved arrays an
iteration, its own cycle estimate ranks that form under ``shared_fc1``'s
name alone, and the chip ranks it above (PERF.md, PR 43).
"""

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import latent_moe, mamba2
from .eva_attention import EvaAttention
from .gated_mlp import GatedMLP
from .gated_moe import GatedMoE
from .latent_moe import STATS, LatentMoE
from .layer_norm import RMSNorm
from .mamba2 import Mamba2Mixer
from .multihead_attention import GroupedQueryAttention

KINDS = "M*EAFSGR"

#: every name a layer kind gives an array it wants kept across the forward
#: pass: each kind's own tuple (``E`` and ``R`` share ``latent_moe``'s)
KEPT = latent_moe.KEPT + mamba2.KEPT


def _remat(cls):
    """``cls`` rematerialized in the backward pass, but for what its
    layers name: :data:`KEPT` (a name no layer of a pattern gives keeps
    nothing; a kind that names an array adds its tuple there)."""
    return nn.remat(
        cls, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))


def split_pattern(pattern: str) -> Tuple[str, str, int]:
    """``(head, unit, repeats)`` with ``pattern == head + unit * repeats``
    and ``repeats >= 2``, the split that leaves the fewest distinct layer
    bodies (``len(head) + len(unit)``; the shorter head on a tie); or
    ``(pattern, "", 0)`` where nothing repeats."""
    best = (pattern, "", 0)
    n = len(pattern)
    for h in range(n):
        for u in range(1, (n - h) // 2 + 1):
            reps, rest = divmod(n - h, u)
            if rest == 0 and pattern[h:] == pattern[h:h + u] * reps:
                if h + u < len(best[0]) + len(best[1]):
                    best = (pattern[:h], pattern[h:h + u], reps)
                break
    return best


class HybridBlock(nn.Module):
    kind: str
    embed_dim: int
    norm_eps: float
    mamba: Optional[dict] = None
    attention: Optional[dict] = None
    moe: Optional[dict] = None
    eva: Optional[dict] = None
    mlp: Optional[dict] = None
    window_attention: Optional[dict] = None
    full_attention: Optional[dict] = None
    gated_moe: Optional[dict] = None
    norm_unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        h = RMSNorm(self.embed_dim, eps=self.norm_eps, name="norm",
                    unit_offset=self.norm_unit_offset)(x)
        stats = jnp.zeros((len(STATS),), jnp.float32)
        if self.kind == "M":
            y = Mamba2Mixer(self.embed_dim, name="mamba", **self.mamba)(h)
        elif self.kind == "*":
            y = GroupedQueryAttention(
                self.embed_dim, name="self_attn", **self.attention
            )(h)
        elif self.kind == "E":
            y, stats = LatentMoE(self.embed_dim, name="moe", **self.moe)(h)
        elif self.kind == "A":
            y = EvaAttention(self.embed_dim, name="self_attn", **self.eva)(h)
        elif self.kind == "F":
            y = GatedMLP(self.embed_dim, name="mlp", **self.mlp)(h)
        elif self.kind in "SG":
            y = GroupedQueryAttention(
                self.embed_dim, name="self_attn", banded=True,
                **(self.window_attention if self.kind == "S"
                   else self.full_attention),
            )(h)
        elif self.kind == "R":
            y, stats = GatedMoE(
                self.embed_dim, name="moe", **self.gated_moe)(h)
        else:
            raise ValueError(
                f"layer kind {self.kind!r} is not one of {KINDS!r}"
            )
        return x + y, stats


class _Unit(nn.Module):
    """One repeat of the pattern's repeated tail, as a scan body."""

    pattern: str
    block: dict

    @nn.compact
    def __call__(self, carry, _):
        x, stats = carry
        for j, kind in enumerate(self.pattern):
            x, s = HybridBlock(kind=kind, name=f"layer_{j}", **self.block)(x)
            stats = stats + s
        return (x, stats), None


class HybridDecoder(nn.Module):
    pattern: str
    embed_dim: int
    norm_eps: float
    # the sizes of each layer kind the pattern holds
    mamba: Optional[dict] = None       # M: Mamba2Mixer's
    attention: Optional[dict] = None   # *: GroupedQueryAttention's
    moe: Optional[dict] = None         # E: LatentMoE's
    eva: Optional[dict] = None         # A: EvaAttention's
    mlp: Optional[dict] = None         # F: GatedMLP's
    # S, G: GroupedQueryAttention's, banded (S states a window; each its rope)
    window_attention: Optional[dict] = None
    full_attention: Optional[dict] = None
    gated_moe: Optional[dict] = None   # R: GatedMoE's
    remat: bool = True
    norm_unit_offset: bool = False  # every norm's gain is 1 + its parameter

    @nn.compact
    def __call__(self, x):
        """``x`` (B, L, embed_dim) -> ``(x, stats)``: the final-normed
        stream and the expert layers' routing stats summed over layers
        (``latent_moe.STATS``; all zero where no layer is ``E``)."""
        block = dict(embed_dim=self.embed_dim, norm_eps=self.norm_eps,
                     mamba=self.mamba, attention=self.attention, moe=self.moe,
                     eva=self.eva, mlp=self.mlp,
                     window_attention=self.window_attention,
                     full_attention=self.full_attention,
                     gated_moe=self.gated_moe,
                     norm_unit_offset=self.norm_unit_offset)
        wrap = _remat if self.remat else (lambda cls: cls)
        head, unit, repeats = split_pattern(self.pattern)
        stats = jnp.zeros((len(STATS),), jnp.float32)
        for i, kind in enumerate(head):
            x, s = wrap(HybridBlock)(kind=kind, name=f"layers_{i}", **block)(x)
            stats = stats + s
        if repeats:
            (x, stats), _ = nn.scan(
                wrap(_Unit), variable_axes={"params": 0},
                split_rngs={"params": True}, length=repeats,
            )(pattern=unit, block=block, name="units")((x, stats), None)
        x = RMSNorm(self.embed_dim, eps=self.norm_eps, name="final_norm",
                    unit_offset=self.norm_unit_offset)(x)
        return x, stats
