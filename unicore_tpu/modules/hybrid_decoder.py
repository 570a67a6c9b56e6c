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
kind is two of these: ``AF``.  A kind is one row of :data:`TABLE`, and a
model states the sizes of the kinds its pattern holds as one mapping,
``sizes={"A": dict(...), "F": dict(...)}``.

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

from typing import Callable, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.ops import eva_attention, flash_attention
from . import latent_moe, mamba2
from .eva_attention import EvaAttention
from .gated_mlp import GatedMLP
from .gated_moe import GatedMoE
from .latent_moe import STATS, LatentMoE
from .layer_norm import RMSNorm
from .mamba2 import Mamba2Mixer
from .multihead_attention import GroupedQueryAttention


class Kind(NamedTuple):
    """One layer kind: the class of its mixer, the name the mixer takes in
    the parameter tree, what it is always built with (beside the sizes a
    model states for the kind), whether it returns ``(y, stats)``
    (``latent_moe.STATS``) and not ``y`` alone, the names it gives the
    arrays it wants kept across the forward pass, and what the makers of
    the stats a model logs of such layers say of them: ``logs`` to the
    training log (each a function of an update's logging outputs) and
    ``marks`` to a profiler capture (each from an update's summed logging
    output to ``{mark: stats}``, empty where the sums hold none of its)."""

    module: type
    name: str
    always: dict = {}
    stats: bool = False
    kept: Tuple[str, ...] = ()
    logs: Tuple[Callable, ...] = ()
    marks: Tuple[Callable, ...] = ()


# E and R share one loop, S and G one band
_EXPERTS = dict(stats=True, kept=latent_moe.KEPT,
                logs=(latent_moe.route_scalars,),
                marks=(latent_moe.route_mark,))
_BANDED = dict(always=dict(banded=True), marks=(flash_attention.band_mark,))

#: the layer kinds by their character in a pattern: a new mixer is its
#: module and a row here
TABLE = {
    "M": Kind(Mamba2Mixer, "mamba", kept=mamba2.KEPT),
    "*": Kind(GroupedQueryAttention, "self_attn"),
    "E": Kind(LatentMoE, "moe", **_EXPERTS),
    "A": Kind(EvaAttention, "self_attn", marks=(eva_attention.keys_mark,)),
    "F": Kind(GatedMLP, "mlp"),
    "S": Kind(GroupedQueryAttention, "self_attn", **_BANDED),
    "G": Kind(GroupedQueryAttention, "self_attn", **_BANDED),
    "R": Kind(GatedMoE, "moe", **_EXPERTS),
}


def _each_once(field):
    return tuple(dict.fromkeys(
        v for row in TABLE.values() for v in getattr(row, field)))


KINDS = "".join(TABLE)

#: every name a layer kind gives an array it wants kept across the forward
#: pass
KEPT = _each_once("kept")

#: what the kinds' stats tell the log and a profiler capture: a loss runs
#: over them (``losses/lm_cross_entropy.py``) and names none
LOGS, MARKS = _each_once("logs"), _each_once("marks")


def _remat(cls):
    """``cls`` rematerialized in the backward pass, but for what its
    layers name: :data:`KEPT` (a name no layer of a pattern gives keeps
    nothing; a kind that names an array states it in its row)."""
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
    sizes: dict  # the pattern's kinds -> what each one's mixer is built with
    norm_unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        row = TABLE.get(self.kind)
        if row is None:
            raise ValueError(
                f"layer kind {self.kind!r} is not one of {KINDS!r}"
            )
        h = RMSNorm(self.embed_dim, eps=self.norm_eps, name="norm",
                    unit_offset=self.norm_unit_offset)(x)
        stats = jnp.zeros((len(STATS),), jnp.float32)
        y = row.module(self.embed_dim, name=row.name, **row.always,
                       **self.sizes[self.kind])(h)
        if row.stats:
            y, stats = y
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
    # for each kind the pattern holds (:data:`TABLE`), the sizes its mixer
    # is built with: ``{"S": dict(num_heads=..., window=..., rope=...), ...}``
    sizes: dict
    remat: bool = True
    norm_unit_offset: bool = False  # every norm's gain is 1 + its parameter

    @nn.compact
    def __call__(self, x):
        """``x`` (B, L, embed_dim) -> ``(x, stats)``: the final-normed
        stream and the expert layers' routing stats summed over layers
        (``latent_moe.STATS``; all zero where no layer returns any)."""
        block = dict(embed_dim=self.embed_dim, norm_eps=self.norm_eps,
                     sizes=self.sizes, norm_unit_offset=self.norm_unit_offset)
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
