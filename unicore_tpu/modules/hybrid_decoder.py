"""A decoder whose layers follow a pattern string (``nemotron_h``'s
``hybrid_override_pattern``): ``M`` a Mamba-2 mixer, ``*`` grouped-KV
causal attention, ``E`` a LatentMoE with a shared expert, ``A`` EVA
attention (a window's keys plus the earlier windows' chunk summaries, with
rotary positions), ``F`` a gated feed-forward layer, ``S`` and ``G``
grouped-KV attention with rotary positions under a band the kernels mask
themselves (``S`` over a sliding window, ``G`` over the whole row; each
with its own rotary table), ``R`` routed gated experts with a softmax
router and no shared expert, ``C`` attention in a compressed,
convolution-mixed latent (``modules/cca.py``), ``Z`` gated experts one a
token under a router that is a network with a state carried from one ``Z``
layer to the next and a skip expert (``modules/zaya_moe.py``), ``L``
multi-head latent attention: queries, keys and values expanded from two
normed latents beside one rotary key every head shares, keys wider than
values (``modules/mla.py``).  ``R`` with ``scoring="sigmoid"`` scores each
expert by its own sigmoid and chooses on the score plus a bias leaf no
gradient reaches (``modules/gated_moe.py``).  Every layer is

    x = x + mixer(RMSNorm(x))

with one mixer per layer, a final RMSNorm after the last, no biases (the
convolution's apart) and no dropout.  A transformer layer of the usual
kind is two of these: ``AF``.  A kind is one row of :data:`TABLE`, and a
model states the sizes of the kinds its pattern holds as one mapping,
``sizes={"A": dict(...), "F": dict(...)}``.

Two things a kind or a model may state beside that.  A kind whose row has
``side`` **carries a side stream**: its mixer is called ``mixer(h, side)``
and returns ``(y, stats, side)``; the decoder starts the stream once
(``Kind.side(x, sizes)``), hands it from layer to layer through the loop
over the layers before the repeated unit and through the ``nn.scan`` carry
of the unit, and every kind without ``side`` passes it on untouched.  A
pattern none of whose kinds has one carries ``None``, an empty tree, and
traces what it traced before the stream existed.  A model may state
``scaled_merge``: the block then ends

    x = s_x (x + b_x) + s_f (mixer(RMSNorm(x)) + b_f)

with four learned per-channel vectors (:class:`ScaledMerge`).  A kind that
returns stats beyond ``latent_moe.STATS`` names them (``more_stats``), and
the decoder's stats are :func:`stat_names` of its pattern.

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
and gated norm are made again from it); ``Z`` names what ``R`` names (its router's logits, the choice, the layout,
the routed sum); ``L`` names its two normed latents and its rotary key
(``mla.KEPT``: 2,112 channels a token, for which the backward pass runs no
second down-projection of queries or of keys and values); ``*``, ``A``,
``F``, ``S``, ``G`` and ``C`` name nothing and are made again whole.  What a name is worth is the
chip's to say: with ``in_proj``'s result kept the compiler lays the scanned
backward loop out against the forward loop's and copies two saved arrays an
iteration, its own cycle estimate ranks that form under ``shared_fc1``'s
name alone, and the chip ranks it above (PERF.md, PR 43).
"""

from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.ops import eva_attention, flash_attention
from . import latent_moe, mamba2, mla, zaya_moe
from .cca import CompressedConvAttention
from .eva_attention import EvaAttention
from .gated_mlp import GatedMLP
from .gated_moe import GatedMoE
from .latent_moe import STATS, LatentMoE
from .layer_norm import RMSNorm
from .mamba2 import Mamba2Mixer
from .mla import LatentAttention
from .multihead_attention import GroupedQueryAttention
from .zaya_moe import ZayaMoE


class Kind(NamedTuple):
    """One layer kind: the class of its mixer, the name the mixer takes in
    the parameter tree, what it is always built with (beside the sizes a
    model states for the kind), whether it returns ``(y, stats)``
    (``latent_moe.STATS``) and not ``y`` alone, the names it gives the
    arrays it wants kept across the forward pass, and what the makers of
    the stats a model logs of such layers say of them: ``logs`` to the
    training log (each a function of an update's logging outputs) and
    ``marks`` to a profiler capture (each from an update's summed logging
    output to ``{mark: stats}``, empty where the sums hold none of its);
    ``side``, for a kind that carries a side stream beside ``x``: the
    stream that goes into the pattern's first layer, from ``x`` and the
    kind's sizes (its mixer maps ``(h, side)`` to ``(y, stats, side)``);
    ``more_stats``, the names of the stats it returns after ``STATS``."""

    module: type
    name: str
    always: dict = {}
    stats: bool = False
    kept: Tuple[str, ...] = ()
    logs: Tuple[Callable, ...] = ()
    marks: Tuple[Callable, ...] = ()
    side: Optional[Callable] = None
    more_stats: Tuple[str, ...] = ()


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
    "C": Kind(CompressedConvAttention, "self_attn",
              marks=(flash_attention.band_mark,
                     flash_attention.band_call_mark)),
    "Z": Kind(ZayaMoE, "moe", stats=True, kept=latent_moe.KEPT,
              logs=(latent_moe.route_scalars, zaya_moe.skip_scalars),
              marks=(latent_moe.route_mark, zaya_moe.skip_mark),
              side=zaya_moe.side_start, more_stats=zaya_moe.MORE_STATS),
    "L": Kind(LatentAttention, "self_attn", kept=mla.KEPT,
              marks=(flash_attention.band_mark,
                     flash_attention.band_call_mark, mla.mla_mark)),
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


def stat_names(pattern):
    """The stats a decoder over ``pattern`` returns, summed over its
    layers: ``latent_moe.STATS``, then what its kinds return after them
    (``Kind.more_stats``; none of the kinds but ``Z``)."""
    return STATS + tuple(dict.fromkeys(
        n for kind in pattern if kind in TABLE
        for n in TABLE[kind].more_stats))


def side_start(pattern, x, sizes):
    """The side stream that goes into the first layer of ``pattern``
    beside ``x``: that of the first of its kinds that carries one
    (``Kind.side``), or None where no kind does."""
    for kind in dict.fromkeys(pattern):
        if kind in TABLE and TABLE[kind].side is not None:
            return TABLE[kind].side(x, sizes[kind])
    return None


class _ScaleBias(nn.Module):
    """``scale * (t + bias)`` in float32, both learned vectors (ones and
    zeros at the start)."""

    dim: int

    @nn.compact
    def __call__(self, t):
        scale = self.param("scale", nn.initializers.ones, (self.dim,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.dim,),
                          jnp.float32)
        return scale * (t.astype(jnp.float32) + bias)


class ScaledMerge(nn.Module):
    """``x <- s_x (x + b_x) + s_f (f + b_f)``: the residual merge with four
    learned per-channel vectors (``x/scale``, ``x/bias``, ``f/scale``,
    ``f/bias``; ones and zeros at the start, where it is ``x + f``), in
    float32, rounded once to the stream's dtype."""

    embed_dim: int

    @nn.compact
    def __call__(self, x, f):
        with jax.named_scope("merge"):
            y = (_ScaleBias(self.embed_dim, name="x")(x)
                 + _ScaleBias(self.embed_dim, name="f")(f))
            return y.astype(x.dtype)


class HybridBlock(nn.Module):
    kind: str
    embed_dim: int
    norm_eps: float
    sizes: dict  # the pattern's kinds -> what each one's mixer is built with
    norm_unit_offset: bool = False
    scaled_merge: bool = False
    stats: Tuple[str, ...] = STATS  # :func:`stat_names` of the pattern

    @nn.compact
    def __call__(self, x, side=None):
        """``(x, side) -> (x, stats, side)``: a kind that carries the side
        stream reads and replaces it, every other hands it on."""
        row = TABLE.get(self.kind)
        if row is None:
            raise ValueError(
                f"layer kind {self.kind!r} is not one of {KINDS!r}"
            )
        h = RMSNorm(self.embed_dim, eps=self.norm_eps, name="norm",
                    unit_offset=self.norm_unit_offset)(x)
        stats = jnp.zeros((len(self.stats),), jnp.float32)
        mixer = row.module(self.embed_dim, name=row.name, **row.always,
                           **self.sizes[self.kind])
        if row.side is not None:
            y, own, side = mixer(h, side)
        else:
            y = mixer(h)
            if row.stats:
                y, own = y
        if row.stats:
            names = STATS + row.more_stats
            stats = own if names == self.stats else stats.at[
                jnp.asarray([self.stats.index(n) for n in names])].set(own)
        if self.scaled_merge:
            return ScaledMerge(self.embed_dim, name="merge")(x, y), stats, side
        return x + y, stats, side


class _Unit(nn.Module):
    """One repeat of the pattern's repeated tail, as a scan body."""

    pattern: str
    block: dict

    @nn.compact
    def __call__(self, carry, _):
        x, stats, side = carry
        for j, kind in enumerate(self.pattern):
            x, s, side = HybridBlock(
                kind=kind, name=f"layer_{j}", **self.block)(x, side)
            stats = stats + s
        return (x, stats, side), None


class HybridDecoder(nn.Module):
    pattern: str
    embed_dim: int
    norm_eps: float
    # for each kind the pattern holds (:data:`TABLE`), the sizes its mixer
    # is built with: ``{"S": dict(num_heads=..., window=..., rope=...), ...}``
    sizes: dict
    remat: bool = True
    norm_unit_offset: bool = False  # every norm's gain is 1 + its parameter
    scaled_merge: bool = False      # the merge is :class:`ScaledMerge`

    @nn.compact
    def __call__(self, x):
        """``x`` (B, L, embed_dim) -> ``(x, stats)``: the final-normed
        stream and the layers' stats summed over layers
        (:func:`stat_names` of the pattern: ``latent_moe.STATS`` for every
        pattern without ``Z``; all zero where no layer returns any)."""
        names = stat_names(self.pattern)
        block = dict(embed_dim=self.embed_dim, norm_eps=self.norm_eps,
                     sizes=self.sizes, norm_unit_offset=self.norm_unit_offset,
                     scaled_merge=self.scaled_merge, stats=names)
        wrap = _remat if self.remat else (lambda cls: cls)
        head, unit, repeats = split_pattern(self.pattern)
        stats = jnp.zeros((len(names),), jnp.float32)
        side = side_start(self.pattern, x, self.sizes)
        for i, kind in enumerate(head):
            x, s, side = wrap(HybridBlock)(
                kind=kind, name=f"layers_{i}", **block)(x, side)
            stats = stats + s
        if repeats:
            (x, stats, side), _ = nn.scan(
                wrap(_Unit), variable_axes={"params": 0},
                split_rngs={"params": True}, length=repeats,
            )(pattern=unit, block=block, name="units")((x, stats, side), None)
        x = RMSNorm(self.embed_dim, eps=self.norm_eps, name="final_norm",
                    unit_offset=self.norm_unit_offset)(x)
        return x, stats
