"""Routed gated experts, as a layer that is TOLD which experts it holds:
a router over all ``n_routed`` experts, ``top_k`` a token with the chosen
scores renormalised, each expert a gated three-matrix feed-forward layer at
the model's width; no latent; a shared expert where ``shared_dim`` states
one.

    p = softmax(h W_r)                      float32, over ALL n_routed
    chosen = the top_k largest of p;   w_e = routed_scale p_e / sum_{chosen} p
    y = sum_{e chosen and held} w_e W_down,e (silu(W_gate,e h) * (W_up,e h))
        + W_down,s (silu(W_gate,s h) * (W_up,s h))       where shared_dim > 0

``scoring="sigmoid"`` is DeepSeek-V3's router (arXiv:2412.19437 section
2.1.2, ``scoring_func: sigmoid`` with ``topk_method: noaux_tc``): each
expert's score is its own sigmoid, and a bias an expert is added to the
scores that CHOOSE and to nothing else,

    s = sigmoid(h W_r);   chosen = the top_k largest of s + b
    w_e = routed_scale s_e / (sum_{chosen} s + 1e-20)

``b`` is the leaf ``router_bias`` (n_routed,), which no gradient reaches:
the published recipe's trainer moves it between updates by the experts'
loads (arXiv:2408.15664), this one's carries no such state (ROADMAP R8), so
the leaf stays what it was loaded as.  It is read where ``balancing`` is
``none``; under ``batch_bias`` the chooser is :func:`balanced_scores` and
the leaf, still in the tree, is read by nothing.

It is ``modules/latent_moe.py``'s machinery with another body: the chosen
scores are read where they lie (:func:`~.latent_moe.top_k_set`, no gather),
the (token, held expert) pairs are laid out expert by expert in whole
tiles (:func:`~.latent_moe.buffer_layout`), one loop walks the tiles in
use and its written-out backward walks them again
(:func:`~.latent_moe.routed_experts` with ``act="silu_gate"``:
``experts_fc1`` holds ``[W_gate | W_up]`` side by side, the gate's
columns first, ``experts_fc2`` is ``W_down``); dropless, no capacity
factor.  Where the even load ``n * top_k / n_routed`` fills them
(:func:`~.latent_moe.wide_rows`, from shapes as the layer is traced) an
expert's tiles go ``latent_moe.WIDE`` rows at a time as far as they fill
whole wide trips, and only the tiles that leaves go ``TILE`` rows a trip:
an expert's kernels (12 MB at the published widths) are read, and its
float32 ``dw`` rewritten, once a wide trip.  What is sized by the worst
case stays the layout's index arrays alone.

The shared expert is the same gated body at width ``shared_dim``, every
token through it, unweighted: ``shared_fc1`` holds ``[W_gate | W_up]``,
``shared_fc2`` ``W_down``, under the scope ``moe_shared`` with its result
named ``moe_shared_out``, as ``latent_moe.py`` has them.  Every share of a
layer computes it alike: summed over the shares it counts ONCE.

The layer holds experts ``first_held .. first_held + n_held - 1``,
routes over all ``n_routed`` and computes its own experts' part; what the
absent experts would add is left out, as on one chip of an expert-parallel
deployment before the exchange, so the shares' results add up to the uncut
layer's (``tests/test_mellum.py``).  The exchange is not built (ROADMAP
R8), and there is no auxiliary balancing loss.

``balancing="batch_bias"`` is a rule of TRAINING: loss-free balancing
(Wang et al., arXiv:2408.15664, DeepSeek-V3's rule: a bias an expert on
the scores that CHOOSE, raised where an expert got fewer tokens than its
share and lowered where it got more, the weights still the scores'), with
the bias solved anew on every batch instead of carried from step to step
(:func:`balanced_scores`), because the trainer carries no state beside
parameters and moments.  What it is solved on is each expert's logits
standardised over the batch's tokens plus noisy top-k gating's noise
(Shazeer et al., arXiv:1701.06538) at one spread, from a fixed table: a
bias an expert cannot part tokens whose logits are the same, and a few
updates into training most of a batch's are (PERF.md, PR 40).  Scores and
bias are over ALL ``n_routed`` experts and a function of the router's
product alone, so every share of a layer chooses the same set.  With it
each expert gets its share of the batch's pairs to within a few percent,
on seeded weights and while training draws the hidden states together.

It returns the same ``STATS`` and names the same arrays for a
rematerializing caller (``latent_moe.KEPT``: the router's product,
``top_k``'s indices and set, the layout, the routed sum, the shared
expert's result and its ``shared_fc1`` product before the gate).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .gated_mlp import _Kernel
from .latent_moe import (
    STATS, buffer_rows, route_stats, routed_experts, silu_gate, top_k_set,
    wide_rows,
)

_init = nn.initializers.normal(0.02)

BALANCINGS = ("none", "batch_bias")
SCORINGS = ("softmax", "sigmoid")

#: :func:`balanced_scores`: the noise's scale in spreads of an expert's
#: logits, the count-and-correct rounds, and the step of each (in the
#: standardised scores' units)
NOISE = 1.0
BIAS_ROUNDS = 8
BIAS_GAIN = 0.55


def noise_table(n, E):
    """The fixed ``(n, E)`` standard normal table the scores that choose
    are dithered with: the same numbers on every backend, every step and
    every layer (the plain reference draws it the same way)."""
    return jax.random.normal(
        jax.random.key(0, impl="threefry2x32"), (n, E), jnp.float32)


def balanced_scores(logits, k, rounds=BIAS_ROUNDS, gain=BIAS_GAIN):
    """``logits`` (n, E) float32.  Scores ``u + b`` whose ``k`` largest a
    row fall evenly on the columns:

        m_e = mean_t logits_te,   s_e = sqrt(mean_t (logits_te - m_e)^2)
        u_te = (logits_te - m_e) / s_e + NOISE * table_te
        b = 0;  rounds times:  c_e = the tokens whose k largest hold e
                               b_e = b_e - gain * ln((c_e + 1) / (n k / E + 1))

    ``m`` takes out what all tokens have in common (most of a seeded
    router's logits, and the direction in which a share's router unlearns
    its held experts).  The step of ``b`` is loss-free balancing's (an
    expert with more than its share is lowered), in proportion to the log
    of the excess.  The noise is what makes the rounds settle: a step of
    the bias wins or loses tokens in proportion to how densely their
    scores lie about the k-th place, and with ``u``'s spread never under
    ``NOISE`` the gain stays under one, whether the logits are spread like
    a seeded router's or, a few updates later, nearly the same for most
    tokens (without it the rounds swing between all and nothing there).

    The rounds are ONE traced body (``lax.fori_loop``), so a step program
    has one ``top_k`` a layer for them and not ``rounds``: at 256 experts
    each copy costs the TPU's compiler about a second a layer (``laguna``'s
    step compiles in 60 s with the loop and 100 s with the copies, for a
    described v5e)."""
    n, E = logits.shape
    share = n * k / E
    mean = jnp.mean(logits, axis=0)
    spread = jnp.sqrt(jnp.mean(jnp.square(logits - mean), axis=0))
    u = (logits - mean) / (spread + 1e-6) + NOISE * noise_table(n, E)

    def round_(_, b):
        _, sel = top_k_set(u + b, k)
        c = jnp.sum(sel, axis=0).astype(logits.dtype)
        return b - gain * jnp.log((c + 1.0) / (share + 1.0))

    return u + jax.lax.fori_loop(
        0, rounds, round_, jnp.zeros((E,), logits.dtype))


class GatedMoE(nn.Module):
    embed_dim: int
    expert_dim: int
    n_routed: int
    top_k: int
    n_held: int = 0           # 0: all of n_routed
    first_held: int = 0
    norm_topk_prob: bool = True
    balancing: str = "none"   # of BALANCINGS
    routed_scale: float = 1.0  # on the weights, after they are renormalised
    shared_dim: int = 0       # 0: no shared expert
    scoring: str = "softmax"  # of SCORINGS

    @nn.compact
    def __call__(self, h):
        """``h`` (B, S, embed_dim), already normalised by the block.
        Returns ``(y, stats)``; ``stats`` is float32 of ``len(STATS)``."""
        E = self.n_routed
        Eh = self.n_held or E
        if not 0 <= self.first_held <= E - Eh:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + Eh - 1} "
                f"are not among {E}"
            )
        if self.balancing not in BALANCINGS:
            raise ValueError(
                f"balancing {self.balancing!r} is not one of {BALANCINGS}")
        if self.scoring not in SCORINGS:
            raise ValueError(
                f"scoring {self.scoring!r} is not one of {SCORINGS}")
        sigmoid = self.scoring == "sigmoid"
        B, S, d = h.shape
        n = B * S
        dtype = h.dtype
        f32 = jnp.float32
        tokens = h.reshape(n, d)

        with jax.named_scope("moe_router"):
            w_r = self.param("router", _init, (d, E), jnp.float32)
            # float32 scores: bfloat16 operands multiply exactly into the
            # float32 accumulator, float32 ones take the full product
            logits = checkpoint_name(jnp.dot(
                tokens, w_r.astype(dtype), preferred_element_type=f32,
                precision=None if dtype == jnp.bfloat16
                else jax.lax.Precision.HIGHEST,
            ), "moe_logits")
            p = (jax.nn.sigmoid(logits) if sigmoid
                 else jax.nn.softmax(logits, axis=-1))
            # the selection is not differentiated: it only decides WHICH
            # scores are summed
            chooser = jax.lax.stop_gradient(p)
            if sigmoid:
                chooser = chooser + jax.lax.stop_gradient(self.param(
                    "router_bias", nn.initializers.zeros, (E,), f32))
            if self.balancing == "batch_bias":
                chooser = balanced_scores(
                    jax.lax.stop_gradient(logits), self.top_k)
            idx, sel = top_k_set(chooser, self.top_k)
            idx = checkpoint_name(idx, "moe_top_k_idx")
            sel = checkpoint_name(sel, "moe_top_k_sel")
            pair = sel[:, self.first_held:self.first_held + Eh]     # (n, Eh)
            w_held = jnp.where(
                pair, p[:, self.first_held:self.first_held + Eh], 0.0)
            if self.norm_topk_prob:
                chosen = jnp.sum(
                    jnp.where(sel, p, 0.0), axis=-1, keepdims=True)
                w_held = w_held / (chosen + 1e-20 if sigmoid else chosen)
            if self.routed_scale != 1.0:
                w_held = w_held * self.routed_scale
            load = pair.sum(axis=0)                                 # (Eh,)

        with jax.named_scope("moe_routed"):
            w1 = self.param("experts_fc1", _init,
                            (Eh, d, 2 * self.expert_dim),
                            jnp.float32).astype(dtype)
            w2 = self.param("experts_fc2", _init,
                            (Eh, self.expert_dim, d),
                            jnp.float32).astype(dtype)
            wide = wide_rows(n, self.top_k, E)
            routed = routed_experts(
                tokens, w_held, w1, w2, buffer_rows(n, self.top_k, Eh), pair,
                "silu_gate", wide,
            )
            stats = route_stats(load, wide)
            y = checkpoint_name(routed.astype(dtype), "moe_routed_sum")

        if self.shared_dim:
            with jax.named_scope("moe_shared"):
                s1 = _Kernel((d, 2 * self.shared_dim), name="shared_fc1")()
                s2 = _Kernel((self.shared_dim, d), name="shared_fc2")()
                # the gate's activation and its product with ``up`` in
                # float32, rounded once (as modules/gated_mlp.py)
                with jax.named_scope("shared_fc1"):
                    # named before the gate, whose backward reads both
                    # halves of it
                    mid = checkpoint_name(
                        jnp.dot(tokens, s1.astype(dtype)), "moe_shared_fc1")
                    mid = silu_gate(mid.astype(f32)).astype(dtype)
                with jax.named_scope("shared_fc2"):
                    y = y + checkpoint_name(
                        jnp.dot(mid, s2.astype(dtype)), "moe_shared_out")
        return y.reshape(B, S, d), stats
