"""``zaya``'s expert sublayer (the ZAYA1 technical report, arXiv:2511.17127):
gated experts one a token, chosen by a ROUTER THAT IS A NETWORK with a
state carried from one expert layer to the next, and whose last output is a
**skip expert** that costs nothing and adds nothing.

    r = h W_d + b_d + gamma * r_prev          (L, R); r is the next layer's r_prev
    logits = W_3 gelu(W_2 gelu(W_1 RMSNorm_R(r) + b_1) + b_2)      (L, E + 1)
    p = softmax(logits);   column E is the skip expert
    e* = argmax(chooser)                       not differentiated
    f = p_e* W_down,e* (silu(W_gate,e* h) * (W_up,e* h))   e* < E and held, else 0

``r_prev`` is the **side stream** a decoder carries beside ``x`` for this
kind (``modules/hybrid_decoder.py``; zeros into the model's first layer:
:func:`side_start`), float32 like everything of the router from ``r`` on
(``gamma * r_prev`` averages the routers' inputs over depth with weights
that fall off exponentially).  The chosen score weights the expert's
result as it is: one expert a token, nothing to renormalise over.

The chooser is ``p`` as far as this file builds the published rule (the
published one adds a selection bias an optimiser of the recipe moves, which
needs trainer state beside parameters and moments: ROADMAP R8);
``balancing="batch_bias"`` chooses by ``gated_moe.balanced_scores`` over
all ``E + 1`` columns, the skip column among them, so that an even routing
sends each held expert, and the skip column, ``n / (E + 1)`` tokens.

The experts are ``modules/gated_moe.py``'s: the same layout, the same wide
and narrow loops and their written-out backward
(:func:`~.latent_moe.routed_experts` with ``silu_gate``), told which experts
are held (``first_held .. first_held + n_held - 1`` of ``n_routed``).  Top-1
with a skip column gives FEWER pairs than tokens.  The router is whole on
every share and the skip column is every share's alike: summed over the
shares it counts once, and the shares' ``f`` add up to the uncut layer's
(``tests/test_zaya.py``).

Returns ``(f, stats, r)``; ``stats`` is ``latent_moe.STATS`` and then
:data:`MORE_STATS` (the tokens that chose the skip column, and all tokens),
which a model logs (:func:`skip_log`) without a column more in the stats
every other expert layer returns.  It names the same arrays for a
rematerializing caller (``moe_logits``, ``moe_top_k_idx``,
``moe_top_k_sel``, the layout, ``moe_routed_sum``).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from unicore_tpu.logging import metrics
from .cca import _Vector
from .gated_mlp import _Kernel
from .gated_moe import BALANCINGS, balanced_scores
from .latent_moe import (
    STATS, buffer_rows, route_stats, routed_experts, top_k_set, wide_rows,
)
from .layer_norm import RMSNorm

_init = nn.initializers.normal(0.02)

#: what the layer returns after ``latent_moe.STATS``
MORE_STATS = ("skipped", "tokens")


def side_start(x, sizes):
    """The router state that goes into a model's first layer: zeros,
    ``(B, L, router_dim)`` float32."""
    return jnp.zeros(x.shape[:-1] + (sizes["router_dim"],), jnp.float32)


def skip_log(stats):
    """What a model logs of its expert layers' skip column, from the
    decoder's summed stats (:data:`MORE_STATS` after ``STATS``)."""
    return {"moe_" + k: stats[len(STATS) + i]
            for i, k in enumerate(MORE_STATS)}


def skip_scalars(logging_outputs):
    """The log's line of the skip column: the share of (token, layer)
    pairs that chose it."""
    tokens = sum(log.get("moe_tokens", 0) for log in logging_outputs)
    if tokens > 0:
        skipped = sum(log.get("moe_skipped", 0) for log in logging_outputs)
        metrics.log_scalar("moe_skip_share", skipped / tokens, 1, round=4)


def skip_mark(sums):
    """What a profiler capture is told of one update's skip column: one
    ``unicore:moe_skip`` mark with the (token, layer) pairs that chose it
    and all of them.  Nothing where no layer has one."""
    if not sums.get("moe_tokens", 0):
        return {}
    return {"moe_skip": dict(skipped=int(sums["moe_skipped"]),
                             tokens=int(sums["moe_tokens"]))}


def _product(x, w):
    """float32 ``x @ w`` at full precision (the router's own products)."""
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


class ZayaMoE(nn.Module):
    embed_dim: int
    expert_dim: int
    n_routed: int             # experts, without the skip column
    router_dim: int
    n_held: int = 0           # 0: all of n_routed
    first_held: int = 0
    norm_eps: float = 1e-5
    balancing: str = "none"   # of gated_moe.BALANCINGS

    @nn.compact
    def __call__(self, h, r_prev):
        """``h`` (B, S, embed_dim), already normalised by the block;
        ``r_prev`` (B, S, router_dim) float32."""
        E, R = self.n_routed, self.router_dim
        Eh = self.n_held or E
        if not 0 <= self.first_held <= E - Eh:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + Eh - 1} "
                f"are not among {E}"
            )
        if self.balancing not in BALANCINGS:
            raise ValueError(
                f"balancing {self.balancing!r} is not one of {BALANCINGS}")
        B, S, d = h.shape
        n = B * S
        dtype, f32 = h.dtype, jnp.float32
        tokens = h.reshape(n, d)
        held = slice(self.first_held, self.first_held + Eh)

        with jax.named_scope("moe_router"):
            with jax.named_scope("router_down"):
                w_d = _Kernel((d, R), name="router_down")()
                b_d = _Vector((R,), name="router_down_bias")()
                gamma = _Vector((R,), "scale", name="depth_gain")()
                # float32 state: bfloat16 operands multiply exactly into the
                # float32 accumulator, float32 ones take the full product
                r = jnp.dot(
                    tokens, w_d.astype(dtype), preferred_element_type=f32,
                    precision=None if dtype == jnp.bfloat16
                    else jax.lax.Precision.HIGHEST,
                ) + b_d + gamma * r_prev.reshape(n, R)
            with jax.named_scope("router_mlp"):
                t = RMSNorm(R, eps=self.norm_eps, name="router_norm")(r)
                for name in ("router_w1", "router_w2"):
                    t = jax.nn.gelu(
                        _product(t, _Kernel((R, R), name=name)())
                        + _Vector((R,), name=name + "_bias")(),
                        approximate=False)
                logits = checkpoint_name(
                    _product(t, _Kernel((R, E + 1), name="router_out")()),
                    "moe_logits")
            with jax.named_scope("router_choose"):
                p = jax.nn.softmax(logits, axis=-1)
                # the selection is not differentiated: it only decides
                # WHICH score weights the token's expert
                chooser = jax.lax.stop_gradient(p)
                if self.balancing == "batch_bias":
                    chooser = balanced_scores(
                        jax.lax.stop_gradient(logits), 1)
                idx, sel = top_k_set(chooser, 1)
                idx = checkpoint_name(idx, "moe_top_k_idx")
                sel = checkpoint_name(sel, "moe_top_k_sel")
                pair = sel[:, held]                                 # (n, Eh)
                w_held = jnp.where(pair, p[:, held], 0.0)
                load = pair.sum(axis=0)                             # (Eh,)
                skipped = sel[:, E].sum()

        with jax.named_scope("moe_routed"):
            w1 = self.param("experts_fc1", _init,
                            (Eh, d, 2 * self.expert_dim),
                            jnp.float32).astype(dtype)
            w2 = self.param("experts_fc2", _init,
                            (Eh, self.expert_dim, d),
                            jnp.float32).astype(dtype)
            wide = wide_rows(n, 1, E + 1)
            routed = routed_experts(
                tokens, w_held, w1, w2, buffer_rows(n, 1, Eh), pair,
                "silu_gate", wide,
            )
            stats = jnp.concatenate([
                route_stats(load, wide),
                jnp.stack([skipped, n]).astype(f32)])
            y = checkpoint_name(routed.astype(dtype), "moe_routed_sum")
        return y.reshape(B, S, d), stats, r.reshape(B, S, R)
