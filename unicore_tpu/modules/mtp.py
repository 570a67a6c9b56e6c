"""A multi-token-prediction module of depth 1 (DeepSeek-V3,
arXiv:2412.19437 section 2.2, eq. 21 - 25): one more block that trains with
the model and predicts the token AFTER the next one.  With ``x`` the
decoder's final-normed stream and ``t`` a row's tokens:

    u_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(x_i)]      W_eh: 2d -> d
    z   = Block(u)             the model's last layer over again: its kinds
    out = RMSNorm_s(z)         scored by the model's OWN head against t_{i+2}

``Emb`` and the head are the model's (``models/hybrid_lm.py`` hands the
module the row's embeddings one position early and runs its head over the
result), so the embedding and the head kernel each receive the gradients of
both passes.  The block is ``hybrid_decoder.HybridBlock`` per kind of
``pattern`` (``joyai``: ``LR``, a latent-attention and an expert sublayer at
the held shares), each rematerialized as the decoder's are, with the norms
and ``W_eh`` one more rematerialized unit in front.  The embedding's half
stands FIRST under ``W_eh`` (the public DeepSeek-V3 inference code); the
product is two, one a half, so no ``(L, 2d)`` array is made.

Everything of the module runs under the scope ``mtp`` (the module's name in
the model), the projection under ``mtp_eh``.  The loss that scores a stream
beyond the decoder's (``losses/lm_cross_entropy.py``) logs, for a model
whose ``ahead`` names the stream ``mtp``, the stream's summed NLL as
``mtp_loss`` scaled to the main pass's sample size and the main pass's own
as ``nll_loss``; :func:`loss_scalars` puts the mean of the first in the
training log and :func:`loss_mark` both means in a profiler capture.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.logging import metrics
from .gated_mlp import _Kernel
from .hybrid_decoder import HybridBlock, _remat, stat_names
from .layer_norm import RMSNorm


def loss_scalars(logging_outputs):
    """The log's line of a prediction module: its mean NLL a target, in
    bits as the loss is logged; nothing for a model without one."""
    total = sum(log.get("mtp_loss", 0) for log in logging_outputs)
    size = sum(log.get("sample_size", 0) for log in logging_outputs)
    if any("mtp_loss" in log for log in logging_outputs) and size > 0:
        metrics.log_scalar("mtp_loss", total / size / jnp.log(2), size,
                           round=3)


def loss_mark(sums):
    """What a profiler capture is told of one update of a model with a
    prediction module, from that update's summed logging output: one
    ``unicore:mtp_loss`` mark with the module's mean NLL and the main
    pass's (nats a target).  Nothing for a model without one."""
    size = sums.get("sample_size", 0)
    if "mtp_loss" not in sums or not size:
        return {}
    return {"mtp_loss": dict(mtp=sums["mtp_loss"] / size,
                             main=sums["nll_loss"] / size)}


#: what the loss's ``reduce_metrics`` and ``trace_marks`` run beside the
#: layer kinds' own
LOGS = (loss_scalars,)
MARKS = (loss_mark,)


class _Join(nn.Module):
    """``W_eh [RMSNorm_e(e) ; RMSNorm_h(x)]``."""

    embed_dim: int
    norm_eps: float
    norm_unit_offset: bool = False

    @nn.compact
    def __call__(self, x, e):
        d = self.embed_dim
        norm = lambda name: RMSNorm(d, eps=self.norm_eps, name=name,
                                    unit_offset=self.norm_unit_offset)
        e, x = norm("enorm")(e.astype(x.dtype)), norm("hnorm")(x)
        with jax.named_scope("mtp_eh"):
            w = _Kernel((2 * d, d), name="eh_proj")().astype(x.dtype)
            return jnp.dot(e, w[:d]) + jnp.dot(x, w[d:])


class MultiTokenPrediction(nn.Module):
    pattern: str        # the block's layer kinds (``hybrid_decoder.TABLE``)
    embed_dim: int
    norm_eps: float
    sizes: dict
    remat: bool = True
    norm_unit_offset: bool = False
    scaled_merge: bool = False

    @nn.compact
    def __call__(self, x, e):
        """``x`` (B, L, d) the decoder's final-normed stream, ``e`` (B, L,
        d) the embedding of each position's NEXT token -> ``(out, stats)``:
        the module's final-normed stream and its block's stats
        (``stat_names(pattern)``)."""
        names = stat_names(self.pattern)
        wrap = _remat if self.remat else (lambda cls: cls)
        u = wrap(_Join)(self.embed_dim, self.norm_eps, self.norm_unit_offset,
                        name="join")(x, e)
        stats = jnp.zeros((len(names),), jnp.float32)
        side = None
        for j, kind in enumerate(self.pattern):
            u, s, side = wrap(HybridBlock)(
                kind=kind, name=f"layers_{j}", embed_dim=self.embed_dim,
                norm_eps=self.norm_eps, sizes=self.sizes,
                norm_unit_offset=self.norm_unit_offset,
                scaled_merge=self.scaled_merge, stats=names)(u, side)
            stats = stats + s
        out = RMSNorm(self.embed_dim, eps=self.norm_eps, name="final_norm",
                      unit_offset=self.norm_unit_offset)(u)
        return out, stats
