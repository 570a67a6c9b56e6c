"""Shifted (next-token) cross-entropy for causal LMs.

The model predicts position t+1 from positions <= t, so the loss pairs
``logits[:, :-1]`` with ``target[:, 1:]`` and masks pad targets — the
causal-LM counterpart of losses/cross_entropy.py, matching the
tasks/causal_lm.py contract (target == input token stream).

A model that states ``loss_chunk`` (models/hybrid_lm.py) is asked for its
final hidden states instead of its logits, and the output head and the
loss run over ``loss_chunk`` tokens at a time (:func:`chunked_lm_nll`): at
8,192 x 16,384 the float32 logits alone would be 512 MB, and as much again
for their gradient.  No chunk's logits are kept for the backward pass and
none are made twice: the loss is a sum, so a chunk's ``softmax - onehot``
is formed while its logits are alive and multiplied into the hidden
states' gradient and the head's there, and the backward pass scales those
two by the loss's cotangent.  What such a model returns beside the hidden states
(an expert layer's routing stats, an attention layer's key counts) goes
into the logging output.

A model that states ``num_pred_heads`` = M > 1 (models/evabyte.py) predicts
the next M tokens at every position: its head's columns are M blocks of
the vocabulary, block ``m`` at position ``t`` is scored against token
``t + m`` (m = 1 .. M), a target past the row's end (or a pad) does not
count, and every (position, head) pair that counts weighs the same: the
loss is their sum and the sample size their number.

A model that trains a prediction module (``modules/mtp.py``) returns more
than one stream of hidden states and states ``ahead = ((name, weight),)``:
the stream after the decoder's is scored by the SAME head against the token
one further ahead (``t + 2``; the last two positions of a row have none),
under the scope ``name``, and

    loss = nll_main + weight (n_main / n_name) nll_name,  sample size n_main

so that the module's MEAN loss is added at ``weight``.  The head's kernel
receives both passes' gradients.  The logging output then holds
``nll_loss`` (the decoder's own sum) and ``<name>_loss`` (the stream's,
scaled to the main pass's sample size) beside ``loss``.
"""

import functools

import jax
import jax.numpy as jnp

from unicore_tpu.logging import metrics
from unicore_tpu.modules import mtp
from unicore_tpu.modules.hybrid_decoder import LOGS, MARKS
from . import register_loss
from .unicore_loss import UnicoreLoss


def _chunks(x, target, valid, chunk):
    """``x`` (T, d), ``target`` and ``valid`` (T[, M]) as ``T / chunk``
    chunks of ``chunk`` tokens, a tail padded with tokens that do not
    count."""
    T, d = x.shape
    pad = (-T) % chunk
    if pad:
        rows = ((0, pad),) + ((0, 0),) * (target.ndim - 1)
        x = jnp.pad(x, ((0, pad), (0, 0)))
        target = jnp.pad(target, rows)
        valid = jnp.pad(valid, rows)
    n = (T + pad) // chunk
    return (
        x.reshape(n, chunk, d), target.reshape((n, chunk) + target.shape[1:]),
        valid.reshape((n, chunk) + valid.shape[1:]),
    )


def _chunk_nll(kernel, xc, tc, vc):
    """One chunk's summed loss, its float32 logits ``(chunk[, M], V)`` (the
    product's accumulator, not a rounded copy) and their log-sum-exp."""
    with jax.named_scope("lm_head"):
        logits = jnp.dot(xc, kernel, preferred_element_type=jnp.float32)
    with jax.named_scope("loss"):
        logits = logits.reshape(tc.shape + (-1,))
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, tc[..., None], axis=-1
        )[..., 0]
        return jnp.sum(jnp.where(vc, lse - picked, 0.0)), logits, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def chunked_lm_nll(x, kernel, target, valid, chunk):
    """Summed next-token negative log-likelihood with the logits of only
    ``chunk`` tokens alive at a time.  ``x`` (T, d) hidden states, ``kernel``
    (d, V), ``target`` (T,) and ``valid`` (T,) already shifted; or
    ``kernel`` (d, M * V) with ``target`` and ``valid`` (T, M), column
    ``m`` the head's ``m``-th block of ``V`` logits' own target.  Each
    chunk's logits are float32 and are made once, differentiated or not.

    Differentiated, the one walk over the chunks forms each chunk's
    ``softmax - onehot`` beside its logits and multiplies it into ``x``'s
    gradient and the kernel's there (three vocabulary-sized products a
    chunk, the kernel's gradient summed over the chunks in float32); what
    is kept for the backward pass is those two gradients at a cotangent of
    1, and the backward pass multiplies them by the loss's cotangent.  The
    loss is a sum, so that cotangent is one scalar; it scales the products'
    float32 results, where ``jax.grad`` of the plain form scales
    ``softmax - onehot`` before the products round it: at a cotangent of 1
    nothing differs, at another the two agree to a rounding of ``x``'s
    dtype.  ``x``'s gradient is kept in ``x``'s dtype, or in float32 for a
    float16 ``x``, whose small gradients only the scale still to come lifts
    into float16's range.  There is no forward-mode rule."""
    kernel = kernel.astype(x.dtype)

    def one(loss, args):
        return loss + _chunk_nll(kernel, *args)[0], None

    return jax.lax.scan(
        one, jnp.zeros((), jnp.float32), _chunks(x, target, valid, chunk)
    )[0]


def _chunked_lm_nll_fwd(x, kernel, target, valid, chunk):
    held = kernel.astype(x.dtype)
    keep = jnp.float32 if x.dtype == jnp.float16 else x.dtype

    def one(carry, args):
        loss, dkernel = carry
        xc, tc, vc = args
        nll, logits, lse = _chunk_nll(held, xc, tc, vc)
        with jax.named_scope("loss"):
            softmax = jnp.exp(logits - lse[..., None])
            onehot = jax.nn.one_hot(tc, logits.shape[-1], dtype=logits.dtype)
            dlogits = jnp.where(
                vc[..., None], softmax - onehot, 0.0
            ).reshape(xc.shape[0], -1)
        with jax.named_scope("lm_head"):
            # float32 ``dlogits`` against operands of ``x``'s dtype at the
            # default precision: what the transposes of the logits'
            # product are handed
            dxc = jax.lax.dot_general(
                dlogits, held, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dkernel += jax.lax.dot_general(
                xc, dlogits, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        return (loss + nll, dkernel), dxc.astype(keep)

    (loss, dkernel), dx = jax.lax.scan(
        one,
        (jnp.zeros((), jnp.float32), jnp.zeros(kernel.shape, jnp.float32)),
        _chunks(x, target, valid, chunk),
    )
    dx = dx.reshape(-1, x.shape[1])[:x.shape[0]]
    # zero rows of each input: their dtypes, which the cotangents take
    return loss, (dx, dkernel, x[:0], kernel[:0])


def _chunked_lm_nll_bwd(chunk, kept, g):
    dx, dkernel, x, kernel = kept
    return (
        (dx * g).astype(x.dtype), (dkernel * g).astype(kernel.dtype),
        None, None,
    )


chunked_lm_nll.defvjp(_chunked_lm_nll_fwd, _chunked_lm_nll_bwd)


def shifted_targets(target, heads, pad_idx):
    """``target`` (B, L) -> what position ``t`` predicts: (B, L), token
    ``t + 1``, for one head; (B, L, heads), tokens ``t + 1 .. t + heads``,
    for more.  A target past the row's end is ``pad_idx``."""
    B = target.shape[0]
    ahead = lambda m: jnp.concatenate(
        [target[:, m:], jnp.full((B, m), pad_idx, target.dtype)], axis=1
    )
    if heads == 1:
        return ahead(1)
    return jnp.stack([ahead(m) for m in range(1, heads + 1)], axis=-1)


@register_loss("lm_cross_entropy")
class LMCrossEntropyLoss(UnicoreLoss):
    def __init__(self, task):
        super().__init__(task)
        self.padding_idx = task.dictionary.pad()

    def forward(self, model, params, sample, rngs=None, train=True):
        heads = getattr(model, "num_pred_heads", 1)
        if getattr(model, "loss_chunk", 0) or heads > 1:
            return self._forward_chunked(
                model, params, sample, rngs, train, heads
            )
        logits = model.apply(
            params, **sample["net_input"], train=train, rngs=rngs
        )
        if isinstance(logits, tuple):
            logits = logits[0]
        target = sample["target"][:, 1:]
        valid = target != self.padding_idx
        lprobs = jax.nn.log_softmax(
            logits[:, :-1].astype(jnp.float32), axis=-1
        )
        safe_target = jnp.where(valid, target, 0)
        nll = -jnp.take_along_axis(
            lprobs, safe_target[..., None], axis=-1
        )[..., 0]
        loss = jnp.sum(jnp.where(valid, nll, 0.0))
        sample_size = jnp.sum(valid).astype(jnp.float32)
        logging_output = {
            "loss": loss,
            "sample_size": sample_size,
            "bsz": jnp.asarray(target.shape[0], dtype=jnp.float32),
        }
        return loss, sample_size, logging_output

    def _forward_chunked(self, model, params, sample, rngs, train, heads=1):
        x, extra = model.apply(
            params, **sample["net_input"], train=train, rngs=rngs,
            features_only=True,
        )
        further = ()
        if isinstance(x, tuple):  # the decoder's stream, then those ahead
            x, further = x[0], x[1:]
        B, L, d = x.shape

        def nll_of(x, target):
            """``x`` against ``target``, position for position: the summed
            NLL and the targets that count."""
            target = target.reshape((B * L,) + target.shape[2:])
            valid = target != self.padding_idx
            return chunked_lm_nll(
                x.reshape(B * L, d), model.head_kernel(params),
                jnp.where(valid, target, 0), valid,
                int(model.loss_chunk) or B * L,
            ), jnp.sum(valid).astype(jnp.float32)

        # every position predicts its successor(s); the last has none
        target = shifted_targets(sample["target"], heads, self.padding_idx)
        loss, sample_size = nll_of(x, target)
        parts = {"nll_loss": loss} if further else {}
        for z, (name, weight) in zip(further, model.ahead):
            # each further stream predicts one token further ahead
            target = shifted_targets(target, 1, self.padding_idx)
            with jax.named_scope(name):
                nll, size = nll_of(z, target)
            # the stream's mean NLL, over the main pass's sample size
            parts[f"{name}_loss"] = nll * (sample_size / jnp.maximum(size, 1.0))
            loss = loss + weight * parts[f"{name}_loss"]
        logging_output = {
            "loss": loss,
            "sample_size": sample_size,
            "bsz": jnp.asarray(B, dtype=jnp.float32),
            **parts,
            **extra,
        }
        return loss, sample_size, logging_output

    @staticmethod
    def reduce_metrics(logging_outputs, split="train") -> None:
        loss_sum = sum(log.get("loss", 0) for log in logging_outputs)
        sample_size = sum(log.get("sample_size", 0) for log in logging_outputs)
        metrics.log_scalar(
            "loss", loss_sum / sample_size / jnp.log(2), sample_size, round=3
        )
        for log_stats in LOGS + mtp.LOGS:
            log_stats(logging_outputs)

    @staticmethod
    def trace_marks(sums):
        """What a profiler capture is told of one update, from that
        update's summed logging output (``Trainer._mark_update``):
        ``{name: stats}``, each entry one ``unicore:<name>`` mark.  Which
        marks, from which of the stats a model logs, is for the makers of
        those stats to say, each beside the function that counts them; the
        layer kinds' table lists them (``modules/hybrid_decoder.MARKS``),
        and a stat none of them knows is in no mark."""
        marks = {}
        for mark_of in MARKS + mtp.MARKS:
            marks.update(mark_of(sums))
        return marks

    @staticmethod
    def logging_outputs_can_be_summed(is_train) -> bool:
        return True
