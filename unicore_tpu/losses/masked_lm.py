"""Masked-LM loss (reference /root/reference/unicore/losses/masked_lm.py:12-66).

The reference projects only the masked positions (boolean advanced indexing,
model.py:183-194) — a dynamic shape.  TPU-native design: the model receives
the boolean ``masked_tokens`` map and the loss weights the per-position NLL by
it, so XLA sees static shapes; the flagship models additionally support a
fixed-size masked-position gather (``max_masked`` padding) for the
memory-saving variant.
"""

import jax
import jax.numpy as jnp

from unicore_tpu.logging import metrics
from . import register_loss
from .unicore_loss import UnicoreLoss


@register_loss("masked_lm")
class MaskedLMLoss(UnicoreLoss):
    def __init__(self, task):
        super().__init__(task)
        self.padding_idx = task.dictionary.pad()
        # static bound on masked positions per row: the masking dataset
        # draws int(mask_prob * (sz - 2) + u) <= int(mask_prob * L) + 1
        self.mask_prob = getattr(task.args, "mask_prob", 0.15) if task.args else 0.15

    def forward(self, model, params, sample, rngs=None, train=True):
        target = sample["target"]
        masked_tokens = target != self.padding_idx
        sample_size = jnp.sum(masked_tokens).astype(jnp.float32)

        if getattr(model, "supports_masked_gather", False):
            return self._forward_gather(
                model, params, sample, target, masked_tokens, sample_size,
                rngs, train,
            )

        logits, aux = self._apply_model(
            model, params,
            **sample["net_input"],
            masked_tokens=masked_tokens,
            train=train,
            rngs=rngs,
        )
        if isinstance(logits, tuple):
            logits = logits[0]
        loss = self._masked_nll(logits, target, masked_tokens)
        loss = loss + aux * sample_size
        return loss, sample_size, self._logging(loss, target, sample_size)

    @staticmethod
    def _masked_nll(logits, target, valid):
        """Summed NLL of ``target`` over the positions ``valid``.  Under a
        ``loss`` scope: no module's name stack covers the loss, and a
        device profile by scope (telemetry/hlo_scopes.py) should say whose
        the log-softmax over the vocabulary is."""
        with jax.named_scope("loss"):
            lprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            safe_target = jnp.where(valid, target, 0)
            nll = -jnp.take_along_axis(
                lprobs, safe_target[..., None], axis=-1
            )[..., 0]
            return jnp.sum(jnp.where(valid, nll, 0.0))

    # hook: the MoE variant collects sown auxiliary losses here
    def _apply_model(self, model, params, **kwargs):
        return model.apply(params, **kwargs), 0.0

    def _forward_gather(
        self, model, params, sample, target, masked_tokens, sample_size,
        rngs, train,
    ):
        """Project only the masked positions (fixed-size gather) — the
        static-shape form of the reference's boolean indexing
        (examples/bert/model.py:183-194)."""
        bsz, seq_len = target.shape
        n_masked = min(seq_len, int(self.mask_prob * seq_len) + 2)
        # top_k on the 0/1 mask: returns the masked positions first (ties
        # broken by lowest index), padded with unmasked positions
        vals, positions = jax.lax.top_k(masked_tokens.astype(jnp.int32), n_masked)
        valid = vals > 0
        logits, aux = self._apply_model(
            model, params,
            **sample["net_input"],
            masked_tokens=masked_tokens,
            masked_positions=positions,
            train=train,
            rngs=rngs,
        )
        if isinstance(logits, tuple):
            logits = logits[0]
        gathered_target = jnp.take_along_axis(target, positions, axis=1)
        loss = self._masked_nll(logits, gathered_target, valid)
        loss = loss + aux * sample_size
        return loss, sample_size, self._logging(loss, target, sample_size)

    def _logging(self, loss, target, sample_size):
        return {
            "loss": loss,
            "bsz": jnp.asarray(target.shape[0], dtype=jnp.float32),
            "sample_size": sample_size,
            "seq_len": jnp.asarray(
                target.shape[1] * target.shape[0], dtype=jnp.float32
            ),
        }

    @staticmethod
    def reduce_metrics(logging_outputs, split="train") -> None:
        loss_sum = sum(log.get("loss", 0) for log in logging_outputs)
        bsz = sum(log.get("bsz", 0) for log in logging_outputs)
        sample_size = sum(log.get("sample_size", 0) for log in logging_outputs)
        seq_len = sum(log.get("seq_len", 0) for log in logging_outputs)
        metrics.log_scalar(
            "loss", loss_sum / sample_size / jnp.log(2), sample_size, round=3
        )
        metrics.log_scalar("seq_len", seq_len / bsz, 1, round=3)


@register_loss("masked_lm_moe")
class MaskedLMMoELoss(MaskedLMLoss):
    """Masked LM + the router load-balance auxiliary loss sown by MoE
    layers (modules/moe.py).  Use with --arch bert_moe_* / --moe-experts."""

    def __init__(self, task, moe_aux_loss_weight: float = 0.01):
        super().__init__(task)
        self.moe_aux_loss_weight = moe_aux_loss_weight

    @classmethod
    def add_args(cls, parser):
        parser.add_argument(
            "--moe-aux-loss-weight", default=0.01, type=float,
            help="weight of the MoE router load-balance loss",
        )

    def _apply_model(self, model, params, **kwargs):
        out, mod_vars = model.apply(
            params, mutable=("losses", "metrics"), **kwargs
        )
        sown = jax.tree_util.tree_leaves(mod_vars.get("losses", {}))
        aux = sum(jnp.sum(a) for a in sown) if sown else jnp.zeros(())
        # router-health scalars sown to 'metrics' (moe_overflow per layer);
        # stashed for _logging — safe because forward() always runs
        # _apply_model then _logging within one trace
        over = jax.tree_util.tree_leaves(mod_vars.get("metrics", {}))
        self._moe_logs = {
            "moe_aux": jnp.sum(aux),
            "moe_overflow": (
                sum(jnp.mean(o) for o in over) / len(over)
                if over else jnp.zeros(())
            ),
        }
        return out, self.moe_aux_loss_weight * aux

    def _logging(self, loss, target, sample_size):
        log = super()._logging(loss, target, sample_size)
        # scaled by bsz so summing across micro-batches/hosts then dividing
        # by total bsz in reduce_metrics recovers the mean fraction
        for k, v in getattr(self, "_moe_logs", {}).items():
            log[k] = v * log["bsz"]
        return log

    @staticmethod
    def reduce_metrics(logging_outputs, split="train") -> None:
        MaskedLMLoss.reduce_metrics(logging_outputs, split)
        bsz = sum(log.get("bsz", 0) for log in logging_outputs)
        if bsz > 0:
            over = sum(log.get("moe_overflow", 0) for log in logging_outputs)
            aux = sum(log.get("moe_aux", 0) for log in logging_outputs)
            metrics.log_scalar("moe_overflow", over / bsz, 1, round=4)
            metrics.log_scalar("moe_aux", aux / bsz, 1, round=4)

    @staticmethod
    def logging_outputs_can_be_summed(is_train) -> bool:
        return True
