"""EvaByte (``model_type: evabyte``, ``attention_class: eva``): a
tokenizer-free byte-level causal LM.  A byte embedding, pre-norm layers of
EVA attention (exact inside a window, one learned-pooled key/value per
chunk of every earlier window, one softmax; rotary positions) and a gated
SiLU feed-forward layer, RMSNorm with a unit offset, and an untied head
that predicts the next ``--num-pred-heads`` bytes at every position
(``lm_cross_entropy`` sums the cross-entropy over the shifted targets).

The layers run through :class:`~unicore_tpu.modules.hybrid_decoder.
HybridDecoder` as the pattern ``AF`` x layers: one scanned body, each
rematerialized in the backward pass, one final norm.

Arguments carry the names of the published ``config.json`` keys and state
the MODEL.  Two more say what of it is HELD in this process, the whole
model by default, or one chip's share of a deployment: ``--layers-held``
(a stretch of the stack; the rest lies on further pipeline stages) and
``--attention-shares`` (the attention heads divided that many ways, this
process holding one share: its columns of ``q_proj`` / ``k_proj`` /
``v_proj``, its rows of the pooling vectors and of ``out_proj``; the
feed-forward layers, norms, embedding and head are whole).
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
from unicore_tpu.modules.hybrid_decoder import HybridDecoder
from unicore_tpu.ops.eva_attention import key_counts

_init = nn.initializers.normal(0.02)


@register_model("evabyte")
class EvaByteModel(BaseUnicoreModel):
    vocab_size: int = 320
    padding_idx: int = 0
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    layers_held: int = 0
    num_attention_heads: int = 32
    attention_shares: int = 1
    intermediate_size: int = 11008
    hidden_act: str = "silu"
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rms_norm_eps: float = 1e-5
    norm_add_unit_offset: bool = True
    rope_theta: float = 1e5
    # memory
    remat: bool = True
    loss_chunk: int = 1024
    mlp_row_chunk: int = 0

    @classmethod
    def add_args(cls, parser):
        add = parser.add_argument
        add("--hidden-size", type=int)
        add("--num-hidden-layers", type=int)
        add("--layers-held", type=int,
            help="layers held here (0: all --num-hidden-layers)")
        add("--num-attention-heads", type=int)
        add("--attention-shares", type=int,
            help="the attention heads are divided this many ways and this "
                 "process holds one share")
        add("--intermediate-size", type=int)
        add("--hidden-act", type=str)
        add("--window-size", type=int,
            help="positions attended exactly; earlier windows are seen "
                 "through their chunks' summaries")
        add("--chunk-size", type=int,
            help="positions pooled into one key/value summary")
        add("--num-pred-heads", type=int,
            help="bytes predicted at every position (targets t+1 .. t+N)")
        add("--rms-norm-eps", type=float)
        add("--norm-add-unit-offset", type=utils.str_to_bool)
        add("--rope-theta", type=float)
        add("--remat", type=utils.str_to_bool,
            help="rematerialize each layer in the backward pass")
        add("--loss-chunk", type=int,
            help="tokens per chunk of the output head and loss (0: all "
                 "logits at once)")
        add("--mlp-row-chunk", type=int,
            help="tokens per chunk of the feed-forward layers (0: the "
                 "whole batch at once)")

    @classmethod
    def build_model(cls, args, task):
        evabyte_base_architecture(args)
        n = args.attention_shares
        if n < 1 or args.num_attention_heads % n:
            raise ValueError(
                f"--attention-shares {n} does not divide "
                f"{args.num_attention_heads} heads"
            )
        if not 0 <= args.layers_held <= args.num_hidden_layers:
            raise ValueError(
                f"--layers-held {args.layers_held} of "
                f"{args.num_hidden_layers} layers"
            )
        if args.hidden_size % args.num_attention_heads:
            raise ValueError(
                f"{args.num_attention_heads} heads do not divide a hidden "
                f"size of {args.hidden_size}"
            )
        fields = {f: getattr(args, f) for f in cls.__dataclass_fields__
                  if hasattr(args, f) and f not in ("name", "parent")}
        fields.update(vocab_size=len(task.dictionary),
                      padding_idx=task.dictionary.pad())
        return cls(**fields)

    @property
    def heads_held(self):
        return self.num_attention_heads // self.attention_shares

    def setup(self):
        self.embed_tokens = nn.Embed(
            self.vocab_size, self.hidden_size, embedding_init=_init,
            name="embed_tokens", param_dtype=jnp.float32,
        )
        layers = self.layers_held or self.num_hidden_layers
        self.decoder = HybridDecoder(
            pattern="AF" * layers,
            embed_dim=self.hidden_size,
            norm_eps=self.rms_norm_eps,
            norm_unit_offset=self.norm_add_unit_offset,
            eva=dict(
                num_heads=self.heads_held,
                head_dim=self.hidden_size // self.num_attention_heads,
                window_size=self.window_size, chunk_size=self.chunk_size,
                rope_theta=self.rope_theta,
            ),
            mlp=dict(ffn_dim=self.intermediate_size,
                     activation=self.hidden_act,
                     row_chunk=self.mlp_row_chunk),
            remat=self.remat,
            name="decoder",
        )
        self.lm_head = self.param(
            "lm_head", _init,
            (self.hidden_size, self.num_pred_heads * self.vocab_size),
            jnp.float32,
        )

    def __call__(self, src_tokens, train: bool = False,
                 features_only: bool = False, **kwargs):
        x, _ = self.decoder(self.embed_tokens(src_tokens))
        if features_only:
            return x, self.attention_counts(*src_tokens.shape)
        with jax.named_scope("lm_head"):
            # (B, L, num_pred_heads * vocab): head m's logits for byte t + m
            return jnp.dot(x, self.lm_head.astype(x.dtype),
                           preferred_element_type=jnp.float32)

    def attention_counts(self, rows, length):
        """What the loss logs of the attention's work, from shapes: per
        layer and head, summed over the batch's queries, the keys the
        kernel form scores and the keys a query may see
        (``ops/eva_attention.key_counts``), with the batch's windows and
        chunks."""
        counts = key_counts(length, self.window_size, self.chunk_size)
        out = dict(eva_keys_computed=counts["computed"],
                   eva_keys_visible=counts["visible"],
                   eva_windows=counts["windows"], eva_chunks=counts["chunks"],
                   eva_rows=1)
        return {k: jnp.asarray(rows * v, jnp.float32) for k, v in out.items()}

    def init_params(self, rng, sample):
        src_tokens = jnp.asarray(sample["net_input"]["src_tokens"])
        return strip_diagnostic_collections(
            self.init({"params": rng}, src_tokens, train=False)
        )


@register_model_architecture("evabyte", "evabyte")
def evabyte_base_architecture(args):
    """Unset sizes default to EvaByte's (6.5 B), whole."""
    for field, default in EvaByteModel.__dataclass_fields__.items():
        if field in ("name", "parent", "vocab_size", "padding_idx"):
            continue
        if getattr(args, field, None) is None:
            setattr(args, field, default.default)


@register_model_architecture("evabyte", "evabyte_tiny")
def evabyte_tiny_architecture(args):
    """Every mechanism at a size a CPU test holds: three layers, windows of
    32 positions in chunks of 4, four heads of 16, three bytes predicted."""
    tiny = dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
        intermediate_size=96, window_size=32, chunk_size=4, num_pred_heads=3,
        loss_chunk=48, mlp_row_chunk=64,
    )
    for field, value in tiny.items():
        if getattr(args, field, None) is None:
            setattr(args, field, value)
    evabyte_base_architecture(args)
