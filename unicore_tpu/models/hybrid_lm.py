"""What the decoders over :class:`~unicore_tpu.modules.hybrid_decoder.
HybridDecoder` share (``nemotron_h``, ``evabyte``, ``mellum``, ``laguna``,
``zaya``, ``joyai``): a token embedding, the decoder over the model's
``pattern``, and a float32 output head (untied, or the embedding itself
where a decoder states ``tie_word_embeddings`` and builds it: ``zaya``),
and, where a decoder states ``mtp_pattern``, a prediction module beside the
decoder that reads the same embedding and is scored by the same head
(``modules/mtp.py``: ``joyai``); their arguments; how they are built from an
argument namespace; how their architectures are registered.

A decoder is a subclass of :class:`HybridLM` that states what is its own
(docs/hybrid_lm.md, "Adding a decoder"):

- its fields: the keys of the published ``config.json`` under their own
  names, which state the MODEL, and those that say what of it is HELD in
  this process (the whole model by default, or one chip's share of a
  deployment).  Every field is an argument of the same name
  (:meth:`HybridLM.add_args`); :data:`HELP` has the help texts of those that
  more than one decoder has, the class's ``HELP`` those of its own;
- ``check()``: what the keys can say and the program does not build (a
  ``ValueError`` that names the keys);
- ``pattern``: the held layers in the decoder's characters, and
  ``layers()``: the norms' epsilon and the sizes each layer kind of the
  pattern takes;
- ``logged()``: what it logs of an update beside the loss.

The loss does not need all logits at once: ``features_only=True`` returns
the final hidden states (with a prediction module a tuple: the decoder's,
then the module's) and what the model logs, and ``lm_cross_entropy`` runs
head and loss over ``--loss-chunk`` tokens at a time.
"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu import utils
from unicore_tpu.models import MODEL_REGISTRY, register_model_architecture
from unicore_tpu.models.unicore_model import (
    BaseUnicoreModel,
    strip_diagnostic_collections,
)
from unicore_tpu.modules.gated_moe import BALANCINGS
from unicore_tpu.modules.hybrid_decoder import HybridDecoder, stat_names
from unicore_tpu.modules.mtp import MultiTokenPrediction
from unicore_tpu.ops.flash_attention import band_log

_init = nn.initializers.normal(0.02)

#: fields that are no arguments: the task's dictionary states the first two
_NO_ARGUMENTS = ("vocab_size", "padding_idx", "name", "parent")

_ARGUMENT_TYPES = {int: int, float: float, str: str, bool: utils.str_to_bool}

#: the arguments that take one of a few values
CHOICES = dict(router_balancing=BALANCINGS)

#: the help texts of the arguments more than one decoder has: what is held,
#: training's load-balancing rule, memory, the lists and the group
HELP = dict(
    layers_held="layers held here, from the first (0: all); the rest lie on "
                "further pipeline stages",
    attention_shares="every attention layer's query heads are divided this "
                     "many ways, with their KV heads where it has them (at "
                     "least one), and this process holds one share",
    num_experts_held="experts held here (0: all): a layer of experts routes "
                     "over all of them and computes the held ones' part",
    router_balancing="how the chosen set is balanced over the experts: none "
                     "(the top scores, as published) or batch_bias "
                     "(loss-free balancing's bias on the scores that "
                     "choose, solved anew on every batch; the weights still "
                     "the scores': modules/gated_moe.py)",
    remat="rematerialize each layer in the backward pass",
    loss_chunk="tokens per chunk of the output head and loss (0: all logits "
               "at once)",
    mlp_row_chunk="rows per chunk of a dense feed-forward layer (0: all "
                  "rows at once)",
    layer_types="JSON list, one of sliding_attention / full_attention a "
                "layer",
    rope_parameters="JSON group with a full_attention and a "
                    "sliding_attention rotary table (rope_type default or "
                    "yarn, each with an optional partial_rotary_factor)",
)


def parsed(value):
    """A list or group given as such, or as JSON text (the command line's
    and the benchmark's argument namespaces carry text)."""
    return json.loads(value) if isinstance(value, str) else value


def shares_divide(shares, heads, kv_heads=1):
    """Whether ``heads`` query heads on ``kv_heads`` KV heads can be divided
    ``shares`` ways: every share the same whole number of query heads, a
    whole number of them on each KV head it holds."""
    return (shares >= 1 and heads % shares == 0
            and (heads // shares) % max(1, kv_heads // shares) == 0)


def held_attention(heads, kv_heads, shares, **more):
    """``GroupedQueryAttention``'s sizes for one of ``shares`` shares of a
    layer's heads."""
    return dict(
        num_heads=heads // shares,
        # fewer KV heads than shares: the shares of one KV head's query
        # heads each hold a copy of it
        num_kv_heads=max(1, kv_heads // shares), **more)


class HybridLM(BaseUnicoreModel):
    vocab_size: int = 0
    padding_idx: int = 0
    # memory
    remat: bool = True
    loss_chunk: int = 1024

    #: the fields that are lists or groups, as JSON text
    GROUPS = ()
    #: the help texts of the class's own arguments, by field
    HELP = {}
    #: ``preferred_element_type`` of the full-logits product (None: the
    #: stream's dtype)
    logits_dtype = None
    #: the layer kinds of a prediction module's block (``modules/mtp.py``),
    #: for a decoder that trains one (``""``: none), and what the loss is
    #: told of the stream it returns: ``((name, weight),)``, the stream
    #: after the decoder's scored against the token one further ahead, its
    #: mean NLL added to the loss times ``weight``
    mtp_pattern = ""
    ahead = ()

    @classmethod
    def arguments(cls):
        """``{field: its dataclass field}`` of the fields that are arguments,
        each under its own name."""
        return {name: field for name, field in cls.__dataclass_fields__.items()
                if name not in _NO_ARGUMENTS}

    @classmethod
    def add_args(cls, parser):
        for name, field in cls.arguments().items():
            more = {"choices": CHOICES[name]} if name in CHOICES else {}
            parser.add_argument(
                "--" + name.replace("_", "-"),
                type=_ARGUMENT_TYPES[field.type],
                help=cls.HELP.get(name, HELP.get(name)), **more)

    @classmethod
    def fill(cls, args, over=None):
        """Every argument ``args`` leaves unset takes ``over``'s value, or
        the field's default: the published model, whole."""
        values = {name: field.default
                  for name, field in cls.arguments().items()}
        values.update(over or {})
        for name, value in values.items():
            if getattr(args, name, None) is None:
                setattr(args, name, value)

    @classmethod
    def build_model(cls, args, task):
        cls.fill(args)
        for key in cls.GROUPS:
            value = getattr(args, key)
            if not isinstance(value, str):  # a namespace made from a config
                setattr(args, key, json.dumps(value))
        model = cls(**{name: getattr(args, name) for name in cls.arguments()},
                    vocab_size=len(task.dictionary),
                    padding_idx=task.dictionary.pad())
        model.check()  # raises on what the program does not build
        return model

    def check(self):
        """Raise ``ValueError`` on what the fields state and the program
        does not build."""

    @nn.nowrap
    def logged(self, stats, rows, length):
        """What the model logs of an update of ``rows`` rows of ``length``
        beside the loss, ``stats`` the decoder's: every key is an output of
        the step program, and whoever makes a stat names its keys.
        ``nn.nowrap`` (an override's too): what it computes stays in the
        model's own scope, with no ``<Model>.logged`` in its operations'
        paths."""
        return {}

    @property
    def head_columns(self):
        return self.vocab_size

    def setup(self):
        self.embed_tokens = nn.Embed(
            self.vocab_size, self.hidden_size, embedding_init=_init,
            name="embed_tokens", param_dtype=jnp.float32,
        )
        self.decoder = HybridDecoder(
            pattern=self.pattern, embed_dim=self.hidden_size,
            remat=self.remat, name="decoder", **self.layers(),
        )
        if self.mtp_pattern:
            if stat_names(self.mtp_pattern) != stat_names(self.pattern):
                raise ValueError(
                    f"a prediction block of kinds {self.mtp_pattern!r} "
                    f"returns other stats than the pattern {self.pattern!r}")
            self.mtp = MultiTokenPrediction(
                pattern=self.mtp_pattern, embed_dim=self.hidden_size,
                remat=self.remat, name="mtp", **self.layers(),
            )
        if not self.tied:
            self.lm_head = self.param(
                "lm_head", _init, (self.hidden_size, self.head_columns),
                jnp.float32,
            )

    def __call__(self, src_tokens, train: bool = False,
                 features_only: bool = False, **kwargs):
        emb = self.embed_tokens(src_tokens)
        x, stats = self.decoder(emb)
        streams = x
        # the full logits are the decoder's alone: the module runs for the
        # loss (and once to make its parameters)
        if self.mtp_pattern and (features_only or self.is_initializing()):
            # Emb(t_{i+1}): the row's embeddings one position early (the
            # last position has no next token, and no target either)
            early = jnp.concatenate(
                [emb[:, 1:], jnp.zeros_like(emb[:, :1])], axis=1)
            z, more = self.mtp(x, early)
            streams, stats = (x, z), stats + more
        if features_only:
            return streams, self.logged(stats, *src_tokens.shape)
        kernel = (self.embed_tokens.embedding.T if self.tied
                  else self.lm_head)
        with jax.named_scope("lm_head"):
            return jnp.dot(x, kernel.astype(x.dtype),
                           preferred_element_type=self.logits_dtype)

    @property
    def tied(self):
        """Whether the head is the embedding (``tie_word_embeddings``, for
        a decoder whose ``check()`` lets it through): the model then has no
        ``lm_head`` and its logits are ``x E^T``."""
        return bool(getattr(self, "tie_word_embeddings", False))

    @nn.nowrap
    def head_kernel(self, params):
        """The head's ``(hidden, columns)`` kernel out of ``params``, for a
        loss that runs the head itself (``lm_cross_entropy``'s chunks): the
        ``lm_head`` leaf or, tied, the embedding transposed, whose gradient
        is then the sum of the gather's and the head's."""
        tree = params["params"]
        if self.tied:
            return tree["embed_tokens"]["embedding"].T
        return tree["lm_head"]

    def band_heads(self):
        """``{"window": .., "full": ..}``, the query heads held on a layer
        under each band, for a model that logs them (its two kinds differ
        there); None: it logs none."""
        return None

    def band_counts(self, rows, length):
        """What a model whose ``S`` and ``G`` layers run under bands logs
        of their work (``ops/flash_attention.band_log``)."""
        layers = {"window": self.pattern.count("S"),
                  "full": self.pattern.count("G")}
        return band_log(rows, length, self.sliding_window, layers,
                        self.band_heads())

    def init_params(self, rng, sample):
        src_tokens = jnp.asarray(sample["net_input"]["src_tokens"])
        return strip_diagnostic_collections(
            self.init({"params": rng}, src_tokens, train=False)
        )


def register_architecture(model_name, arch_name, over=None):
    """Register ``arch_name`` for the model registered as ``model_name``:
    what the arguments leave unset is ``over``'s, then the published
    model's.  Returns the function that fills a namespace so."""
    cls = MODEL_REGISTRY[model_name]

    @register_model_architecture(model_name, arch_name)
    def architecture(args):
        cls.fill(args, over)

    return architecture
