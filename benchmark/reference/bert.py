"""Plain reference for ``bert_base``: BERT (Devlin et al. 2018) as upstream
Uni-Core's ``examples/bert`` builds it.

Departures from the paper, all upstream's: a learned bucketed
relative-position bias added to every layer's attention scores (T5-style
buckets: 32 bins, exact up to 8, log-spaced to 128, signed) beside the
learned absolute positions; no segment embeddings and no next-sentence
head; embeddings are zeroed at padding positions after the embedding
LayerNorm; the LM head's output projection is tied to the token
embedding.  Post-LN, exact (erf) GELU, LayerNorm eps 1e-5.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import plain


def param_shapes(cfg, hyper):
    a = cfg
    d, f, H = a["encoder_embed_dim"], a["encoder_ffn_embed_dim"], a["encoder_attention_heads"]
    V = int(hyper["vocab_size"])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    norm = lambda n: {"weight": s(n), "bias": s(n)}
    enc = {
        "emb_layer_norm": norm(d),
        "relative_attention_bias": {"embedding": s(32, H)},
    }
    for i in range(a["encoder_layers"]):
        enc[f"layers_{i}"] = plain.layer_shapes(d, f)
    return {"params": {
        "embed_tokens": {"embedding": s(V, d)},
        "embed_positions": {"embedding": s(a["max_seq_len"], d)},
        "sentence_encoder": enc,
        "lm_head": {"dense": {"kernel": s(d, d), "bias": s(d)},
                    "layer_norm": norm(d), "bias": s(V)},
    }}


def rel_pos_buckets(L, bins=32, max_distance=128):
    """(L, L) bucket of (key position - query position), shifted to start
    at 0: half the bins for each sign, exact for small distances and
    log-spaced beyond."""
    rel = np.arange(L)[None, :] - np.arange(L)[:, None]
    half = bins // 2
    n = np.abs(rel)
    exact = half // 2
    big = exact + np.ceil(
        np.log(np.maximum(n, 1) / exact) / math.log((max_distance - 1) / exact)
        * (half - 1 - exact)
    ).astype(np.int64)
    big = np.minimum(big, half - 1)
    bucket = np.where(n < exact, n, big) * np.sign(rel)
    return bucket - bucket.min()


def logits(params, cfg, tokens, pad_idx, precision="float32", positions=None):
    """(B, L) tokens -> (B, L, V) logits (or at ``positions`` only)."""
    a = cfg
    P = params["params"]
    L = tokens.shape[1]
    H = a["encoder_attention_heads"]
    pad = tokens == pad_idx
    x = P["embed_tokens"]["embedding"][tokens] + P["embed_positions"]["embedding"][:L]
    enc = P["sentence_encoder"]
    x = plain.layer_norm(x, enc["emb_layer_norm"])
    x = x * (1.0 - pad[..., None].astype(x.dtype))
    bias = enc["relative_attention_bias"]["embedding"][rel_pos_buckets(L)]
    bias = bias.transpose(2, 0, 1)[None]  # (1, H, L, L)
    for i in range(a["encoder_layers"]):
        x, _ = plain.encoder_layer(
            x, enc[f"layers_{i}"], H, bias, pad, True, precision
        )
    if positions is not None:
        x = jnp.take_along_axis(x, positions[..., None], axis=1)
    h = plain.gelu(plain.dense(x, P["lm_head"]["dense"], precision))
    h = plain.layer_norm(h, P["lm_head"]["layer_norm"])
    out = plain.dense(h, {"kernel": P["embed_tokens"]["embedding"].T}, precision)
    return out + P["lm_head"]["bias"]


def loss_sum(params, cfg, batch, pad_idx, precision="float32", at_most=None):
    """Summed masked-LM negative log-likelihood of a block of rows.  With
    ``at_most``, the head runs only at each row's masked positions (no row
    has more than ``at_most``): the same sum, a sixth of the work."""
    tokens, target = batch["net_input"]["src_tokens"], batch["target"]
    if at_most is None:
        lg = logits(params, cfg, tokens, pad_idx, precision)
        return plain.masked_nll_sum(lg, target, pad_idx)
    order = jnp.argsort(target == pad_idx, axis=1, stable=True)[:, :at_most]
    lg = logits(params, cfg, tokens, pad_idx, precision, positions=order)
    return plain.masked_nll_sum(
        lg, jnp.take_along_axis(target, order, axis=1), pad_idx
    )


def train_check(cfg, hyper, batches, seed, rows, precision="float32"):
    pad_idx = int(hyper["pad_idx"])
    most = max(
        int((np.asarray(b["target"]) != pad_idx).sum(axis=1).max())
        for b in batches
    )
    at_most = -(-most // 8) * 8
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: loss_sum(p, cfg, b, pad_idx, precision, at_most)
    ))

    def batch_grad(params, batch):
        total, grads = 0.0, None
        for block in plain.row_blocks(batch, rows):
            block = jax.tree_util.tree_map(
                lambda a: np.asarray(a, np.int32), block
            )
            l, g = grad(params, block)
            total += float(l)
            grads = g if grads is None else plain.tree_add(grads, g)
        size = float((np.asarray(batch["target"]) != pad_idx).sum())
        return total, size, grads

    return plain.follow(param_shapes(cfg, hyper), seed, hyper, batches, batch_grad)
