"""Plain reference for ``unimol``: Uni-Mol (Zhou et al., ICLR 2023), the
molecular pretraining model of upstream ``unimol/models/unimol.py``.

Atom-type embeddings; every pair of positions gets a Gaussian-basis
expansion of its distance (an affine map per pair of atom types, then 128
Gaussians with learned means and widths), projected to one bias per head.
A pre-LN Transformer whose every layer adds the running pair bias to its
attention scores and hands the scores (bias included) on as the next
layer's bias.  Three heads: masked-atom logits (tied to the embedding), an
SE(3)-equivariant coordinate update (pair weights times the difference
vectors, averaged over the molecule's other atoms), and a symmetric
distance head.  Loss: 1 x token NLL + 5 x coordinate smooth-L1 +
10 x distance smooth-L1 over the masked atoms, plus 0.01 x the RMS of the
atom representation and 0.01 x the RMS of the pair update, as the
configuration's file states them.

Departures from the paper, all this framework's and noted where they act:
the two norm regularisers are plain RMS values (upstream penalises their
distance from 1); the RMS of the pair update averages over every entry of
the padded (B, H, L, L) array.

The two RMS terms couple all rows of a batch, so the batch is followed in
two passes of row blocks: one forward pass for the two sums of squares,
then a gradient pass in which each block's share of those sums carries the
derivative of the square root at the batch's totals.  The result is the
gradient of the whole batch's loss, exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import plain


def param_shapes(cfg, hyper):
    d, f, H = (cfg["encoder_embed_dim"], cfg["encoder_ffn_embed_dim"],
               cfg["encoder_attention_heads"])
    K, V = cfg["gaussian_kernels"], int(hyper["vocab_size"])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    norm = lambda n: {"weight": s(n), "bias": s(n)}
    lin = lambda i, o: {"kernel": s(i, o), "bias": s(o)}
    enc = {"emb_layer_norm": norm(d), "final_layer_norm": norm(d),
           "final_head_layer_norm": norm(H)}
    for i in range(cfg["encoder_layers"]):
        enc[f"layers_{i}"] = plain.layer_shapes(d, f)
    return {"params": {
        "embed_tokens": {"embedding": s(V, d)},
        "gbf": {"mul": {"embedding": s(V * V, 1)},
                "bias": {"embedding": s(V * V, 1)},
                "means": s(K), "stds": s(K)},
        "gbf_proj": {"linear1": lin(K, K), "linear2": lin(K, H)},
        "encoder": enc,
        "lm_head": {"dense": lin(d, d), "layer_norm": norm(d), "bias": s(V)},
        "pair2coord_proj": {"linear1": lin(H, H), "linear2": lin(H, 1)},
        "dist_head": {"dense": lin(H, H), "layer_norm": norm(H),
                      "out_proj": lin(H, 1)},
    }}


def smooth_l1(pred, target):
    diff = jnp.abs(pred - target)
    return jnp.where(diff < 1.0, 0.5 * diff * diff, diff - 0.5)


def two_layer(x, p, precision):
    return plain.dense(plain.gelu(plain.dense(x, p["linear1"], precision)),
                       p["linear2"], precision)


def forward(params, cfg, net_input, pad_idx, precision="float32"):
    """Returns (logits, predicted distances, predicted coordinates, the sum
    of squares of the atom representation at real atoms, the sum of
    squares of the pair update)."""
    P = params["params"]
    tokens = net_input["src_tokens"]
    coord = net_input["src_coord"]
    H = cfg["encoder_attention_heads"]
    pad = tokens == pad_idx
    keep = 1.0 - pad.astype(jnp.float32)
    x = P["embed_tokens"]["embedding"][tokens]

    edge = net_input["src_edge_type"]
    g = (P["gbf"]["mul"]["embedding"][edge][..., 0] * net_input["src_distance"]
         + P["gbf"]["bias"]["embedding"][edge][..., 0])
    std = jnp.abs(P["gbf"]["stds"]) + 1e-5
    feat = jnp.exp(-0.5 * jnp.square((g[..., None] - P["gbf"]["means"]) / std))
    feat = feat / (std * jnp.sqrt(2 * jnp.pi))
    graph_bias = two_layer(feat, P["gbf_proj"], precision).transpose(0, 3, 1, 2)

    enc = P["encoder"]
    x = plain.layer_norm(x, enc["emb_layer_norm"]) * keep[..., None]
    bias = graph_bias
    for i in range(cfg["encoder_layers"]):
        x, bias = plain.encoder_layer(
            x, enc[f"layers_{i}"], H, bias, pad, False, precision
        )
    x = plain.layer_norm(x, enc["final_layer_norm"])
    sq_x = jnp.sum(jnp.square(x * keep[..., None]))

    pair_pad = pad[:, None, :, None] | pad[:, None, None, :]
    pair = jnp.where(pair_pad, 0.0, bias)
    delta = jnp.where(pair_pad, 0.0, bias - graph_bias)
    sq_delta = jnp.sum(jnp.square(delta))
    delta = plain.layer_norm(
        delta.transpose(0, 2, 3, 1), enc["final_head_layer_norm"]
    )  # (B, L, L, H)

    h = plain.gelu(plain.dense(x, P["lm_head"]["dense"], precision))
    h = plain.layer_norm(h, P["lm_head"]["layer_norm"])
    logits = plain.dense(
        h, {"kernel": P["embed_tokens"]["embedding"].T}, precision
    ) + P["lm_head"]["bias"]

    weights = two_layer(delta, P["pair2coord_proj"], precision)[..., 0]
    diff = coord[:, :, None, :] - coord[:, None, :, :]
    others = jnp.maximum(jnp.sum(keep, axis=1) - 1.0, 1.0)[:, None, None]
    coord_pred = coord + jnp.sum(weights[..., None] * diff, axis=2) / others

    dh = P["dist_head"]
    y = plain.gelu(plain.dense(pair.transpose(0, 2, 3, 1), dh["dense"], precision))
    y = plain.layer_norm(y, dh["layer_norm"])
    y = plain.dense(y, dh["out_proj"], precision)[..., 0]
    dist_pred = 0.5 * (y + y.transpose(0, 2, 1))
    return logits, dist_pred, coord_pred, sq_x, sq_delta


def block_terms(params, cfg, block, pad_idx, pairs_scale, precision):
    """A block's share of the additive losses (already weighted; the
    distance term scaled by the batch's sample size over its pair count),
    and its two sums of squares."""
    ni, tg = block["net_input"], block["target"]
    logits, dist_pred, coord_pred, sq_x, sq_delta = forward(
        params, cfg, ni, pad_idx, precision
    )
    target = tg["tokens_target"]
    masked = target != pad_idx
    token = plain.masked_nll_sum(logits, target, pad_idx)
    coord = jnp.sum(jnp.where(
        masked, smooth_l1(coord_pred, tg["coord_target"]).sum(-1), 0.0
    ))
    pair_mask = masked[:, :, None] & (ni["src_tokens"] != pad_idx)[:, None, :]
    dist = jnp.sum(jnp.where(
        pair_mask, smooth_l1(dist_pred, tg["distance_target"]), 0.0
    ))
    additive = (cfg["masked_token_loss"] * token
                + cfg["masked_coord_loss"] * coord
                + cfg["masked_dist_loss"] * dist * pairs_scale)
    return additive, sq_x, sq_delta


def _cast(block):
    return jax.tree_util.tree_map(
        lambda a: np.asarray(
            a, np.int32 if np.issubdtype(np.asarray(a).dtype, np.integer)
            else np.float32
        ), block,
    )


def train_check(cfg, hyper, batches, seed, rows, precision="float32"):
    pad_idx = int(hyper["pad_idx"])
    d, H = cfg["encoder_embed_dim"], cfg["encoder_attention_heads"]

    terms = jax.jit(
        lambda p, b, ps: block_terms(p, cfg, b, pad_idx, ps, precision)
    )

    def weighted(p, b, ps, kx, kd):
        additive, sq_x, sq_delta = block_terms(p, cfg, b, pad_idx, ps, precision)
        return additive + kx * sq_x + kd * sq_delta

    grad = jax.jit(jax.grad(weighted))

    def batch_grad(params, batch):
        tokens = np.asarray(batch["net_input"]["src_tokens"])
        target = np.asarray(batch["target"]["tokens_target"])
        masked, real = target != pad_idx, tokens != pad_idx
        size = max(float(masked.sum()), 1.0)
        pairs = max(float((masked[:, :, None] & real[:, None, :]).sum()), 1.0)
        B, L = tokens.shape
        n_x = float(real.sum()) * d + 1e-6
        n_delta = float(B * H * L * L)
        scale = np.float32(size / pairs)
        blocks = [_cast(b) for b in plain.row_blocks(batch, rows)]

        additive = sq_x = sq_delta = 0.0
        for b in blocks:
            a, sx, sd = terms(params, b, scale)
            additive, sq_x, sq_delta = (
                additive + float(a), sq_x + float(sx), sq_delta + float(sd)
            )
        x_norm = np.sqrt(sq_x / n_x + 1e-12)
        delta_norm = np.sqrt(sq_delta / n_delta + 1e-12)
        wx = cfg["x_norm_loss"] * size
        wd = cfg["delta_pair_repr_norm_loss"] * size
        loss_sum = additive + wx * x_norm + wd * delta_norm
        kx = np.float32(wx / (2.0 * x_norm * n_x))
        kd = np.float32(wd / (2.0 * delta_norm * n_delta))
        grads = None
        for b in blocks:
            g = grad(params, b, scale, kx, kd)
            grads = g if grads is None else plain.tree_add(grads, g)
        return loss_sum, size, grads

    return plain.follow(param_shapes(cfg, hyper), seed, hyper, batches, batch_grad)
