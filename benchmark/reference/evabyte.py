"""Plain reference for ``evabyte``: one chip's share of EvaByte
(``model_type: evabyte``, ``attention_class: eva``), from its published
``config.json`` and the paper its attention comes from (Zheng et al.,
"Efficient Attention via Control Variates", ICLR 2023), in the simplified
form EvaByte trains with.

Float32 ``jax.numpy`` under ``highest``; nothing is imported from the
program.  It is given the same share as the program (the layers and the
attention heads the configuration file states).

* ``h = E[x]``; bytes are ids ``64 + b``.
* every layer: ``h += Attn(norm(h))``, ``h += MLP(norm(h))`` with ``norm(x)
  = x / sqrt(mean(x^2) + eps) * (1 + w)``.
* ``MLP(x) = W_down(silu(W_gate x) * (W_up x))``; ``fc1`` holds ``[W_gate |
  W_up]``, ``fc2`` is ``W_down``.
* ``Attn``: ``q, k, v`` per held head; rotary on ``q, k`` (rotate-half
  pairs over the whole head, positions from 0 in each row); ``s = D ^
  -1/2``.  With windows of ``window_size`` and chunks of ``chunk_size``,
  per head with its ``mu, phi``: ``k~_c = sum_{j in c} softmax_j(s k_j .
  mu) k_j``, ``v~_c = sum_{j in c} softmax_j(s k_j . phi) v_j``.  Query
  ``i`` sees key ``j`` iff ``j`` is in ``i``'s window and ``j <= i``, and
  chunk summary ``c`` iff ``c``'s window is before ``i``'s; one softmax
  over the scores ``s q_i . k_j`` and ``s q_i . k~_c`` of everything seen;
  the output is the weighted sum of the ``v_j`` and ``v~_c``; then ``W_o``.
* final norm; ``logits = h W_head`` with ``num_pred_heads`` blocks of the
  vocabulary; the loss is the sum, over positions ``t`` and heads ``m = 1 ..
  num_pred_heads``, of the cross-entropy of block ``m`` at ``t`` against
  byte ``t + m``; targets past the row's end (and pads) do not count.

The attention is written as the equations read: for a block of queries,
its scores against EVERY key of the row and EVERY chunk summary, an
explicit mask of what each query may see, one softmax (the program scores
each window against its own keys and the summaries only).

Departures kept for memory and compile time, none of which changes a
result: each layer is rematerialized in the backward pass; the attention
runs over ``QUERY_BLOCK`` queries at a time and the feed-forward layer and
the head over ``ROW_BLOCK`` rows at a time, each block computed again in
the backward pass; the layers are a loop (``lax.scan``) over their stacked
parameters.  The follower (:func:`follow`) is the leaf-by-leaf one of
``reference/nemotron3_super_120b.py`` with the master weights on the host
too: while a gradient is computed the device holds the rounded parameters
and the gradient, and not a third copy of 687 M float32 parameters.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference import nemotron3_super_120b as leafwise
from benchmark.reference import plain

#: queries whose scores against the whole row are alive at once
QUERY_BLOCK = 256
#: rows of the feed-forward layers and of the head alive at once
ROW_BLOCK = 4096


def held(cfg):
    """What of the model this process holds, from the configuration's
    statements: ``layers_held`` of the layers (else all) and one of
    ``attention_shares`` equal shares of the heads (else all)."""
    return dict(
        layers=int(cfg.get("layers_held") or cfg["num_hidden_layers"]),
        heads=cfg["num_attention_heads"] // int(cfg.get("attention_shares") or 1),
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
    )


# -- shapes -------------------------------------------------------------------

def param_shapes(cfg, hyper):
    """The program's tree: the layers are the scanned unit ``AF`` (an
    attention block, then a feed-forward block), stacked on a leading axis;
    a single held layer is two blocks of its own."""
    d, f, V = cfg["hidden_size"], cfg["intermediate_size"], int(hyper["vocab_size"])
    mine = held(cfg)
    H, D, n = mine["heads"], mine["head_dim"], mine["layers"]
    lead = (n,) if n > 1 else ()
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    ls = lambda *shape: s(*(lead + shape))
    attn = {"norm": {"offset": ls(d)}, "self_attn": {
        "q_proj": {"kernel": ls(d, H * D)}, "k_proj": {"kernel": ls(d, H * D)},
        "v_proj": {"kernel": ls(d, H * D)},
        "adaptive_mu_k": ls(H, D), "adaptive_phi": ls(H, D),
        "out_proj": {"kernel": ls(H * D, d)},
    }}
    mlp = {"norm": {"offset": ls(d)}, "mlp": {
        "fc1": {"kernel": ls(d, 2 * f)}, "fc2": {"kernel": ls(f, d)},
    }}
    dec = {"final_norm": {"offset": s(d)}}
    if n > 1:
        dec["units"] = {"layer_0": attn, "layer_1": mlp}
    else:
        dec.update(layers_0=attn, layers_1=mlp)
    return {"params": {
        "embed_tokens": {"embedding": s(V, d)},
        "decoder": dec,
        "lm_head": s(d, cfg["num_pred_heads"] * V),
    }}


# -- layers -------------------------------------------------------------------

def dense(x, kernel, precision):
    return plain.dense(x, {"kernel": kernel}, precision)


def rms_norm(x, offset, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * (1.0 + offset)


def rotary(x, theta):
    """``x`` (b, H, L, D): channel ``i < D / 2`` and channel ``i + D / 2``
    are one pair, turned by ``position * theta ^ (-2 i / D)``."""
    L, D = x.shape[-2:]
    half = D // 2
    angle = (jnp.arange(L, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / D))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def in_blocks(fn, arrays, block, fill):
    """``fn(*blocks)`` over ``arrays`` cut into blocks of ``block`` along
    their first axis (the last block padded with ``fill``, one value per
    array, and the padding cut off the result), one block at a time, each
    computed again in the backward pass."""
    n = arrays[0].shape[0]
    if n <= block:
        return fn(*arrays)
    pad = (-n) % block
    parts = tuple(
        jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1), constant_values=v)
        .reshape((-1, block) + a.shape[1:])
        for a, v in zip(arrays, fill)
    )
    out = jax.lax.map(lambda blocks: jax.checkpoint(fn)(*blocks), parts)
    return out.reshape((-1,) + out.shape[2:])[:n]


def attention(x, p, c, precision, leave_out=None):
    mine = held(c)
    H, D = mine["heads"], mine["head_dim"]
    window, chunk = c["window_size"], c["chunk_size"]
    b, L, _ = x.shape
    heads = lambda t: t.reshape(b, L, H, D).transpose(0, 2, 1, 3)
    q = rotary(heads(dense(x, p["q_proj"]["kernel"], precision)), c["rope_theta"])
    k = rotary(heads(dense(x, p["k_proj"]["kernel"], precision)), c["rope_theta"])
    v = heads(dense(x, p["v_proj"]["kernel"], precision))
    s = D ** -0.5
    # the chunk summaries (a row's last chunk may be short)
    n_chunks = -(-L // chunk)
    pad = n_chunks * chunk - L
    chunks = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        b, H, n_chunks, chunk, D)
    kc, vc = chunks(k), chunks(v)
    real = (jnp.arange(n_chunks * chunk) < L).reshape(n_chunks, chunk)

    def pooled(values, vec):
        logits = s * jnp.einsum("bhncd,hd->bhnc", kc, vec, precision=plain.HIGHEST)
        w = jax.nn.softmax(jnp.where(real, logits, -jnp.inf), axis=-1)
        return jnp.einsum("bhnc,bhncd->bhnd", w, values, precision=plain.HIGHEST)

    k_sum, v_sum = pooled(kc, p["adaptive_mu_k"]), pooled(vc, p["adaptive_phi"])
    if leave_out == "summaries":
        v_sum = jnp.zeros_like(v_sum)
    keys = jnp.concatenate([k, k_sum], axis=2)        # (b, H, L + n_chunks, D)
    values = jnp.concatenate([v, v_sum], axis=2)
    key_at = jnp.arange(L)
    chunk_window = (jnp.arange(n_chunks) * chunk) // window

    def block(qb, i):  # qb (queries, b, H, D) at positions i (queries,)
        local = ((key_at[None, :] // window == i[:, None] // window)
                 & (key_at[None, :] <= i[:, None]))
        summary = chunk_window[None, :] < i[:, None] // window
        seen = jnp.concatenate([local, summary], axis=1)
        scores = s * jnp.einsum("qbhd,bhkd->bhqk", qb, keys,
                                precision=plain.HIGHEST)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->qbhd", probs, values,
                          precision=plain.HIGHEST)

    o = in_blocks(block, (q.transpose(2, 0, 1, 3), key_at), QUERY_BLOCK, (0, 0))
    o = o.transpose(1, 0, 2, 3).reshape(b, L, H * D)
    return dense(o, p["out_proj"]["kernel"], precision)


def mlp(x, p, c, precision):
    f = c["intermediate_size"]

    def rows(xb):
        h = dense(xb, p["fc1"]["kernel"], precision)
        return dense(jax.nn.silu(h[..., :f]) * h[..., f:],
                     p["fc2"]["kernel"], precision)

    b, L, d = x.shape
    return in_blocks(rows, (x.reshape(b * L, d),), ROW_BLOCK, (0,)).reshape(b, L, d)


def hidden(params, cfg, tokens, precision="float32", leave_out=None):
    """(B, L) ids -> the final-normed hidden states (B, L, d).
    ``leave_out="summaries"`` zeroes the chunk summaries' values: breaks
    the mathematics on purpose, for the tests that the comparison notices."""
    P = params["params"]
    dec, eps = P["decoder"], cfg["rms_norm_eps"]

    @jax.checkpoint
    def layer(x, pa, pf):
        h = rms_norm(x, pa["norm"]["offset"], eps)
        x = x + attention(h, pa["self_attn"], cfg, precision, leave_out)
        h = rms_norm(x, pf["norm"]["offset"], eps)
        return x + mlp(h, pf["mlp"], cfg, precision)

    x = P["embed_tokens"]["embedding"][tokens]
    if "units" in dec:
        x, _ = jax.lax.scan(
            lambda x, p: (layer(x, p["layer_0"], p["layer_1"]), None),
            x, dec["units"],
        )
    else:
        x = layer(x, dec["layers_0"], dec["layers_1"])
    return rms_norm(x, dec["final_norm"]["offset"], eps)


def loss_sum(params, cfg, batch, pad_idx, precision="float32", leave_out=None):
    """The summed cross-entropy over positions and prediction heads, and
    the number of (position, head) pairs that count."""
    tokens, target = batch["net_input"]["src_tokens"], batch["target"]
    M, V = cfg["num_pred_heads"], params["params"]["lm_head"].shape[1] // cfg["num_pred_heads"]
    x = hidden(params, cfg, tokens, precision, leave_out)
    B, L, d = x.shape
    # head m (1 .. M) at position t is held against token t + m
    ahead = jnp.stack([
        jnp.pad(target[:, m:], ((0, 0), (0, m)), constant_values=pad_idx)
        for m in range(1, M + 1)
    ], axis=-1)                                           # (B, L, M)

    def rows(xb, tb):
        logits = dense(xb, params["params"]["lm_head"], precision)
        lp = jax.nn.log_softmax(logits.reshape(-1, M, V), axis=-1)
        counts = tb != pad_idx
        nll = -jnp.take_along_axis(
            lp, jnp.where(counts, tb, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(counts, nll, 0.0), axis=-1)

    return jnp.sum(in_blocks(
        rows, (x.reshape(B * L, d), ahead.reshape(B * L, M)), ROW_BLOCK,
        (0, pad_idx),
    ))


def sample_size(batch, cfg, pad_idx):
    target = np.asarray(batch["target"])
    return float(sum(
        (target[:, m:] != pad_idx).sum()
        for m in range(1, cfg["num_pred_heads"] + 1)
    ))


def follow(shapes, seed, hyper, batches, batch_grad):
    """What ``plain.follow`` does (three updates from the seeded weights;
    each update's loss, the leaf norms of the first gradient as the
    optimizer gets it, the leaf norms of the master weights' change), with
    the master weights and Adam's moments on the host and the update made
    leaf by leaf (``leafwise``'s own programs, one leaf at a time)."""
    with jax.default_matmul_precision("highest"):
        bf16 = bool(hyper.get("bf16", True))
        made, treedef = jax.tree_util.tree_flatten(weights.make(shapes, seed))
        names = weights.leaf_names(shapes)
        decayed = [n.rsplit("/", 1)[-1] in leafwise.DECAYED for n in names]
        master = []
        while made:  # the seeded weights as the program holds them at first
            master.append(np.asarray(plain.round_bf16(made.pop(0), bf16)))
        m = [np.zeros(x.shape, np.float32) for x in master]
        v = [np.zeros(x.shape, np.float32) for x in master]
        b1, b2 = (float(b) for b in hyper["adam_betas"])
        clip = float(hyper["clip_norm"])
        losses, grad_norms = [], None
        for k, batch in enumerate(batches):
            t0 = time.perf_counter()
            rounded = jax.tree_util.tree_unflatten(treedef, [
                plain.round_bf16(jnp.asarray(x), bf16) for x in master
            ])
            loss_total, size, grads = batch_grad(rounded, batch)
            del rounded
            grads = jax.tree_util.tree_leaves(grads)
            losses.append(float(loss_total) / float(size))
            t1 = time.perf_counter()
            grads = [leafwise._scaled(g, jnp.float32(1.0 / size)) for g in grads]
            gnorm = float(np.sqrt(sum(float(leafwise._sq_sum(g)) for g in grads)))
            coef = min(clip / (gnorm + 1e-6), 1.0) if clip > 0 else 1.0
            norms = []
            for i in range(len(master)):
                g = leafwise._scaled(grads[i], jnp.float32(coef))
                grads[i] = None
                if k == 0:
                    norms.append(float(jnp.sqrt(leafwise._sq_sum(g))))
                new = leafwise._adam_leaf(
                    jnp.asarray(master[i]), m[i], v[i], g, jnp.float32(k + 1),
                    jnp.float32(hyper["lr"]), b1=b1, b2=b2,
                    eps=float(hyper["adam_eps"]),
                    wd=float(hyper["weight_decay"]) if decayed[i] else 0.0,
                )
                master[i], m[i], v[i] = (np.asarray(a) for a in new)
            if k == 0:
                grad_norms = np.asarray(norms, np.float64)
            print(f"reference: update {k + 1}: loss and gradient "
                  f"{t1 - t0:.1f}s, Adam leaf by leaf "
                  f"{time.perf_counter() - t1:.1f}s", flush=True)
        del m, v, grads
        start = jax.tree_util.tree_leaves(weights.make(shapes, seed))
        delta = []
        while start:
            delta.append(float(leafwise._norm_of_change(
                jnp.asarray(master.pop(0)),
                plain.round_bf16(start.pop(0), bf16))))
        return {"loss": losses, "grad_norms": grad_norms,
                "delta_norms": np.asarray(delta, np.float64), "names": names}


def train_check(cfg, hyper, batches, seed, rows, precision="float32",
                leave_out=None):
    """``rows`` is not used: a block of this cell is one whole sequence,
    and the blocks above are what make it fit."""
    pad_idx = int(hyper["pad_idx"])
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: loss_sum(p, cfg, b, pad_idx, precision, leave_out)
    ))

    def batch_grad(params, batch):
        batch = jax.tree_util.tree_map(lambda a: np.asarray(a, np.int32), batch)
        total, grads = grad(params, batch)
        return total, sample_size(batch, cfg, pad_idx), grads

    return follow(param_shapes(cfg, hyper), seed, hyper, batches, batch_grad)
