"""What the plain references share: straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")`` — no kernels, no
cache, no batching tricks — plus the lower-precision *control* (every
dense matrix product with both operands rounded to int8) and the optimizer
the configurations state (Adam with decoupled weight decay, global-norm
clipping, fp32 master weights behind bf16 parameters).

Nothing here imports the program, and nothing here is given anything the
program made: weights come from ``benchmark/weights.py`` and the seed,
inputs from the host batches the driver kept.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

HIGHEST = jax.lax.Precision.HIGHEST


# -- precision ---------------------------------------------------------------

def _q8(x, axis=None):
    """Round to the 255 levels of a symmetric int8 grid (per tensor, or per
    slice along ``axis``); the gradient passes straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def as_bf16(x):
    """``x`` rounded to bfloat16's 8 mantissa bits, still float32 (inside a
    compiled program a pair of casts would not do: see ``round_bf16``)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dense(x, p, precision):
    """``x @ kernel + bias``.  ``precision``: ``float32`` (the reference),
    ``int8`` (the control: activations per tensor, weights per output
    channel, as a post-training-quantized serving or training path would),
    or ``bfloat16`` (operands rounded to bf16; used by the CPU tests)."""
    w = p["kernel"]
    if precision == "int8":
        x, w = _q8(x), _q8(w, axis=0)
    elif precision == "bfloat16":
        x, w = as_bf16(x), as_bf16(w)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    y = jnp.matmul(x, w, precision=HIGHEST)
    return y + p["bias"] if "bias" in p else y


def layer_norm(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["weight"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


# -- the encoder layer both configurations share -----------------------------

def attention(x, p, heads, bias, key_pad, precision):
    """Multi-head self-attention with an additive bias.  ``bias`` broadcasts
    against (B, H, L, L); ``key_pad`` (B, L) is true at padding keys.
    Returns (output, pre-softmax scores including the bias)."""
    B, L, d = x.shape
    hd = d // heads
    qkv = dense(x, p["in_proj"], precision)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda t: t.reshape(B, L, heads, hd).transpose(0, 2, 1, 3)
    q, k, v = split(q) * hd ** -0.5, split(k), split(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST)
    scores = jnp.where(key_pad[:, None, None, :], -jnp.inf, scores)
    if bias is not None:
        scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HIGHEST)
    o = o.transpose(0, 2, 1, 3).reshape(B, L, d)
    return dense(o, p["out_proj"], precision), scores


def encoder_layer(x, p, heads, bias, key_pad, post_ln, precision):
    """Vaswani et al.'s layer; ``post_ln`` as BERT has it, pre-LN as
    Uni-Mol.  No dropout: the cells train with it off (see PERF.md)."""
    res = x
    if not post_ln:
        x = layer_norm(x, p["self_attn_layer_norm"])
    a, scores = attention(x, p["self_attn"], heads, bias, key_pad, precision)
    x = res + a
    if post_ln:
        x = layer_norm(x, p["self_attn_layer_norm"])
    res = x
    if not post_ln:
        x = layer_norm(x, p["final_layer_norm"])
    x = dense(gelu(dense(x, p["fc1"], precision)), p["fc2"], precision)
    x = res + x
    if post_ln:
        x = layer_norm(x, p["final_layer_norm"])
    return x, scores


def layer_shapes(d, f):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    norm = lambda n: {"weight": s(n), "bias": s(n)}
    lin = lambda i, o: {"kernel": s(i, o), "bias": s(o)}
    return {
        "self_attn": {"in_proj": lin(d, 3 * d), "out_proj": lin(d, d)},
        "self_attn_layer_norm": norm(d),
        "fc1": lin(d, f), "fc2": lin(f, d),
        "final_layer_norm": norm(d),
    }


def masked_nll_sum(logits, target, pad_idx):
    """Sum over the masked positions (target != pad) of -log p(target)."""
    masked = target != pad_idx
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, jnp.where(masked, target, 0)[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(masked, nll, 0.0))


# -- the optimizer the configurations state ----------------------------------

def decays(name, leaf):
    """Weight decay on matrices only: no bias, no norm gain (the
    framework's convention, stated in the configuration files)."""
    n = name.lower()
    return leaf.ndim > 1 and not any(
        s in n for s in ("bias", "layer_norm", "layernorm")
    )


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x)))
            for x in jax.tree_util.tree_leaves(tree)]


def leaf_norms(tree):
    return np.asarray(jax.device_get(_leaf_norms(tree)), dtype=np.float64)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "clip", "mask"))
def _adam_update(master, m, v, grads, step, lr, *, b1, b2, eps, wd, clip, mask):
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    coef = jnp.minimum(clip / (gnorm + 1e-6), 1.0) if clip > 0 else 1.0
    grads = jax.tree_util.tree_map(lambda g: g * coef, grads)
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    size = lr * jnp.sqrt(bc2) / bc1
    treedef = jax.tree_util.tree_structure(master)
    out_p, out_m, out_v = [], [], []
    for p, mm, vv, g, dec in zip(
        jax.tree_util.tree_leaves(master), jax.tree_util.tree_leaves(m),
        jax.tree_util.tree_leaves(v), jax.tree_util.tree_leaves(grads), mask,
    ):
        if wd and dec:
            p = p * (1.0 - size * wd)
        mm = b1 * mm + (1.0 - b1) * g
        vv = b2 * vv + (1.0 - b2) * jnp.square(g)
        out_p.append(p - size * mm / (jnp.sqrt(vv) + eps))
        out_m.append(mm)
        out_v.append(vv)
    un = lambda xs: jax.tree_util.tree_unflatten(treedef, xs)
    return un(out_p), un(out_m), un(out_v), grads


def round_bf16(tree, on=True):
    """The parameters as the configuration holds them: bfloat16 copies of
    the float32 master weights (unless it trains in float32).  Done leaf by
    leaf OUTSIDE any compiled program, each cast a dispatch of its own:
    inside one jitted program XLA on the chip drops a float32 -> bfloat16 ->
    float32 round trip as excess precision (PR 24 read a parameter-change
    gap of 7.8 that way on the chip)."""
    if not on:
        return tree
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), tree
    )


def follow(shapes, seed, hyper, batches, batch_grad):
    """Three updates from the seeded weights.  ``batch_grad(params, batch)``
    returns the batch's summed loss, its sample size, and the gradient of
    the summed loss; normalisation, clipping and Adam happen here.

    Returns each update's loss, the norm of every leaf of the first
    gradient as the optimizer gets it (normalised and clipped), and the
    norm of every leaf of the master weights' change after the last."""
    with jax.default_matmul_precision("highest"):
        bf16 = bool(hyper.get("bf16", True))
        master = round_bf16(weights.make(shapes, seed), bf16)
        init = master
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))(master)
        m, v = zeros, zeros
        names = weights.leaf_names(master)
        mask = tuple(
            decays(n, x) for n, x in zip(names, jax.tree_util.tree_leaves(master))
        )
        b1, b2 = (float(b) for b in hyper["adam_betas"])
        losses, grad_norms = [], None
        for k, batch in enumerate(batches):
            loss_sum, size, grads = batch_grad(round_bf16(master, bf16), batch)
            losses.append(float(loss_sum) / float(size))
            grads = tree_scale(grads, jnp.float32(1.0 / size))
            master, m, v, clipped = _adam_update(
                master, m, v, grads, jnp.float32(k + 1),
                jnp.float32(hyper["lr"]), b1=b1, b2=b2,
                eps=float(hyper["adam_eps"]), wd=float(hyper["weight_decay"]),
                clip=float(hyper["clip_norm"]), mask=mask,
            )
            if k == 0:
                grad_norms = leaf_norms(clipped)
        delta = tree_sub(master, init)
        return {"loss": losses, "grad_norms": grad_norms,
                "delta_norms": leaf_norms(delta), "names": names}


def row_blocks(batch, rows):
    """The batch cut into blocks of ``rows`` rows (every array's axis 0)."""
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    for at in range(0, n, rows):
        yield jax.tree_util.tree_map(lambda a: np.asarray(a)[at:at + rows], batch)


tree_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
tree_sub = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))
tree_scale = jax.jit(lambda a, k: jax.tree_util.tree_map(lambda x: x * k, a))
