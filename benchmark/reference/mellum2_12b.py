"""Plain reference for ``mellum2_12b``: one chip's share of
Mellum2-12B-A2.5B-Instruct (``model_type: mellum``), from its published
``config.json``.

Float32 ``jax.numpy`` under ``highest``; nothing is imported from the
program, no kernel, no sort.  It is given the same share as the program
(the layers, attention heads and experts the configuration file states).

    h   = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w
    q,k,v = h W_q, h W_k, h W_v          (H x D, KV x D, KV x D)
    q,k = rot(q), rot(k)                 rotate-half over the whole head,
                                         positions 0 .. L-1 of the row
      sliding layer: inv_freq_i = theta^(-2i/D), c = 1
      full layer (YaRN): e_i = theta^(-2i/D), n_i = e_i / factor
          dim(r) = D ln(original / (2 pi r)) / (2 ln theta)
          low = max(floor(dim(beta_fast)), 0), high = min(ceil(dim(beta_slow)), D-1)
          ramp_i = clip((i - low) / (high - low), 0, 1)
          inv_freq_i = n_i ramp_i + e_i (1 - ramp_i),  c = attention_factor
      rot uses cos(p inv_freq) c and sin(p inv_freq) c
    s_ij = q_i . k_j / sqrt(D), key j visible to query i iff 0 <= i - j < W
      (W = sliding_window on a sliding layer, L on a full layer); query
      head g reads KV head g // (H / KV)
    x   = x + concat_heads(softmax_j(s_ij) v_j) W_o
    h   = RMSNorm(x)
    z   = h W_r;  p = softmax(z) over ALL experts
    C   = the top_k largest of p, or under router_balancing "batch_bias"
          of u + b, solved on the batch's n tokens:
          m_e = mean_t z_te,  s_e = sqrt(mean_t (z_te - m_e)^2),
          u_te = (z_te - m_e) / s_e + NOISE table_te  (a fixed normal table),
          b = 0, then BIAS_ROUNDS times
          c_e = #{t: e among the top_k largest of u_t + b},
          b_e = b_e - BIAS_GAIN ln((c_e + 1) / (n top_k / E + 1))
    w_e = p_e / sum_{c in C} p_c
    x   = x + sum_{e in C and held} w_e W_down,e (silu(W_gate,e h) * (W_up,e h))
    logits = RMSNorm(x_final) W_head;  loss = mean next-token NLL

The attention is written as the equations read: for a block of queries,
its scores against EVERY key of the row under an explicit mask (the
program's kernels visit the band's blocks only).  Each held expert is a
dense product over ALL tokens times its column of weights (the program
sorts the pairs into tiles and computes those).

Departures kept for memory and compile time, none of which changes a
result: each layer is rematerialized in the backward pass; the attention
runs over ``QUERY_BLOCK`` queries at a time and the experts and the head
over ``ROW_BLOCK`` rows at a time, each block computed again in the
backward pass; the held experts are a loop (``lax.scan``) over their
stacked kernels.  The follower is ``reference/nemotron3_super_120b.py``'s
(``plain.follow`` leaf by leaf, Adam's moments on the host).
"""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import nemotron3_super_120b as leafwise
from benchmark.reference import plain
from benchmark.reference.evabyte import in_blocks

#: queries whose scores against the whole row are alive at once
QUERY_BLOCK = 256
#: rows of the experts and of the head alive at once
ROW_BLOCK = 4096
#: the "batch_bias" balancing rule's noise scale, rounds and step
NOISE = 1.0
BIAS_ROUNDS = 8
BIAS_GAIN = 0.55


def _group(cfg, key):
    """A list or group of the configuration, given as such or as JSON
    text (a test's tiny configuration states them as text, which the
    train driver hands on to the program)."""
    value = cfg[key]
    return json.loads(value) if isinstance(value, str) else value


def held(cfg):
    """What of the model this process holds, from the configuration's
    statements (each defaults to the whole model)."""
    n = int(cfg.get("attention_shares") or 1)
    layers = int(cfg.get("layers_held") or cfg["num_hidden_layers"])
    return dict(
        kinds=_group(cfg, "layer_types")[:layers],
        heads=cfg["num_attention_heads"] // n,
        kv_heads=max(1, cfg["num_key_value_heads"] // n),
        experts=int(cfg.get("num_experts_held") or cfg["num_experts"]),
        first_expert=int(cfg.get("first_expert_held") or 0),
    )


# -- shapes -------------------------------------------------------------------

def param_shapes(cfg, hyper):
    """The program's tree: layer ``i`` is two blocks, ``layers_<2i>`` its
    attention and ``layers_<2i+1>`` its experts, each with its norm."""
    d, f, V = cfg["hidden_size"], cfg["moe_intermediate_size"], int(hyper["vocab_size"])
    mine = held(cfg)
    H, KV, D, Eh = mine["heads"], mine["kv_heads"], cfg["head_dim"], mine["experts"]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dec = {"final_norm": {"weight": s(d)}}
    for i in range(len(mine["kinds"])):
        dec[f"layers_{2 * i}"] = {"norm": {"weight": s(d)}, "self_attn": {
            "q_proj": {"kernel": s(d, H * D)}, "k_proj": {"kernel": s(d, KV * D)},
            "v_proj": {"kernel": s(d, KV * D)},
            "out_proj": {"kernel": s(H * D, d)},
        }}
        dec[f"layers_{2 * i + 1}"] = {"norm": {"weight": s(d)}, "moe": {
            "router": s(d, cfg["num_experts"]),
            "experts_fc1": s(Eh, d, 2 * f), "experts_fc2": s(Eh, f, d),
        }}
    return {"params": {
        "embed_tokens": {"embedding": s(V, d)},
        "decoder": dec,
        "lm_head": s(d, V),
    }}


# -- layers -------------------------------------------------------------------

def dense(x, kernel, precision):
    return plain.dense(x, {"kernel": kernel}, precision)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * weight


def rope_table(rp, D):
    """``(inv_freq (D / 2,), c)`` of one ``rope_parameters`` group, float64
    on the host, as the equations above read."""
    i = np.arange(D // 2, dtype=np.float64)
    e = float(rp["rope_theta"]) ** (-2.0 * i / D)
    if rp.get("rope_type", "default") == "default":
        return e, 1.0
    original, theta = rp["original_max_position_embeddings"], rp["rope_theta"]
    dim = lambda r: D * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(dim(rp["beta_fast"])), 0)
    high = min(math.ceil(dim(rp["beta_slow"])), D - 1)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    c = rp.get("attention_factor") or 0.1 * math.log(rp["factor"]) + 1.0
    return e / rp["factor"] * ramp + e * (1.0 - ramp), c


def rotary(x, table, leave_out=None):
    """``x`` (b, H, L, D): channel ``i < D / 2`` and channel ``i + D / 2``
    are one pair, turned by ``position * inv_freq_i`` and scaled by ``c``."""
    inv_freq, c = table
    if leave_out == "attention_factor":
        c = 1.0
    L, D = x.shape[-2:]
    half = D // 2
    angle = (jnp.arange(L, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32))
    cos, sin = jnp.cos(angle) * c, jnp.sin(angle) * c
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, c, kind, precision, leave_out=None):
    mine = held(c)
    H, KV, D = mine["heads"], mine["kv_heads"], c["head_dim"]
    b, L, _ = x.shape
    sliding = kind == "sliding_attention"
    width = c["sliding_window"] if sliding and leave_out != "window" else L
    table = rope_table(_group(c, "rope_parameters")[kind], D)
    heads = lambda t, n: t.reshape(b, L, n, D).transpose(0, 2, 1, 3)
    q = rotary(heads(dense(x, p["q_proj"]["kernel"], precision), H), table, leave_out)
    k = rotary(heads(dense(x, p["k_proj"]["kernel"], precision), KV), table, leave_out)
    v = heads(dense(x, p["v_proj"]["kernel"], precision), KV)
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
    key_at = jnp.arange(L)

    def block(qb, i):  # qb (queries, b, H, D) at positions i (queries,)
        ahead = i[:, None] - key_at[None, :]
        seen = (ahead >= 0) & (ahead < width)
        scores = D ** -0.5 * jnp.einsum("qbhd,bhkd->bhqk", qb, k,
                                        precision=plain.HIGHEST)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->qbhd", probs, v, precision=plain.HIGHEST)

    o = in_blocks(block, (q.transpose(2, 0, 1, 3), key_at), QUERY_BLOCK, (0, 0))
    o = o.transpose(1, 0, 2, 3).reshape(b, L, H * D)
    return dense(o, p["out_proj"]["kernel"], precision)


def router(t, p, c, precision):
    """``t`` (n, d), ALL tokens of the batch: the scores ``probs`` (n, E)
    and the chosen experts ``idx`` (n, top_k)."""
    z = dense(t, p["router"], precision)
    probs = jax.nn.softmax(z, axis=-1)
    k = c["num_experts_per_tok"]
    chooser = probs
    if c.get("router_balancing", "none") == "batch_bias":
        n, E = z.shape
        mean = jnp.mean(z, axis=0)
        spread = jnp.sqrt(jnp.mean(jnp.square(z - mean), axis=0))
        table = jax.random.normal(
            jax.random.key(0, impl="threefry2x32"), (n, E), jnp.float32)
        u = (z - mean) / (spread + 1e-6) + NOISE * table
        bias = jnp.zeros((E,), z.dtype)
        for _ in range(BIAS_ROUNDS):
            _, chosen = jax.lax.top_k(u + bias, k)
            count = jnp.zeros((E,), z.dtype).at[chosen.reshape(-1)].add(1.0)
            bias = bias - BIAS_GAIN * jnp.log((count + 1.0) / (n * k / E + 1.0))
        chooser = u + bias
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(chooser), k)
    return probs, idx


def experts(h, p, c, precision):
    mine = held(c)
    f = c["moe_intermediate_size"]
    b, L, d = h.shape
    first = mine["first_expert"]
    tokens = h.reshape(b * L, d)
    probs, idx = router(tokens, p, c, precision)
    w = jnp.take_along_axis(probs, idx, axis=1)
    if c.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def rows(t, w, idx):
        def one(y, expert):
            j, w1, w2 = expert
            w_e = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
            pre = dense(t, w1, precision)
            out = dense(jax.nn.silu(pre[:, :f]) * pre[:, f:], w2, precision)
            return y + w_e[:, None] * out, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(t), (
            jnp.arange(mine["experts"]), p["experts_fc1"], p["experts_fc2"]))
        return y

    return in_blocks(rows, (tokens, w, idx), ROW_BLOCK, (0, 0, 0)).reshape(b, L, d)


def hidden(params, cfg, tokens, precision="float32", leave_out=None):
    """(B, L) ids -> the final-normed hidden states (B, L, d).
    ``leave_out`` (``"window"``: the sliding layers see the whole row;
    ``"attention_factor"``: ``c`` left at 1) breaks the mathematics on
    purpose, for the tests that the comparison notices."""
    P = params["params"]
    dec, eps = P["decoder"], cfg["rms_norm_eps"]

    def layer(kind):
        @jax.checkpoint
        def run(x, pa, pe):
            h = rms_norm(x, pa["norm"]["weight"], eps)
            x = x + attention(h, pa["self_attn"], cfg, kind, precision, leave_out)
            h = rms_norm(x, pe["norm"]["weight"], eps)
            return x + experts(h, pe["moe"], cfg, precision)
        return run

    x = P["embed_tokens"]["embedding"][tokens]
    for i, kind in enumerate(held(cfg)["kinds"]):
        x = layer(kind)(x, dec[f"layers_{2 * i}"], dec[f"layers_{2 * i + 1}"])
    return rms_norm(x, dec["final_norm"]["weight"], eps)


def loss_sum(params, cfg, batch, pad_idx, precision="float32", leave_out=None):
    """Summed next-token negative log-likelihood: position ``t`` predicts
    token ``t + 1``; padding targets do not count."""
    tokens, target = batch["net_input"]["src_tokens"], batch["target"]
    x = hidden(params, cfg, tokens, precision, leave_out)
    B, L, d = x.shape
    ahead = jnp.pad(target[:, 1:], ((0, 0), (0, 1)), constant_values=pad_idx)

    def rows(xb, tb):
        logits = dense(xb, params["params"]["lm_head"], precision)
        counts = tb != pad_idx
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            lp, jnp.where(counts, tb, 0)[:, None], axis=-1)[:, 0]
        return jnp.where(counts, nll, 0.0)

    return jnp.sum(in_blocks(
        rows, (x.reshape(B * L, d), ahead.reshape(B * L)), ROW_BLOCK,
        (0, pad_idx),
    ))


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
        size = float((np.asarray(batch["target"])[:, 1:] != pad_idx).sum())
        return total, size, grads

    return leafwise.follow(
        param_shapes(cfg, hyper), seed, hyper, batches, batch_grad)
