"""Plain reference for ``zaya1_8b``: one chip's share of ZAYA1-8B (Zyphra,
``model_type: zaya``), from its published ``config.json`` and the two
papers its mechanisms come from (CCA: arXiv:2510.04476; the router, the
skip expert and the scaled merge: arXiv:2511.17127).

Float32 ``jax.numpy`` under ``highest``; nothing is imported from the
program, no kernel, no sort.  It is given the same share as the program
(the layers, KV heads and experts the configuration file states).  ``L``
positions a row, rows independent; ``d`` the stream, ``H`` query heads on
``KV`` KV heads of ``D``, ``G = H / KV``.

    h = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w

    attention sublayer (CCA)
    q~ = h W_q (L, H D),  k~ = h W_k (L, KV D), as heads of D
    v_j = h_t W_v,j for the model's first half of KV heads,
          h_{t-1} W_v,j for its second half (h_{-1} = 0)
    z = [q~ ; k~];  z_{-1} = z_{-2} = 0 (the one front padding)
    z1_t = a0 * z_{t-1} + a1 * z_t + c0          t = -1 .. L-1; z1_{-1} = c0
    z2_t[head] = z1_{t-1}[head] A0[head] + z1_t[head] A1[head] + c1[head]
    m_q[i] = (q~[i] + k~[i // G]) / 2,  m_k[j] = mean_{i in j's group} m_q[i]
    q = z2_q + m_q,  k = z2_k + m_k
    q = sqrt(D) q / |q|,  k = tau_j sqrt(D) k / |k|
    q, k = rot(q), rot(k): positions 0 .. L-1, the first R = D x
      partial_rotary_factor channels (channel i with i + R/2, inv_freq_i =
      theta^(-2i/R)), the rest passed through
    o_i = sum_{j <= t} softmax_j(q_i . k_j / sqrt(D)) v_j, head i on i // G
    f = concat_i(o_i) W_o

    expert sublayer, with the router state r_prev (zeros into layer 0)
    r = h W_d + b_d + gamma * r_prev                     handed to the next
    logits = W_3 gelu(W_2 gelu(W_1 RMSNorm(r) + b_1) + b_2)     (L, E + 1)
    p = softmax(logits);  column E is the skip expert
    e* = argmax p, or under router_balancing "batch_bias" of u + b, solved
      on the batch's n tokens over the E + 1 columns:
      m_e = mean_t z_te, s_e = sqrt(mean_t (z_te - m_e)^2),
      u_te = (z_te - m_e) / s_e + NOISE table_te  (a fixed normal table),
      b = 0, then BIAS_ROUNDS times c_e = #{t: e = argmax(u_t + b)},
      b_e = b_e - BIAS_GAIN ln((c_e + 1) / (n / (E + 1) + 1))
    f = p_e* W_down,e* (silu(W_gate,e* h) * (W_up,e* h))   e* < E and held;
      0 for the skip column and for an expert held elsewhere

    after each sublayer  x = s_x (x + b_x) + s_f (f + b_f)
    logits = RMSNorm(x_final) E^T (the embedding: tied);  loss = mean
    next-token NLL

The convolutions and the late value are written as the equations read:
explicit sums over two positions of a front-padded row.  The experts are a
dense loop over the columns: each held expert a product over ALL tokens
times its column of weights; the skip column adds nothing.  The attention
scores every key of the row under an explicit mask.

Departures kept for memory and compile time, none of which changes a
result: each layer is rematerialized in the backward pass; the held layers,
built alike, run as one traced body under ``lax.scan`` over their stacked
parameters (the program stacks them the same way); the attention runs over
``QUERY_BLOCK`` queries at a time and the experts and the head over
``ROW_BLOCK`` rows at a time; ``W_q`` and ``W_k`` stand side by side in one
product.  The follower is ``reference/nemotron3_super_120b.py``'s.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import nemotron3_super_120b as leafwise
from benchmark.reference import plain
from benchmark.reference.evabyte import in_blocks
from benchmark.reference.laguna_s_2_1 import rope_table, rotary

#: queries whose scores against the whole row are alive at once
QUERY_BLOCK = 256
#: rows of the experts and of the head alive at once
ROW_BLOCK = 1024
#: the "batch_bias" balancing rule's noise scale, rounds and step
NOISE = 1.0
BIAS_ROUNDS = 8
BIAS_GAIN = 0.55


def _group(cfg, key):
    value = cfg[key]
    return json.loads(value) if isinstance(value, str) else value


def held(cfg):
    """What of the model this process holds, from the configuration's
    statements (each defaults to the whole model)."""
    shares = int(cfg.get("attention_shares") or 1)
    KV = cfg["num_key_value_heads"]
    return dict(
        layers=int(cfg.get("layers_held") or cfg["num_hidden_layers"]),
        kv_heads=KV // shares,
        first_kv_head=int(cfg.get("first_kv_head_held") or 0),
        heads=cfg["num_attention_heads"] // shares,
        experts=int(cfg.get("num_experts_held") or cfg["num_experts"]),
        first_expert=int(cfg.get("first_expert_held") or 0),
    )


# -- shapes -------------------------------------------------------------------

def param_shapes(cfg, hyper):
    """The program's tree: layer ``i`` is two blocks, its attention and its
    experts, each with its norm and its merge; two layers or more are one
    repeated unit, stacked on a leading axis (``units/layer_0``,
    ``units/layer_1``).  No ``lm_head``: the head is the embedding."""
    d, V = cfg["hidden_size"], int(hyper["vocab_size"])
    f, R, E = (cfg["moe_intermediate_size"], cfg["router_hidden_size"],
               cfg["num_experts"])
    mine = held(cfg)
    H, KV, D, Eh = mine["heads"], mine["kv_heads"], cfg["head_dim"], mine["experts"]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    kernel = lambda *shape: {"kernel": s(*shape)}
    bias = lambda *shape: {"bias": s(*shape)}
    merge = lambda: {"x": {"scale": s(d), "bias": s(d)},
                     "f": {"scale": s(d), "bias": s(d)}}
    attention = {"norm": {"weight": s(d)}, "merge": merge(), "self_attn": {
        "q_proj": kernel(d, H * D), "k_proj": kernel(d, KV * D),
        "v_proj": kernel(d, KV * D),
        "conv0": kernel(2, H + KV, D), "conv0_bias": bias(H + KV, D),
        "conv1": kernel(2, H + KV, D, D), "conv1_bias": bias(H + KV, D),
        "temperature": {"scale": s(KV)}, "out_proj": kernel(H * D, d),
    }}
    experts = {"norm": {"weight": s(d)}, "merge": merge(), "moe": {
        "router_down": kernel(d, R), "router_down_bias": bias(R),
        "depth_gain": {"scale": s(R)}, "router_norm": {"weight": s(R)},
        "router_w1": kernel(R, R), "router_w1_bias": bias(R),
        "router_w2": kernel(R, R), "router_w2_bias": bias(R),
        "router_out": kernel(R, E + 1),
        "experts_fc1": s(Eh, d, 2 * f), "experts_fc2": s(Eh, f, d),
    }}
    _head, _unit, repeats = leafwise.split_pattern("CZ" * mine["layers"])
    if repeats:  # every leaf of the repeated unit on a leading axis
        stacked = lambda tree: jax.tree_util.tree_map(
            lambda leaf: s(repeats, *leaf.shape), tree)
        dec = {"units": {"layer_0": stacked(attention),
                         "layer_1": stacked(experts)}}
    else:
        dec = {"layers_0": attention, "layers_1": experts}
    dec["final_norm"] = {"weight": s(d)}
    return {"params": {"embed_tokens": {"embedding": s(V, d)}, "decoder": dec}}


# -- layers -------------------------------------------------------------------

def rounded(x, w, precision):
    """The operands of a dense product as ``precision`` holds them (the
    kernel's contracting axis is its last but one)."""
    if precision == "int8":
        return plain._q8(x), plain._q8(w, axis=-2)
    if precision == "bfloat16":
        return plain.as_bf16(x), plain.as_bf16(w)
    if precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return x, w


def dense(x, kernel, precision):
    return jnp.matmul(*rounded(x, kernel, precision), precision=plain.HIGHEST)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * weight


def before(x):
    """``x`` (b, L, ...) at the position before: ``x_{t-1}`` at ``t``,
    zero at the row's first position."""
    return jnp.pad(x, [(0, 0), (1, 0)] + [(0, 0)] * (x.ndim - 2))[:, :-1]


def cca(h, p, c, precision, leave_out=None):
    mine = held(c)
    H, KV, D = mine["heads"], mine["kv_heads"], c["head_dim"]
    G = H // KV
    b, L, _ = h.shape
    # [W_q | W_k] side by side: one product, column for column the two
    z = dense(h, jnp.concatenate(
        [p["q_proj"]["kernel"], p["k_proj"]["kernel"]], axis=1), precision
    ).reshape(b, L, H + KV, D)
    # a KV head of the model's second half reads the token before
    half = (c["num_key_value_heads"] + 1) // 2
    v = jnp.stack([
        dense(before(h) if (mine["first_kv_head"] + j >= half
                            and leave_out != "late_value") else h,
              p["v_proj"]["kernel"][:, j * D:(j + 1) * D], precision)
        for j in range(KV)], axis=2)                          # (b, L, KV, D)

    # both convolutions over the row padded in front by two zero positions
    a, c0 = p["conv0"]["kernel"], p["conv0_bias"]["bias"]
    A, c1 = p["conv1"]["kernel"], p["conv1_bias"]["bias"]
    padded = jnp.pad(z, ((0, 0), (2, 0), (0, 0), (0, 0)))     # t = -2 .. L-1
    z1 = a[0] * padded[:, :-1] + a[1] * padded[:, 1:] + c0     # t = -1 .. L-1
    if leave_out == "first_tap":  # padded again between the convolutions
        z1 = z1.at[:, 0].set(0.0)
    per_head = lambda x, w: jnp.einsum(
        "blhc,hcd->blhd", *rounded(x, w, precision), precision=plain.HIGHEST)
    z2 = per_head(z1[:, :-1], A[0]) + per_head(z1[:, 1:], A[1]) + c1

    q_, k_ = z[:, :, :H].reshape(b, L, KV, G, D), z[:, :, H:]
    m_q = 0.5 * (q_ + k_[:, :, :, None])
    if leave_out == "qk_mean":
        m_q = jnp.zeros_like(m_q)
    q = z2[:, :, :H] + m_q.reshape(b, L, H, D)
    k = z2[:, :, H:] + jnp.mean(m_q, axis=3)
    tau = p["temperature"]["scale"]
    if leave_out == "temperature":
        tau = jnp.ones_like(tau)
    norm = lambda t: jnp.sqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True))
    q = D ** 0.5 * q / norm(q)
    k = tau[:, None] * D ** 0.5 * k / norm(k)
    # rotary on the first R = D x partial_rotary_factor channels of every
    # head (``reference/laguna_s_2_1.py``'s, over (b, heads, L, D))
    table = rope_table(_group(c, "rope_parameters")["hybrid"], D)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    q, k = rotary(q, table), rotary(k, table)
    k, v = (jnp.repeat(t, G, axis=1) for t in (k, v))
    key_at = jnp.arange(L)

    def block(qb, i):  # qb (queries, b, H, D) at positions i (queries,)
        seen = key_at[None, :] <= i[:, None]
        scores = D ** -0.5 * jnp.einsum("qbhd,bhkd->bhqk", qb, k,
                                        precision=plain.HIGHEST)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->qbhd", probs, v, precision=plain.HIGHEST)

    o = in_blocks(block, (q.transpose(2, 0, 1, 3), key_at), QUERY_BLOCK, (0, 0))
    return dense(o.transpose(1, 0, 2, 3).reshape(b, L, H * D),
                 p["out_proj"]["kernel"], precision)


def router(h, r_prev, p, c, precision, leave_out=None):
    """``h`` (n, d) and ``r_prev`` (n, R), ALL tokens of the batch: the
    state ``r`` (n, R), the scores ``probs`` (n, E + 1) and each token's
    column ``chosen`` (n,)."""
    r = dense(h, p["router_down"]["kernel"], precision) + p[
        "router_down_bias"]["bias"]
    if leave_out != "depth_state":
        r = r + p["depth_gain"]["scale"] * r_prev
    t = rms_norm(r, p["router_norm"]["weight"], c["rms_norm_eps"])
    for name in ("router_w1", "router_w2"):
        t = plain.gelu(dense(t, p[name]["kernel"], precision)
                       + p[name + "_bias"]["bias"])
    z = dense(t, p["router_out"]["kernel"], precision)
    if leave_out == "skip_column":  # the skip expert never chosen
        z = z.at[:, -1].set(-jnp.inf)
    probs = jax.nn.softmax(z, axis=-1)
    chooser = probs
    if c.get("router_balancing", "none") == "batch_bias":
        n, E1 = z.shape
        z = jax.lax.stop_gradient(z)
        mean = jnp.mean(z, axis=0)
        spread = jnp.sqrt(jnp.mean(jnp.square(z - mean), axis=0))
        table = jax.random.normal(
            jax.random.key(0, impl="threefry2x32"), (n, E1), jnp.float32)
        u = (z - mean) / (spread + 1e-6) + NOISE * table
        bias = jnp.zeros((E1,), z.dtype)
        for _ in range(BIAS_ROUNDS):
            count = jnp.zeros((E1,), z.dtype).at[
                jnp.argmax(u + bias, axis=-1)].add(1.0)
            bias = bias - BIAS_GAIN * jnp.log((count + 1.0) / (n / E1 + 1.0))
        chooser = u + bias
    return r, probs, jnp.argmax(jax.lax.stop_gradient(chooser), axis=-1)


def gated(t, w1, w2, precision):
    """``W_down (silu(W_gate t) * (W_up t))`` with ``w1 = [W_gate | W_up]``."""
    f = w2.shape[0]
    pre = dense(t, w1, precision)
    return dense(jax.nn.silu(pre[:, :f]) * pre[:, f:], w2, precision)


def experts(h, r_prev, p, c, precision, leave_out=None):
    mine = held(c)
    b, L, d = h.shape
    tokens = h.reshape(b * L, d)
    r, probs, chosen = router(
        tokens, r_prev.reshape(b * L, -1), p, c, precision, leave_out)
    score = jnp.take_along_axis(probs, chosen[:, None], axis=1)[:, 0]
    held_experts = (mine["first_expert"] + jnp.arange(mine["experts"]),
                    p["experts_fc1"], p["experts_fc2"])

    def rows(t, score, chosen):
        # every column in turn; the skip column (and an expert held
        # elsewhere) is in no trip and adds nothing
        def one(y, expert):
            e, w1, w2 = expert
            w_e = jnp.where(chosen == e, score, 0.0)
            return y + w_e[:, None] * gated(t, w1, w2, precision), None

        return jax.lax.scan(one, jnp.zeros_like(t), held_experts)[0]

    f = in_blocks(rows, (tokens, score, chosen), ROW_BLOCK, (0, 0, 0))
    return f.reshape(b, L, d), r.reshape(b, L, -1)


def merge(x, f, p, leave_out=None):
    if leave_out == "merge":
        return x + f
    return (p["x"]["scale"] * (x + p["x"]["bias"])
            + p["f"]["scale"] * (f + p["f"]["bias"]))


def hidden(params, cfg, tokens, precision="float32", leave_out=None):
    """(B, L) ids -> the final-normed hidden states (B, L, d).
    ``leave_out`` (``"qk_mean"``, ``"late_value"``, ``"first_tap"``: the row
    padded again between the convolutions, ``"temperature"``,
    ``"depth_state"``, ``"skip_column"``, ``"merge"``: ``x + f``) breaks the
    mathematics on purpose, for the tests that the comparison notices."""
    P = params["params"]
    dec, eps = P["decoder"], cfg["rms_norm_eps"]

    @jax.checkpoint
    def layer(carry, blocks):
        (x, r), (pa, pm) = carry, blocks
        h = rms_norm(x, pa["norm"]["weight"], eps)
        x = merge(x, cca(h, pa["self_attn"], cfg, precision, leave_out),
                  pa["merge"], leave_out)
        h = rms_norm(x, pm["norm"]["weight"], eps)
        f, r = experts(h, r, pm["moe"], cfg, precision, leave_out)
        return (merge(x, f, pm["merge"], leave_out), r), None

    x = P["embed_tokens"]["embedding"][tokens]
    carry = (x, jnp.zeros(x.shape[:-1] + (cfg["router_hidden_size"],), x.dtype))
    if "units" in dec:  # one traced body for the stacked layers
        carry, _ = jax.lax.scan(
            layer, carry, (dec["units"]["layer_0"], dec["units"]["layer_1"]))
    else:
        carry, _ = layer(carry, (dec["layers_0"], dec["layers_1"]))
    return rms_norm(carry[0], dec["final_norm"]["weight"], eps)


def loss_sum(params, cfg, batch, pad_idx, precision="float32", leave_out=None):
    """Summed next-token negative log-likelihood: position ``t`` predicts
    token ``t + 1``; padding targets do not count.  The head is the
    embedding."""
    tokens, target = batch["net_input"]["src_tokens"], batch["target"]
    x = hidden(params, cfg, tokens, precision, leave_out)
    B, L, d = x.shape
    ahead = jnp.pad(target[:, 1:], ((0, 0), (0, 1)), constant_values=pad_idx)
    head = params["params"]["embed_tokens"]["embedding"].T

    def rows(xb, tb):
        logits = dense(xb, head, precision)
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
    """``rows`` is not used: the balancing rule is solved over the whole
    batch, so the batch is taken whole, and the blocks above are what make
    it fit."""
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
