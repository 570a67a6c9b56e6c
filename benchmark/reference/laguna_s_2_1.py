"""Plain reference for ``laguna_s_2_1``: one chip's share of Laguna-S-2.1
(poolside, ``model_type: laguna``), from its published ``config.json``.

Float32 ``jax.numpy`` under ``highest``; nothing is imported from the
program, no kernel, no sort.  It is given the same share as the program
(the layers, attention heads and experts the configuration file states).
Layer ``l`` has ``H_l`` query heads (``num_attention_heads_per_layer[l]``
over ``attention_shares``) on ``KV`` KV heads of ``D`` channels.

    h   = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w
    q,k,v = h W_q, h W_k, h W_v          (H_l x D, KV x D, KV x D)
    g   = sigmoid(h W_g)                 (H_l: one number a head and token)
    q,k = rot(q), rot(k)                 positions 0 .. L-1 of the row
      R = D x partial_rotary_factor of the layer's rope_parameters group;
      channels 0 .. R-1 are rotated (channel i with i + R/2, i < R/2),
      channels R .. D-1 pass through, neither rotated nor scaled
      default: inv_freq_i = theta^(-2i/R), c = 1
      yarn:    e_i = theta^(-2i/R), n_i = e_i / factor
          dim(r) = R ln(original / (2 pi r)) / (2 ln theta)
          low = max(floor(dim(beta_fast)), 0), high = min(ceil(dim(beta_slow)), R-1)
          ramp_i = clip((i - low) / (high - low), 0, 1)
          inv_freq_i = n_i ramp_i + e_i (1 - ramp_i),  c = attention_factor
      rot uses cos(p inv_freq) c and sin(p inv_freq) c
    s_ij = q_i . k_j / sqrt(D), key j visible to query i iff 0 <= i - j < W
      (W = sliding_window on a sliding layer, L on a full layer); query
      head a reads KV head a // (H_l / KV)
    o_a = g_a sum_j softmax_j(s_ij) v_j
    x   = x + concat_a(o_a) W_o
    h   = RMSNorm(x)
    dense layer:  x = x + W_down (silu(W_gate h) * (W_up h))
    sparse layer: z = h W_r;  p = softmax(z) over ALL experts
      C = the top_k largest of p, or under router_balancing "batch_bias"
          of u + b, solved on the batch's n tokens:
          m_e = mean_t z_te,  s_e = sqrt(mean_t (z_te - m_e)^2),
          u_te = (z_te - m_e) / s_e + NOISE table_te  (a fixed normal table),
          b = 0, then BIAS_ROUNDS times
          c_e = #{t: e among the top_k largest of u_t + b},
          b_e = b_e - BIAS_GAIN ln((c_e + 1) / (n top_k / E + 1))
      w_e = moe_routed_scaling_factor p_e / sum_{c in C} p_c
      x = x + sum_{e in C and held} w_e E_e(h) + S(h)
      E_e, S: W_down (silu(W_gate .) * (W_up .)); S unweighted
    logits = RMSNorm(x_final) W_head;  loss = mean next-token NLL

The attention is written as the equations read: for a block of queries,
its scores against EVERY key of the row under an explicit mask (the
program's kernels visit the band's blocks only).  Each held expert is a
dense product over ALL tokens times its column of weights (the program
sorts the pairs into tiles and computes those).

Departures kept for memory and compile time, none of which changes a
result: each layer is rematerialized in the backward pass; the attention
runs over ``QUERY_BLOCK`` queries at a time and the feed-forward layers and
the head over ``ROW_BLOCK`` rows at a time, each block computed again in
the backward pass; the held experts are a loop (``lax.scan``) over their
stacked kernels.  A run of the cell has to end inside the benchmark's time
limit with nothing cached, and the TPU's compiler spends 5 - 15 s of a core
on every float32 product of these sizes that the gradient program holds
(forward, both forwards again, both gradients: 136 of them as the
equations read), so three more departures make them fewer (86): consecutive
layers that are built alike (the three sliding sparse ones) run as one
traced body under ``lax.scan`` over their stacked parameters; ``W_q``,
``W_k``, ``W_v`` and ``W_g`` stand side by side in one product, column for
column the four; the shared expert, where it has the routed experts' width,
is one more trip of their loop at weight 1.  The follower is
``reference/nemotron3_super_120b.py``'s (``plain.follow`` leaf by leaf,
Adam's moments on the host).
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
#: rows of a feed-forward layer and of the head alive at once
ROW_BLOCK = 1024
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
    statements (each defaults to the whole model): per held layer its
    attention kind, its MLP kind and its query heads."""
    n = int(cfg.get("attention_shares") or 1)
    layers = int(cfg.get("layers_held") or cfg["num_hidden_layers"])
    return dict(
        kinds=_group(cfg, "layer_types")[:layers],
        mlps=_group(cfg, "mlp_layer_types")[:layers],
        heads=[h // n for h in
               _group(cfg, "num_attention_heads_per_layer")[:layers]],
        kv_heads=max(1, cfg["num_key_value_heads"] // n),
        experts=int(cfg.get("num_experts_held") or cfg["num_experts"]),
        first_expert=int(cfg.get("first_expert_held") or 0),
    )


# -- shapes -------------------------------------------------------------------

def param_shapes(cfg, hyper):
    """The program's tree: layer ``i`` is two blocks, ``layers_<2i>`` its
    attention and ``layers_<2i+1>`` its MLP or experts, each with its
    norm."""
    d, V = cfg["hidden_size"], int(hyper["vocab_size"])
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    mine = held(cfg)
    KV, D, Eh = mine["kv_heads"], cfg["head_dim"], mine["experts"]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dec = {"final_norm": {"weight": s(d)}}
    for i, (H, mlp) in enumerate(zip(mine["heads"], mine["mlps"])):
        dec[f"layers_{2 * i}"] = {"norm": {"weight": s(d)}, "self_attn": {
            "q_proj": {"kernel": s(d, H * D)}, "k_proj": {"kernel": s(d, KV * D)},
            "v_proj": {"kernel": s(d, KV * D)}, "gate_proj": {"kernel": s(d, H)},
            "out_proj": {"kernel": s(H * D, d)},
        }}
        if mlp == "dense":
            body = {"mlp": {
                "fc1": {"kernel": s(d, 2 * cfg["intermediate_size"])},
                "fc2": {"kernel": s(cfg["intermediate_size"], d)}}}
        else:
            body = {"moe": {
                "router": s(d, cfg["num_experts"]),
                "experts_fc1": s(Eh, d, 2 * f), "experts_fc2": s(Eh, f, d),
                "shared_fc1": {"kernel": s(d, 2 * fs)},
                "shared_fc2": {"kernel": s(fs, d)},
            }}
        dec[f"layers_{2 * i + 1}"] = dict(body, norm={"weight": s(d)})
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
    """``(inv_freq (R / 2,), c)`` of one ``rope_parameters`` group, float64
    on the host, as the equations above read: ``R = D x
    partial_rotary_factor`` stands where a whole head's size would."""
    R = int(D * rp.get("partial_rotary_factor", 1.0))
    i = np.arange(R // 2, dtype=np.float64)
    e = float(rp["rope_theta"]) ** (-2.0 * i / R)
    if rp.get("rope_type", "default") == "default":
        return e, 1.0
    original, theta = rp["original_max_position_embeddings"], rp["rope_theta"]
    dim = lambda r: R * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(dim(rp["beta_fast"])), 0)
    high = min(math.ceil(dim(rp["beta_slow"])), R - 1)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    c = rp.get("attention_factor") or 0.1 * math.log(rp["factor"]) + 1.0
    return e / rp["factor"] * ramp + e * (1.0 - ramp), c


def rotary(x, table, leave_out=None):
    """``x`` (b, H, L, D): with ``R = 2 len(inv_freq)``, channel ``i < R /
    2`` and channel ``i + R / 2`` are one pair, turned by ``position *
    inv_freq_i`` and scaled by ``c``; channels ``R ..`` are left as they
    are."""
    inv_freq, c = table
    if leave_out == "attention_factor":
        c = 1.0
    L = x.shape[-2]
    half = len(inv_freq)
    if leave_out == "partial_rotary":  # the frequencies over the whole head
        inv_freq = np.concatenate([inv_freq, inv_freq])
        half = x.shape[-1] // 2
    angle = (jnp.arange(L, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq[:half], jnp.float32))
    cos, sin = jnp.cos(angle) * c, jnp.sin(angle) * c
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(x, p, c, kind, H, precision, leave_out=None):
    mine = held(c)
    KV, D = mine["kv_heads"], c["head_dim"]
    b, L, _ = x.shape
    sliding = kind == "sliding_attention"
    width = c["sliding_window"] if sliding and leave_out != "window" else L
    table = rope_table(_group(c, "rope_parameters")[kind], D)
    heads = lambda t, n: t.reshape(b, L, n, D).transpose(0, 2, 1, 3)
    # [W_q | W_k | W_v | W_g] side by side: one product, column for column
    # the four (a shorter compile)
    q, k, v, gate = jnp.split(dense(x, jnp.concatenate([
        p[name]["kernel"] for name in ("q_proj", "k_proj", "v_proj", "gate_proj")
    ], axis=1), precision), np.cumsum([H * D, KV * D, KV * D]), axis=-1)
    q, k = (rotary(heads(t, n), table, leave_out) for t, n in ((q, H), (k, KV)))
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, heads(v, KV)))
    gate = jax.nn.sigmoid(gate)                                   # (b, L, H)
    if leave_out == "gate":
        gate = jnp.ones_like(gate)
    key_at = jnp.arange(L)

    def block(qb, i):  # qb (queries, b, H, D) at positions i (queries,)
        ahead = i[:, None] - key_at[None, :]
        seen = (ahead >= 0) & (ahead < width)
        scores = D ** -0.5 * jnp.einsum("qbhd,bhkd->bhqk", qb, k,
                                        precision=plain.HIGHEST)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->qbhd", probs, v, precision=plain.HIGHEST)

    o = in_blocks(block, (q.transpose(2, 0, 1, 3), key_at), QUERY_BLOCK, (0, 0))
    o = o.transpose(1, 0, 2, 3) * gate[..., None]                # (b, L, H, D)
    return dense(o.reshape(b, L, H * D), p["out_proj"]["kernel"], precision)


def gated(t, w1, w2, precision):
    """``W_down (silu(W_gate t) * (W_up t))`` with ``w1 = [W_gate | W_up]``."""
    f = w2.shape[0]
    pre = dense(t, w1, precision)
    return dense(jax.nn.silu(pre[:, :f]) * pre[:, f:], w2, precision)


def mlp(h, p, precision):
    b, L, d = h.shape
    rows = lambda t: gated(t, p["fc1"]["kernel"], p["fc2"]["kernel"], precision)
    return in_blocks(rows, (h.reshape(b * L, d),), ROW_BLOCK, (0,)).reshape(b, L, d)


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


def experts(h, p, c, precision, leave_out=None):
    mine = held(c)
    b, L, d = h.shape
    first = mine["first_expert"]
    tokens = h.reshape(b * L, d)
    probs, idx = router(tokens, p, c, precision)
    w = jnp.take_along_axis(probs, idx, axis=1)
    if c.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if leave_out != "routed_scale":
        w = w * float(c.get("moe_routed_scaling_factor", 1.0))

    # expert ``first + j`` for j < held; the shared expert, where it has the
    # routed ones' width, is one more trip of their loop, at weight 1
    trips = [jnp.arange(mine["experts"]), p["experts_fc1"], p["experts_fc2"]]
    shared = ([] if leave_out == "shared_expert" else
              [p["shared_fc1"]["kernel"], p["shared_fc2"]["kernel"]])
    if shared and shared[0].shape == p["experts_fc1"].shape[1:]:
        trips = [jnp.concatenate([held_, one_more[None]]) for held_, one_more
                 in zip(trips, [jnp.asarray(-1)] + shared)]
        shared = []

    def rows(t, w, idx):
        def one(y, expert):
            j, w1, w2 = expert
            w_e = jnp.where(j < 0, 1.0, jnp.sum(
                jnp.where(idx == first + j, w, 0.0), axis=-1))
            return y + w_e[:, None] * gated(t, w1, w2, precision), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(t), tuple(trips))
        return y + gated(t, *shared, precision) if shared else y

    return in_blocks(rows, (tokens, w, idx), ROW_BLOCK, (0, 0, 0)).reshape(b, L, d)


def hidden(params, cfg, tokens, precision="float32", leave_out=None):
    """(B, L) ids -> the final-normed hidden states (B, L, d).
    ``leave_out`` (``"window"``: the sliding layers see the whole row;
    ``"attention_factor"``: ``c`` left at 1; ``"gate"``: every gate 1;
    ``"partial_rotary"``: the table's frequencies over the whole head;
    ``"routed_scale"``: the routed sum unscaled; ``"shared_expert"``: none)
    breaks the mathematics on purpose, for the tests that the comparison
    notices."""
    P = params["params"]
    dec, eps = P["decoder"], cfg["rms_norm_eps"]
    mine = held(cfg)

    def layer(kind, H, kind_mlp):
        @jax.checkpoint
        def run(x, pa, pm):
            h = rms_norm(x, pa["norm"]["weight"], eps)
            x = x + attention(h, pa["self_attn"], cfg, kind, H, precision,
                              leave_out)
            h = rms_norm(x, pm["norm"]["weight"], eps)
            if kind_mlp == "dense":
                return x + mlp(h, pm["mlp"], precision)
            return x + experts(h, pm["moe"], cfg, precision, leave_out)
        return run

    x = P["embed_tokens"]["embedding"][tokens]
    layers = list(zip(mine["kinds"], mine["heads"], mine["mlps"]))
    i = 0
    while i < len(layers):
        run = layer(*layers[i])
        n = 1  # the layers from i on that are built alike
        while i + n < len(layers) and layers[i + n] == layers[i]:
            n += 1
        blocks = [(dec[f"layers_{2 * j}"], dec[f"layers_{2 * j + 1}"])
                  for j in range(i, i + n)]
        if n == 1:
            x = run(x, *blocks[0])
        else:  # one traced body for the run of layers: a shorter compile
            stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *blocks)
            x, _ = jax.lax.scan(lambda x, b: (run(x, *b), None), x, stacked)
        i += n
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
