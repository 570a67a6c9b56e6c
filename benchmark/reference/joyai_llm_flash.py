"""Plain reference for ``joyai_llm_flash``: one chip's share of
JoyAI-LLM-Flash (jdopensource, ``model_type: joyai_llm_flash``; key for key
a DeepSeek-V3 configuration), from its published ``config.json`` and the
papers its mechanisms come from (latent attention: DeepSeek-V2,
arXiv:2405.04434 section 2.1; the sigmoid router with a selection bias and
the multi-token-prediction module: DeepSeek-V3, arXiv:2412.19437 sections
2.1.2 and 2.2; the bias rule: arXiv:2408.15664).

Float32 ``jax.numpy`` under ``highest``; nothing is imported from the
program, no kernel, no sort.  It is given the same share as the program (the
layers, heads and experts the configuration file states).  ``L`` positions a
row, rows independent; ``d`` the stream; ``H`` heads held; ``N`` / ``R`` /
``Dv`` ``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``; ``Cq``
/ ``C`` the query and key/value latents; every norm

    RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * w

    attention sublayer (MLA), h = RMSNorm(x)
    cq = RMSNorm(h W_qa)                  [q_nope ; q_rope] = cq W_qb  per head
    [c' ; k_rope] = h W_kva               c = RMSNorm(c');  ONE k_rope a token
    [k_nope ; v] = c W_kvb                per head
    q_rope, k_rope = rot(q_rope), rot(k_rope): positions 0 .. L-1, channels
      2i and 2i + 1 one pair (rope_interleave), turned by position *
      rope_theta^(-2i/R)
    s_ij = ([q_nope ; q_rope]_i . [k_nope ; k_rope]_j) / sqrt(N + R), j <= i
    f = concat_heads(softmax_j(s_ij) v_j) W_o;   x = x + f

    feed-forward sublayer, h = RMSNorm(x)
    dense (the first first_k_dense_replace layers):
      x = x + W_down (silu(W_gate h) * (W_up h))
    sparse: z = h W_r;  s = sigmoid(z) over ALL experts
      C = the num_experts_per_tok largest of s + b (b the leaf router_bias),
          or under router_balancing "batch_bias" of u + b', solved on the
          batch's n tokens:
          m_e = mean_t z_te,  sd_e = sqrt(mean_t (z_te - m_e)^2),
          u_te = (z_te - m_e) / sd_e + NOISE table_te  (a fixed normal table),
          b' = 0, then BIAS_ROUNDS times
          c_e = #{t: e among the top_k largest of u_t + b'},
          b'_e = b'_e - BIAS_GAIN ln((c_e + 1) / (n top_k / E + 1))
      w_e = routed_scaling_factor s_e / (sum_{c in C} s_c + 1e-20)
      x = x + sum_{e in C and held} w_e E_e(h) + S(h)
      E_e, S: W_down (silu(W_gate .) * (W_up .)); S unweighted

    y = RMSNorm(x_final);  nll_main = sum_i CE(y_i W_head, t_{i+1})

    prediction module (depth 1)
    u_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(y_i)] W_eh   (the last position
      of a row has no next token: zeros)
    u = u + MLA(RMSNorm(u));  u = u + Experts(RMSNorm(u))    its own weights
    nll_mtp = sum_i CE(RMSNorm_s(u_i) W_head, t_{i+2})       Emb, W_head shared
    loss = nll_main + mtp_loss_weight (n_main / n_mtp) nll_mtp, over n_main

The attention is written as the equations read: for a block of queries, its
scores against EVERY key of the row under an explicit mask, keys 192 wide
and values 128 (no padding).  Each held expert is a dense product over ALL
tokens times its column of weights.

Departures kept for memory and compile time, none of which changes a
result: each layer is rematerialized in the backward pass; the sparse
layers, built alike, run as one traced body under ``lax.scan`` over their
stacked parameters (the program stacks them the same way); the attention
runs over ``QUERY_BLOCK`` queries at a time and the feed-forward layers and
the head over ``ROW_BLOCK`` rows at a time; the shared expert, which has the
routed experts' width, is one more trip of their loop at weight 1.  The
follower (:func:`follow`) is ``reference/nemotron3_super_120b.py``'s with
the whole tree in each compiled call.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference import nemotron3_super_120b as leafwise
from benchmark.reference import plain
from benchmark.reference.evabyte import in_blocks
from benchmark.reference.zaya1_8b import dense, gated, rms_norm

#: queries whose scores against the whole row are alive at once
QUERY_BLOCK = 256
#: rows of a feed-forward layer and of the head alive at once
ROW_BLOCK = 1024
#: the "batch_bias" balancing rule's noise scale, rounds and step
NOISE = 1.0
BIAS_ROUNDS = 8
BIAS_GAIN = 0.55

#: what ``leave_out`` may name: each breaks one mechanism on purpose, for
#: the tests that the comparison notices
LEAVE_OUT = (
    "q_norm", "kv_norm", "shared_rope_key", "rotary", "scale",
    "selection_bias", "renormalisation", "routed_scale", "shared_expert",
    "eh_embedding", "eh_hidden", "mtp_final_norm", "mtp_weight",
    "shift_by_two",
)


def held(cfg):
    """What of the model this process holds, from the configuration's
    statements (each defaults to the whole model)."""
    layers = int(cfg.get("layers_held") or cfg["num_hidden_layers"])
    dense_layers = int(cfg["first_k_dense_replace"])
    module = int(cfg.get("num_nextn_predict_layers") or 0)
    last = "F" if cfg["num_hidden_layers"] <= dense_layers else "R"
    return dict(
        pattern="".join("L" + ("F" if i < dense_layers else "R")
                        for i in range(layers)),
        module="L" + last if module else "",
        heads=cfg["num_attention_heads"] // int(
            cfg.get("attention_shares") or 1),
        experts=int(cfg.get("num_experts_held") or cfg["n_routed_experts"]),
        first_expert=int(cfg.get("first_expert_held") or 0),
    )


# -- shapes -------------------------------------------------------------------

def layer_shapes(kind, c, lead=()):
    s = lambda *shape: jax.ShapeDtypeStruct(lead + shape, jnp.float32)
    lin = lambda i, o: {"kernel": s(i, o)}
    d, mine = c["hidden_size"], held(c)
    out = {"norm": {"weight": s(d)}}
    if kind == "L":
        H, Cq, C = mine["heads"], c["q_lora_rank"], c["kv_lora_rank"]
        N, R, Dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"])
        out["self_attn"] = {
            "q_a_proj": lin(d, Cq), "q_norm": {"weight": s(Cq)},
            "q_b_proj": lin(Cq, H * (N + R)),
            "kv_a_proj": lin(d, C + R), "kv_norm": {"weight": s(C)},
            "kv_b_proj": lin(C, H * (N + Dv)), "out_proj": lin(H * Dv, d),
        }
    elif kind == "F":
        f = c["intermediate_size"]
        out["mlp"] = {"fc1": lin(d, 2 * f), "fc2": lin(f, d)}
    elif kind == "R":
        E, Eh, f = c["n_routed_experts"], mine["experts"], c[
            "moe_intermediate_size"]
        fs = c["n_shared_experts"] * f
        out["moe"] = {
            "router": s(d, E), "router_bias": s(E),
            "experts_fc1": s(Eh, d, 2 * f), "experts_fc2": s(Eh, f, d),
            "shared_fc1": lin(d, 2 * fs), "shared_fc2": lin(fs, d),
        }
    else:
        raise ValueError(f"layer kind {kind!r}")
    return out


def param_shapes(cfg, hyper):
    """The program's tree: layer ``i`` is two blocks, its attention and its
    MLP or experts, each with its norm; the repeated tail of the pattern is
    one unit, stacked on a leading axis (``units/layer_0``,
    ``units/layer_1``); the prediction module under ``mtp``."""
    d, V = cfg["hidden_size"], int(hyper["vocab_size"])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    mine = held(cfg)
    head, unit, repeats = leafwise.split_pattern(mine["pattern"])
    dec = {"final_norm": {"weight": s(d)}}
    for i, kind in enumerate(head):
        dec[f"layers_{i}"] = layer_shapes(kind, cfg)
    if repeats:
        dec["units"] = {f"layer_{j}": layer_shapes(kind, cfg, (repeats,))
                        for j, kind in enumerate(unit)}
    tree = {"embed_tokens": {"embedding": s(V, d)}, "decoder": dec,
            "lm_head": s(d, V)}
    if mine["module"]:
        tree["mtp"] = {
            "join": {"enorm": {"weight": s(d)}, "hnorm": {"weight": s(d)},
                     "eh_proj": {"kernel": s(2 * d, d)}},
            "final_norm": {"weight": s(d)},
            **{f"layers_{j}": layer_shapes(kind, cfg)
               for j, kind in enumerate(mine["module"])},
        }
    return {"params": tree}


# -- layers -------------------------------------------------------------------

def rotary(x, c):
    """``x`` (b, heads, L, R), every channel rotated at positions ``0 ..
    L-1``: with ``rope_interleave`` channels ``2i`` and ``2i + 1`` are one
    pair, else channels ``i`` and ``i + R / 2``; the pair is turned by
    ``position * rope_theta^(-2i/R)``."""
    L, R = x.shape[-2:]
    inv_freq = float(c["rope_theta"]) ** (
        -2.0 * np.arange(R // 2, dtype=np.float64) / R)
    angle = (jnp.arange(L, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if c.get("rope_interleave", True):
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla(h, p, c, precision, leave_out=None):
    H = held(c)["heads"]
    C, N, R = c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    b, L, _ = h.shape
    eps = c["rms_norm_eps"]
    cq = dense(h, p["q_a_proj"]["kernel"], precision)
    if leave_out != "q_norm":
        cq = rms_norm(cq, p["q_norm"]["weight"], eps)
    heads = lambda t: t.reshape(b, L, H, -1).transpose(0, 2, 1, 3)
    q = heads(dense(cq, p["q_b_proj"]["kernel"], precision))  # (b, H, L, N+R)
    kva = dense(h, p["kv_a_proj"]["kernel"], precision)
    latent, k_rope = kva[..., :C], kva[:, None, :, C:]        # (b, 1, L, R)
    if leave_out != "kv_norm":
        latent = rms_norm(latent, p["kv_norm"]["weight"], eps)
    kv = heads(dense(latent, p["kv_b_proj"]["kernel"], precision))
    k_nope, v = kv[..., :N], kv[..., N:]
    q_nope, q_rope = q[..., :N], q[..., N:]
    if leave_out != "rotary":
        q_rope, k_rope = rotary(q_rope, c), rotary(k_rope, c)
    if leave_out == "shared_rope_key":  # no rotary term in the scores
        k_rope = jnp.zeros_like(k_rope)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, H, L, R))], axis=-1)
    scale = (N if leave_out == "scale" else N + R) ** -0.5
    key_at = jnp.arange(L)

    def block(qb, i):  # qb (queries, b, H, N + R) at positions i (queries,)
        seen = key_at[None, :] <= i[:, None]
        scores = scale * jnp.einsum("qbhd,bhkd->bhqk", qb, k,
                                    precision=plain.HIGHEST)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->qbhd", probs, v, precision=plain.HIGHEST)

    o = in_blocks(block, (q.transpose(2, 0, 1, 3), key_at), QUERY_BLOCK, (0, 0))
    return dense(o.transpose(1, 0, 2, 3).reshape(b, L, -1),
                 p["out_proj"]["kernel"], precision)


def mlp(h, p, precision):
    b, L, d = h.shape
    rows = lambda t: gated(t, p["fc1"]["kernel"], p["fc2"]["kernel"], precision)
    return in_blocks(rows, (h.reshape(b * L, d),), ROW_BLOCK, (0,)).reshape(b, L, d)


def router(t, p, c, precision, leave_out=None):
    """``t`` (n, d), ALL tokens of the batch: the scores ``s`` (n, E) and
    the chosen experts ``idx`` (n, top_k)."""
    z = dense(t, p["router"], precision)
    s = jax.nn.sigmoid(z)
    k = c["num_experts_per_tok"]
    chooser = s if leave_out == "selection_bias" else s + p["router_bias"]
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
    return s, idx


def experts(h, p, c, precision, leave_out=None):
    mine = held(c)
    b, L, d = h.shape
    first = mine["first_expert"]
    tokens = h.reshape(b * L, d)
    s, idx = router(tokens, p, c, precision, leave_out)
    w = jnp.take_along_axis(s, idx, axis=1)
    if c.get("norm_topk_prob", True) and leave_out != "renormalisation":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    if leave_out != "routed_scale":
        w = w * float(c.get("routed_scaling_factor", 1.0))

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


def block(x, p, kind, c, precision, leave_out=None):
    h = rms_norm(x, p["norm"]["weight"], c["rms_norm_eps"])
    if kind == "L":
        return x + mla(h, p["self_attn"], c, precision, leave_out)
    if kind == "F":
        return x + mlp(h, p["mlp"], precision)
    return x + experts(h, p["moe"], c, precision, leave_out)


def layers(tree, names, kinds, c, precision, leave_out):
    """``(run, ps)``: the blocks ``tree[name]`` of ``kinds`` as one
    rematerialized unit ``run(x, ps)``."""
    @jax.checkpoint
    def run(x, ps):
        for p, kind in zip(ps, kinds):
            x = block(x, p, kind, c, precision, leave_out)
        return x
    return run, [tree[name] for name in names]


def hidden(params, cfg, tokens, precision="float32", leave_out=None):
    """(B, L) ids -> the decoder's final-normed hidden states (B, L, d).
    ``leave_out`` (one of :data:`LEAVE_OUT`) breaks the mathematics on
    purpose."""
    P = params["params"]
    dec = P["decoder"]
    head, unit, repeats = leafwise.split_pattern(held(cfg)["pattern"])
    x = P["embed_tokens"]["embedding"][tokens]
    for i in range(0, len(head), 2):  # a layer: its attention, its MLP
        run, ps = layers(dec, [f"layers_{i}", f"layers_{i + 1}"],
                         head[i:i + 2], cfg, precision, leave_out)
        x = run(x, ps)
    if repeats:  # one traced body for the stacked layers
        run, ps = layers(dec["units"],
                         [f"layer_{j}" for j in range(len(unit))], unit, cfg,
                         precision, leave_out)
        x, _ = jax.lax.scan(lambda x, p: (run(x, p), None), x, ps)
    return rms_norm(x, dec["final_norm"]["weight"], cfg["rms_norm_eps"])


def module_hidden(params, cfg, tokens, y, precision="float32", leave_out=None):
    """The prediction module's final-normed stream from the decoder's
    ``y``: position ``i`` reads token ``i + 1``'s embedding."""
    P = params["params"]
    p, eps = P["mtp"], cfg["rms_norm_eps"]
    d = y.shape[-1]
    e = P["embed_tokens"]["embedding"][tokens]
    e = jnp.pad(e[:, 1:], ((0, 0), (0, 1), (0, 0)))   # no next token: zeros
    e = rms_norm(e, p["join"]["enorm"]["weight"], eps)
    yh = rms_norm(y, p["join"]["hnorm"]["weight"], eps)
    if leave_out == "eh_embedding":
        e = jnp.zeros_like(e)
    if leave_out == "eh_hidden":
        yh = jnp.zeros_like(yh)
    # the embedding's half first under W_eh
    u = dense(jnp.concatenate([e, yh], axis=-1),
              p["join"]["eh_proj"]["kernel"], precision)
    kinds = held(cfg)["module"]
    run, ps = layers(p, [f"layers_{j}" for j in range(len(kinds))], kinds,
                     cfg, precision, leave_out)
    u = run(u, ps)
    if leave_out == "mtp_final_norm":
        return u
    return rms_norm(u, p["final_norm"]["weight"], eps)


def head_nll(x, head, target, ahead, pad_idx, precision):
    """Summed NLL of position ``t`` against token ``t + ahead`` and the
    targets that count (padding and the row's end do not)."""
    B, L, d = x.shape
    t = jnp.pad(target[:, ahead:], ((0, 0), (0, ahead)),
                constant_values=pad_idx)

    def rows(xb, tb):
        logits = dense(xb, head, precision)
        counts = tb != pad_idx
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            lp, jnp.where(counts, tb, 0)[:, None], axis=-1)[:, 0]
        return jnp.where(counts, nll, 0.0)

    nll = jnp.sum(in_blocks(
        rows, (x.reshape(B * L, d), t.reshape(B * L)), ROW_BLOCK,
        (0, pad_idx)))
    return nll, jnp.sum(t != pad_idx).astype(jnp.float32)


def loss_parts(params, cfg, batch, pad_idx, precision="float32",
               leave_out=None):
    """``(loss, nll_main, nll_mtp, n_main, n_mtp)``: the summed weighted
    loss over the main pass's sample size, and its parts."""
    tokens, target = batch["net_input"]["src_tokens"], batch["target"]
    head = params["params"]["lm_head"]
    y = hidden(params, cfg, tokens, precision, leave_out)
    nll, n = head_nll(y, head, target, 1, pad_idx, precision)
    if not held(cfg)["module"]:
        return nll, nll, jnp.zeros(()), n, jnp.zeros(())
    z = module_hidden(params, cfg, tokens, y, precision, leave_out)
    nll2, n2 = head_nll(z, head, target,
                        1 if leave_out == "shift_by_two" else 2, pad_idx,
                        precision)
    weight = 1.0 if leave_out == "mtp_weight" else float(
        cfg.get("mtp_loss_weight", 0.3))
    return nll + weight * (n / jnp.maximum(n2, 1.0)) * nll2, nll, nll2, n, n2


def loss_sum(params, cfg, batch, pad_idx, precision="float32", leave_out=None):
    return loss_parts(params, cfg, batch, pad_idx, precision, leave_out)[0]


# -- the follower ---------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=(
    "b1", "b2", "eps", "wd", "clip", "decayed"))
def _update(master, m, v, grads, step, lr, inv_size, *, b1, b2, eps, wd, clip,
            decayed):
    """One update of the whole tree (lists of leaves): the gradient of the
    summed loss normalised by the sample size and clipped to a global norm
    of ``clip``, then Adam with decoupled decay on the ``decayed`` leaves.
    Returns the new master weights and moments and the norm of every leaf
    of the gradient as Adam got it."""
    grads = [g * inv_size for g in grads]
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads))
    coef = jnp.minimum(clip / (norm + 1e-6), 1.0) if clip > 0 else 1.0
    grads = [g * coef for g in grads]
    size = lr * jnp.sqrt(1.0 - b2 ** step) / (1.0 - b1 ** step)
    out = []
    for p, mm, vv, g, decays in zip(master, m, v, grads, decayed):
        if wd and decays:
            p = p * (1.0 - size * wd)
        mm = b1 * mm + (1.0 - b1) * g
        vv = b2 * vv + (1.0 - b2) * jnp.square(g)
        out.append((p - size * mm / (jnp.sqrt(vv) + eps), mm, vv))
    return (*map(list, zip(*out)),
            jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g))) for g in grads]))


_as_bf16 = jax.jit(lambda leaves: [plain.as_bf16(x) for x in leaves])
_change_norms = jax.jit(lambda a, b: jnp.stack(
    [jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in zip(a, b)]))


def follow(shapes, seed, hyper, batches, batch_grad):
    """What ``reference/nemotron3_super_120b.follow`` does, to the letter of
    its equations (three updates from the seeded weights; each update's
    loss, the leaf norms of the first gradient as the optimizer gets it, the
    leaf norms of the master weights' change; the master weights on the
    device and Adam's moments on the host between updates), with the WHOLE
    tree in each compiled call where that one compiles an Adam, two
    scalings, a sum of squares and two casts for every distinct leaf shape:
    this tree has 31, and a run with nothing cached paid 23 s for them
    (PERF.md section 6, PR 50).  The parameters are rounded to bfloat16's
    grid by ``lax.reduce_precision``, which a compiled program keeps (a pair
    of casts it would drop as excess precision)."""
    with jax.default_matmul_precision("highest"):
        bf16 = bool(hyper.get("bf16", True))
        rounded = _as_bf16 if bf16 else (lambda leaves: leaves)
        seeded, treedef = jax.tree_util.tree_flatten(weights.make(shapes, seed))
        master = rounded(seeded)
        del seeded
        names = weights.leaf_names(shapes)
        decayed = tuple(n.rsplit("/", 1)[-1] in leafwise.DECAYED for n in names)
        m = [np.zeros(x.shape, np.float32) for x in master]
        v = [np.zeros(x.shape, np.float32) for x in master]
        b1, b2 = (float(b) for b in hyper["adam_betas"])
        losses, grad_norms = [], None
        for k, batch in enumerate(batches):
            t0 = time.perf_counter()
            total, size, grads = batch_grad(
                jax.tree_util.tree_unflatten(treedef, rounded(master)), batch)
            losses.append(float(total) / float(size))
            t1 = time.perf_counter()
            master, m, v, norms = _update(
                master, [jnp.asarray(x) for x in m],
                [jnp.asarray(x) for x in v], jax.tree_util.tree_leaves(grads),
                jnp.float32(k + 1), jnp.float32(hyper["lr"]),
                jnp.float32(1.0 / size), b1=b1, b2=b2,
                eps=float(hyper["adam_eps"]), wd=float(hyper["weight_decay"]),
                clip=float(hyper["clip_norm"]), decayed=decayed)
            del grads
            m, v = [np.asarray(x) for x in m], [np.asarray(x) for x in v]
            if k == 0:
                grad_norms = np.asarray(norms, np.float64)
            print(f"reference: update {k + 1}: loss and gradient "
                  f"{t1 - t0:.1f}s, Adam over the tree "
                  f"{time.perf_counter() - t1:.1f}s", flush=True)
        del m, v
        start = rounded(jax.tree_util.tree_leaves(weights.make(shapes, seed)))
        return {"loss": losses, "grad_norms": grad_norms,
                "delta_norms": np.asarray(_change_norms(master, start),
                                          np.float64),
                "names": names}


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

    return follow(param_shapes(cfg, hyper), seed, hyper, batches, batch_grad)
