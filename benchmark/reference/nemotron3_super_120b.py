"""Plain reference for ``nemotron3_super_120b``: one chip's share of
NVIDIA-Nemotron-3-Super-120B-A12B (``model_type: nemotron_h``), from its
published ``config.json`` and the papers its layers come from (Mamba-2: Dao
& Gu 2024, arXiv:2405.21060; the routing: DeepSeek-V3, arXiv:2412.19437).

Float32 ``jax.numpy`` under ``highest``; the state-space recurrence TOKEN
BY TOKEN (not the chunked form the program computes); the held experts as
a plain loop, each over every token with the tokens that did not choose it
weighted zero (no buffer, so nothing can be dropped); full softmax
attention over the dense causal mask.  Nothing is imported from the
program.  It is given the same share as the program (the heads, groups,
experts and vocabulary slice the configuration file states); what the
absent experts would add is left out here as there.

Every layer is ``x = x + mixer(RMSNorm(x))``:

* ``M``: ``z, xBC, dt = split(u W_in)``; ``xBC = silu(conv(xBC) + b)``
  (causal, depthwise, 4 taps); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head, with its group's ``B_t, C_t``:
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``;
  ``out = RMSNorm_group(y * silu(z)) W_out``.
* ``*``: ``num_attention_heads`` query heads on ``num_key_value_heads`` KV
  heads (query head ``h`` on KV head ``h // ratio``), scale
  ``head_dim ** -0.5``, causal, no bias, no rotary embedding (assumed: see
  the configuration file).
* ``E``: ``s = sigmoid(h W_r)``; the ``num_experts_per_tok`` largest of
  ``s + b_corr`` are chosen; ``w = s_chosen / sum(s_chosen) *
  routed_scaling_factor``; ``l = h W_down``; ``r = sum over chosen AND held
  e of w_e W2_e relu(W1_e l)^2``; ``y = r W_up + W2_s relu(W1_s h)^2``.

Departures kept for memory and compile time, none of which changes a
result: each layer is rematerialized in the backward pass; the recurrence
keeps its state every ``SEGMENT`` tokens and recomputes the steps between
(still one token at a time); the pattern's repeated unit is a loop
(``lax.scan``) over its stacked parameters, so its layers are compiled once
and not once per repeat (the first run of a checkout compiles this
reference inside the run's time limit).  The follower (:func:`follow`) keeps Adam's moments on the
host and updates leaf by leaf: ``plain.follow`` would hold about seven
float32 copies of 701 M parameters on the device.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference import plain

#: tokens between kept states of the recurrence
SEGMENT = 128
#: leaves that are matrices in their layer, and so decay (the stacked
#: units give every leaf one more axis, so rank cannot say)
DECAYED = ("kernel", "embedding", "router", "experts_fc1", "experts_fc2",
           "conv_kernel", "lm_head")


def split_pattern(pattern):
    """``(head, unit, repeats)``: the program stacks the parameters of a
    pattern's repeated tail on a leading axis; the tree here has the same
    shape (see ``unicore_tpu/modules/hybrid_decoder.py``: the fewest
    distinct layer bodies, the shorter head on a tie)."""
    best = (pattern, "", 0)
    n = len(pattern)
    for h in range(n):
        for u in range(1, (n - h) // 2 + 1):
            reps, rest = divmod(n - h, u)
            if rest == 0 and pattern[h:] == pattern[h:h + u] * reps:
                if h + u < len(best[0]) + len(best[1]):
                    best = (pattern[:h], pattern[h:h + u], reps)
                break
    return best


def held(cfg):
    """What of the model this process holds, from the configuration's
    statements: the layers (``pattern_held``, else all), the mixers' heads
    divided ``mixer_shares`` ways (a share keeps at least one KV head), the
    routed experts ``first_routed_expert_held ..`` (else all)."""
    n = int(cfg.get("mixer_shares") or 1)
    E = cfg["n_routed_experts"]
    return dict(
        pattern=cfg.get("pattern_held") or cfg["hybrid_override_pattern"],
        mamba_heads=cfg["mamba_num_heads"] // n, groups=cfg["n_groups"] // n,
        heads=cfg["num_attention_heads"] // n,
        kv_heads=max(1, cfg["num_key_value_heads"] // n),
        experts=cfg.get("n_routed_experts_held") or E,
        first_expert=int(cfg.get("first_routed_expert_held") or 0),
    )


# -- shapes -------------------------------------------------------------------

def layer_shapes(kind, c, lead=()):
    s = lambda *shape: jax.ShapeDtypeStruct(lead + shape, jnp.float32)
    lin = lambda i, o: {"kernel": s(i, o)}
    d = c["hidden_size"]
    mine = held(c)
    out = {"norm": {"weight": s(d)}}
    if kind == "M":
        H, P = mine["mamba_heads"], c["mamba_head_dim"]
        bc = mine["groups"] * c["ssm_state_size"]
        inner = H * P
        out["mamba"] = {
            "in_proj": lin(d, 2 * inner + 2 * bc + H),
            "conv_kernel": s(c["conv_kernel"], inner + 2 * bc),
            "conv_bias": s(inner + 2 * bc),
            "dt_bias": s(H), "A_log": s(H), "D_skip": s(H),
            "norm": {"weight": s(inner)},
            "out_proj": lin(inner, d),
        }
    elif kind == "*":
        H, KV, D = mine["heads"], mine["kv_heads"], c["head_dim"]
        out["self_attn"] = {
            "q_proj": lin(d, H * D), "k_proj": lin(d, KV * D),
            "v_proj": lin(d, KV * D), "out_proj": lin(H * D, d),
        }
    elif kind == "E":
        E, Eh = c["n_routed_experts"], mine["experts"]
        lat, f = c["moe_latent_size"], c["moe_intermediate_size"]
        fs = c["moe_shared_expert_intermediate_size"]
        out["moe"] = {
            "router": s(d, E), "correction": s(E),
            "latent_down": lin(d, lat), "latent_up": lin(lat, d),
            "experts_fc1": s(Eh, lat, f), "experts_fc2": s(Eh, f, lat),
            "shared_fc1": lin(d, fs), "shared_fc2": lin(fs, d),
        }
    else:
        raise ValueError(f"layer kind {kind!r}")
    return out


def param_shapes(cfg, hyper):
    d, V = cfg["hidden_size"], int(hyper["vocab_size"])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    head, unit, repeats = split_pattern(held(cfg)["pattern"])
    dec = {"final_norm": {"weight": s(d)}}
    for i, kind in enumerate(head):
        dec[f"layers_{i}"] = layer_shapes(kind, cfg)
    if repeats:
        dec["units"] = {
            f"layer_{j}": layer_shapes(kind, cfg, (repeats,))
            for j, kind in enumerate(unit)
        }
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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def recurrence(x, dt, A, B, C, D, leave_out_skip=False):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``; ``y_t = h_t C_t +
    D x_t``, one token per step.  ``x`` (b, L, H, P), ``dt`` (b, L, H),
    ``A``, ``D`` (H,), ``B``, ``C`` (b, L, G, N); head ``h`` reads group
    ``h // (H / G)``."""
    b, L, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    pad = (-L) % SEGMENT
    if pad:  # dt = 0: the state passes through unchanged
        widths = lambda a: ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
        x, dt, B, C = (jnp.pad(a, widths(a)) for a in (x, dt, B, C))

    def token(h, inp):
        x_t, dt_t, B_t, C_t = inp            # (b,H,P) (b,H) (b,G,N) (b,G,N)
        B_h = jnp.repeat(B_t, R, axis=1)     # (b, H, N)
        C_h = jnp.repeat(C_t, R, axis=1)
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., :, None] * B_h[..., None, :])
        return h, jnp.sum(h * C_h[..., None, :], axis=-1)

    @jax.checkpoint
    def segment(h, inps):
        return jax.lax.scan(token, h, inps)

    by_segment = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (-1, SEGMENT) + a.shape[:1] + a.shape[2:]
    )
    _, y = jax.lax.scan(
        segment, jnp.zeros((b, H, P, N), jnp.float32),
        tuple(by_segment(a) for a in (x, dt, B, C)),
    )
    y = jnp.moveaxis(y.reshape((-1, b, H, P)), 0, 1)[:, :L]
    if leave_out_skip:
        return y
    return y + x[:, :L] * D[:, None]


def mamba(u, p, c, precision, leave_out=None):
    mine = held(c)
    H, P, G, N = (mine["mamba_heads"], c["mamba_head_dim"], mine["groups"],
                  c["ssm_state_size"])
    inner, bc = H * P, G * N
    b, L, _ = u.shape
    zxbcdt = dense(u, p["in_proj"]["kernel"], precision)
    z, xBC, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], axis=-1)
    K = p["conv_kernel"].shape[0]
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + L] * p["conv_kernel"][k] for k in range(K))
    xBC = jax.nn.silu(conv + p["conv_bias"])
    x, B, C = jnp.split(xBC, [inner, inner + bc], axis=-1)
    y = recurrence(
        x.reshape(b, L, H, P), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), B.reshape(b, L, G, N), C.reshape(b, L, G, N),
        p["D_skip"], leave_out_skip=leave_out == "skip",
    ).reshape(b, L, inner)
    y = y * jax.nn.silu(z)
    y = rms_norm(
        y.reshape(b, L, G, inner // G), 1.0, c["layer_norm_epsilon"]
    ).reshape(b, L, inner) * p["norm"]["weight"]
    return dense(y, p["out_proj"]["kernel"], precision)


def attention(x, p, c, precision):
    mine = held(c)
    H, KV, D = mine["heads"], mine["kv_heads"], c["head_dim"]
    b, L, _ = x.shape
    heads = lambda t, n: t.reshape(b, L, n, D).transpose(0, 2, 1, 3)
    q = heads(dense(x, p["q_proj"]["kernel"], precision), H) * D ** -0.5
    k = jnp.repeat(heads(dense(x, p["k_proj"]["kernel"], precision), KV),
                   H // KV, axis=1)
    v = jnp.repeat(heads(dense(x, p["v_proj"]["kernel"], precision), KV),
                   H // KV, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=plain.HIGHEST)
    future = jnp.arange(L)[None, :] > jnp.arange(L)[:, None]
    probs = jax.nn.softmax(jnp.where(future, -jnp.inf, scores), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=plain.HIGHEST)
    o = o.transpose(0, 2, 1, 3).reshape(b, L, H * D)
    return dense(o, p["out_proj"]["kernel"], precision)


def latent_moe(h, p, c, precision, leave_out=None):
    Eh, first = held(c)["experts"], held(c)["first_expert"]
    b, L, d = h.shape
    t = h.reshape(b * L, d)
    s = jax.nn.sigmoid(dense(t, p["router"], precision))
    _, idx = jax.lax.top_k(s + p["correction"], c["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=1)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    latent = dense(t, p["latent_down"]["kernel"], precision)
    routed = jnp.zeros_like(latent)
    for j in range(Eh):
        if leave_out == "expert" and j == 0:
            continue
        w_e = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        routed = routed + w_e[:, None] * dense(
            relu2(dense(latent, p["experts_fc1"][j], precision)),
            p["experts_fc2"][j], precision,
        )
    y = dense(routed, p["latent_up"]["kernel"], precision)
    y = y + dense(
        relu2(dense(t, p["shared_fc1"]["kernel"], precision)),
        p["shared_fc2"]["kernel"], precision,
    )
    return y.reshape(b, L, d)


def block(x, p, kind, c, precision, leave_out=None):
    h = rms_norm(x, p["norm"]["weight"], c["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba(h, p["mamba"], c, precision, leave_out)
    if kind == "*":
        return x + attention(h, p["self_attn"], c, precision)
    return x + latent_moe(h, p["moe"], c, precision, leave_out)


def hidden(params, cfg, tokens, precision="float32", leave_out=None):
    """(B, L) tokens -> the final-normed hidden states (B, L, d).
    ``leave_out`` (``"skip"``: the ``D x_t`` term; ``"expert"``: the first
    held expert's output) breaks the mathematics on purpose, for the tests
    that the comparison notices."""
    P = params["params"]
    dec = P["decoder"]
    head, unit, repeats = split_pattern(held(cfg)["pattern"])
    layer = {
        kind: jax.checkpoint(
            lambda x, p, kind=kind: block(x, p, kind, cfg, precision, leave_out)
        )
        for kind in set(head + unit)
    }
    x = P["embed_tokens"]["embedding"][tokens]
    for i, kind in enumerate(head):
        x = layer[kind](x, dec[f"layers_{i}"])
    if repeats:
        def one_unit(x, p):  # p: the unit's parameters, one repeat's slice
            for j, kind in enumerate(unit):
                x = layer[kind](x, p[f"layer_{j}"])
            return x, None

        x, _ = jax.lax.scan(one_unit, x, dec["units"])
    return rms_norm(x, dec["final_norm"]["weight"], cfg["layer_norm_epsilon"])


def loss_sum(params, cfg, batch, pad_idx, precision="float32", leave_out=None):
    """Summed next-token negative log-likelihood: position ``t`` predicts
    token ``t + 1``; padding targets do not count."""
    tokens, target = batch["net_input"]["src_tokens"], batch["target"]
    x = hidden(params, cfg, tokens, precision, leave_out)
    logits = dense(x[:, :-1], params["params"]["lm_head"], precision)
    return plain.masked_nll_sum(logits, target[:, 1:], pad_idx)


# -- the follower -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def _adam_leaf(p, m, v, g, step, lr, *, b1, b2, eps, wd):
    """``plain._adam_update`` for one leaf (``g`` already clipped)."""
    size = lr * jnp.sqrt(1.0 - b2 ** step) / (1.0 - b1 ** step)
    if wd:
        p = p * (1.0 - size * wd)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    return p - size * m / (jnp.sqrt(v) + eps), m, v


_sq_sum = jax.jit(lambda x: jnp.sum(jnp.square(x)))
_scaled = jax.jit(lambda g, k: g * k)
_norm_of_change = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))


def follow(shapes, seed, hyper, batches, batch_grad):
    """What ``plain.follow`` does (three updates from the seeded weights;
    each update's loss, the leaf norms of the first gradient as the
    optimizer gets it, the leaf norms of the master weights' change), with
    the master weights on the device, Adam's moments on the host, and the
    update made leaf by leaf, so that the device holds three copies of the
    weights (master, rounded parameters, gradient) and not seven."""
    with jax.default_matmul_precision("highest"):
        bf16 = bool(hyper.get("bf16", True))
        master, treedef = jax.tree_util.tree_flatten(
            plain.round_bf16(weights.make(shapes, seed), bf16)
        )
        names = weights.leaf_names(shapes)
        decayed = [n.rsplit("/", 1)[-1] in DECAYED for n in names]
        m = [np.zeros(x.shape, np.float32) for x in master]
        v = [np.zeros(x.shape, np.float32) for x in master]
        b1, b2 = (float(b) for b in hyper["adam_betas"])
        clip = float(hyper["clip_norm"])
        losses, grad_norms = [], None
        for k, batch in enumerate(batches):
            t0 = time.perf_counter()
            rounded = jax.tree_util.tree_unflatten(
                treedef, jax.tree_util.tree_leaves(
                    plain.round_bf16(master, bf16))
            )
            loss_total, size, grads = batch_grad(rounded, batch)
            del rounded
            grads = jax.tree_util.tree_leaves(grads)
            losses.append(float(loss_total) / float(size))
            t1 = time.perf_counter()
            grads = [_scaled(g, jnp.float32(1.0 / size)) for g in grads]
            gnorm = float(np.sqrt(sum(float(_sq_sum(g)) for g in grads)))
            coef = min(clip / (gnorm + 1e-6), 1.0) if clip > 0 else 1.0
            norms = []
            for i in range(len(master)):
                g = _scaled(grads[i], jnp.float32(coef))
                grads[i] = None
                if k == 0:
                    norms.append(float(jnp.sqrt(_sq_sum(g))))
                master[i], m_i, v_i = _adam_leaf(
                    master[i], m[i], v[i], g, jnp.float32(k + 1),
                    jnp.float32(hyper["lr"]), b1=b1, b2=b2,
                    eps=float(hyper["adam_eps"]),
                    wd=float(hyper["weight_decay"]) if decayed[i] else 0.0,
                )
                m[i], v[i] = np.asarray(m_i), np.asarray(v_i)
            if k == 0:
                grad_norms = np.asarray(norms, np.float64)
            print(f"reference: update {k + 1}: loss and gradient "
                  f"{t1 - t0:.1f}s, Adam leaf by leaf "
                  f"{time.perf_counter() - t1:.1f}s", flush=True)
        del m, v, grads
        start = jax.tree_util.tree_leaves(
            plain.round_bf16(weights.make(shapes, seed), bf16)
        )
        delta = np.asarray(
            [float(_norm_of_change(a, b)) for a, b in zip(master, start)],
            np.float64,
        )
        return {"loss": losses, "grad_norms": grad_norms,
                "delta_norms": delta, "names": names}


def train_check(cfg, hyper, batches, seed, rows, precision="float32",
                leave_out=None):
    """``rows`` is not used: a block of this cell is one whole sequence,
    and the layers' rematerialization is what makes it fit."""
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
