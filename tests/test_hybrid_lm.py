"""The hybrid decoder's mechanisms at sizes a CPU holds: the chunked
state-space scan against the token-by-token recurrence, the Mamba-2 mixer,
grouped-KV attention, the LatentMoE that is told which experts it holds
(its shares add up to the uncut layer; no pair is lost at the worst
skew), the packing dataset, the chunked loss, and the whole model through
the trainer.  The whole model against the plain reference over three
updates is ``tests/benchmark/test_nemotron3.py``."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.modules.hybrid_decoder import split_pattern
from unicore_tpu.modules.latent_moe import STATS, LatentMoE, relu2
from unicore_tpu.modules.mamba2 import Mamba2Mixer
from unicore_tpu.modules.multihead_attention import GroupedQueryAttention
from unicore_tpu.ops.ssd_scan import ssd_recurrence, ssd_scan


def scan_inputs(L, b=2, H=4, P=8, G=2, N=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (
        jax.random.normal(ks[0], (b, L, H, P)),
        jax.nn.softplus(jax.random.normal(ks[1], (b, L, H))),
        -jnp.exp(0.3 * jax.random.normal(ks[2], (H,))),
        jax.random.normal(ks[3], (b, L, G, N)),
        jax.random.normal(ks[4], (b, L, G, N)),
        jax.random.normal(ks[5], (H,)),
    )


# lengths that are, and are not, multiples of the chunk (16); one shorter
# than a chunk
@pytest.mark.parametrize("L", [32, 64, 37, 5])
def test_chunked_scan_is_the_recurrence_forward_and_gradients(L):
    args = scan_inputs(L)
    got, want = ssd_scan(*args, chunk=16), ssd_recurrence(*args)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    every = tuple(range(len(args)))
    g_got = jax.grad(lambda *a: jnp.sum(jnp.sin(ssd_scan(*a, chunk=16))), every)(*args)
    g_want = jax.grad(lambda *a: jnp.sum(jnp.sin(ssd_recurrence(*a))), every)(*args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def test_chunked_scan_without_the_skip_term_differs():
    """``D x_t`` is part of the result: leaving it out is seen."""
    args = scan_inputs(32)
    assert float(jnp.abs(
        ssd_scan(*args, chunk=16) - ssd_scan(*args[:5], None, chunk=16)
    ).max()) > 0.1


@pytest.mark.parametrize("pattern,want", [
    ("*EMEMEMEMEM", ("*", "EM", 5)),
    ("*EMEM", ("*", "EM", 2)),
    ("MMMM", ("", "M", 4)),
    ("M*E", ("M*E", "", 0)),
    ("MEMEM*EMEM", ("MEMEM*", "EM", 2)),
])
def test_split_pattern(pattern, want):
    head, unit, repeats = split_pattern(pattern)
    assert (head, unit, repeats) == want
    assert head + unit * repeats == pattern


# -- shares of the mixers --------------------------------------------------------

def mamba_share(params, j, shares, H, P, G, N):
    """The parameters of share ``j`` of a mixer whose heads and groups are
    divided evenly over ``shares``."""
    inner, bc = H * P, G * N
    hs = slice(j * inner // shares, (j + 1) * inner // shares)
    gs = slice(j * bc // shares, (j + 1) * bc // shares)
    heads = slice(j * H // shares, (j + 1) * H // shares)
    cols = lambda a, parts: jnp.concatenate(
        [a[..., lo:lo + w][..., s] for lo, w, s in parts], axis=-1
    )
    xbc = [(0, inner, hs), (inner, bc, gs), (inner + bc, bc, gs)]
    p = params["params"]
    return {"params": {
        "in_proj": {"kernel": cols(
            p["in_proj"]["kernel"],
            [(0, inner, hs)] + [(inner + lo, w, s) for lo, w, s in xbc]
            + [(2 * inner + 2 * bc, H, heads)],
        )},
        "conv_kernel": cols(p["conv_kernel"], xbc),
        "conv_bias": cols(p["conv_bias"], xbc),
        "dt_bias": p["dt_bias"][heads], "A_log": p["A_log"][heads],
        "D_skip": p["D_skip"][heads],
        "norm": {"weight": p["norm"]["weight"][hs]},
        "out_proj": {"kernel": p["out_proj"]["kernel"][hs]},
    }}


def test_mamba_head_shares_add_up_to_the_uncut_mixer():
    """8 heads in 2 groups over 2 shares: a share holds 4 heads and their
    group, and the gated norm is per group, so the shares' ``out_proj``
    outputs add up to the whole mixer's."""
    H, P, G, N, d = 8, 8, 2, 16, 32
    sizes = dict(head_dim=P, state_size=N, chunk_size=16)
    whole = Mamba2Mixer(d, num_heads=H, n_groups=G, **sizes)
    u = jax.random.normal(jax.random.key(1), (2, 40, d))
    params = whole.init(jax.random.key(2), u)
    params = jax.tree_util.tree_map(  # the conv bias starts at zero
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(3), a.shape), params
    )
    part = Mamba2Mixer(d, num_heads=H // 2, n_groups=G // 2, **sizes)
    total = sum(
        part.apply(mamba_share(params, j, 2, H, P, G, N), u) for j in range(2)
    )
    np.testing.assert_allclose(total, whole.apply(params, u), atol=2e-5)


def test_attention_head_shares_add_up_and_grouped_kv_is_repeated_kv():
    H, KV, D, d, L = 4, 2, 16, 32, 24
    whole = GroupedQueryAttention(d, num_heads=H, num_kv_heads=KV, head_dim=D)
    x = jax.random.normal(jax.random.key(1), (2, L, d))
    params = whole.init(jax.random.key(2), x)
    p = params["params"]
    want = whole.apply(params, x)

    # against plain attention with the KV heads repeated to the query heads
    heads = lambda t, n: t.reshape(2, L, n, D).transpose(0, 2, 1, 3)
    q = heads(x @ p["q_proj"]["kernel"], H) * D ** -0.5
    k = jnp.repeat(heads(x @ p["k_proj"]["kernel"], KV), H // KV, axis=1)
    v = jnp.repeat(heads(x @ p["v_proj"]["kernel"], KV), H // KV, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    scores = jnp.where(jnp.arange(L)[None] > jnp.arange(L)[:, None], -jnp.inf, scores)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    plain = o.transpose(0, 2, 1, 3).reshape(2, L, H * D) @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(want, plain, atol=2e-5)

    # 4 query heads over 2 shares, each with its own KV head
    part = GroupedQueryAttention(d, num_heads=2, num_kv_heads=1, head_dim=D)
    total = 0.0
    for j in range(2):
        qs, ks = slice(j * 2 * D, (j + 1) * 2 * D), slice(j * D, (j + 1) * D)
        total = total + part.apply({"params": {
            "q_proj": {"kernel": p["q_proj"]["kernel"][:, qs]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, ks]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, ks]},
            "out_proj": {"kernel": p["out_proj"]["kernel"][qs]},
        }}, x)
    np.testing.assert_allclose(total, want, atol=2e-5)


MOE = dict(latent_dim=16, expert_dim=24, shared_dim=40, n_routed=16, top_k=4,
           routed_scale=2.5)


def moe_layer_and_params(d=32, n=48):
    whole = LatentMoE(d, **MOE)
    h = jax.random.normal(jax.random.key(1), (2, n // 2, d))
    params = whole.init(jax.random.key(2), h)
    params = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(3), a.shape), params
    )
    return whole, params, h


def moe_by_hand(p, h, held, top_k=MOE["top_k"], scale=MOE["routed_scale"]):
    """The layer in the gather form, the oracle: ``top_k``'s indices, the
    chosen scores read by ``take_along_axis``, a plain loop over the
    experts ``held`` with ALL experts' weights in ``p``.  Returns
    ``(y (n, d), idx (n, top_k))``."""
    tokens = h.reshape(-1, h.shape[-1])
    s = jax.nn.sigmoid(tokens @ p["router"])
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["correction"]), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    w = chosen / chosen.sum(-1, keepdims=True) * scale
    latent = tokens @ p["latent_down"]["kernel"]
    routed = jnp.zeros_like(latent)
    for e in held:
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        routed = routed + w_e[:, None] * (
            relu2(latent @ p["experts_fc1"][e]) @ p["experts_fc2"][e]
        )
    y = routed @ p["latent_up"]["kernel"] + (
        relu2(tokens @ p["shared_fc1"]["kernel"]) @ p["shared_fc2"]["kernel"]
    )
    return y, idx


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares: the held parts of all shares, with the
    shared expert counted once, are the uncut layer's output."""
    d = 32
    whole, params, h = moe_layer_and_params(d)
    p = params["params"]
    want, stats = whole.apply(params, h)
    shared = relu2(h @ p["shared_fc1"]["kernel"]) @ p["shared_fc2"]["kernel"]
    total, pairs = shared, 0.0
    for j in range(4):
        held = slice(4 * j, 4 * j + 4)
        share = dict(p, experts_fc1=p["experts_fc1"][held],
                     experts_fc2=p["experts_fc2"][held])
        y, st = LatentMoE(d, n_held=4, first_held=4 * j, **MOE).apply(
            {"params": share}, h
        )
        total = total + (y - shared)
        pairs += float(st[STATS.index("pairs_here")])
    np.testing.assert_allclose(total, want, atol=5e-5)
    # every token's top_k choices fall in exactly one share each
    assert pairs == h.shape[0] * h.shape[1] * MOE["top_k"]


@pytest.mark.parametrize("favoured", [(5,), (4, 5, 6, 7)])
def test_no_pair_is_lost_under_a_routing_skewed_on_purpose(monkeypatch, favoured):
    """Routing skewed on purpose: the selection bias sends every token to
    one held expert (its load is every token, the others' are whatever the
    scores give), or to all four held experts at once: every token on every
    held expert it can choose, the case the buffer is sized for
    (``buffer_rows``), which fills it to the last pair.  Either way the
    layer's routed part is the plain loop over the held experts, pair for
    pair."""
    from unicore_tpu.modules import latent_moe

    monkeypatch.setattr(latent_moe, "TILE", 8)
    d = 32
    _, params, h = moe_layer_and_params(d)
    n = h.shape[0] * h.shape[1]
    p = dict(params["params"])
    p["correction"] = p["correction"].at[jnp.asarray(favoured)].set(100.0)
    share = dict(p, experts_fc1=p["experts_fc1"][4:8],
                 experts_fc2=p["experts_fc2"][4:8])
    got, st = LatentMoE(d, n_held=4, first_held=4, **MOE).apply(
        {"params": share}, h
    )

    # the same, by hand: every chosen (token, held expert) pair, one by one
    want, idx = moe_by_hand(p, h, range(4, 8))
    loads = [int((idx == e).sum()) for e in range(4, 8)]
    np.testing.assert_allclose(got.reshape(n, d), want, atol=5e-5)
    assert float(st[STATS.index("pairs_here")]) == sum(loads)
    assert float(st[STATS.index("load_max")]) == n
    assert float(st[STATS.index("tiles_used")]) == sum(-(-l // 8) for l in loads)
    if len(favoured) == 4:  # the buffer's worst case, reached
        assert sum(loads) == n * 4
        assert latent_moe.buffer_rows(n, MOE["top_k"], 4) == n * 4 + 4 * 8


def router_case(ties, n=96):
    """The layer's parameters (all 16 experts) and input with a selection
    bias that is not zero, and ties at the k-th place made on purpose:
    ``"scores"``: two router columns equal (and their bias), so two experts
    score alike on every token and some tokens have room for only one of
    them; ``"bias"``: a bias of 2**22 on six experts, where float32 holds
    halves only, so ``s + b`` ties where ``s`` does not."""
    _, params, h = moe_layer_and_params(n=n)
    p = dict(params["params"])
    p["correction"] = 0.05 * jax.random.normal(jax.random.key(7), (16,))
    if ties == "scores":
        p["router"] = p["router"].at[:, 9].set(p["router"][:, 5])
        p["correction"] = p["correction"].at[9].set(p["correction"][5])
    elif ties == "bias":
        p["correction"] = p["correction"].at[
            jnp.asarray([2, 3, 7, 8, 12, 13])].set(2.0 ** 22)
    return p, h


@pytest.mark.parametrize("ties", ["scores", "bias"])
def test_the_chosen_set_is_top_ks_under_ties(ties):
    """Exactly ``top_k`` experts a token, and ``lax.top_k``'s own, where
    the k-th place is tied: ``>=`` alone would take both of a tied pair."""
    from unicore_tpu.modules.latent_moe import top_k_set

    p, h = router_case(ties)
    k = MOE["top_k"]
    s = jax.nn.sigmoid(h.reshape(-1, h.shape[-1]) @ p["router"])
    x = s + p["correction"]
    vals, want = jax.lax.top_k(x, k)
    # rows where ``>=`` alone would take more than ``top_k``
    assert ((x >= vals[:, -1:]).sum(-1) > k).sum() >= 3, "no tie at the k-th place"
    if ties == "scores":   # one of the twins in, the other out
        assert ((want == 5).any(1) & ~(want == 9).any(1)).any()
    else:                  # no two scores of a token are equal
        assert all(len(set(row)) == 16 for row in np.asarray(s).tolist())
    idx, sel = jax.jit(top_k_set, static_argnums=1)(x, k)
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_array_equal(sel.sum(-1), k)
    np.testing.assert_array_equal(
        sel, (want[:, :, None] == jnp.arange(16)).any(1))


@pytest.mark.parametrize("n_held,first_held,ties", [
    (4, 0, None), (4, 4, None), (16, 0, None), (4, 4, "scores"), (16, 0, "bias"),
], ids=["first-share", "second-share", "all-held", "ties-in-the-scores",
        "ties-from-the-bias"])
def test_the_dense_router_is_the_gather_form_with_its_gradients(
        n_held, first_held, ties):
    """The layer sums the chosen scores where they lie and cuts the held
    weights out of the scores (no ``take_along_axis``): its output and
    the gradients of the router, the input and the experts' weights are
    the gather form's, to float32 rounding; the selection bias gets no
    gradient from either."""
    p, h = router_case(ties)
    held = slice(first_held, first_held + n_held)
    g = jax.random.normal(jax.random.key(8), h.shape)
    layer = LatentMoE(h.shape[-1], n_held=n_held, first_held=first_held, **MOE)

    def put(router, correction, h, fc1, fc2):
        return dict(p, router=router, correction=correction,
                    experts_fc1=fc1, experts_fc2=fc2), h

    def dense(*a):
        q, h = put(*a)
        share = dict(q, experts_fc1=q["experts_fc1"][held],
                     experts_fc2=q["experts_fc2"][held])
        return jnp.sum(layer.apply({"params": share}, h)[0] * g)

    def gathered(*a):
        q, h = put(*a)
        y, _ = moe_by_hand(q, h, range(held.start, held.stop))
        return jnp.sum(y.reshape(h.shape) * g)

    args = (p["router"], p["correction"], h, p["experts_fc1"], p["experts_fc2"])
    every = tuple(range(len(args)))
    got, g_got = jax.value_and_grad(dense, every)(*args)
    want, g_want = jax.value_and_grad(gathered, every)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))
    assert g_want[0].any() and not g_got[1].any() and not g_want[1].any()


def _loads(n, loads):
    """Expert ``e`` chosen by ``loads[e]`` tokens, spread over the ``n``."""
    return jnp.stack([
        jnp.zeros((n,), bool).at[(jnp.arange(l) * 7 + 3 * e) % n].set(True)
        if l < n else jnp.ones((n,), bool)
        for e, l in enumerate(loads)
    ], axis=1)


@pytest.mark.parametrize("tile,n,loads", [
    (8, 48, (0, 0, 0, 0)),         # no token on any held expert: no trip
    (8, 48, (0, 48, 0, 0)),        # every token on one expert
    (8, 48, (48, 48, 48, 48)),     # the worst case: the layout full to the last pair
    (8, 48, (13, 0, 1, 30)),       # uneven, the experts' last tiles partly empty
    (8, 44, (44, 5, 0, 17)),       # n no multiple of the tile
    (128, 100, (100, 0, 37, 99)),  # the real tile, longer than a column of tokens
], ids=["none", "one-expert", "every-expert", "uneven", "n-not-whole-tiles",
        "tile-longer-than-n"])
def test_routed_experts_are_the_plain_sum_over_pairs(monkeypatch, tile, n, loads):
    """``routed_experts`` against ``sum_e w[n, e] W2_e relu2(W1_e l_n)``
    written out with no layout: the output and the gradients of all four
    arguments, in float32."""
    from unicore_tpu.modules import latent_moe

    monkeypatch.setattr(latent_moe, "TILE", tile)
    lat, f, Eh, top_k = 16, 24, len(loads), 4
    ks = jax.random.split(jax.random.key(5), 5)
    latent = jax.random.normal(ks[0], (n, lat))
    w1 = 0.3 * jax.random.normal(ks[1], (Eh, lat, f))
    w2 = 0.3 * jax.random.normal(ks[2], (Eh, f, lat))
    pair = _loads(n, loads)
    assert tuple(int(l) for l in pair.sum(0)) == loads
    w_held = jnp.where(pair, jax.random.uniform(ks[3], (n, Eh), minval=0.1), 0.0)
    g = jax.random.normal(ks[4], (n, lat))
    rows = latent_moe.buffer_rows(n, top_k, Eh)

    def plain(latent, w_held, w1, w2):
        w = jnp.where(pair, w_held, 0.0)
        return sum(w[:, e:e + 1] * (relu2(latent @ w1[e]) @ w2[e])
                   for e in range(Eh))

    routed = lambda *a: latent_moe.routed_experts(*a, rows, pair)
    args = (latent, w_held, w1, w2)
    got, want = routed(*args), plain(*args)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)
    every = (0, 1, 2, 3)
    g_got = jax.grad(lambda *a: jnp.sum(routed(*a) * g), every)(*args)
    g_want = jax.grad(lambda *a: jnp.sum(plain(*a) * g), every)(*args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))
    tiles = int(latent_moe.buffer_layout(pair, w_held, rows)["tiles_used"])
    assert tiles == sum(-(-l // tile) for l in loads) <= rows // tile
    if not any(loads):
        assert tiles == 0 and not got.any()
        assert not any(a.any() for a in g_got)


WIDE_LOADS = {
    # W = 32 rows a wide trip, tiles of 8: an expert at 0, W - 1, W, W + 1,
    # 2 W + TILE + 3, one a pair into its fourth tile, one that holds every
    # token
    "about-w": (0, 31, 32, 33, 75, 25, 200),
    "whole-trips": (64, 0, 96, 32),      # no tile is left to the narrow loop
    "none-fills-a-trip": (24, 8, 0, 17),  # two loops built, the wide one idle
}


@pytest.mark.parametrize("loads", list(WIDE_LOADS.values()), ids=list(WIDE_LOADS))
@pytest.mark.parametrize("act", ["relu2", "silu_gate"])
def test_wide_trips_are_the_loop_over_the_tiles(monkeypatch, act, loads):
    """``routed_experts`` with wide trips against the loop over the tiles
    alone: the value and all four cotangents (float32 sums in another
    order), for both expert bodies.  Expert ``e``'s tiles go ``W / TILE`` at
    a time as far as they fill whole wide trips, the rest one by one; the
    layout's rows and tiles are what they were."""
    from unicore_tpu.modules import latent_moe

    tile, wide, n = 8, 32, 200
    monkeypatch.setattr(latent_moe, "TILE", tile)
    lat, f, Eh, top_k = 16, 24, len(loads), 4
    ks = jax.random.split(jax.random.key(7), 5)
    rng = np.random.default_rng(3)
    pair = np.zeros((n, Eh), bool)
    for e, l in enumerate(loads):
        pair[rng.choice(n, l, replace=False), e] = True
    pair = jnp.asarray(pair)
    latent = jax.random.normal(ks[0], (n, lat))
    w1 = 0.3 * jax.random.normal(
        ks[1], (Eh, lat, 2 * f if act == "silu_gate" else f))
    w2 = 0.3 * jax.random.normal(ks[2], (Eh, f, lat))
    w_held = jnp.where(pair, jax.random.uniform(ks[3], (n, Eh), minval=0.1), 0.0)
    g = jax.random.normal(ks[4], (n, lat))
    rows = latent_moe.buffer_rows(n, top_k, Eh)
    args = (latent, w_held, w1, w2)

    def value_and_cotangents(wide):
        routed = lambda *a: latent_moe.routed_experts(*a, rows, pair, act, wide)
        return (routed(*args),) + jax.grad(
            lambda *a: jnp.sum(routed(*a) * g), (0, 1, 2, 3))(*args)

    for a, b in zip(value_and_cotangents(wide), value_and_cotangents(0)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))

    lay = latent_moe.buffer_layout(pair, w_held, rows, wide)
    narrow = latent_moe.buffer_layout(pair, w_held, rows)
    assert set(lay) - set(narrow) == {
        "wide_start", "wide_expert", "wide_trips", "narrow_tile", "narrow_trips"}
    for k, v in narrow.items():
        np.testing.assert_array_equal(lay[k], v)
    tiles = [-(-l // tile) for l in loads]
    trips = [t // (wide // tile) for t in tiles]
    assert int(lay["wide_trips"]) == sum(trips) <= rows // wide
    assert int(lay["tiles_used"]) == sum(tiles)
    assert int(lay["narrow_trips"]) == sum(tiles) - sum(trips) * (wide // tile)
    # a wide trip's rows are whole tiles of the trip's expert, every tile in
    # use is walked by exactly one of the two loops, and no tile out of use
    seen, pairs_wide = [], 0
    for j in range(sum(trips)):
        first, e = int(lay["wide_start"][j]), int(lay["wide_expert"][j])
        assert first % tile == 0
        assert (lay["tile_expert"][first // tile:(first + wide) // tile] == e).all()
        seen += range(first // tile, (first + wide) // tile)
        pairs_wide += int(lay["valid"][first:first + wide].sum())
    seen += [int(t) for t in lay["narrow_tile"][:int(lay["narrow_trips"])]]
    assert sorted(seen) == list(range(sum(tiles)))

    stats = dict(zip(STATS, latent_moe.route_stats(pair.sum(axis=0), wide)))
    assert stats["rows_wide"] == pairs_wide == sum(
        min(l, t * wide) for l, t in zip(loads, trips))
    assert stats["tiles_used"] == sum(tiles) and stats["pairs_here"] == sum(loads)
    assert dict(zip(STATS, latent_moe.route_stats(pair.sum(axis=0), 0)))[
        "rows_wide"] == 0


@pytest.mark.parametrize("layer", ["latent", "gated"])
@pytest.mark.parametrize("n,loops", [(124, 1), (128, 2)], ids=["below", "at"])
def test_the_even_load_decides_whether_wide_loops_are_built(
        monkeypatch, layer, n, loops):
    """``n`` tokens choosing 4 of 16 experts at ``WIDE`` = 32: an even load
    of 31 traces the one loop over the tiles forward and one backward, of
    32 a wide and a narrow loop each way, whatever the routing turns out
    to be; the stats count the wide rows only where they are built."""
    from unicore_tpu.modules import gated_moe, latent_moe

    monkeypatch.setattr(latent_moe, "TILE", 8)
    monkeypatch.setattr(latent_moe, "WIDE", 32)
    assert latent_moe.wide_rows(n, 4, 16) == (32 if loops == 2 else 0)
    if layer == "latent":
        module = LatentMoE(32, n_held=4, first_held=4, **MOE)
    else:
        module = gated_moe.GatedMoE(32, expert_dim=24, n_routed=16, top_k=4,
                                    n_held=4, first_held=4)
    h = jax.random.normal(jax.random.key(2), (1, n, 32))
    params = module.init(jax.random.key(1), h)
    fwd = lambda p, h: module.apply(p, h)
    both = jax.value_and_grad(lambda p, h: jnp.sum(fwd(p, h)[0] ** 2), (0, 1))
    count = lambda fn: str(jax.make_jaxpr(fn)(params, h)).count("while[")
    assert count(fwd) == loops and count(both) == 2 * loops
    stats = dict(zip(STATS, fwd(params, h)[1]))
    assert (stats["rows_wide"] > 0) == (loops == 2 and stats["load_max"] >= 32)
    assert stats["rows_wide"] <= stats["pairs_here"] <= 4 * n


def test_a_row_without_a_pair_reads_nothing_of_its_token(monkeypatch):
    """A tile's rows beyond its expert's load hold some token's index; what
    that token's row holds (here: not finite) reaches neither the output
    nor a gradient."""
    from unicore_tpu.modules import latent_moe

    monkeypatch.setattr(latent_moe, "TILE", 8)
    n, lat, f = 16, 8, 12
    ks = jax.random.split(jax.random.key(6), 3)
    pair = jnp.zeros((n, 2), bool).at[:3, 0].set(True)
    latent = jax.random.normal(ks[0], (n, lat)).at[3:].set(jnp.inf)
    w1 = jax.random.normal(ks[1], (2, lat, f))
    w2 = jax.random.normal(ks[2], (2, f, lat))
    w_held = jnp.where(pair, 0.5, 0.0)
    rows = latent_moe.buffer_rows(n, 2, 2)
    total = lambda *a: jnp.sum(latent_moe.routed_experts(*a, rows, pair))
    out, grads = jax.value_and_grad(total, (0, 1, 2, 3))(latent, w_held, w1, w2)
    assert np.isfinite(out) and all(np.isfinite(a).all() for a in grads)
    assert not grads[0][3:].any() and grads[0][:3].any()


TRIP_LOADS = {
    # n = 64 tokens, tiles of 8, wide trips of 32: an expert at 0, 1, TILE,
    # n - 1 and n pairs (its last tile then reaches the end of its column of
    # n entries, or past it)
    "none": (0, 0, 0),
    "one": (1, 0, 1),
    "a-tile": (8, 8, 1),
    "n-less-one": (63, 63, 1),
    "every-token": (64, 64, 64),
    "even-fills-wide-trips": (32, 32, 32, 32),
    "lumped": (64, 0, 3, 37, 9),
}


@pytest.mark.parametrize("loads", list(TRIP_LOADS.values()), ids=list(TRIP_LOADS))
def test_no_index_occurs_twice_among_the_rows_of_a_trip(monkeypatch, loads):
    """What the loops rely on since they tell the add that no row meets
    another: within every trip, wide or narrow, rows without a pair
    included, the indices are distinct and ascending; a pair's row holds
    its token, a row without a pair an index beyond the tokens that no
    other row of the layout holds."""
    from unicore_tpu.modules import latent_moe

    tile, wide, n = 8, 32, 64
    monkeypatch.setattr(latent_moe, "TILE", tile)
    pair = _loads(n, loads)
    Eh = len(loads)
    w_held = jnp.where(pair, 0.5, 0.0)
    rows = latent_moe.buffer_rows(n, Eh, Eh)
    lay = {k: np.asarray(v) for k, v in
           latent_moe.buffer_layout(pair, w_held, rows, wide).items()}
    token, valid = lay["token_of_row"], lay["valid"]
    trips = [(int(first), wide) for first in lay["wide_start"][:lay["wide_trips"]]]
    trips += [(int(t) * tile, tile) for t in lay["narrow_tile"][:lay["narrow_trips"]]]
    every_tile = [(t * tile, tile) for t in range(int(lay["tiles_used"]))]
    assert sum(size for _, size in trips) == len(every_tile) * tile
    for first, size in trips + every_tile:   # with wide trips, and without
        at, pairs = token[first:first + size], valid[first:first + size]
        assert (np.diff(at) > 0).all(), (first, size, at)
        assert (at[pairs] < n).all() and (at[~pairs] >= n).all()
        assert not pairs[np.argmin(pairs):].any() or pairs.all()
    # all of an expert's pairs, in token order, and nothing else
    used = int(lay["tiles_used"]) * tile
    for e in range(Eh):
        mine = np.repeat(lay["tile_expert"], tile)[:used] == e
        np.testing.assert_array_equal(
            token[:used][mine & valid[:used]], np.flatnonzero(np.asarray(pair[:, e])))
    assert int(valid[:used].sum()) == sum(loads) and not valid[used:].any()
    beyond = token[~valid]
    assert (beyond >= n).all() and len(set(beyond.tolist())) == len(beyond)


def _parent_routed(latent, g, w1, w2, lay, act, wide, tile):
    """The loops as they stood before the add was told anything (PR 43's
    trip bodies: ``out.at[token].add``, no flag, no kernel), on the same
    layout: the value of ``routed_experts`` and its four cotangents for
    the output's cotangent ``g``."""
    from unicore_tpu.modules.latent_moe import ACTS, _rows_at

    f32 = jnp.float32
    act_fn, act_vjp = ACTS[act]
    rows_of = lambda table, token, valid: jnp.where(
        valid[:, None], table.at[token].get(mode="clip"), 0)

    def trips(trip, carry):
        one = lambda t, c: trip(lay["tile_expert"][t], t * tile, tile, c)
        if not wide:
            return jax.lax.fori_loop(0, lay["tiles_used"], one, carry)
        carry = jax.lax.fori_loop(
            0, lay["wide_trips"],
            lambda j, c: trip(lay["wide_expert"][j], lay["wide_start"][j],
                              wide, c), carry)
        return jax.lax.fori_loop(
            0, lay["narrow_trips"], lambda j, c: one(lay["narrow_tile"][j], c),
            carry)

    def fwd(e, first, size, out):
        token, weight, valid = _rows_at(lay, first, size)
        x_t = rows_of(latent, token, valid)
        h = act_fn(jnp.dot(x_t, w1[e], preferred_element_type=f32))
        y_t = jnp.dot(h.astype(latent.dtype), w2[e], preferred_element_type=f32)
        return out.at[token].add(weight[:, None] * y_t)

    def bwd(e, first, size, carry):
        dx, dweight, dw1, dw2 = carry
        token, weight, valid = _rows_at(lay, first, size)
        x_t, d_t = rows_of(latent, token, valid), rows_of(g, token, valid)
        h, act_bwd = act_vjp(jnp.dot(x_t, w1[e], preferred_element_type=f32))
        y_t = jnp.dot(h, w2[e], preferred_element_type=f32)
        dy_t = d_t * weight[:, None]
        dpre = act_bwd(jnp.dot(dy_t, w2[e].T, preferred_element_type=f32))
        dx_t = jnp.dot(dpre, w1[e].T, preferred_element_type=f32)
        dw1 = dw1.at[e].add(jnp.dot(x_t.T, dpre, preferred_element_type=f32))
        dw2 = dw2.at[e].add(jnp.dot(h.T, dy_t, preferred_element_type=f32))
        dweight = dweight.at[e, token].add(jnp.sum(y_t * d_t, axis=-1))
        return dx.at[token].add(dx_t), dweight, dw1, dw2

    n, Eh = latent.shape[0], w1.shape[0]
    out = trips(fwd, jnp.zeros(latent.shape, f32))
    dx, dweight, dw1, dw2 = trips(bwd, (
        jnp.zeros(latent.shape, f32), jnp.zeros((Eh, n), f32),
        jnp.zeros(w1.shape, f32), jnp.zeros(w2.shape, f32)))
    return out, dx, dweight.T, dw1, dw2


@pytest.mark.parametrize("lat", [16, 128], ids=["scatter", "kernel"])
@pytest.mark.parametrize("wide", [0, 32], ids=["tiles", "wide-trips"])
@pytest.mark.parametrize("act", ["relu2", "silu_gate"])
def test_told_that_no_row_meets_another_the_adds_are_the_parents(
        monkeypatch, act, wide, lat):
    """``routed_experts`` and all four cotangents against the parent's trip
    bodies on the same trips, in float32: the same adds in the same order
    for every token, a row without a pair (which the parent added as an
    exact zero) dropped.  Bit for bit where XLA's scatter adds them (rows
    of 16; rows of 128 under the one loop over the tiles).  Where the
    kernel adds the rows (rows of 128 with wide loops built, both loops,
    interpreted here) to the last place or two: XLA's CPU backend
    contracts the multiply that makes a trip's rows into the interpreted
    kernel's add, one rounding where the scatter, and the chip, make two
    (handed the rows as an argument the kernel is exact:
    ``tests/test_rows_add.py``; PERF.md, PR 44, has the chip's answer)."""
    from unicore_tpu.modules import latent_moe
    from unicore_tpu.ops import rows_add

    tile, n, loads = 8, 200, (0, 31, 32, 33, 75, 25, 200)
    monkeypatch.setattr(latent_moe, "TILE", tile)
    f, Eh, top_k = 24, len(loads), 4
    ks = jax.random.split(jax.random.key(11), 5)
    rng = np.random.default_rng(5)
    pair = np.zeros((n, Eh), bool)
    for e, l in enumerate(loads):
        pair[rng.choice(n, l, replace=False), e] = True
    pair = jnp.asarray(pair)
    latent = jax.random.normal(ks[0], (n, lat))
    w1 = 0.3 * jax.random.normal(
        ks[1], (Eh, lat, 2 * f if act == "silu_gate" else f))
    w2 = 0.3 * jax.random.normal(ks[2], (Eh, f, lat))
    w_held = jnp.where(pair, jax.random.uniform(ks[3], (n, Eh), minval=0.1), 0.0)
    g = jax.random.normal(ks[4], (n, lat))
    rows = latent_moe.buffer_rows(n, top_k, Eh)
    assert rows_add.kernel_takes(latent) == (lat == 128)

    routed = lambda *a: latent_moe.routed_experts(*a, rows, pair, act, wide)
    args = (latent, w_held, w1, w2)
    got = (routed(*args),) + jax.grad(
        lambda *a: jnp.sum(routed(*a) * g), (0, 1, 2, 3))(*args)
    lay = latent_moe.buffer_layout(pair, w_held, rows, wide)
    want = jax.jit(_parent_routed, static_argnums=(5, 6, 7))(
        latent, g, w1, w2, lay, act, wide, tile)
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32 and a.any()
        if wide and lat == 128:
            np.testing.assert_allclose(
                a, b, rtol=0, atol=2e-6 * float(jnp.abs(b).max()))
        else:
            np.testing.assert_array_equal(a, b)


# -- the loss and the data ----------------------------------------------------------

def test_chunked_loss_is_the_whole_loss_with_its_gradients():
    from unicore_tpu.losses.lm_cross_entropy import chunked_lm_nll

    T, d, V = 50, 16, 37  # 50 tokens in chunks of 16: a padded tail
    x = jax.random.normal(jax.random.key(1), (T, d))
    w = jax.random.normal(jax.random.key(2), (d, V))
    target = jax.random.randint(jax.random.key(3), (T,), 0, V)
    valid = jnp.arange(T) % 7 != 0

    def whole(x, w):
        lp = jax.nn.log_softmax(x @ w, axis=-1)
        nll = -jnp.take_along_axis(lp, target[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(valid, nll, 0.0))

    chunked = lambda x, w: chunked_lm_nll(x, w, target, valid, 16)
    np.testing.assert_allclose(chunked(x, w), whole(x, w), rtol=1e-5)
    for a, b in zip(jax.grad(chunked, (0, 1))(x, w), jax.grad(whole, (0, 1))(x, w)):
        np.testing.assert_allclose(a, b, atol=1e-5)


class Documents:
    """Documents of distinct tokens: document ``i`` is ``100 i + 0, 1, ...``."""

    def __init__(self, sizes):
        self.sizes = sizes

    def __len__(self):
        return len(self.sizes)

    def __getitem__(self, i):
        return 100 * i + np.arange(self.sizes[i])


def test_packing_fills_every_block_and_loses_or_doubles_no_token():
    from unicore_tpu.data import TokenBlockDataset

    sizes = [7, 30, 1, 12, 25, 3, 18]  # 96 tokens: 9 blocks of 10, 6 left
    packed = TokenBlockDataset(Documents(sizes), block_size=10, seed=3)
    assert len(packed) == 9
    orders = []
    for epoch in (1, 2):
        packed.set_epoch(epoch)
        blocks = [packed[i] for i in range(len(packed))]
        assert all(len(b) == 10 for b in blocks)
        stream = np.concatenate(blocks)
        assert len(set(stream)) == 90  # nothing doubled
        # the stream is whole documents in the epoch's order, each in its
        # own order: only the tail of the last one is missing
        docs = [d for d, _ in zip(*np.unique(stream // 100, return_index=True))]
        first_seen = sorted(docs, key=lambda d: list(stream // 100).index(d))
        want = np.concatenate(
            [100 * d + np.arange(sizes[d]) for d in first_seen]
        )[:90]
        np.testing.assert_array_equal(stream, want)
        orders.append(first_seen)
    assert orders[0] != orders[1]  # the order is drawn per epoch ...
    packed.set_epoch(1)
    again = np.concatenate([packed[i] for i in range(len(packed))])
    packed.set_epoch(2)
    np.testing.assert_array_equal(  # ... from (seed, epoch)
        np.concatenate([packed[i] for i in range(9)]), stream
    )
    assert list(again // 100)[0] == orders[0][0]


def test_packing_refuses_a_corpus_that_fills_no_block():
    from unicore_tpu.data import TokenBlockDataset

    with pytest.raises(ValueError, match="do not fill one block"):
        TokenBlockDataset(Documents([3, 4]), block_size=10, seed=1)


def test_tokenizer_cuts_a_document_only_when_asked(tmp_path):
    from unicore_tpu.data import BertTokenizeDataset

    words = ["[PAD]", "[UNK]", "[CLS]", "[SEP]"] + [f"w{c}" for c in "abcdefgh"]
    (tmp_path / "dict.txt").write_text("\n".join(words) + "\n")
    text = [" ".join(["wa", "wb", "wc"] * 10)]
    cut = BertTokenizeDataset(text, str(tmp_path / "dict.txt"), max_seq_len=8)
    whole = BertTokenizeDataset(text, str(tmp_path / "dict.txt"), max_seq_len=None)
    assert len(cut[0]) == 8 and len(whole[0]) == 32


def test_the_loss_states_what_a_capture_is_told_of_an_update():
    """``trace_marks``: from one update's summed logging output, a
    ``moe_route`` mark with the pairs of all expert layers, the tiles of
    rows they filled and the loads per layer; nothing for a model without
    routed experts."""
    from unicore_tpu.losses.lm_cross_entropy import LMCrossEntropyLoss

    sums = {"loss": 9.0, "_n": 1.0, "moe_layers": 5.0, "moe_pairs_here": 2422.0,
            "moe_load_max": 1702.0, "moe_load_mean": 302.75,
            "moe_tiles_used": 41.0, "moe_rows_wide": 1536.0}
    assert LMCrossEntropyLoss.trace_marks(sums) == {"moe_route": {
        "pairs_here": 2422, "tiles_used": 41, "rows_wide": 1536,
        "load_max": 340.4, "load_mean": 60.55}}
    assert LMCrossEntropyLoss.trace_marks({"loss": 9.0, "_n": 1.0}) == {}


@pytest.mark.parametrize("n,top_k,held,want", [
    (8192, 22, 8, 8192 * 8 + 8 * 128),    # the benchmark's share: 66,560 rows
    (100, 2, 8, 256 + 8 * 128),           # 200 pairs at most, in whole tiles
])
def test_the_buffer_is_the_worst_case_from_shapes(n, top_k, held, want):
    from unicore_tpu.modules.latent_moe import TILE, buffer_rows

    rows = buffer_rows(n, top_k, held)
    assert rows == want and rows % TILE == 0
    # every token on every held expert it can choose, each expert's last
    # tile nearly empty: no routing needs more
    assert rows >= n * min(top_k, held) + held * (TILE - 1)


# -- the whole model through the trainer ----------------------------------------------

def tiny_task(tmp_path, held):
    """``--task causal_lm --arch nemotron_h_tiny --loss lm_cross_entropy``
    on a small corpus, as ``unicore-tpu-train`` builds them (its parser,
    the task's own pipeline): ``(args, task, model, loss)``."""
    from unicore_tpu import options, tasks
    from unicore_tpu.data.indexed_dataset import make_builder
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models import build_model

    words = [f"w{a}{b}" for a in "abcdefgh" for b in "abcdefgh"]
    (tmp_path / "dict.txt").write_text(
        "\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]"] + words) + "\n"
    )
    rng = np.random.default_rng(0)
    builder = make_builder(str(tmp_path / "train"))
    for n in rng.integers(20, 200, 80):
        builder.add_item(" ".join(rng.choice(words[:8], n)))
    builder.finalize()

    parser = options.get_training_parser()
    args = options.parse_args_and_arch(parser, [
        str(tmp_path), "--task", "causal_lm", "--loss", "lm_cross_entropy",
        "--arch", "nemotron_h_tiny", "--tokens-per-sample", "64",
        "--n-routed-experts-held", str(held),
        "--optimizer", "adam", "--lr-scheduler", "fixed", "--lr", "3e-3",
        "--batch-size", "1", "--max-update", "20", "--seed", "1",
    ])
    task = tasks.setup_task(args)
    task.load_dataset("train")
    return args, task, build_model(args, task), LOSS_REGISTRY[args.loss](task)


def test_tiny_hybrid_trains_through_task_and_trainer(tmp_path):
    """The tiny model through ``Trainer.train_step``: a falling loss, every
    block full, the routing stats in the step's sums."""
    from unicore_tpu.trainer import Trainer

    args, task, model, loss = tiny_task(tmp_path, held=8)
    trainer = Trainer(args, task, model, loss)
    batches = task.get_batch_iterator(
        task.datasets["train"], batch_size=8, seed=1, epoch=1,
    ).next_epoch_itr(shuffle=True)
    sums = []
    for _, batch in zip(range(6), batches):
        assert batch["net_input"]["src_tokens"].shape == (8, 64)
        assert (np.asarray(batch["net_input"]["src_tokens"]) != 0).all()
        trainer.train_step([batch])
        sums.append({k: float(v) for k, v in jax.device_get(trainer._macc).items()})
    per_update = np.diff([0.0] + [s["loss"] / 1.0 for s in sums])
    assert per_update[-1] < per_update[0]
    assert sums[-1]["moe_layers"] == 6 * 2
    assert sums[-1]["moe_pairs_here"] > 0
    assert sums[-1]["moe_tiles_used"] >= sums[-1]["moe_pairs_here"] / 128
    assert sums[-1]["moe_rows_wide"] == 0

    # inside a profiler capture the loss's marks reach the trace: one
    # ``unicore:moe_route`` per update, three updates late, from that
    # update's own sums (the first update read only sets the base)
    from benchmark import reduce, trace_scopes
    from unicore_tpu import telemetry

    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for _, batch in zip(range(6), batches):
            trainer.train_step([batch])
        jax.block_until_ready(trainer.state["params"])
    finally:
        jax.profiler.stop_trace()
        telemetry.hlo_scopes.reset()  # the scope tables the capture stashed
    (found,) = [os.path.join(d, f) for d, _s, fs in os.walk(str(tmp_path / "trace"))
                for f in fs if f.endswith(".xplane.pb")]
    marks = [s[3] for spans in trace_scopes.host_spans(reduce._load(found)).values()
             for s in spans if s[2] == "unicore:moe_route"]
    assert [m["update"] for m in marks] == [7, 8]
    for m in marks:  # 8 x 64 tokens, 2 expert layers, 8 of 16 experts held
        assert set(m) == {"update", "pairs_here", "tiles_used", "rows_wide",
                          "load_max", "load_mean"}
        # an even load of 8 x 64 x 4 / 16 = 128 fills no wide trip: none built
        assert m["rows_wide"] == 0
        assert 0 < m["pairs_here"] <= 2 * 8 * 64 * 4
        # a tile holds 128 rows of one expert: 16 (layer, expert) columns
        assert m["pairs_here"] / 128 <= m["tiles_used"] < m["pairs_here"] / 128 + 16
        assert m["load_mean"] == pytest.approx(m["pairs_here"] / 2 / 8)
        assert m["load_mean"] <= m["load_max"] <= 8 * 64


def test_the_step_holds_no_worst_case_buffer_of_rows(tmp_path):
    """Loss and gradient of the tiny model, compiled: no array in the
    program has ``rows x latent_dim`` elements (tokens laid out in the
    worst case's rows) or ``n x n_held x latent_dim`` (every token's row
    from every held expert).  Dispatch and combine move tiles; what the
    trips add their rows to holds ``n`` rows in whichever layout (``(n,
    lat / 128, 128)`` under the kernel of ``ops/rows_add.py``, where wide
    loops are built: ``n x latent_dim`` elements either way), never the
    layout's ``rows``."""
    from unicore_tpu.modules.latent_moe import TILE, buffer_rows

    _, task, model, loss = tiny_task(tmp_path, held=5)
    batch = next(task.get_batch_iterator(
        task.datasets["train"], batch_size=3, seed=1, epoch=1,
    ).next_epoch_itr(shuffle=False))
    n, held, lat = 3 * 64, 5, model.moe_latent_size
    assert batch["net_input"]["src_tokens"].shape == (3, 64)
    params = model.init_params(jax.random.key(0), batch)
    step = jax.jit(jax.value_and_grad(
        lambda p: loss.forward(model, p, batch)[0]))
    text = step.lower(params).compile().as_text()
    sizes = {
        int(np.prod([int(d) for d in dims.split(",") if d]))
        for dims in re.findall(r"\b(?:pred|[fsu]\d+|bf16)\[([\d,]*)\]", text)
    }
    rows = buffer_rows(n, model.num_experts_per_tok, held)
    # what the search would find: the tokens' latent rows, the layout's
    # index arrays and a tile of rows are in the program
    assert {n * lat, rows, TILE * lat, n * held} <= sizes
    assert rows * lat not in sizes and n * held * lat not in sizes


def test_the_router_holds_no_gather_and_no_scatter():
    """A small ``LatentMoE``'s loss and gradient, compiled: no ``gather``
    and no ``scatter`` runs under ``moe_router``, forward or backward (the
    chosen scores are summed where they lie, the held ones cut out as a
    static slice), while the search does find the tiles' under
    ``moe_routed``."""
    layer = LatentMoE(32, n_held=4, first_held=4, **MOE)
    _, params, h = moe_layer_and_params()
    share = dict(params["params"])
    share.update(experts_fc1=share["experts_fc1"][4:8],
                 experts_fc2=share["experts_fc2"][4:8])
    step = jax.jit(jax.value_and_grad(
        lambda p, h: jnp.sum(layer.apply({"params": p}, h)[0] ** 2), (0, 1)))
    text = step.lower(share, h).compile().as_text()
    moved = re.findall(
        r"= \S+ (gather|scatter)\(.*op_name=\"([^\"]*)\"", text)
    assert {op for op, path in moved if "moe_routed" in path} == {"gather", "scatter"}
    assert not [m for m in moved if "moe_router" in m[1]], moved
    # the router is in the program under its name, both ways
    router = re.findall(r"op_name=\"([^\"]*moe_router[^\"]*)\"", text)
    assert any("transpose(" in path for path in router)
    assert any("transpose(" not in path for path in router)


# -- the seam: one skeleton, one table of kinds, a loss that names no stat ---------

def _seam_sf():
    """A decoder no program file knows: sliding-window attention, then a
    gated MLP, ``--num-hidden-layers`` times."""
    import flax.linen as nn

    from unicore_tpu.models import register_model
    from unicore_tpu.models.hybrid_lm import (
        HybridLM, held_attention, register_architecture)

    @register_model("seam_sf")
    class SeamSF(HybridLM):
        hidden_size: int = 32
        num_hidden_layers: int = 2
        num_attention_heads: int = 4
        sliding_window: int = 16
        intermediate_size: int = 48
        attention_shares: int = 1
        loss_chunk: int = 32

        def check(self):
            if self.num_attention_heads % self.attention_shares:
                raise ValueError("--attention-shares does not divide the heads")

        @property
        def pattern(self):
            return "SF" * self.num_hidden_layers

        def layers(self):
            return dict(norm_eps=1e-6, sizes={
                "S": held_attention(
                    self.num_attention_heads, 2, self.attention_shares,
                    head_dim=8, window=self.sliding_window,
                    rope=dict(rope_theta=100.0)),
                "F": dict(ffn_dim=self.intermediate_size)})

        @nn.nowrap
        def logged(self, stats, rows, length):
            return self.band_counts(rows, length)

    register_architecture("seam_sf", "seam_sf")
    return SeamSF


SEAM_SF = _seam_sf()


def test_a_decoder_defined_here_trains_and_logs_its_stats(tmp_path):
    """A subclass of the skeleton with a pattern of kinds the table has,
    defined in this file: its fields are its arguments, ``build_model``
    builds it from the command line, what it asks for and is not built is
    refused, and one ``lm_cross_entropy`` update through ``Trainer`` logs
    the band's counts and yields their mark, with no file under
    ``unicore_tpu/`` knowing its name."""
    import inspect

    from unicore_tpu import options, tasks
    from unicore_tpu.data.indexed_dataset import make_builder
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models import build_model
    from unicore_tpu.ops.flash_attention import Band, band_counts
    from unicore_tpu.trainer import Trainer

    assert len(inspect.getsource(SEAM_SF).splitlines()) < 40
    words = [f"w{a}" for a in "abcdefgh"]
    (tmp_path / "dict.txt").write_text(
        "\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]"] + words) + "\n")
    rng = np.random.default_rng(0)
    builder = make_builder(str(tmp_path / "train"))
    for n in rng.integers(20, 200, 40):
        builder.add_item(" ".join(rng.choice(words, n)))
    builder.finalize()
    argv = [str(tmp_path), "--task", "causal_lm", "--loss", "lm_cross_entropy",
            "--arch", "seam_sf", "--tokens-per-sample", "64",
            "--sliding-window", "8", "--optimizer", "adam",
            "--lr-scheduler", "fixed", "--lr", "3e-3", "--batch-size", "1",
            "--max-update", "2", "--seed", "1"]
    args = options.parse_args_and_arch(options.get_training_parser(), argv)
    task = tasks.setup_task(args)
    task.load_dataset("train")
    model = build_model(args, task)
    assert isinstance(model, SEAM_SF) and model.pattern == "SFSF"
    assert (model.sliding_window, model.vocab_size) == (8, len(task.dictionary))
    with pytest.raises(ValueError, match="attention-shares"):
        build_model(options.parse_args_and_arch(
            options.get_training_parser(), argv + ["--attention-shares", "3"]),
            task)

    loss = LOSS_REGISTRY[args.loss](task)
    trainer = Trainer(args, task, model, loss)
    batch = next(task.get_batch_iterator(
        task.datasets["train"], batch_size=4, seed=1, epoch=1,
    ).next_epoch_itr(shuffle=False))
    trainer.train_step([batch])
    sums = {k: float(v) for k, v in jax.device_get(trainer._macc).items()}
    assert np.isfinite(sums["loss"]) and sums["band_rows"] == 4
    computed, visible = band_counts(Band(8), 128, 128)
    assert sums["band_window_keys_visible"] == 4 * 2 * visible
    assert "band_window_heads" not in sums and "moe_layers" not in sums
    assert loss.trace_marks(sums) == {"attn_band": {
        "window_keys_computed": 2 * computed, "window_keys_visible": 2 * visible,
        "window_layers": 2, "full_keys_computed": 0, "full_keys_visible": 0,
        "full_layers": 0}}
    params = trainer.state["params"]["params"]
    assert set(params) == {"embed_tokens", "decoder", "lm_head"}
    assert set(params["decoder"]["units"]["layer_0"]) == {"norm", "self_attn"}
    assert set(params["decoder"]["units"]["layer_1"]) == {"norm", "mlp"}


def _leaves(*groups):
    """``{path: shape}`` from ``(layers, {leaf: shape})`` groups: every
    layer named in ``layers`` (blank-separated) has the group's leaves."""
    return {f"{layer}/{leaf}".lstrip("/"): shape for layers, leaves in groups
            for layer in (layers.split() or [""]) for leaf, shape in leaves.items()}


_ATTN_4 = {"norm/weight": (64,), "self_attn/k_proj/kernel": (64, 32),
           "self_attn/out_proj/kernel": (64, 64),
           "self_attn/q_proj/kernel": (64, 64),
           "self_attn/v_proj/kernel": (64, 32)}
_GATED_EXPERTS = {"norm/weight": (64,), "moe/experts_fc1": (8, 64, 96),
                  "moe/experts_fc2": (8, 48, 64), "moe/router": (64, 8)}
_ENDS = {"decoder/final_norm/weight": (64,), "embed_tokens/embedding": (384, 64),
         "lm_head": (64, 384)}
#: the four tiny architectures' parameter trees over a dictionary of 384
#: entries, written down at the commit before ``models/hybrid_lm.py`` (PR 44):
#: every leaf float32
TINY_TREES = {
    "nemotron_h_tiny": _leaves(
        ("", _ENDS), ("decoder/layers_0", _ATTN_4),
        ("decoder/units/layer_0", {
            "norm/weight": (2, 64), "moe/correction": (2, 16),
            "moe/experts_fc1": (2, 16, 32, 48), "moe/experts_fc2": (2, 16, 48, 32),
            "moe/latent_down/kernel": (2, 64, 32),
            "moe/latent_up/kernel": (2, 32, 64), "moe/router": (2, 64, 16),
            "moe/shared_fc1/kernel": (2, 64, 96),
            "moe/shared_fc2/kernel": (2, 96, 64)}),
        ("decoder/units/layer_1", {
            "norm/weight": (2, 64), "mamba/A_log": (2, 4), "mamba/D_skip": (2, 4),
            "mamba/conv_bias": (2, 96), "mamba/conv_kernel": (2, 4, 96),
            "mamba/dt_bias": (2, 4), "mamba/in_proj/kernel": (2, 64, 132),
            "mamba/norm/weight": (2, 32), "mamba/out_proj/kernel": (2, 32, 64)})),
    "evabyte_tiny": _leaves(
        ("", {"decoder/final_norm/offset": (64,),
              "embed_tokens/embedding": (384, 64), "lm_head": (64, 3 * 384)}),
        ("decoder/units/layer_0", {
            "norm/offset": (3, 64), "self_attn/adaptive_mu_k": (3, 4, 16),
            "self_attn/adaptive_phi": (3, 4, 16),
            "self_attn/k_proj/kernel": (3, 64, 64),
            "self_attn/out_proj/kernel": (3, 64, 64),
            "self_attn/q_proj/kernel": (3, 64, 64),
            "self_attn/v_proj/kernel": (3, 64, 64)}),
        ("decoder/units/layer_1", {
            "norm/offset": (3, 64), "mlp/fc1/kernel": (3, 64, 192),
            "mlp/fc2/kernel": (3, 96, 64)})),
    "mellum_tiny": _leaves(
        ("", _ENDS),
        ("decoder/layers_0 decoder/layers_2 decoder/layers_4", _ATTN_4),
        ("decoder/layers_1 decoder/layers_3 decoder/layers_5", _GATED_EXPERTS)),
    "laguna_tiny": _leaves(
        ("", _ENDS),
        ("decoder/layers_0 decoder/layers_6",
         dict(_ATTN_4, **{"self_attn/gate_proj/kernel": (64, 4)})),
        ("decoder/layers_2 decoder/layers_4", {
            "norm/weight": (64,), "self_attn/gate_proj/kernel": (64, 6),
            "self_attn/k_proj/kernel": (64, 32),
            "self_attn/out_proj/kernel": (96, 64),
            "self_attn/q_proj/kernel": (64, 96),
            "self_attn/v_proj/kernel": (64, 32)}),
        ("decoder/layers_1", {"norm/weight": (64,), "mlp/fc1/kernel": (64, 192),
                              "mlp/fc2/kernel": (96, 64)}),
        ("decoder/layers_3 decoder/layers_5 decoder/layers_7",
         dict(_GATED_EXPERTS, **{"moe/shared_fc1/kernel": (64, 80),
                                 "moe/shared_fc2/kernel": (40, 64)}))),
}
#: the options each model registers itself
OPTIONS = {"nemotron_h_tiny": 24, "evabyte_tiny": 16, "mellum_tiny": 28,
           "laguna_tiny": 35}


@pytest.mark.parametrize("arch", list(TINY_TREES))
def test_the_tiny_trees_are_the_parents_path_for_path(arch):
    from argparse import ArgumentParser, Namespace

    from unicore_tpu.models import ARCH_CONFIG_REGISTRY, ARCH_MODEL_REGISTRY

    class Dictionary:
        pad = staticmethod(lambda: 0)
        __len__ = lambda self: 384

    class task:
        dictionary = Dictionary()

    cls = ARCH_MODEL_REGISTRY[arch]
    args = Namespace()
    ARCH_CONFIG_REGISTRY[arch](args)
    model = cls.build_model(args, task)
    tok = np.zeros((2, 64), np.int32)
    got = jax.eval_shape(lambda: model.init_params(
        jax.random.key(0), {"net_input": {"src_tokens": tok}}))
    flat = {"/".join(k.key for k in path): (leaf.shape, leaf.dtype)
            for path, leaf in jax.tree_util.tree_flatten_with_path(got["params"])[0]}
    assert flat == {k: (v, jnp.float32) for k, v in TINY_TREES[arch].items()}
    # every field but the two the task states is an argument, and no other
    parser = ArgumentParser()
    cls.add_args(parser)
    options = {s for a in parser._actions for s in a.option_strings} - {"-h", "--help"}
    assert options == {
        "--" + f.replace("_", "-") for f in cls.__dataclass_fields__
        if f not in ("name", "parent", "vocab_size", "padding_idx")}
    assert len(options) == OPTIONS[arch]


_KIND_SIZES = {
    "M": dict(num_heads=4, head_dim=8, n_groups=2, state_size=16,
              conv_kernel=4, chunk_size=8),
    "*": dict(num_heads=4, num_kv_heads=2, head_dim=8),
    "E": dict(latent_dim=16, expert_dim=24, shared_dim=40, n_routed=8, top_k=2),
    "A": dict(num_heads=2, head_dim=16, window_size=8, chunk_size=4,
              rope_theta=1e4),
    "F": dict(ffn_dim=48),
    "S": dict(num_heads=4, num_kv_heads=2, head_dim=8, window=8,
              rope=dict(rope_theta=1e4)),
    "G": dict(num_heads=4, num_kv_heads=2, head_dim=8, rope=dict(rope_theta=1e4)),
    "R": dict(expert_dim=24, n_routed=8, top_k=2),
    "C": dict(num_heads=4, num_kv_heads=2, head_dim=8, rope=dict(rope_theta=1e4)),
    "Z": dict(expert_dim=24, n_routed=8, router_dim=12),
    "L": dict(num_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
              qk_rope_head_dim=8, v_head_dim=8, rope=dict(rope_theta=1e4)),
}


@pytest.mark.parametrize("kind", list("M*EAFSGRCZL") + ["X"])
def test_every_kind_of_the_table_builds_alone(kind):
    """One layer of each kind under ``HybridDecoder``, with that kind's
    sizes and no other's: the mixer under the name its row states, stats
    from the kinds whose rows say so; a character the table lacks raises."""
    from unicore_tpu.modules import hybrid_decoder
    from unicore_tpu.modules.hybrid_decoder import KINDS, TABLE, HybridDecoder

    assert KINDS == "M*EAFSGRCZL" == "".join(_KIND_SIZES)
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    decoder = HybridDecoder(pattern=kind, embed_dim=32, norm_eps=1e-5,
                            sizes={kind: _KIND_SIZES.get(kind, {})})
    if kind not in TABLE:
        with pytest.raises(ValueError, match=re.escape(
                "layer kind 'X' is not one of 'M*EAFSGRCZL'")):
            decoder.init(jax.random.key(1), x)
        return
    row = TABLE[kind]
    params = decoder.init(jax.random.key(1), x)["params"]
    assert set(params) == {"layers_0", "final_norm"}
    assert set(params["layers_0"]) == {"norm", row.name}
    y, stats = decoder.apply({"params": params}, x)
    # a kind's own stats follow the six every expert layer returns
    assert y.shape == x.shape and stats.shape == (
        len(STATS) + len(row.more_stats),)
    assert hybrid_decoder.stat_names(kind) == STATS + row.more_stats
    assert bool(stats.any()) == row.stats
    assert set(row.kept) <= set(hybrid_decoder.KEPT)
    assert set(row.marks) <= set(hybrid_decoder.MARKS)
    assert set(row.logs) <= set(hybrid_decoder.LOGS)


def test_the_loss_names_no_stat_and_passes_over_one_no_owner_knows():
    from unicore_tpu.losses import lm_cross_entropy
    from unicore_tpu.losses.lm_cross_entropy import LMCrossEntropyLoss
    from unicore_tpu.modules import hybrid_decoder

    with open(lm_cross_entropy.__file__) as f:
        source = f.read()
    for prefix in ("moe_", "eva_", "band_", "mla_", "mtp_"):
        assert prefix not in source, prefix
    assert [f.__name__ for f in hybrid_decoder.MARKS] == [
        "route_mark", "keys_mark", "band_mark", "band_call_mark", "skip_mark",
        "mla_mark"]
    sums = {"loss": 9.0, "_n": 1.0, "moe_layers": 2.0, "moe_pairs_here": 600.0,
            "moe_load_max": 400.0, "moe_load_mean": 150.0, "moe_tiles_used": 9.0,
            "moe_rows_wide": 0.0, "eva_rows": 2.0, "eva_keys_computed": 4096.0,
            "eva_keys_visible": 2100.0, "eva_windows": 4.0, "eva_chunks": 32.0,
            "band_rows": 2.0, "band_full_layers": 6.0, "band_full_keys_computed": 64.0,
            "band_full_keys_visible": 32.0}
    marks = LMCrossEntropyLoss.trace_marks(sums)
    # full layers alone run under one map: the pairs of ONE call are stated
    assert list(marks) == ["moe_route", "eva_keys", "attn_band",
                           "attn_band_call"]
    assert marks["attn_band"] == {
        "full_keys_computed": 32, "full_keys_visible": 16, "full_layers": 3}
    assert marks["attn_band_call"] == {"keys_computed": 21, "keys_visible": 10}
    two_maps = dict(sums, band_window_layers=2.0)
    assert "attn_band_call" not in LMCrossEntropyLoss.trace_marks(two_maps)
    unknown = dict(sums, spectral_gap=3.0, band_width_guess=7.0, moe_mood=1.0)
    assert LMCrossEntropyLoss.trace_marks(unknown) == marks
    assert LMCrossEntropyLoss.trace_marks({"spectral_gap": 3.0}) == {}
