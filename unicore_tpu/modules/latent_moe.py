"""LatentMoE with a shared expert, as a layer that is TOLD which routed
experts it holds (``nemotron_h``'s ``E`` layer; routing as DeepSeek-V3's:
sigmoid scores, a selection bias, normalised and scaled weights).

    s = sigmoid(h W_r)                      float32, over ALL n_routed
    chosen = the top_k largest of s + b_corr
    w_e = s_e / sum_{chosen} s * routed_scale   for e chosen
    l = h W_down                            embed_dim -> latent_dim
    r = sum_{e chosen and held} w_e W2_e relu(W1_e l)^2
    y = r W_up + W2_s relu(W1_s h)^2        the shared expert, full width

The layer holds experts ``first_held .. first_held + n_held - 1`` of
``n_routed`` (all of them when ``n_held == n_routed``).  It routes over all
``n_routed`` and computes its own experts' part of ``r``; what the absent
experts would add is left out, as on one chip of an expert-parallel
deployment before the exchange.  The router, both latent projections and
the shared expert are whole on every chip, so summing the ``r`` of all
shares and adding the shared expert once gives the uncut layer
(``tests/test_hybrid_lm.py``).  Nothing here stands in for the absent
chips; the exchange across chips is not built (ROADMAP R8).

The router reads its chosen scores where they lie.  ``top_k`` gives the
indices; the weights need only the SUM of the chosen scores and the
scores of the experts held, and both are in the dense ``(tokens,
n_routed)`` array ``s`` already: the sum is ``sum(where(sel, s, 0))``
over a mask ``sel`` of ``top_k``'s set (:func:`top_k_set`), and the held
experts' scores are a static slice ``s[:, first_held : first_held +
n_held]``, zeroed off the pairs.  So no ``take_along_axis`` reads 22
scalars a token out of ``s``, and the backward has no scatter-add into
``(tokens, n_routed)``: the cotangent of ``s`` is a pad of the slice's and
a broadcast of the sum's under ``sel``.  ``sel`` marks what lies above the
k-th value ``top_k`` returns, and of what is level with it the columns up
to the last index ``top_k`` took: ``>=`` alone would take every column
tied at the k-th place, more than ``top_k`` of them (with float32 scores
over 512 experts two are level there about once in 40,000 tokens, and a
selection bias can tie what the scores do not).  The 22 scores are added
in index order where the gather form added them in score order: float32
rounding.

No capacity factor and no dropped pair.  The (token, held expert) pairs
are laid out expert by expert in rows shared by the held experts, each
expert's rows rounded up to whole tiles of ``TILE``
(:func:`buffer_layout`).  One loop walks the tiles IN USE only, with a
trip count that is the routing's (:func:`_grouped_ffn`): a trip gathers
its tile's ``TILE`` rows of the latent tokens, runs them through the
tile's expert and adds the weighted result at their tokens; the backward
(:func:`_grouped_ffn_bwd`, through :func:`routed_experts`, a
``custom_vjp``) walks the same tiles with the cotangents.  So the products
AND the rows moved follow the pairs there are, however unevenly they fall.

A trip reads its expert's kernels whole (forward once, backward twice more)
and, backward, reads and rewrites the expert's float32 ``dw`` accumulators,
whatever rows it carries: at ``TILE`` rows that traffic, not the products,
is a trip's time once an expert's kernels are megabytes (PERF.md, PR 41).
So where an expert's load fills them the loops take **wide trips** of
:data:`WIDE` rows, a whole multiple of ``TILE``, all of one expert: expert
``e``'s tiles go ``WIDE / TILE`` at a time as far as they fill whole wide
trips, and the tiles that leaves it (fewer than ``WIDE / TILE``) go through
the loop over the tiles as before.  A wide trip's rows are whole tiles of
the layout, so no row is padded beyond its expert's last tile, which
``TILE`` alone would pad as well: an expert a few pairs short of a whole
multiple of ``WIDE`` still goes wide to its last row.  The layout is the
same; it hands the loops two more small index arrays and two trip counts.
The body is the same at another row count (bfloat16 operands, float32
accumulators and activation, rows without a pair zeroed by their flag; a
wide trip's ``x.T @ dpre`` sums its rows in one product where the tiles'
were added one by one: float32 rounding in another order).  Whether the
wide loops are built is decided when the layer is traced, from shapes
alone (:func:`wide_rows`): where the EVEN load ``n * top_k / n_routed``,
which a balanced router hands every expert, is at least ``WIDE``.  Below
it the function traces the one loop over the tiles, forward and backward.
``rows_wide`` of :data:`STATS` says how many pairs went wide.

How a trip's rows reach their tokens.  A trip adds ``size`` rows of
``lat`` float32 into an ``(n, lat)`` accumulator, forward (``out``) and
backward (``dx``), at indices XLA's scatter-add must assume may repeat:
it finishes each row's read-add-write before the next, 0.27 us a row of
9 KB on a v5e, a tenth of the memory's bandwidth, and that was over half
of the layer's time once the products ran wide (PERF.md, PR 44).  The
layout knows more, and since PR 44 GUARANTEES it (:func:`buffer_layout`):
all rows of a trip are one expert's and a token pairs with an expert at
most once, so a trip's pairs hold distinct, ascending tokens, and its rows
without a pair hold ``n + row``: out of bounds, distinct, ascending too.
No two rows of a trip ever meet.  Where wide loops are built the
accumulators live in the layout of ``ops/rows_add.py``'s kernel for both
loops (:func:`_trips`), and a trip's rows go a group at a time: read into
VMEM, added, written back, the next group's reads in flight meanwhile,
which is safe BECAUSE no row occurs twice.  The loops depend on the
guarantee: a repeated index could lose an update.  Rows out of bounds are
skipped there and dropped by the scatter; the gathers clip them and mask
the row by ``valid`` as before.  Same float32 adds in the same order for
every token (experts in trip order), so the values are the scatter's, bit
for bit on the chip.  With the one loop over the tiles the accumulator
stays ``(n, lat)`` under XLA's scatter-add, which is told nothing: the
chip said what the flags buy (``unique_indices`` nothing,
``indices_are_sorted`` a form twelve times slower).

What is sized by the worst case (:func:`buffer_rows`: every token on every
held expert it can choose, which no routing can exceed) is the layout's
index arrays alone: a token, a weight and a flag per row, an expert per
tile, and with wide trips a row and an expert per ``WIDE`` rows and a tile
per tile.  That is why the
layer is dropless by construction and has no bound to set.  No array of
that many rows of activations, and none of (tokens x held experts x
latent), is built, forward or backward (``tests/test_hybrid_lm.py``
searches the compiled step for one).

The layout, the loops and their written-out backward are shared with
``modules/gated_moe.py``: :func:`routed_experts` takes the expert's
activation by name (:data:`ACTS`; ``relu2`` here, ``silu_gate`` for a
gated three-matrix expert whose first kernel holds ``[gate | up]``), and
with ``relu2`` traces what it traced before it took one.

What a rematerializing caller should keep.  The layer names
(``checkpoint_name``) the arrays that are small beside the work that makes
them (:data:`KEPT`), and ``modules/hybrid_decoder.py`` keeps arrays by
those names across the forward pass and nothing else.  A name changes no
value and no dtype, and without such a caller it does nothing.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from unicore_tpu.logging import metrics
from unicore_tpu.ops import rows_add
from unicore_tpu.quant.dense import QuantDense

_init = nn.initializers.normal(0.02)

#: what :meth:`LatentMoE.__call__` returns beside ``y``, in this order
#: (:func:`route_stats`): the (token, held expert) pairs, the most loaded
#: held expert's and the mean load, 1 (so that sums over layers count them),
#: the layout's tiles of ``TILE`` rows in use (wide trips or not), and the
#: pairs in the rows of wide trips (0 where no wide loop is built)
STATS = ("pairs_here", "load_max", "load_mean", "layers", "tiles_used",
         "rows_wide")


def route_log(stats):
    """What a model logs of one update's expert layers: their summed
    :data:`STATS`, under the names the two functions below read."""
    return {"moe_" + k: stats[i] for i, k in enumerate(STATS)}


def route_scalars(logging_outputs):
    """The log's lines of an expert layer's routing, per layer and update:
    how uneven the held experts' loads are, how many tiles of rows they
    fill, and how many of the pairs went through the loop's wide trips."""
    layers = sum(log.get("moe_layers", 0) for log in logging_outputs)
    if layers > 0:
        for key in ("moe_load_max", "moe_load_mean", "moe_tiles_used",
                    "moe_rows_wide"):
            total = sum(log.get(key, 0) for log in logging_outputs)
            metrics.log_scalar(key, total / layers, 1, round=2)


def route_mark(sums):
    """What a profiler capture is told of one update of a model with routed
    experts, from that update's summed logging output: one
    ``unicore:moe_route`` mark with the (token, held expert) pairs of all
    its expert layers, the tiles of :data:`TILE` rows they filled (what
    dispatch and combine moved, each way), the pairs among them that went
    :data:`WIDE` rows a trip (``rows_wide / pairs_here``: how often the wide
    loop engages; 0 where the even load builds none) and the most loaded
    held expert's and the mean load, per layer.  Nothing where no layer
    routes."""
    layers = sums.get("moe_layers", 0)
    if not layers:
        return {}
    return {"moe_route": dict(
        pairs_here=int(sums["moe_pairs_here"]),
        tiles_used=int(sums["moe_tiles_used"]),
        rows_wide=int(sums["moe_rows_wide"]),
        load_max=sums["moe_load_max"] / layers,
        load_mean=sums["moe_load_mean"] / layers,
    )}


#: rows per tile of the grouped products (the MXU's 128 rows)
TILE = 128

#: rows per wide trip of the grouped products, a whole multiple of ``TILE``:
#: what an expert's kernels are read, and its ``dw`` accumulators rewritten,
#: once for (:func:`wide_rows` says where the loops are built).  Chosen on the
#: chip among 256, 512 and 1,024 by the rate of the cell that fills them
#: (PERF.md, PR 41: 41,343 / 44,878 / 46,896 tokens/s against 35,066)
WIDE = 1024

#: the arrays :meth:`LatentMoE.__call__` names (``checkpoint_name``) for a
#: rematerializing caller to keep across the forward pass: cheap to hold,
#: dear to make again.  For ``n`` tokens, in this order: the router's
#: float32 product ``(n, n_routed)``, ``top_k``'s indices ``(n, top_k)``
#: int32 and its set ``(n, n_routed)`` bool (with the three no second
#: product and no second sort); ``latent_down``'s result and the routed
#: experts' sum after its cast, ``(n, latent_dim)`` each (no second
#: ``latent_down``, no second forward loop over the tiles); the layout's
#: index arrays (no second sort of the pairs); the shared expert's result
#: ``(n, embed_dim)`` (no second ``shared_fc2``); ``shared_fc1``'s product
#: ``(n, shared_dim)`` before its activation, which the activation's
#: backward reads (no second ``shared_fc1``, the layer's largest product;
#: the activation is made again from it as an epilogue).  At the
#: benchmark's 8,192 tokens 211 MB a layer, 88 MB of it ``shared_fc1``'s:
#: as wide as the work that makes it, so a trade against the step's peak,
#: taken since the compiled step has the room (PERF.md, PR 43).  Not among
#: them, for a measured reason (PERF.md, PR 36): the layer's own result in
#: the shared expert's place.  ``latent_up``'s small second forward is left
#: in the backward pass on purpose, because the array that the NEXT layer's
#: second forward starts from is then made by a product, and that keeps the
#: backward loop in the forward loop's layout.
KEPT = ("moe_logits", "moe_top_k_idx", "moe_top_k_sel", "moe_latent_down",
        "moe_routed_sum", "moe_layout", "moe_shared_out", "moe_shared_fc1")


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _relu2_vjp(pre):
    r = jax.nn.relu(pre)
    return jnp.square(r), lambda dh: dh * 2.0 * r


def silu_gate(pre):
    """``silu(gate) * up`` of ``pre = [gate | up]`` (the gate's columns
    first), float32."""
    f = pre.shape[-1] // 2
    return jax.nn.silu(pre[..., :f]) * pre[..., f:]


def _silu_gate_vjp(pre):
    f = pre.shape[-1] // 2
    g, u = pre[..., :f], pre[..., f:]
    sg = jax.nn.sigmoid(g)
    a = g * sg
    return a * u, lambda dh: jnp.concatenate(
        [dh * u * (sg * (1.0 + g * (1.0 - sg))), dh * a], axis=-1)


#: an expert's activation between its two products, by name: what the
#: forward loop applies to ``x W1_e`` (float32), and the same with its
#: written-out backward, ``pre -> (h, dh -> dpre)``.  ``relu2``: ``W1_e``
#: has the expert's width; ``silu_gate``: ``W1_e`` is ``[gate | up]``, twice
#: the width ``W2_e`` reads
ACTS = {"relu2": (relu2, _relu2_vjp), "silu_gate": (silu_gate, _silu_gate_vjp)}


def tiles_of(load):
    """Whole tiles that ``load`` pairs of one expert fill."""
    return (load + TILE - 1) // TILE


def route_stats(load, wide):
    """:data:`STATS` of one layer whose held experts got ``load`` (Eh,)
    pairs, its loops built with wide trips of ``wide`` rows (or 0)."""
    tiles = tiles_of(load)
    # the pairs in an expert's wide trips: all of them, or its trips' rows
    rows_wide = jnp.minimum(
        load, tiles // (wide // TILE) * wide).sum() if wide else 0
    return jnp.stack([
        load.sum(), load.max(), load.astype(jnp.float32).mean(), 1,
        tiles.sum(), rows_wide,
    ]).astype(jnp.float32)


def buffer_rows(n, top_k, n_held):
    """Rows the held experts share for ``n`` tokens, in whole tiles: every
    token on every held expert it can choose, and each expert's last tile
    nearly empty.  No routing needs more."""
    rows = n * min(top_k, n_held) + n_held * TILE
    return -(-rows // TILE) * TILE


def top_k_set(x, k):
    """``lax.top_k``'s choice among each row of ``x`` (n, E), twice: its
    indices ``idx`` (n, k) and the same set as a mask ``sel`` (n, E) with
    exactly ``k`` true in a row, so that what is summed over the chosen
    can be summed where it lies, with no gather.  A column is chosen when
    it lies above the k-th value, or level with it and no further along
    than the last index ``top_k`` took: ``top_k`` takes equal values in
    index order, so of those level with the k-th it took the first ones."""
    vals, idx = jax.lax.top_k(x, k)
    kth, last = vals[:, -1:], idx[:, -1:]
    along = jax.lax.broadcasted_iota(idx.dtype, x.shape, 1)
    return idx, (x > kth) | ((x == kth) & (along <= last))


def wide_rows(n, top_k, n_routed):
    """Rows of a wide trip for ``n`` tokens that choose ``top_k`` of
    ``n_routed`` experts each: :data:`WIDE` where the even load ``n *
    top_k / n_routed`` fills one, else 0 (no wide loop is built).  Static:
    from shapes alone, the load a balanced router hands every expert."""
    return WIDE if n * top_k >= WIDE * n_routed else 0


def buffer_layout(pair, w_held, rows, wide=0):
    """Where each pair sits.  ``pair`` (n, Eh) bool; ``w_held`` (n, Eh) the
    pairs' weights; ``rows`` the layout's length (:func:`buffer_rows`, the
    worst case: these index arrays are all that is sized by it); ``wide``
    the rows of a wide trip (:func:`wide_rows`; a whole multiple of
    ``TILE``, or 0).  Expert ``e``'s pairs take rows ``start_e .. start_e +
    load_e - 1`` in token order, ``start_e`` the tile-aligned end of expert
    ``e - 1``'s.  Returns

    * ``token_of_row`` (rows,), ``weight_of_row`` (rows,), ``valid`` (rows,):
      a row that holds no pair has ``valid`` false, weight zero and the
      index ``n + row``, which is out of bounds and no other row's.  So
      **no index occurs twice among the rows of one expert**, and a trip's
      rows are all one expert's: its pairs' tokens are distinct (a token
      pairs with an expert at most once) and ascending, and the rows
      after them ascend beyond ``n``.  The loops DEPEND on this
      (:func:`_add_rows`: the kernel that adds a trip's rows moves many
      at once, which only rows that never meet allow); what reads a table
      at these indices clips them and masks the row by ``valid``
      (:func:`_trip_rows`), what adds at them drops them;
    * ``tile_expert`` (rows / TILE,), ``tiles_used`` (scalar): the rows the
      loops read are those of the first ``tiles_used`` tiles;
    * with ``wide``, which of those tiles go ``wide`` rows at a time: expert
      ``e`` makes ``tiles_e // (wide / TILE)`` wide trips over its first
      tiles (its last tile, partly empty, may be among them: ``valid``
      says) and the tiles that leaves it, fewer than ``wide / TILE``, go
      through the narrow loop.  ``wide_start`` and ``wide_expert``
      (rows / wide,): the first row and the expert of each wide trip, and
      ``wide_trips`` (scalar) how many there are; ``narrow_tile``
      (rows / TILE,) and ``narrow_trips``: the tiles left, in order.
      Cumulative sums over the ``Eh`` loads.

    No gather and no search: each expert's tokens come out of one stable
    sort of its column (chosen tokens first, in token order, their weights
    carried along) and are written at the expert's start; what a column
    holds beyond its load is overwritten by the next expert's or cut off
    (the arrays are ``n`` rows longer than ``rows`` while they are
    written, so the last expert's column always fits)."""
    n, Eh = pair.shape
    load = pair.sum(axis=0, dtype=jnp.int32)
    tiles = tiles_of(load)
    ends = jnp.cumsum(tiles)                                 # in tiles
    start = (ends - tiles) * TILE                            # in rows
    n_tiles = rows // TILE
    tile_expert = _trip_expert(n_tiles, ends)
    unchosen, tokens, weights = jax.lax.sort(
        ((~pair).astype(jnp.int32),
         jax.lax.broadcasted_iota(jnp.int32, (n, Eh), 0),
         w_held.astype(jnp.float32)),
        dimension=0, is_stable=True, num_keys=1,
    )
    token_of_row = jnp.zeros((rows + n,), jnp.int32)
    weight_of_row = jnp.zeros((rows + n,), jnp.float32)
    valid = jnp.zeros((rows + n,), bool)
    for e in range(Eh):
        at = (start[e],)
        token_of_row = jax.lax.dynamic_update_slice(
            token_of_row, tokens[:, e], at)
        weight_of_row = jax.lax.dynamic_update_slice(
            weight_of_row, weights[:, e], at)
        valid = jax.lax.dynamic_update_slice(valid, unchosen[:, e] == 0, at)
    valid = valid[:rows]
    # what a column holds beyond its load is tokens the expert did not
    # choose, then leftovers and zeros: any of them may be a pair's token
    # too, so a row without a pair gets an index of its own, out of bounds
    token_of_row = jnp.where(valid, token_of_row[:rows],
                             n + jnp.arange(rows, dtype=jnp.int32))
    lay = dict(
        token_of_row=token_of_row, weight_of_row=weight_of_row[:rows],
        valid=valid, tile_expert=tile_expert,
        tiles_used=ends[-1].astype(jnp.int32),
    )
    if wide:
        per = wide // TILE
        trips = tiles // per
        lay["wide_start"], lay["wide_expert"], lay["wide_trips"] = _places(
            trips, start, wide, rows // wide)
        lay["narrow_tile"], _, lay["narrow_trips"] = _places(
            tiles - trips * per, start // TILE + trips * per, 1, n_tiles)
    return lay


def _trip_expert(n_trips, ends):
    """The expert of each of ``n_trips`` trips, where expert ``e``'s end
    before trip ``ends[e]``; the last expert's beyond the trips in use."""
    return jnp.minimum(
        jnp.sum(jnp.arange(n_trips)[:, None] >= ends[None, :], axis=1),
        ends.shape[0] - 1,
    ).astype(jnp.int32)


def _places(count, first, stride, n_trips):
    """Expert ``e`` makes ``count[e]`` trips, at ``first[e]``, ``first[e] +
    stride``, ...; the experts' trips in a row.  Of each of ``n_trips``
    trips (the worst case) its place and its expert, and how many are in
    use; what lies beyond those is not read.  A sum under a mask over the
    experts, no gather."""
    ends = jnp.cumsum(count)
    expert = _trip_expert(n_trips, ends)
    # trip j of expert e is the expert's (j - the trips before e)-th
    base = first - (ends - count) * stride
    held = jnp.arange(count.shape[0], dtype=jnp.int32)
    place = jnp.sum(jnp.where(expert[:, None] == held[None, :],
                              base[None, :], 0), axis=1, dtype=jnp.int32)
    return (place + jnp.arange(n_trips, dtype=jnp.int32) * stride, expert,
            ends[-1])


def _rows_at(lay, first, size):
    """``size`` entries of the layout's row arrays from row ``first``."""
    cut = lambda a: jax.lax.dynamic_slice(a, (first,), (size,))
    return (cut(lay["token_of_row"]), cut(lay["weight_of_row"]),
            cut(lay["valid"]))


def _trip_rows(table, token, valid):
    """``table``'s rows at a trip's tokens; zeros where the row holds no
    pair (its index lies beyond the table and is clipped: whatever row
    that reads is dropped here, never a ``0 x inf``)."""
    return jnp.where(valid[:, None], table.at[token].get(mode="clip"), 0)


def _add_rows(acc, token, rows):
    """``acc`` with a trip's ``rows`` added at their tokens, under a scope
    of its own (forward and backward, so that a trace splits a trip into
    products, gathers and adds).  The trip's indices are distinct and
    ascending (:func:`buffer_layout`), which is what lets the kernel
    (``ops/rows_add.py``) move a group of rows at a time; a row without a
    pair lies out of bounds and is dropped."""
    with jax.named_scope("row_adds"):
        return rows_add.add_rows_at(acc, token, rows)


def _trips(lay, wide, trip, acc, *carry):
    """``trip(e, first, size, acc, *carry)`` over the rows of the first
    ``tiles_used`` tiles of ``lay``: one loop over the tiles, or with
    ``wide`` one over the wide trips and one over the tiles they leave
    (under scopes of their own, so that a trace tells them apart).  Trip
    counts from the data.  ``acc`` (n, lat) float32 is what the trips add
    their rows to (:func:`_add_rows`).  Where wide loops are built, both
    loops carry it in the layout of the kernel that adds a trip's rows,
    where there is one (``ops/rows_add.py``): opened before the first
    loop and closed after the second, two relayouts of ``acc`` that an
    even load of ``WIDE`` rows an expert pays for many times over.  With
    the one loop over the tiles (an even load under ``WIDE``: few trips)
    they would cost what the kernel saves, and ``acc`` stays as it is,
    under XLA's scatter-add (PERF.md, PR 44)."""
    tile = lambda t, c: trip(lay["tile_expert"][t], t * TILE, TILE, *c)
    if not wide:
        return jax.lax.fori_loop(0, lay["tiles_used"], tile, (acc,) + carry)
    opened = rows_add.kernel_takes(acc)
    state = (rows_add.open_rows(acc) if opened else acc,) + carry
    with jax.named_scope("wide_trips"):
        state = jax.lax.fori_loop(
            0, lay["wide_trips"],
            lambda j, c: trip(lay["wide_expert"][j], lay["wide_start"][j],
                              wide, *c),
            state)
    with jax.named_scope("narrow_trips"):
        acc, *carry = jax.lax.fori_loop(
            0, lay["narrow_trips"],
            lambda j, c: tile(lay["narrow_tile"][j], c), state)
    return (rows_add.close_rows(acc) if opened else acc, *carry)


def _grouped_ffn(latent, w1, w2, lay, act="relu2", wide=0):
    """``sum_e weight * act(latent W1_e) W2_e`` over the pairs of the
    first ``tiles_used`` tiles of ``lay`` (:func:`buffer_layout`), (n, lat)
    float32.  Each trip gathers its ``TILE`` (or ``wide``) rows of
    ``latent``, runs them through its expert and adds the weighted result
    at their tokens.  Loops with trip counts from the data
    (:func:`_trips`): only forward (the backward is
    :func:`_grouped_ffn_bwd`)."""
    f32 = jnp.float32
    act_fn = ACTS[act][0]

    def trip(e, first, size, out):
        token, weight, valid = _rows_at(lay, first, size)
        x_t = _trip_rows(latent, token, valid)
        h = act_fn(jnp.dot(x_t, w1[e], preferred_element_type=f32))
        y_t = jnp.dot(h.astype(latent.dtype), w2[e],
                      preferred_element_type=f32)
        return (_add_rows(out, token, weight[:, None] * y_t),)

    return _trips(lay, wide, trip, jnp.zeros(latent.shape, f32))[0]


def _grouped_ffn_bwd(latent, d_out, w1, w2, lay, act="relu2", wide=0):
    """Cotangents of :func:`_grouped_ffn` for ``d_out`` (n, lat) float32:
    ``d_latent`` (n, lat), the pairs' weights' (Eh, n), ``dw1``, ``dw2``,
    all float32.  The hidden states are computed again trip by trip; a
    wide trip reads its expert's kernels, and adds to the expert's
    ``dw1``, ``dw2``, once for ``wide`` rows."""
    dtype = latent.dtype
    f32 = jnp.float32
    act_vjp = ACTS[act][1]

    def trip(e, first, size, dx, dweight, dw1, dw2):
        token, weight, valid = _rows_at(lay, first, size)
        x_t = _trip_rows(latent, token, valid)
        d_t = _trip_rows(d_out, token, valid)
        h, act_bwd = act_vjp(
            jnp.dot(x_t, w1[e], preferred_element_type=f32))
        h = h.astype(dtype)
        y_t = jnp.dot(h, w2[e], preferred_element_type=f32)
        dy_t = (d_t * weight[:, None]).astype(dtype)
        dh = jnp.dot(dy_t, w2[e].T, preferred_element_type=f32)
        dpre = act_bwd(dh).astype(dtype)
        dx_t = jnp.dot(dpre, w1[e].T, preferred_element_type=f32)
        dw1 = dw1.at[e].add(jnp.dot(x_t.T, dpre, preferred_element_type=f32))
        dw2 = dw2.at[e].add(jnp.dot(h.T, dy_t, preferred_element_type=f32))
        # a row without a pair is dropped, here too (its x_t and d_t are
        # zero, so its dx_t, y_t and weight's cotangent are)
        dweight = dweight.at[e, token].add(
            jnp.sum(y_t * d_t, axis=-1), mode="drop")
        return _add_rows(dx, token, dx_t), dweight, dw1, dw2

    return _trips(
        lay, wide, trip, jnp.zeros(latent.shape, f32),
        jnp.zeros((w1.shape[0], latent.shape[0]), f32),
        jnp.zeros(w1.shape, f32), jnp.zeros(w2.shape, f32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 6, 7))
def routed_experts(latent, w_held, w1, w2, rows, pair, act="relu2", wide=0):
    """``sum_e w_held[n, e] * W2_e act(W1_e latent[n])`` over the pairs
    ``pair`` marks.  ``latent`` (n, lat); ``w_held`` (n, Eh) float32, zero
    off the pairs; ``w1`` (Eh, lat, f), ``w2`` (Eh, f, lat) (``act``
    ``silu_gate``: ``w1`` (Eh, lat, 2 f), :data:`ACTS`); ``rows`` static,
    the layout's length (:func:`buffer_rows`); ``pair`` (n, Eh) bool, not
    differentiated; ``wide`` static, the rows of a wide trip
    (:func:`wide_rows`) or 0 for the loop over the tiles alone.  Returns
    (n, lat) float32."""
    return _routed_fwd(latent, w_held, w1, w2, rows, pair, act, wide)[0]


def _routed_fwd(latent, w_held, w1, w2, rows, pair, act="relu2", wide=0):
    lay = {k: checkpoint_name(v, "moe_layout")
           for k, v in buffer_layout(pair, w_held, rows, wide).items()}
    return _grouped_ffn(latent, w1, w2, lay, act, wide), (latent, w1, w2, lay)


def _routed_bwd(rows, act, wide, residuals, d_out):
    latent, w1, w2, lay = residuals
    d_out = d_out.astype(jnp.float32)
    d_latent, dweight, dw1, dw2 = _grouped_ffn_bwd(
        latent, d_out, w1, w2, lay, act, wide)
    return (d_latent.astype(latent.dtype), dweight.T, dw1.astype(w1.dtype),
            dw2.astype(w2.dtype), None)


routed_experts.defvjp(_routed_fwd, _routed_bwd)


class LatentMoE(nn.Module):
    embed_dim: int
    latent_dim: int
    expert_dim: int
    shared_dim: int
    n_routed: int
    top_k: int
    n_held: int = 0           # 0: all of n_routed
    first_held: int = 0
    routed_scale: float = 1.0

    @nn.compact
    def __call__(self, h):
        """``h`` (B, S, embed_dim), already normalised by the block.
        Returns ``(y, stats)``; ``stats`` is float32 of ``len(STATS)``."""
        E = self.n_routed
        Eh = self.n_held or E
        if not 0 <= self.first_held <= E - Eh:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + Eh - 1} "
                f"are not among {E}"
            )
        B, S, d = h.shape
        n = B * S
        rows = buffer_rows(n, self.top_k, Eh)
        dtype = h.dtype
        f32 = jnp.float32
        tokens = h.reshape(n, d)
        dense = lambda name, features: QuantDense(
            features, use_bias=False, name=name, kernel_init=_init,
            dtype=dtype, param_dtype=jnp.float32,
        )

        with jax.named_scope("moe_router"):
            w_r = self.param("router", _init, (d, E), jnp.float32)
            b_corr = self.param("correction", nn.initializers.zeros, (E,),
                                jnp.float32)
            # float32 scores.  bfloat16 operands (a bf16 run's activations
            # and parameter copies) multiply exactly into the float32
            # accumulator; float32 operands take the full-precision product
            logits = checkpoint_name(jnp.dot(
                tokens, w_r.astype(dtype), preferred_element_type=f32,
                precision=None if dtype == jnp.bfloat16
                else jax.lax.Precision.HIGHEST,
            ), "moe_logits")
            s = jax.nn.sigmoid(logits)
            # the selection is not differentiated: it only decides WHICH
            # scores are summed
            idx, sel = top_k_set(
                jax.lax.stop_gradient(s + b_corr.astype(f32)), self.top_k
            )
            idx = checkpoint_name(idx, "moe_top_k_idx")
            sel = checkpoint_name(sel, "moe_top_k_sel")
            denom = jnp.sum(jnp.where(sel, s, 0.0), axis=-1, keepdims=True)
            pair = (idx[:, :, None] == (
                self.first_held + jnp.arange(Eh, dtype=idx.dtype)
            )).any(axis=1)                          # (n, k, Eh) -> (n, Eh)
            s_held = s[:, self.first_held:self.first_held + Eh]
            w_held = jnp.where(pair, s_held, 0.0) / (denom + 1e-20)
            w_held = w_held * self.routed_scale                    # (n, Eh)
            load = pair.sum(axis=0)                                # (Eh,)

        with jax.named_scope("moe_latent"):
            latent = checkpoint_name(
                dense("latent_down", self.latent_dim)(tokens),
                "moe_latent_down")

        with jax.named_scope("moe_routed"):
            w1 = self.param("experts_fc1", _init,
                            (Eh, self.latent_dim, self.expert_dim),
                            jnp.float32).astype(dtype)
            w2 = self.param("experts_fc2", _init,
                            (Eh, self.expert_dim, self.latent_dim),
                            jnp.float32).astype(dtype)
            wide = wide_rows(n, self.top_k, E)
            routed = routed_experts(latent, w_held, w1, w2, rows, pair,
                                    "relu2", wide)
            stats = route_stats(load, wide)
            routed = checkpoint_name(routed.astype(dtype), "moe_routed_sum")

        with jax.named_scope("moe_latent"):
            y = dense("latent_up", d)(routed)

        with jax.named_scope("moe_shared"):
            # named BEFORE the activation: relu2's backward reads what went
            # in, and relu2 of a kept array is an elementwise epilogue
            mid = checkpoint_name(
                dense("shared_fc1", self.shared_dim)(tokens),
                "moe_shared_fc1")
            y = y + checkpoint_name(
                dense("shared_fc2", d)(relu2(mid)), "moe_shared_out")
        return y.reshape(B, S, d), stats
