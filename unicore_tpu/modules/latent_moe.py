"""LatentMoE with a shared expert, as a layer that is TOLD which routed
experts it holds (``nemotron_h``'s ``E`` layer; routing as DeepSeek-V3's:
sigmoid scores, a selection bias, normalised and scaled weights).

    s = sigmoid(h W_r)                      float32, over ALL n_routed
    chosen = the top_k largest of s + b_corr
    w = s_chosen / sum(s_chosen) * routed_scale
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

No capacity factor and no dropped pair.  The (token, held expert) pairs
are laid out expert by expert in one buffer of rows shared by the held
experts, each expert's rows rounded up to whole tiles of ``TILE``; the
experts' products run tile by tile over the tiles IN USE only (a loop
whose trip count is the routing's, :func:`_grouped_ffn`), so the work
follows the pairs there are, however unevenly they fall.  The buffer is
sized from the shapes for the worst case (:func:`buffer_rows`: every token
on every held expert it can choose), which no routing can exceed, so the
layer is dropless by construction and has no bound to set; the size costs
memory and two gathers of that many rows, not products.

Dispatch and combine are gathers in both directions
(:func:`routed_experts`, a ``custom_vjp``): row ``r`` holds pair ``(n, e)``
and pair ``(n, e)`` knows its row, so the forward gathers tokens into rows
and rows back into tokens, and the backward does the same with the
cotangents; nothing scatters, and no (rows x tokens) one-hot matrix exists.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.quant.dense import QuantDense

_init = nn.initializers.normal(0.02)

#: what :meth:`LatentMoE.__call__` returns beside ``y``, in this order
STATS = ("pairs_here", "load_max", "load_mean", "layers")


#: rows per tile of the grouped products (the MXU's 128 rows)
TILE = 128


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def buffer_rows(n, top_k, n_held):
    """Rows the held experts share for ``n`` tokens, in whole tiles: every
    token on every held expert it can choose, and each expert's last tile
    nearly empty.  No routing needs more."""
    rows = n * min(top_k, n_held) + n_held * TILE
    return -(-rows // TILE) * TILE


def buffer_layout(pair, w_held, rows):
    """Where each pair sits.  ``pair`` (n, Eh) bool; ``w_held`` (n, Eh) the
    pairs' weights; ``rows`` the buffer's size (:func:`buffer_rows`).
    Expert ``e``'s pairs take rows ``start_e .. start_e + load_e - 1`` in
    token order, ``start_e`` the tile-aligned end of expert ``e - 1``'s.
    Returns

    * ``row_of_pair`` (n, Eh): the pair's row; ``rows`` (one past the end,
      a row of zeros) for a token that did not choose the expert;
    * ``token_of_row`` (rows,), ``weight_of_row`` (rows,), ``valid`` (rows,);
    * ``tile_expert`` (rows / TILE,), ``tiles_used`` (scalar).

    No gather and no search: each expert's tokens come out of one stable
    sort of its column (chosen tokens first, in token order, their weights
    carried along) and are written at the expert's start; what a column
    holds beyond its load is overwritten by the next expert's or cut off
    (the arrays are ``n`` rows longer than the buffer while they are
    written, so the last expert's column always fits)."""
    n, Eh = pair.shape
    count = jnp.cumsum(pair.astype(jnp.int32), axis=0)       # (n, Eh)
    load = count[-1]                                         # (Eh,)
    tiles = (load + TILE - 1) // TILE
    ends = jnp.cumsum(tiles)                                 # in tiles
    start = (ends - tiles) * TILE                            # in rows
    row = start[None, :] + count - 1
    row_of_pair = jnp.where(pair, row, rows)
    n_tiles = rows // TILE
    tile_expert = jnp.minimum(
        jnp.sum(jnp.arange(n_tiles)[:, None] >= ends[None, :], axis=1), Eh - 1
    ).astype(jnp.int32)
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
    return dict(
        row_of_pair=row_of_pair, token_of_row=token_of_row[:rows],
        weight_of_row=weight_of_row[:rows], valid=valid[:rows],
        tile_expert=tile_expert,
        tiles_used=ends[-1].astype(jnp.int32),
    )


def _grouped_ffn(x_rows, w1, w2, tile_expert, tiles_used):
    """``relu2(x W1_e) W2_e`` for each tile of ``TILE`` rows with its
    expert's weights, over the first ``tiles_used`` tiles; the rest stay
    zero.  A loop with a trip count from the data: only forward (the
    backward is :func:`_grouped_ffn_bwd`)."""
    lat = x_rows.shape[1]

    def body(t, y):
        e = tile_expert[t]
        x_t = jax.lax.dynamic_slice(x_rows, (t * TILE, 0), (TILE, lat))
        h = relu2(jnp.dot(x_t, w1[e], preferred_element_type=jnp.float32))
        y_t = jnp.dot(h.astype(x_rows.dtype), w2[e],
                      preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice(
            y, y_t.astype(y.dtype), (t * TILE, 0)
        )

    return jax.lax.fori_loop(0, tiles_used, body, jnp.zeros_like(x_rows))


def _grouped_ffn_bwd(x_rows, dy_rows, w1, w2, tile_expert, tiles_used):
    """Cotangents of :func:`_grouped_ffn`: the hidden states are computed
    again tile by tile; the weights' cotangents accumulate in float32."""
    lat = x_rows.shape[1]
    dtype = x_rows.dtype
    f32 = jnp.float32

    def body(t, carry):
        dx, dw1, dw2 = carry
        e = tile_expert[t]
        x_t = jax.lax.dynamic_slice(x_rows, (t * TILE, 0), (TILE, lat))
        dy_t = jax.lax.dynamic_slice(dy_rows, (t * TILE, 0), (TILE, lat))
        r = jax.nn.relu(jnp.dot(x_t, w1[e], preferred_element_type=f32))
        h = jnp.square(r).astype(dtype)
        dh = jnp.dot(dy_t, w2[e].T, preferred_element_type=f32)
        dpre = (dh * 2.0 * r).astype(dtype)
        dx_t = jnp.dot(dpre, w1[e].T, preferred_element_type=f32)
        dw1 = dw1.at[e].add(jnp.dot(x_t.T, dpre, preferred_element_type=f32))
        dw2 = dw2.at[e].add(jnp.dot(h.T, dy_t, preferred_element_type=f32))
        dx = jax.lax.dynamic_update_slice(
            dx, dx_t.astype(dtype), (t * TILE, 0)
        )
        return dx, dw1, dw2

    return jax.lax.fori_loop(
        0, tiles_used, body,
        (jnp.zeros_like(x_rows), jnp.zeros(w1.shape, f32),
         jnp.zeros(w2.shape, f32)),
    )


def _gather_rows(table, index):
    """``table`` (m, c) read at ``index``; an index of ``m`` (one past the
    end) reads zeros."""
    m = table.shape[0]
    got = table[jnp.minimum(index, m - 1)]
    return jnp.where((index < m)[..., None], got, 0).astype(table.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def routed_experts(latent, w_held, w1, w2, rows, pair):
    """``sum_e w_held[n, e] * W2_e relu2(W1_e latent[n])`` over the pairs
    ``pair`` marks.  ``latent`` (n, lat); ``w_held`` (n, Eh) float32, zero
    off the pairs; ``w1`` (Eh, lat, f), ``w2`` (Eh, f, lat); ``rows``
    static; ``pair`` (n, Eh) bool, not differentiated.  Returns (n, lat)
    float32."""
    return _routed_fwd(latent, w_held, w1, w2, rows, pair)[0]


def _routed_fwd(latent, w_held, w1, w2, rows, pair):
    lay = buffer_layout(pair, w_held, rows)
    x_rows = jnp.where(
        lay["valid"][:, None], latent[lay["token_of_row"]], 0
    ).astype(latent.dtype)
    y_rows = _grouped_ffn(x_rows, w1, w2, lay["tile_expert"], lay["tiles_used"])
    back = _gather_rows(y_rows, lay["row_of_pair"])          # (n, Eh, lat)
    out = jnp.einsum("nel,ne->nl", back.astype(jnp.float32), w_held)
    return out, (x_rows, back, w1, w2, lay)


def _routed_bwd(rows, residuals, d_out):
    x_rows, back, w1, w2, lay = residuals
    f32 = jnp.float32
    d_out = d_out.astype(f32)
    d_w_held = jnp.einsum("nel,nl->ne", back.astype(f32), d_out)
    # a row that holds no pair holds a token that did not choose the
    # expert, whose weight is zero: no mask needed
    dy_rows = (
        d_out[lay["token_of_row"]] * lay["weight_of_row"][:, None]
    ).astype(x_rows.dtype)
    dx_rows, dw1, dw2 = _grouped_ffn_bwd(
        x_rows, dy_rows, w1, w2, lay["tile_expert"], lay["tiles_used"]
    )
    d_latent = _gather_rows(dx_rows, lay["row_of_pair"]).astype(f32).sum(axis=1)
    return (d_latent.astype(x_rows.dtype), d_w_held, dw1.astype(w1.dtype),
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
            logits = jnp.dot(
                tokens, w_r.astype(dtype), preferred_element_type=f32,
                precision=None if dtype == jnp.bfloat16
                else jax.lax.Precision.HIGHEST,
            )
            s = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(
                s + jax.lax.stop_gradient(b_corr.astype(f32)), self.top_k
            )
            chosen = jnp.take_along_axis(s, idx, axis=1)          # (n, k)
            w = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
            w = w * self.routed_scale
            held = idx[:, :, None] == (
                self.first_held + jnp.arange(Eh, dtype=idx.dtype)
            )                                                      # (n, k, Eh)
            w_held = jnp.sum(w[:, :, None] * held, axis=1)         # (n, Eh)
            pair = held.any(axis=1)                                # (n, Eh)
            load = pair.sum(axis=0)                                # (Eh,)

        with jax.named_scope("moe_latent"):
            latent = dense("latent_down", self.latent_dim)(tokens)

        with jax.named_scope("moe_routed"):
            w1 = self.param("experts_fc1", _init,
                            (Eh, self.latent_dim, self.expert_dim),
                            jnp.float32).astype(dtype)
            w2 = self.param("experts_fc2", _init,
                            (Eh, self.expert_dim, self.latent_dim),
                            jnp.float32).astype(dtype)
            routed = routed_experts(latent, w_held, w1, w2, rows, pair)
            stats = jnp.stack([
                load.sum(), load.max(), load.astype(f32).mean(), 1,
            ]).astype(f32)
            routed = routed.astype(dtype)

        with jax.named_scope("moe_latent"):
            y = dense("latent_up", d)(routed)

        with jax.named_scope("moe_shared"):
            y = y + dense("shared_fc2", d)(
                relu2(dense("shared_fc1", self.shared_dim)(tokens))
            )
        return y.reshape(B, S, d), stats
