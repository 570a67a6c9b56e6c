"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023) in the form EvaByte trains with: exact causal attention inside a
window, and for every earlier window one learned-pooled key/value per
chunk, all under ONE softmax.

With the row cut into windows of ``window`` positions and chunks of
``chunk`` (``window // chunk`` chunks a window), per head with its learned
pooling vectors ``mu, phi`` (D,) and ``s`` the softmax scale:

    k~_c = sum_{j in c} softmax_j(s k_j . mu) k_j
    v~_c = sum_{j in c} softmax_j(s k_j . phi) v_j        (:func:`eva_prep_kv`)

and query ``i`` of window ``w`` scores its window's keys ``j <= i`` by
``s q_i . k_j`` and every chunk ``c`` of the windows before ``w`` by
``s q_i . k~_c``; one softmax over the union; the output is ``sum_j p_ij
v_j + sum_c p_ic v~_c`` (:func:`eva_agg`).  In window 0 that is plain
causal attention.

:func:`eva_agg` treats windows as batch rows of the blockwise flash kernels
(``ops/flash_attention.py``): window ``w``'s queries against ``[its own
keys ; every chunk summary of the row, padded to the kernel's tile]`` under
a grouped bias ``(W, 1, window, window + summaries)`` made of iotas: the
causal triangle on the left, "the chunk's window is before ``w``" on the
right.  The bias is a constant, so no bias gradient is computed.  Scored
whole, that form computes about twice the keys a query may see (the upper
triangle and the later windows' summaries are scored and masked), so the
kernels also get a BLOCK MAP (:func:`visible_blocks`, from the bias's own
predicate; ``flash_attention.block_map``): of a window's ``(block_q,
block_k)`` blocks they visit those that hold a visible key and skip the
ones that are ``NEG`` throughout, which changes no bit of the output or of
a gradient.  At ``(256, 512)`` and 16 windows of 2,048 that is 608 of
1,024 blocks, 1.23 times the visible keys (the partly masked blocks are
still scored whole); :func:`key_counts` counts both from the same map.
Off the TPU (and outside interpret mode) the same operands go through XLA's
own softmax.
"""

import jax
import jax.numpy as jnp
import numpy as np

from unicore_tpu.ops._pallas import LANE, interpret_enabled, pick_block
from unicore_tpu.platform_utils import on_tpu

NEG = -1e30  # big finite, as the kernels' own mask value


def eva_prep_kv(k, v, mu, phi, chunk, scale):
    """The two pooled summaries of every chunk.  ``k, v`` (B, H, L, D) with
    ``L`` a multiple of ``chunk``; ``mu, phi`` (H, D).  Returns ``(k~, v~)``,
    each (B, H, L // chunk, D) in ``k``'s dtype; the pooling weights are a
    float32 softmax inside each chunk.  Memory-bound: reads ``k, v`` once,
    writes a ``chunk``-th of them."""
    with jax.named_scope("eva_prep_kv"):
        B, H, L, D = k.shape
        kc = k.reshape(B, H, L // chunk, chunk, D)
        vc = v.reshape(B, H, L // chunk, chunk, D)

        def pooled(x, vec):
            logits = jnp.einsum(
                "bhncd,hd->bhnc", kc, vec.astype(kc.dtype),
                preferred_element_type=jnp.float32,
            ) * scale
            w = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum(
                "bhnc,bhncd->bhnd", w, x.astype(jnp.float32),
            ).astype(k.dtype)

        return pooled(kc, mu), pooled(vc, phi)


def visibility_bias(n_windows, window, chunks_per_window, n_summaries, dtype):
    """The additive mask ``(W, 1, window, window + n_summaries)`` of
    :func:`eva_agg`: 0 where window ``w``'s query ``i`` may see the key,
    ``NEG`` elsewhere.  Columns ``< window`` are the window's own keys
    (``j <= i``); column ``window + c`` is chunk summary ``c`` of the row
    (its window ``c // chunks_per_window`` is before ``w``; columns past
    the row's last chunk are padding).  Made of iotas, so the compiled
    program computes it and folds no constant of this size."""
    Lk = window + n_summaries
    shape = (n_windows, 1, window, Lk)
    w = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 3)
    seen = _seen(w, row, col, window, chunks_per_window)
    return jnp.where(seen, 0.0, NEG).astype(dtype)


def _seen(w, row, col, window, chunks_per_window):
    """Whether query ``row`` of window ``w`` may see key column ``col`` of
    :func:`eva_agg`'s layout: THE predicate, of the bias (iotas) and of the
    block map (numpy corners) alike."""
    local = col <= row
    # (col - window) // chunks_per_window < w, without a division
    summary = col - window < w * chunks_per_window
    return ((col < window) & local) | ((col >= window) & summary)


def kernel_blocks(window, n_keys):
    """The ``(block_q, block_k)`` :func:`eva_agg` runs the flash kernels at:
    their defaults, cut to what divides a window and its keys.  A window
    the kernels' tile does not divide (:func:`uses_kernel`) is one block."""
    if window % LANE:
        return window, n_keys
    return pick_block(window, 256), pick_block(n_keys, 512)


def visible_blocks(length, window, chunk, block_q, block_k):
    """``(W, window // block_q, keys // block_k)`` bool, numpy, from shapes:
    whether the slab of :func:`visibility_bias` (the kernel form's, its
    summaries padded to the tile) that a block covers holds a key some query
    of the block may see, i.e. is not ``NEG`` throughout.  :func:`_seen` is
    monotone, so a slab's best corner decides: its last query against its
    first own key, or against its first summary."""
    W, cpw = length // window, window // chunk
    n_keys = window + _padded_summaries(length, chunk)
    w = np.arange(W)[:, None, None]
    last_row = (np.arange(window // block_q)[None, :, None] + 1) * block_q - 1
    col = np.arange(n_keys // block_k)[None, None, :] * block_k
    summary = np.maximum(col, window)  # the slab's first summary, if it has one
    return _seen(w, last_row, col, window, cpw) | (
        (summary < col + block_k) & _seen(w, last_row, summary, window, cpw)
    )


def _padded_summaries(length, chunk):
    n_sum = length // chunk
    return n_sum + (-n_sum) % LANE


def kernel_map(length, window, chunk):
    """``(block_q, block_k, visible)`` of :func:`eva_agg`'s kernel form:
    the one place the kernels' map and :func:`key_counts` both come from."""
    bq, bk = kernel_blocks(window, window + _padded_summaries(length, chunk))
    return bq, bk, visible_blocks(length, window, chunk, bq, bk)


def uses_kernel(window, head_dim, dtype):
    """Whether :func:`eva_agg` hands its operands to the Mosaic flash
    kernels (a TPU, or interpret mode; windows the kernel's tile divides).
    The projections read it before q, k, v exist, to write the kernel's
    ``(B, H, L, D)`` layout themselves."""
    return (
        (on_tpu() or interpret_enabled())
        and window % LANE == 0 and head_dim % 8 == 0
        and dtype in (jnp.float32, jnp.bfloat16)
    )


def eva_agg(q, k, v, k_sum, v_sum, window, chunk, scale):
    """The joint softmax.  ``q, k, v`` (B, H, L, D); ``k_sum, v_sum``
    (B, H, L // chunk, D) from :func:`eva_prep_kv`; ``L`` a multiple of
    ``window``, ``window`` of ``chunk``.  Returns (B, H, L, D)."""
    with jax.named_scope("eva_agg"):
        B, H, L, D = q.shape
        W, cpw = L // window, window // chunk
        n_sum = L // chunk
        kernel = uses_kernel(window, D, q.dtype)
        pad = (-n_sum) % LANE if kernel else 0

        def windows(x):  # (B, H, L, D) -> (W * B, H, window, D), window-major
            x = x.reshape(B, H, W, window, D)
            return jnp.moveaxis(x, 2, 0).reshape(W * B, H, window, D)

        def with_summaries(local, summary):
            if pad:
                summary = jnp.pad(summary, ((0, 0), (0, 0), (0, pad), (0, 0)))
            summary = jnp.broadcast_to(
                summary[None], (W,) + summary.shape
            ).reshape(W * B, H, n_sum + pad, D)
            return jnp.concatenate([windows(local), summary], axis=2)

        qw = windows(q)
        kw, vw = with_summaries(k, k_sum), with_summaries(v, v_sum)
        bias = visibility_bias(W, window, cpw, n_sum + pad, q.dtype)
        if kernel:
            from unicore_tpu.ops.flash_attention import (
                block_map,
                flash_attention,
            )

            # batch row w * B + b reads bias and map group (w * B + b) // B = w
            bq, bk, visible = kernel_map(L, window, chunk)
            o = flash_attention(
                qw, kw, vw, bias=bias, sm_scale=scale, block_q=bq, block_k=bk,
                block_map=block_map(visible),
            )
        else:
            s = jnp.einsum("nhqd,nhkd->nhqk", qw, kw,
                           preferred_element_type=jnp.float32) * scale
            s = s.reshape(W, B, H, window, -1) + bias[:, None].astype(s.dtype)
            p = jax.nn.softmax(s, axis=-1).reshape(W * B, H, window, -1)
            o = jnp.einsum("nhqk,nhkd->nhqd", p.astype(vw.dtype), vw,
                           preferred_element_type=jnp.float32).astype(q.dtype)
        o = o.reshape(W, B, H, window, D)
        return jnp.moveaxis(o, 0, 2).reshape(B, H, L, D)


def key_counts(length, window, chunk):
    """Per row and head, summed over the row's queries: the keys
    :func:`eva_agg`'s flash form scores (``computed``: the blocks of the
    map the kernels get, :func:`kernel_map`,
    each scored whole) and the keys a query may see (``visible``: its
    window's keys up to itself and the summaries of the windows before).
    From shapes."""
    W, cpw = length // window, window // chunk
    bq, bk, blocks = kernel_map(length, window, chunk)
    computed = int(blocks.sum()) * bq * bk
    visible = W * window * (window + 1) // 2 + window * cpw * W * (W - 1) // 2
    return {"computed": computed, "visible": visible, "windows": W,
            "chunks": length // chunk}


def keys_log(rows, length, window, chunk):
    """What a model with this attention logs of an update of ``rows`` rows,
    from shapes: per layer and head, summed over the batch's queries,
    :func:`key_counts`, with the batch's windows and chunks."""
    counts = key_counts(length, window, chunk)
    out = dict(eva_keys_computed=counts["computed"],
               eva_keys_visible=counts["visible"],
               eva_windows=counts["windows"], eva_chunks=counts["chunks"],
               eva_rows=1)
    return {k: jnp.asarray(rows * v, jnp.float32) for k, v in out.items()}


def keys_mark(sums):
    """What a profiler capture is told of one update of such a model, from
    that update's summed logging output: one ``unicore:eva_keys`` mark with
    the keys the kernel form scored and the keys its queries could see, per
    layer and head, and the update's windows and chunks.  Nothing where no
    row was logged."""
    if not sums.get("eva_rows", 0):
        return {}
    return {"eva_keys": dict(
        keys_computed=int(sums["eva_keys_computed"]),
        keys_visible=int(sums["eva_keys_visible"]),
        windows=int(sums["eva_windows"]),
        chunks=int(sums["eva_chunks"]),
    )}
