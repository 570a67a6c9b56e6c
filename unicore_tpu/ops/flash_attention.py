"""Pallas TPU flash attention with pair-bias, padding mask, and in-kernel
dropout.

This is the TPU-native successor to the reference's fused
softmax(+mask)(+bias)+dropout CUDA kernel
(/root/reference/csrc/softmax_dropout/softmax_dropout_kernel.cu) carried one
step further: instead of fusing around a materialized (B*H, L, L) attention
matrix, the whole attention computation is blockwise-online (never writing
the L x L matrix to HBM), which removes the reference's dominant HBM
bandwidth cost and its O(L^2) activation memory.

Capabilities (superset of the reference kernel's semantics):
- additive bias with GROUPED batch broadcast — (Bb, H|1, Lq, Lk) for any
  Bb dividing B, batch b reading group b // (B/Bb): covers shared (Bb=1),
  per-batch (Bb=B), and the Evoformer MSA-row/triangle layout in between
  (the reference kernel's broadcast mode, csrc/softmax_dropout/
  interface.cpp:37-48); bias gradient is summed over the broadcast dims
  inside a dedicated kernel (the reference does this sum in Python,
  modules/softmax_dropout.py:44-48)
- key-padding mask (B, Lk), applied additively AND multiplicatively so fully
  masked rows produce zeros, not NaN
- attention dropout inside the kernel: the bit-mask is regenerated from a
  counter-based PRNG seeded by (seed, b, h, q_block, k_block) in both the
  forward and the backward passes — nothing is stored, mirroring the
  reference's "recompute from Philox counters" design
  (softmax_dropout_kernel.cu:60-68)
- backward recomputes probabilities from the saved (out, logsumexp), i.e.
  activation memory is O(L) per head
- a static BLOCK MAP (:func:`block_map`): which key blocks each query block
  may see, per bias group.  With one, the inner grid axis of every kernel
  walks the map's VISITS — one flat list of (group, block, block) over all
  groups, so no step is idle — instead of all blocks of the other sequence
  axis: the list rides in scalar prefetch and the index maps read a step's
  blocks from it.  A visited block computes what it computed without the
  map, bias and all, so where every skipped block is ``NEG_INF``
  throughout and each query's first visited block holds a key it sees,
  outputs and gradients are bit for bit the unmapped call's
  (docs/performance.md, "The block map")
- a BAND (:class:`Band`): a causal mask, with or without a sliding window,
  stated by static numbers and no operand.  The kernels visit the blocks
  the band leaves visible only (the block map of :func:`band_visible`,
  made from the same numbers and the call's own block sizes); a block it
  leaves wholly visible computes what an unmasked call computes, a partly
  visible block makes its mask in VMEM from iotas of its own query and key
  positions.  No ``(Lq, Lk)`` array exists, forward or backward

Softmax statistics are fp32 regardless of input dtype; the p @ v matmul runs
in the input dtype on the MXU with fp32 accumulation.  The forward kernel
keeps a query block's running statistics WHOLE LANES WIDE: the maximum as
the ``(BQ, 128)`` array a reduction along lanes leaves, every lane of a row
alike, and the sum as 128 partial sums a row (lane ``j`` over the keys ``j,
j + 128, ...``), which plain vector adds keep up and one cross-lane
reduction a ROW, when it ends, turns into ``l``.  A ``(BQ, 1)`` column cut
out of such an array takes a lane permute for every use of it, on the unit
of the chip that a visit waits on (docs/performance.md, "The block map":
what a visit costs in each kernel; ``scripts/flash_visit.py`` measures it).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # big finite: -inf minus -inf would NaN the rescale path

# interpret mode runs the kernels on any backend (CPU tests); dropout uses
# TPU-only PRNG primitives and stays TPU-gated.  The switch is shared by all
# ops/ kernels (ops/_pallas.py); these aliases keep the public API.
from ._pallas import (
    KernelGeometryError,
    audit_case,
    check_vmem_budget,
    interpret_enabled,
    pallas_call as _pallas_call,
    pick_block,
    set_interpret,
)


def _cdiv(a, b):
    return (a + b - 1) // b


def _pick_block(length, preferred):
    """Largest 128-multiple block <= preferred that divides length (the
    shared lane-step picker, ops/_pallas.py — raises KernelGeometryError
    when nothing fits)."""
    return pick_block(length, preferred)


def _seed_block(seed_ref, b, h, iq, ik):
    """Identical PRNG stream per (b, h, q-block, k-block) in fwd and bwd.

    The coordinates are mixed into one int32 (the lowering only takes a
    single seed value); int32 overflow wraps, which is fine for mixing.
    """
    mix = seed_ref[0]
    for coord in (b, h, iq, ik):
        mix = mix * jnp.int32(1000003) + coord.astype(jnp.int32)
    pltpu.prng_seed(mix)


def _keep_mask(shape, dropout_rate):
    """Counter-based keep mask; threshold compare on raw uint32 bits."""
    bits = pltpu.prng_random_bits(shape)
    bits = pltpu.bitcast(bits, jnp.uint32)
    threshold = jnp.uint32(min(int(dropout_rate * (2 ** 32)), 2 ** 32 - 1))
    return bits >= threshold


# ---------------------------------------------------------------------------
# the block map: which blocks a kernel's inner grid axis visits
# ---------------------------------------------------------------------------

#: one visit, packed in an int32: the key (or query) block it names, the
#: block of the kernel's outer sequence axis it belongs to, its map group,
#: whether it is its row's first / last, and whether it does any work
_BLOCK_BITS, _GROUP_BITS = 10, 8
_ROW_SHIFT, _GROUP_SHIFT = _BLOCK_BITS, 2 * _BLOCK_BITS
_FIRST, _LAST, _LIVE = (1 << (_GROUP_SHIFT + _GROUP_BITS + i) for i in range(3))


class BlockMap(NamedTuple):
    """What the walked kernels visit.  ``kv_items`` lists, for every map
    group ``g`` (batch row ``b`` reads group ``b // (B / G)``, the
    grouped-bias contract) and query block ``iq`` in turn, the key blocks
    ``iq`` visits, ascending: one packed int32 a visit (:data:`_FIRST` ...).
    ``q_items`` is the same relation listed by key block, for the key-major
    ``flash_bwd_dkv``.  A row with NO visit keeps one dead item (first and
    last, not live), so its output block is still initialised and written.
    ``kv_counts`` ``(G, nq)`` / ``q_counts`` ``(G, nk)`` are the visits of
    each row; the kernels read the lists only.  Made by :func:`block_map`."""

    kv_items: np.ndarray   # (visits + query blocks without one,)
    kv_counts: np.ndarray  # (G, nq)
    q_items: np.ndarray    # (visits + key blocks without one,)
    q_counts: np.ndarray   # (G, nk)


def block_map(visible) -> BlockMap:
    """The :class:`BlockMap` of ``visible`` ``(G, nq, nk)`` bool: whether
    query block ``iq`` of group ``g`` holds a query that may see a key of
    key block ``ik``.  Host-side numpy: the map is a property of the mask,
    not of the data, so a jitted caller hands the kernels a constant."""
    # lint: host-sync-in-jit; the mask's blocks are static numpy, not data
    visible = np.asarray(visible, bool)
    if visible.ndim != 3:
        raise KernelGeometryError(
            f"block_map takes (groups, query blocks, key blocks), got "
            f"shape {visible.shape}"
        )
    G, nq, nk = visible.shape
    if G > 1 << _GROUP_BITS or max(nq, nk) > 1 << _BLOCK_BITS:
        raise KernelGeometryError(
            f"block_map packs at most {1 << _GROUP_BITS} groups of "
            f"{1 << _BLOCK_BITS} blocks a side, got {visible.shape}"
        )

    def items(vis):
        counts = vis.sum(-1)
        # a row without a visit keeps one dead item that names block 0
        listed = vis.copy()
        listed[..., 0] |= counts == 0
        g, row, other = np.nonzero(listed)  # sorted by (g, row, other)
        new_row = np.ones(g.size + 1, bool)
        new_row[1:-1] = (g[1:] != g[:-1]) | (row[1:] != row[:-1])
        packed = (
            other | row << _ROW_SHIFT | g << _GROUP_SHIFT
            | new_row[:-1] * _FIRST | new_row[1:] * _LAST
            | vis[g, row, other] * _LIVE
        )
        return packed.astype(np.int32), counts.astype(np.int32)

    return BlockMap(*items(visible), *items(visible.transpose(0, 2, 1)))


class Band(NamedTuple):
    """A causal mask stated by numbers: query ``i`` sees key ``j`` iff
    ``0 <= i - j < window`` (positions counted from the row's start; the
    query's own position counts among the ``window``); ``window`` None is
    causal alone."""

    window: Optional[int] = None

    def width(self, length):
        """The band's width in a row of ``length`` keys."""
        return length if self.window is None else min(self.window, length)


def _band_whole(lo, block_q, block_k, width):
    """Whether the band leaves a ``(block_q, block_k)`` block wholly
    visible, ``lo`` the difference of the block's first query and key
    positions: ``i - j`` over the block runs from ``lo - (block_k - 1)`` to
    ``lo + block_q - 1``, and both ends lie in ``[0, width)`` (``width``
    None: no upper end).  On numpy blocks and on a kernel's scalars alike."""
    whole = lo - (block_k - 1) >= 0
    if width is not None:
        whole &= lo + (block_q - 1) < width
    return whole


def band_visible(band, nq, nk, block_q, block_k):
    """``(1, nq, nk)`` bool: the blocks of ``(block_q, block_k)`` in which
    ``band`` leaves some (query, key) pair visible."""
    width = band.width(nk * block_k)
    lo = (np.arange(nq) * block_q)[:, None] - (np.arange(nk) * block_k)[None, :]
    return ((lo + (block_q - 1) >= 0) & (lo - (block_k - 1) < width))[None]


def _band_partly(band, iq, ik, shape):
    """Inside a kernel: whether block ``(iq, ik)`` of ``shape = (BQ, BK)``
    is only PARTLY visible under the band (a scalar: a wholly visible block
    needs no mask, a wholly hidden one is not visited)."""
    BQ, BK = shape
    return jnp.logical_not(
        _band_whole(iq * BQ - ik * BK, BQ, BK, band.window))


def _band_mask(band, iq, ik, shape):
    """Inside a kernel: which (query, key) pairs of block ``(iq, ik)`` the
    band leaves visible, from iotas of the block's own positions."""
    BQ, BK = shape
    diff = (iq * BQ - ik * BK) + (
        jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        - jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    visible = diff >= 0
    if band.window is not None:
        visible &= diff < band.window
    return visible


class _Walk(NamedTuple):
    """How a kernel's grid finds its blocks (:func:`_walk`).  The grid is
    ``(grid[0], H, grid[1], grid[2])``; at program ids ``(b, h, row, t)``,
    ``at(b, row, t, pre)`` is the ``(batch row, block of the outer sequence
    axis, block of the other one)`` the step works on, ``first(t, pre)`` /
    ``last(t, pre)`` whether it is the first / last of its output block and
    ``live(t, pre)`` whether it does work (``live`` is None without a map:
    every step does); ``pre`` are the scalar-prefetch refs ``(seed,
    items)``, so index maps and kernel bodies share the one expression.
    ``operands`` follow the seed."""

    grid: tuple
    at: object
    first: object
    last: object
    live: object
    operands: tuple


def _walk(items, counts, B, rows, n_other):
    """The :class:`_Walk` of a kernel whose outer sequence axis has ``rows``
    blocks and the other ``n_other``.  Without a map the grid is ``(B,
    rows, n_other)`` and the ids are the blocks.  With one, the last axis
    walks the map's flat list of visits, over all groups and rows (no idle
    step: a list padded per row spent 0.4 us on each of its dead steps, a
    tenth of EVA's kernel time), so the grid is ``(batch rows a group, 1,
    visits)`` and the list is the one operand."""
    if items is None:
        return _Walk(
            (B, rows, n_other),
            at=lambda b, row, t, pre: (b, row, t),
            first=lambda t, pre: t == 0,
            last=lambda t, pre: t == n_other - 1,
            live=None, operands=(),
        )
    per_group = B // counts.shape[0]
    mask = (1 << _BLOCK_BITS) - 1

    def at(b, row, t, pre):
        item = pre[1][t]
        group = (item >> _GROUP_SHIFT) & ((1 << _GROUP_BITS) - 1)
        return (group * per_group + b, (item >> _ROW_SHIFT) & mask,
                item & mask)

    def flag(bit):
        return lambda t, pre: (pre[1][t] & bit) != 0

    return _Walk(
        (per_group, 1, items.shape[0]), at, flag(_FIRST), flag(_LAST),
        flag(_LIVE), (jnp.asarray(items),),
    )


def _index_maps(B, at, kv_major):
    """``(qi, ki, maski, biasi)``: the index maps of ``(1, 1, BQ, .)``
    query-side blocks, ``(1, 1, BK, .)`` key-side blocks, the ``(1, 1, BK)``
    padding mask and (``biasi(Bb, Hb)``) the grouped bias, for a grid whose
    third axis walks query blocks or, ``kv_major``, key blocks; ``at`` as
    :func:`_walk` gives it."""
    def where(b, row, t, pre):  # -> (batch row, iq, ik)
        b, row, other = at(b, row, t, pre)
        return (b, other, row) if kv_major else (b, row, other)

    def qi(b, h, row, t, *pre):
        b, iq, _ = where(b, row, t, pre)
        return (b, h, iq, 0)

    def ki(b, h, row, t, *pre):
        b, _, ik = where(b, row, t, pre)
        return (b, h, ik, 0)

    def maski(b, h, row, t, *pre):
        b, _, ik = where(b, row, t, pre)
        return (b, 0, ik)

    def biasi(Bb, Hb):
        """Grouped-broadcast bias indexing: batch b reads bias group
        b // (B/Bb).

        Bb == 1 (one shared bias) and Bb == B (per-batch bias) are the
        degenerate cases; 1 < Bb < B is the Evoformer/Uni-Fold layout, where
        consecutive runs of B/Bb flattened batches (MSA rows of one sequence,
        lead rows of one pair matrix) share a pair-bias slab — the same
        broadcast contract as the reference kernel
        (/root/reference/csrc/softmax_dropout/interface.cpp:37-48).
        """
        gb = B // Bb

        def idx(b, h, row, t, *pre):
            b, iq, ik = where(b, row, t, pre)
            return (b // gb, h if Hb > 1 else 0, iq, ik)

        return idx

    return qi, ki, maski, biasi


def _step(walk, t, pre, body, band=None, iq=None, ik=None, shape=None):
    """Run a grid step's ``body(visible)``: always without a map, with one
    unless the step is a row's dead item.  Under a ``band`` the body is
    traced twice and one of the two runs: with the band's mask of the
    block (``visible``) where the block is partly visible, without
    (``visible`` None) where it is wholly visible."""
    if band is None:
        if walk.live is None:
            body(None)
        else:
            pl.when(walk.live(t, pre))(lambda: body(None))
        return
    live = walk.live(t, pre)
    partly = _band_partly(band, iq, ik, shape)
    pl.when(live & partly)(lambda: body(_band_mask(band, iq, ik, shape)))
    pl.when(live & jnp.logical_not(partly))(lambda: body(None))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _stat_lanes(block_k):
    """How many lanes wide the forward kernel keeps a query block's running
    statistics: one lane tile, or the whole key block where that is no
    multiple of the tile (a short row taken whole)."""
    return 128 if block_k % 128 == 0 else block_k


def _row_lanes(x, n):
    """``x`` ``(rows, W)`` whose lanes all hold their row's one value, as
    ``(rows, n)``: slices and copies of whole lanes, no broadcast from a
    column."""
    w = x.shape[1]
    if n <= w:
        return x if n == w else x[:, :n]
    if n % w == 0:
        return jnp.concatenate([x] * (n // w), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fwd_kernel(
    pre,
    q_ref, k_ref, v_ref, bias_ref, mask_ref,
    o_ref, lse_ref,
    m_s, l_s, acc_s,
    *, sm_scale, dropout_rate, walk, has_bias, has_mask, band=None,
):
    b, h, iq, t = (pl.program_id(i) for i in range(4))
    b, iq, ik = walk.at(b, iq, t, pre)
    # the statistics stay whole lanes wide (module docstring): ``m_s`` a
    # row's maximum in every one of its W lanes, ``l_s`` W partial sums a
    # row, lane ``j`` over the keys ``j, j + W, ...`` of the blocks so far
    W = m_s.shape[1]

    @pl.when(walk.first(t, pre))
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def _visit(visible):
        q = q_ref[0, 0]  # (BQ, D)
        k = k_ref[0, 0]  # (BK, D)
        v = v_ref[0, 0]  # (BK, D)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if sm_scale != 1.0:  # the decoders hand over a pre-scaled q
            s = s * sm_scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if visible is not None:
            # every query sees its own position, so no row of the band is
            # empty over the blocks it visits: the hidden pairs' weights
            # underflow to exact zeros once a visible key has been scored
            s = jnp.where(visible, s, NEG_INF)
        if has_mask:
            kv_mask = mask_ref[0] != 0  # (1, BK) True = masked out
            s = jnp.where(kv_mask, NEG_INF, s)

        m_prev = m_s[...]  # (BQ, W)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _row_lanes(m_next, s.shape[1]))
        if has_mask:
            p = jnp.where(kv_mask, 0.0, p)  # exact zero for fully-masked rows
        corr = jnp.exp(m_prev - m_next)
        part = p[:, :W]
        for j in range(1, p.shape[1] // W):
            part = part + p[:, j * W:(j + 1) * W]
        l_s[...] = corr * l_s[...] + part
        m_s[...] = m_next

        if dropout_rate > 0.0:
            _seed_block(pre[0], b, h, iq, ik)
            keep = _keep_mask(p.shape, dropout_rate)
            p_use = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        else:
            p_use = p

        pv = jax.lax.dot_general(
            p_use.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_s[...] = acc_s[...] * _row_lanes(corr, acc_s.shape[1]) + pv

    _step(walk, t, pre, _visit, band, iq, ik,
          (q_ref.shape[2], k_ref.shape[2]))

    # a query block with no visit at all writes zeros, as a row whose keys
    # are all padding does
    @pl.when(walk.last(t, pre))
    def _finish():
        l = jnp.sum(l_s[...], axis=-1, keepdims=True)  # (BQ, 1)
        inv_l = jnp.where(l > 0.0, 1.0 / l, 0.0)
        o_ref[0, 0] = (acc_s[...] * inv_l).astype(o_ref.dtype)
        lse = m_s[:, :1] + jnp.log(jnp.maximum(l, 1e-37))
        lse_ref[0, 0] = lse.astype(jnp.float32)  # (BQ, 1)


def _fwd(q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate, block_q,
         block_k, block_map=None, band=None):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    BQ, BK = _pick_block(Lq, block_q), _pick_block(Lk, block_k)
    nq, nk = _cdiv(Lq, BQ), _cdiv(Lk, BK)

    has_bias = bias is not None
    has_mask = kv_mask is not None
    kv_items, kv_counts = (block_map or (None,) * 4)[:2]
    walk = _walk(kv_items, kv_counts, B, nq, nk)
    visits = walk.operands

    # refuse here (rather than let Mosaic OOM on-device) when one grid
    # step's resident blocks bust the shared budget — the --kernels
    # auditor prices the identical model (analysis/kernel_geometry.py)
    io_blocks = [
        ((1, 1, BQ, D), q.dtype), ((1, 1, BK, D), k.dtype),
        ((1, 1, BK, D), v.dtype),
        ((1, 1, BQ, D), q.dtype), ((1, 1, BQ, 1), jnp.float32),
    ]
    if has_bias:
        io_blocks.append(((1, 1, BQ, BK), bias.dtype))
    if has_mask:
        io_blocks.append(((1, 1, BK), kv_mask.dtype))
    # the running maximum, the partial sums and the accumulator
    scratch = [((BQ, _stat_lanes(BK)), jnp.float32)] * 2 + [
        ((BQ, D), jnp.float32)]
    check_vmem_budget("flash_attention fwd", io_blocks, scratch)

    qi, ki, maski, biasi = _index_maps(B, walk.at, kv_major=False)
    in_specs = [
        pl.BlockSpec((1, 1, BQ, D), qi),
        pl.BlockSpec((1, 1, BK, D), ki),
        pl.BlockSpec((1, 1, BK, D), ki),
    ]
    inputs = [q, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, BQ, BK), biasi(bias.shape[0], bias.shape[1]))
        )
        inputs.append(bias)
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 1, BK), maski))
        inputs.append(kv_mask)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        dropout_rate=dropout_rate,
        walk=walk,
        has_bias=has_bias,
        has_mask=has_mask,
        band=band,
    )

    def wrapped(*refs):
        pre, refs = refs[:1 + len(visits)], refs[1 + len(visits):]
        n_in = len(inputs)
        in_refs = refs[:n_in]
        out_refs = refs[n_in:n_in + 2]
        scratch = refs[n_in + 2:]
        q_ref, k_ref, v_ref = in_refs[:3]
        i = 3
        bias_ref = in_refs[i] if has_bias else None
        i += int(has_bias)
        mask_ref = in_refs[i] if has_mask else None
        kernel(pre, q_ref, k_ref, v_ref, bias_ref, mask_ref, *out_refs,
               *scratch)

    out, lse = _pallas_call(
        wrapped,
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(visits),
            grid=(walk.grid[0], H, *walk.grid[1:]),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, BQ, D), qi),
                pl.BlockSpec((1, 1, BQ, 1), qi),
            ],
            scratch_shapes=[pltpu.VMEM(*block) for block in scratch],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, Lq, 1), jnp.float32),
        ],
    )(seed, *visits, *inputs)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dq (+ per-batch ds when bias is batch-sized)
# ---------------------------------------------------------------------------

def _recompute_p(q_ref, k_ref, bias_ref, mask_ref, lse_ref, sm_scale,
                 has_bias, has_mask, visible=None):
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * sm_scale
    if has_bias:
        s = s + bias_ref[0, 0].astype(jnp.float32)
    if visible is not None:  # a band's partly visible block
        s = jnp.where(visible, s, NEG_INF)
    kv_mask = None
    if has_mask:
        kv_mask = mask_ref[0] != 0  # (1, BK)
        s = jnp.where(kv_mask, NEG_INF, s)
    lse_col = lse_ref[0, 0]  # (BQ, 1)
    p = jnp.exp(s - lse_col)
    if has_mask:
        p = jnp.where(kv_mask, 0.0, p)
    return p, kv_mask


def _ds_block(seed_ref, p, kv_mask, do_ref, v_ref, di_ref, dropout_rate,
              b, h, iq, ik):
    """Shared ds computation: ds = p * (dropout^T(do @ v^T) - di)."""
    do = do_ref[0, 0]
    v = v_ref[0, 0]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if dropout_rate > 0.0:
        _seed_block(seed_ref, b, h, iq, ik)
        keep = _keep_mask(dp.shape, dropout_rate)
        dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
    di_col = di_ref[0, 0]  # (BQ, 1)
    ds = p * (dp - di_col)
    if kv_mask is not None:
        ds = jnp.where(kv_mask, 0.0, ds)
    return ds


def _dq_kernel(
    pre,
    q_ref, k_ref, v_ref, bias_ref, mask_ref, lse_ref, di_ref, do_ref,
    dq_ref,
    dq_s,
    *, sm_scale, dropout_rate, walk, has_bias, has_mask, band=None,
):
    b, h, iq, t = (pl.program_id(i) for i in range(4))
    b, iq, ik = walk.at(b, iq, t, pre)

    @pl.when(walk.first(t, pre))
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _visit(visible):
        p, kv_mask = _recompute_p(
            q_ref, k_ref, bias_ref, mask_ref, lse_ref, sm_scale, has_bias,
            has_mask, visible
        )
        ds = _ds_block(
            pre[0], p, kv_mask, do_ref, v_ref, di_ref, dropout_rate,
            b, h, iq, ik
        )
        k = k_ref[0, 0]
        dq_s[...] += sm_scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _step(walk, t, pre, _visit, band, iq, ik,
          (q_ref.shape[2], k_ref.shape[2]))

    @pl.when(walk.last(t, pre))
    def _finish():
        dq_ref[0, 0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(
    pre,
    q_ref, k_ref, v_ref, bias_ref, mask_ref, lse_ref, di_ref, do_ref,
    dk_ref, dv_ref,
    dk_s, dv_s,
    *, sm_scale, dropout_rate, walk, has_bias, has_mask, band=None,
):
    b, h, ik, t = (pl.program_id(i) for i in range(4))
    b, ik, iq = walk.at(b, ik, t, pre)

    @pl.when(walk.first(t, pre))
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def _visit(visible):
        p, kv_mask = _recompute_p(
            q_ref, k_ref, bias_ref, mask_ref, lse_ref, sm_scale, has_bias,
            has_mask, visible
        )

        # dv += dropout(p)^T @ do
        do = do_ref[0, 0]
        if dropout_rate > 0.0:
            _seed_block(pre[0], b, h, iq, ik)
            keep = _keep_mask(p.shape, dropout_rate)
            p_drop = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        else:
            p_drop = p
        dv_s[...] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        ds = _ds_block(
            pre[0], p, kv_mask, do_ref, v_ref, di_ref, dropout_rate,
            b, h, iq, ik
        )
        q = q_ref[0, 0]
        dk_s[...] += sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _step(walk, t, pre, _visit, band, iq, ik,
          (q_ref.shape[2], k_ref.shape[2]))

    # a key block no query block visits still writes: zeros, not what the
    # output buffer held
    @pl.when(walk.last(t, pre))
    def _finish():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


def _db_kernel(
    seed_ref,
    q_ref, k_ref, v_ref, bias_ref, mask_ref, lse_ref, di_ref, do_ref,
    db_ref,
    db_s,
    *, sm_scale, dropout_rate, nr, has_bias, has_mask,
):
    # grid (Bb, H, nq, nk, R) with R = B // Bb innermost: each bias group's
    # grad block stays resident in VMEM while its R broadcast batches are
    # reduced.  R == B (one shared bias) and R == 1 (per-batch bias, ds IS
    # the grad) are the degenerate ends of the same loop.
    g, h, iq, ik, r = (pl.program_id(i) for i in range(5))
    b = g * nr + r  # the flat batch this tick visits (dropout stream key)

    @pl.when(r == 0)
    def _init():
        db_s[...] = jnp.zeros_like(db_s)

    p, kv_mask = _recompute_p(
        q_ref, k_ref, bias_ref, mask_ref, lse_ref, sm_scale, has_bias, has_mask
    )
    ds = _ds_block(
        seed_ref, p, kv_mask, do_ref, v_ref, di_ref, dropout_rate, b, h, iq, ik
    )
    db_s[...] += ds

    @pl.when(r == nr - 1)
    def _finish():
        db_ref[0, 0] = db_s[...].astype(db_ref.dtype)


def _bwd_inputs(q, k, v, bias, kv_mask, lse, di, do, BQ, BK, *, kv_major,
                at=lambda b, row, t, pre: (b, row, t)):
    """Input arrays + specs shared by the bwd kernels.

    ``kv_major=False``: the grid's third axis walks query blocks; True: key
    blocks; ``at`` as :func:`_walk` gives it (default: no map).
    """
    qi, ki, maski, biasi = _index_maps(q.shape[0], at, kv_major)
    D = q.shape[-1]
    specs = [
        pl.BlockSpec((1, 1, BQ, D), qi),
        pl.BlockSpec((1, 1, BK, D), ki),
        pl.BlockSpec((1, 1, BK, D), ki),
    ]
    inputs = [q, k, v]
    if bias is not None:
        specs.append(
            pl.BlockSpec((1, 1, BQ, BK), biasi(bias.shape[0], bias.shape[1]))
        )
        inputs.append(bias)
    if kv_mask is not None:
        specs.append(pl.BlockSpec((1, 1, BK), maski))
        inputs.append(kv_mask)
    specs.append(pl.BlockSpec((1, 1, BQ, 1), qi))
    inputs.append(lse)
    specs.append(pl.BlockSpec((1, 1, BQ, 1), qi))
    inputs.append(di)
    specs.append(pl.BlockSpec((1, 1, BQ, D), qi))
    inputs.append(do)
    return inputs, specs


def _make_ref_unpacker(has_bias, has_mask, n_outs, n_scratch):
    def unpack(refs, n_in):
        q_ref, k_ref, v_ref = refs[:3]
        i = 3
        bias_ref = refs[i] if has_bias else None
        i += int(has_bias)
        mask_ref = refs[i] if has_mask else None
        i += int(has_mask)
        lse_ref, di_ref, do_ref = refs[i], refs[i + 1], refs[i + 2]
        outs = refs[n_in:n_in + n_outs]
        scratch = refs[n_in + n_outs:]
        return (q_ref, k_ref, v_ref, bias_ref, mask_ref, lse_ref, di_ref,
                do_ref), outs, scratch

    return unpack


def _bwd(q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate, block_q,
         block_k, out, lse, do, block_map=None, band=None):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    BQ, BK = _pick_block(Lq, block_q), _pick_block(Lk, block_k)
    nq, nk = _cdiv(Lq, BQ), _cdiv(Lk, BK)
    has_bias = bias is not None
    has_mask = kv_mask is not None
    # with a map the bias is a constant (``_constant_bias`` refuses to
    # differentiate it): the dbias kernel walks every block and is not run
    want_dbias = has_bias and block_map is None

    # same budget refusal as the forward, per backward kernel family
    io_common = [
        ((1, 1, BQ, D), q.dtype), ((1, 1, BK, D), k.dtype),
        ((1, 1, BK, D), v.dtype),
        ((1, 1, BQ, 1), jnp.float32), ((1, 1, BQ, 1), jnp.float32),
        ((1, 1, BQ, D), do.dtype),
    ]
    if has_bias:
        io_common.append(((1, 1, BQ, BK), bias.dtype))
    if has_mask:
        io_common.append(((1, 1, BK), kv_mask.dtype))
    check_vmem_budget(
        "flash_attention bwd dq", io_common + [((1, 1, BQ, D), q.dtype)],
        [((BQ, D), jnp.float32)],
    )
    check_vmem_budget(
        "flash_attention bwd dkv",
        io_common + [((1, 1, BK, D), k.dtype), ((1, 1, BK, D), v.dtype)],
        [((BK, D), jnp.float32), ((BK, D), jnp.float32)],
    )
    if want_dbias:
        check_vmem_budget(
            "flash_attention bwd dbias",
            io_common + [((1, 1, BQ, BK), jnp.float32)],
            [((BQ, BK), jnp.float32)],
        )

    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1, keepdims=True)
    kv_items, kv_counts, q_items, q_counts = block_map or (None,) * 4

    def walked(kernel, kv_major, items, counts, rows, n_other, n_outs):
        """The body, map operands, inputs, specs, output index map and grid
        of one of the two walked kernels."""
        walk = _walk(items, counts, B, rows, n_other)
        visits = walk.operands
        inputs, specs = _bwd_inputs(
            q, k, v, bias, kv_mask, lse, di, do, BQ, BK, kv_major=kv_major,
            at=walk.at,
        )
        unpack = _make_ref_unpacker(has_bias, has_mask, n_outs, n_outs)

        def wrapped(*refs):
            pre, refs = refs[:1 + len(visits)], refs[1 + len(visits):]
            in_refs, outs, scratch = unpack(refs, len(inputs))
            kernel(
                pre, *in_refs, *outs, *scratch,
                sm_scale=sm_scale, dropout_rate=dropout_rate, walk=walk,
                has_bias=has_bias, has_mask=has_mask, band=band,
            )

        def outi(b, h, row, t, *pre):
            b, row, _ = walk.at(b, row, t, pre)
            return (b, h, row, 0)

        return (wrapped, visits, inputs, specs, outi,
                (walk.grid[0], H, *walk.grid[1:]))

    # ---- dq: the third grid axis walks query blocks ------------------
    dq_wrapped, visits, inputs, specs, outi, grid = walked(
        _dq_kernel, False, kv_items, kv_counts, nq, nk, 1
    )
    dq = _pallas_call(
        dq_wrapped,
        name="flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(visits),
            grid=grid,
            in_specs=specs,
            out_specs=[pl.BlockSpec((1, 1, BQ, D), outi)],
            scratch_shapes=[pltpu.VMEM((BQ, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
    )(seed, *visits, *inputs)[0]

    # ---- dk, dv: the third grid axis walks key blocks ----------------
    dkv_wrapped, visits, inputs, specs, outi, grid = walked(
        _dkv_kernel, True, q_items, q_counts, nk, nq, 2
    )
    # dkv regenerates the SAME dropout mask the forward applied
    # (recompute-from-counters design, module docstring)
    # lint: shared-prng-stream
    dk, dv = _pallas_call(
        dkv_wrapped,
        name="flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(visits),
            grid=grid,
            in_specs=specs,
            out_specs=[
                pl.BlockSpec((1, 1, BK, D), outi),
                pl.BlockSpec((1, 1, BK, D), outi),
            ],
            scratch_shapes=[
                pltpu.VMEM((BK, D), jnp.float32),
                pltpu.VMEM((BK, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
    )(seed, *visits, *inputs)

    # ---- dbias -------------------------------------------------------
    # One kernel for every broadcast layout: grid (Bb, H, nq, nk, R) with
    # R = B // Bb batches reduced in VMEM per bias group.  Bb == 1 is the
    # classic shared-bias reduction, Bb == B degenerates to "ds IS the
    # grad", and 1 < Bb < B is the grouped Evoformer layout.
    dbias = None
    if want_dbias:
        Bb, Hb = bias.shape[0], bias.shape[1]
        if Hb not in (1, H):
            raise KernelGeometryError(
                f"dbias kernel needs bias heads in (1, {H}), got {Hb}"
            )
        R = B // Bb
        inputs, _ = _bwd_inputs(
            q, k, v, bias, kv_mask, lse, di, do, BQ, BK, kv_major=False
        )

        def bat(g, r):
            return g * R + r

        db_specs = [
            pl.BlockSpec((1, 1, BQ, D),
                         lambda g, h, iq, ik, r, *_: (bat(g, r), h, iq, 0)),
            pl.BlockSpec((1, 1, BK, D),
                         lambda g, h, iq, ik, r, *_: (bat(g, r), h, ik, 0)),
            pl.BlockSpec((1, 1, BK, D),
                         lambda g, h, iq, ik, r, *_: (bat(g, r), h, ik, 0)),
            pl.BlockSpec(
                (1, 1, BQ, BK),
                lambda g, h, iq, ik, r, *_: (g, h if Hb > 1 else 0, iq, ik),
            ),
        ]
        if has_mask:
            db_specs.append(
                pl.BlockSpec((1, 1, BK),
                             lambda g, h, iq, ik, r, *_: (bat(g, r), 0, ik))
            )
        db_specs.extend([
            pl.BlockSpec((1, 1, BQ, 1),
                         lambda g, h, iq, ik, r, *_: (bat(g, r), h, iq, 0)),
            pl.BlockSpec((1, 1, BQ, 1),
                         lambda g, h, iq, ik, r, *_: (bat(g, r), h, iq, 0)),
            pl.BlockSpec((1, 1, BQ, D),
                         lambda g, h, iq, ik, r, *_: (bat(g, r), h, iq, 0)),
        ])

        unpack = _make_ref_unpacker(has_bias, has_mask, 1, 1)

        def db_wrapped(seed_ref, *refs):
            in_refs, outs, scratch = unpack(refs, len(inputs))
            _db_kernel(
                seed_ref, *in_refs, *outs, *scratch,
                sm_scale=sm_scale, dropout_rate=dropout_rate, nr=R,
                has_bias=has_bias, has_mask=has_mask,
            )

        # Hb == 1: the kernel writes per-head grads; reduced below.
        # dbias regenerates the forward's mask (recompute design)
        # lint: shared-prng-stream
        dbias_full = _pallas_call(
            db_wrapped,
            name="flash_bwd_dbias",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(Bb, H, nq, nk, R),
                in_specs=db_specs,
                out_specs=[
                    pl.BlockSpec(
                        (1, 1, BQ, BK),
                        lambda g, h, iq, ik, r, *_: (g, h, iq, ik),
                    ),
                ],
                scratch_shapes=[pltpu.VMEM((BQ, BK), jnp.float32)],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((Bb, H, Lq, Lk), jnp.float32)
            ],
        )(seed, *inputs)[0]
        if Hb == 1:
            dbias_full = jnp.sum(dbias_full, axis=1, keepdims=True)
        dbias = dbias_full.astype(bias.dtype)

    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _flash(q, k, v, bias, kv_mask, seed, block_map, sm_scale, dropout_rate,
           blocks, band=None):
    out, _ = _fwd(
        q, k, v, bias, kv_mask, seed,
        sm_scale, dropout_rate, blocks[0], blocks[1], block_map, band,
    )
    return out


def _flash_fwd(q, k, v, bias, kv_mask, seed, block_map, sm_scale,
               dropout_rate, blocks, band=None):
    out, lse = _fwd(
        q, k, v, bias, kv_mask, seed,
        sm_scale, dropout_rate, blocks[0], blocks[1], block_map, band,
    )
    return out, (q, k, v, bias, kv_mask, seed, block_map, out, lse)


def _flash_bwd(sm_scale, dropout_rate, blocks, band, residuals, do):
    q, k, v, bias, kv_mask, seed, block_map, out, lse = residuals
    dq, dk, dv, dbias = _bwd(
        q, k, v, bias, kv_mask, seed,
        sm_scale, dropout_rate, blocks[0], blocks[1], out, lse, do, block_map,
        band,
    )
    return dq, dk, dv, dbias, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@jax.custom_vjp
def _constant_bias(bias):
    """The bias of a mapped call: the identity, whose backward rule runs
    only if something differentiates through the bias, and refuses."""
    return bias


def _constant_bias_bwd(_, cotangent):
    raise KernelGeometryError(
        "flash_attention with a block_map takes a constant bias: the "
        "bias-gradient kernel walks every block (stop_gradient the bias, "
        "or drop the map)"
    )


_constant_bias.defvjp(lambda bias: (bias, None), _constant_bias_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    kv_padding_mask: Optional[jnp.ndarray] = None,
    dropout_rate: float = 0.0,
    dropout_seed: int = 0,
    sm_scale: float = 1.0,
    block_q: int = 256,
    block_k: int = 512,
    block_map: Optional[BlockMap] = None,
    band: Optional[Band] = None,
) -> jnp.ndarray:
    """Blockwise-online attention: softmax(q k^T * scale + bias, mask) v.

    Args:
        q, k, v: (B, H, L, D).  L must be a multiple of the block size
            (the module layer pads/unpads; data pipelines already pad to a
            multiple of 8 — use block 128-aligned seq lens for peak speed).
        bias: additive bias (Bb, 1|H, Lq, Lk) with B % Bb == 0 — GROUPED
            broadcast: batch b reads bias group b // (B/Bb), so Bb == 1 is
            one shared bias, Bb == B per-batch, and 1 < Bb < B the
            Evoformer/Uni-Fold layout (runs of B/Bb consecutive batches —
            the MSA rows of one sequence — share a pair-bias slab; the
            reference kernel's broadcast contract,
            /root/reference/csrc/softmax_dropout/interface.cpp:37-48).
            Learned biases get correct gradients: every broadcast dim is
            reduced inside the backward kernel.
        kv_padding_mask: (B, Lk) bool/int; nonzero = masked out.
        dropout_rate: attention dropout applied to the probabilities.
        dropout_seed: int32 seed; fold in step/layer ids for decorrelation.
        block_map: :func:`block_map` of which ``(block_q, block_k)`` blocks
            hold a key some query of the block may see, per group ``G``
            (``B % G == 0``, grouped as the bias is): the kernels visit
            those blocks only.  The map decides WHICH blocks are scored,
            never how: bias and mask still apply inside a visited block,
            so the map must not drop a block that holds a visible key.  The
            bias is then a constant (no bias gradient: asking for one
            raises ``KernelGeometryError``).
        band: a :class:`Band`: query ``i`` sees key ``j`` iff ``0 <= i - j
            < window``, beside whatever bias and padding mask say.  The
            call makes the band's own block map (:func:`band_block_map`),
            so it takes no ``block_map`` beside it; a bias is a constant,
            as under any map.
    """
    if bias is not None:
        if bias.ndim == 3:
            bias = bias[None]
        if bias.ndim != 4:
            raise KernelGeometryError(
                f"bias must be rank 3 or 4, got shape {bias.shape}"
            )
        if q.shape[0] % bias.shape[0] != 0:
            raise KernelGeometryError(
                f"bias batch {bias.shape[0]} must divide batch {q.shape[0]}"
            )
        # 1 < Hb < H would silently read out-of-range head blocks (the
        # index map clamps on TPU) — reject here, not just in the dbias
        # backward branch
        if bias.shape[1] not in (1, q.shape[1]):
            raise KernelGeometryError(
                f"bias heads {bias.shape[1]} must be 1 or {q.shape[1]}"
            )
    if band is not None:
        if block_map is not None:
            raise KernelGeometryError(
                "flash_attention takes a band or a block_map, not both: "
                "the band brings the map of its own visible blocks"
            )
        block_map = band_block_map(
            band, q.shape[2], k.shape[2], block_q, block_k)
    if block_map is not None:
        nq = q.shape[2] // _pick_block(q.shape[2], block_q)
        nk = k.shape[2] // _pick_block(k.shape[2], block_k)
        G = block_map.kv_counts.shape[0]
        if (
            q.shape[0] % G != 0
            or block_map.kv_counts.shape != (G, nq)
            or block_map.q_counts.shape != (G, nk)
        ):
            raise KernelGeometryError(
                f"block_map of {block_map.kv_counts.shape} x "
                f"{block_map.q_counts.shape} (groups, blocks) does not fit "
                f"batch {q.shape[0]} with {nq} query and {nk} key blocks"
            )
        if bias is not None:
            bias = _constant_bias(bias)
    if kv_padding_mask is not None:
        kv_padding_mask = kv_padding_mask.astype(jnp.int32)[:, None, :]
    seed = jnp.reshape(jnp.asarray(dropout_seed, dtype=jnp.int32), (1,))
    return _flash(
        q, k, v, bias, kv_padding_mask, seed, block_map,
        # lint: host-sync-in-jit; dropout_rate is a static hyperparameter
        sm_scale, float(dropout_rate), (block_q, block_k), band,
    )


@functools.lru_cache(maxsize=64)
def band_block_map(band, q_len, k_len, block_q=256, block_k=512) -> BlockMap:
    """The :class:`BlockMap` of ``band`` over ``(q_len, k_len)`` positions
    at the block sizes a call with these arguments picks: the blocks in
    which the band leaves some pair visible (:func:`band_visible`)."""
    BQ, BK = _pick_block(q_len, block_q), _pick_block(k_len, block_k)
    return block_map(band_visible(band, q_len // BQ, k_len // BK, BQ, BK))


def band_counts(band, q_len, k_len, block_q=256, block_k=512):
    """``(computed, visible)``: the (query, key) pairs one head of one row
    scores under ``band`` (every pair of a block that the map a call with
    these arguments builds, :func:`band_block_map`, visits) and the pairs
    the band leaves visible."""
    BQ, BK = _pick_block(q_len, block_q), _pick_block(k_len, block_k)
    visits = band_block_map(band, q_len, k_len, block_q, block_k).kv_counts
    seen = np.minimum(np.arange(q_len) + 1, band.width(k_len))
    return int(visits.sum()) * BQ * BK, int(seen.sum())


def band_log(rows, length, window, layers, heads=None):
    """What a model whose attention runs under bands logs of an update of
    ``rows`` rows of ``length``: for its sliding-window layers (``window``
    positions) and its full layers apart, :func:`band_counts` of the maps
    the kernels are handed (the row padded to their 128 tile, their default
    blocks), summed over the batch's rows and over the layers of each kind,
    per head.  ``layers``: the layers of each kind, ``{"window": n, "full":
    n}``.  ``heads``: the same keys, the query heads held on a layer of each
    kind, for a model whose two kinds differ there (one that states none
    logs none: a key more is an output more of its step)."""
    padded = length + (-length) % 128
    out = {"band_rows": 1}
    for kind, band in (("window", Band(window)), ("full", Band(None))):
        computed, visible = band_counts(band, padded, padded)
        out.update({
            f"band_{kind}_keys_computed": layers[kind] * computed,
            f"band_{kind}_keys_visible": layers[kind] * visible,
            f"band_{kind}_layers": layers[kind],
        })
        if heads is not None:
            out[f"band_{kind}_heads"] = heads[kind]
    return {k: jnp.asarray(rows * v, jnp.float32) for k, v in out.items()}


def band_mark(sums):
    """What a profiler capture is told of one update of such a model, from
    that update's summed logging output: one ``unicore:attn_band`` mark
    with, for its sliding-window and its full layers apart (two maps: none
    of its stats is named ``keys_computed``, which a reader takes for the
    pairs of ONE mapped call), the pairs the kernels scored and the pairs a
    query could see, per row and head, summed over the layers of each kind;
    and, where the model logs them, ``window_heads`` and ``full_heads``.
    Nothing where no row was logged."""
    rows = sums.get("band_rows", 0)
    if not rows:
        return {}
    return {"attn_band": {
        f"{kind}_{stat}": int(sums[f"band_{kind}_{stat}"] / rows)
        for kind in ("window", "full")
        for stat in ("keys_computed", "keys_visible", "layers", "heads")
        if f"band_{kind}_{stat}" in sums
    }}


def band_call_mark(sums):
    """For a model whose banded layers ALL see the whole row (no
    sliding-window layer, so one map for every call): one
    ``unicore:attn_band_call`` mark whose ``keys_computed`` is what a
    reader of the kernels' trace events takes it for, the pairs ONE mapped
    call scores a head (the batch's rows, one layer), with ``keys_visible``
    beside it.  Nothing where window layers were logged (:func:`band_mark`
    says why its stats carry other names) or no row was."""
    rows = sums.get("band_rows", 0)
    if (not rows or sums.get("band_window_layers", 0)
            or not sums.get("band_full_layers", 0)):
        return {}
    layers = sums["band_full_layers"] / rows
    return {"attn_band_call": {
        f"keys_{stat}": int(sums[f"band_full_keys_{stat}"] / layers)
        for stat in ("computed", "visible")}}


def mha_reference(q, k, v, bias=None, kv_padding_mask=None, sm_scale=1.0):
    """Pure-jnp reference for numerics tests."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        if bias.ndim == 3:
            bias = bias[None]
        if bias.shape[0] not in (1, q.shape[0]):  # grouped broadcast
            bias = jnp.repeat(bias, q.shape[0] // bias.shape[0], axis=0)
        s = s + bias.astype(jnp.float32)
    if kv_padding_mask is not None:
        s = jnp.where(kv_padding_mask[:, None, None, :].astype(bool), NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    if kv_padding_mask is not None:
        p = jnp.where(kv_padding_mask[:, None, None, :].astype(bool), 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# representative audit shapes (unicore-tpu-lint --kernels; docs/lint.md)
# ---------------------------------------------------------------------------

@audit_case("flash-attention-fwd-bwd")
def _audit_flash_fwd_bwd():
    """BERT-ish training geometry at the default block plan (BQ=256,
    BK=512 -> a 2x2 block grid): grouped bias (Bb=1, so the dbias kernel
    gets a real R=2 reduction axis), padding mask, dropout on — all four
    kernels (fwd, dq, dkv, dbias) capture with every spec branch live."""
    q = jnp.zeros((2, 2, 512, 64), jnp.float32)
    kv = jnp.zeros((2, 2, 1024, 64), jnp.float32)
    bias = jnp.zeros((1, 2, 512, 1024), jnp.float32)
    mask = jnp.zeros((2, 1024), jnp.int32)

    def loss(q, kv, bias):
        out = flash_attention(q, kv, kv, bias=bias, kv_padding_mask=mask,
                              dropout_rate=0.1, dropout_seed=7)
        return jnp.sum(out)

    jax.grad(loss, argnums=(0, 1, 2))(q, kv, bias)


@audit_case("flash-attention-bf16-nobias")
def _audit_flash_bf16():
    """bf16 inference geometry, no bias/mask: the lean spec list on the
    16-row sublane grid."""
    q = jnp.zeros((2, 4, 512, 64), jnp.bfloat16)
    kv = jnp.zeros((2, 4, 512, 64), jnp.bfloat16)
    flash_attention(q, kv, kv, sm_scale=0.125)


@audit_case("flash-attention-block-map")
def _audit_flash_block_map():
    """An EVA-like geometry (ops/eva_attention.py): four windows of 256 as
    batch rows, each against ``[its own 256 keys ; 256 chunk summaries]``
    under a grouped constant bias and a block map of G = 4 groups at blocks
    of (128, 128) — the causal triangle on the left, summary block ``s``
    seen by the windows past ``2 s`` on the right, so window 0 visits
    neither summary block (key blocks with no visitor).  The three walked
    kernels capture with index maps that read the map from scalar prefetch;
    the auditor evaluates them on the map's values."""
    q = jnp.zeros((4, 2, 256, 64), jnp.bfloat16)
    kv = jnp.zeros((4, 2, 512, 64), jnp.bfloat16)
    bias = jnp.zeros((4, 1, 256, 512), jnp.bfloat16)
    w, iq, ik = np.ogrid[:4, :2, :4]
    visits = block_map(np.where(ik < 2, ik <= iq, (ik - 2) * 2 < w))

    def loss(q, kv):
        out = flash_attention(q, kv, kv, bias=bias, block_q=128, block_k=128,
                              block_map=visits)
        return jnp.sum(out.astype(jnp.float32))

    jax.grad(loss, argnums=(0, 1))(q, kv)
