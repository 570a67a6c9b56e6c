"""Matmul operations of one call of each named attention kernel, from the
shapes of its operands as the trace event's own HLO line gives them.

Every product a kernel performs is one ``(Lq x D) x (D x Lk)`` matmul (or a
transpose of it) per batch row and head: ``2 * D`` operations for each
(query, key) pair it scores.  Which products each kernel performs is read
from its body (``unicore_tpu/ops/flash_attention.py``,
``attention_fullrow.py``); the backward kernels compute the scores again,
and that work is counted: a kernel's roofline share is about the kernel as
written, unlike ``train_mfu_pct``, which counts only what the algorithm
needs.

How many pairs a call scores:

* A call without a block map visits every block, padding or not:
  ``B * Lq * Lk`` pairs a head, from the shapes of ``q`` and ``k``.
* A call WITH a block map (``flash_attention(..., block_map=)``, since
  PR 32) visits only the blocks the map lists.  The event shows that it is
  mapped: the flat list of visits rides in scalar prefetch and is the
  operand after the seed, rank 1 (``s32[608]`` for ``flash_fwd`` and
  ``flash_bwd_dq`` on EVA's windows, one item a visited block; ``s32[636]``
  for ``flash_bwd_dkv``, 28 of them dead items of key blocks nobody
  visits).  The event does NOT show how large a block is (the operands are
  whole arrays, the list's values are not in the line), so the items cannot
  be turned into pairs from the event alone; the pairs are what the program
  states of its map: the ``keys_computed`` stat of a ``unicore:``
  annotation (``unicore:eva_keys``, from ``ops/eva_attention.kernel_map``,
  the one place the kernels' map and the stat both come from: visited
  blocks x block_q x block_k, per layer and head, over the batch's rows:
  what one call scores a head).  The caller hands it in as
  ``mapped_pairs``; a mapped call without it, or with more pairs than the
  dense call has, is not counted at all (``None``: the reader then reports
  nothing, never a share of the peak that is too high).
"""

#: kernel ``name=`` -> the products one grid step performs
PRODUCTS = {
    "flash_fwd": ("S = Q K^T", "O = P V"),
    "flash_bwd_dq": ("S = Q K^T", "dP = dO V^T", "dQ = dS K"),
    "flash_bwd_dkv": ("S = Q K^T", "dV = P^T dO", "dP = dO V^T",
                      "dK = dS^T Q"),
    "flash_bwd_dbias": ("S = Q K^T", "dP = dO V^T"),
    "fullrow_attn_fwd": ("S = Q K^T", "O = P V"),
    "fullrow_attn_bwd": ("S = Q K^T", "dP = dO V^T", "dV = P^T dO",
                         "dQ = dS K", "dK = dS^T Q"),
}


def map_items(operand_shapes):
    """The items of a call's block map (visits and dead items), or None
    for a call without one: the operand after the seed, where it has rank 1
    (without a map ``q``, of rank 4, follows the seed)."""
    if len(operand_shapes) > 1 and len(operand_shapes[1]) == 1:
        return operand_shapes[1][0]
    return None


def matmul_flops(kernel, operand_shapes, mapped_pairs=None):
    """Operations of one call of ``kernel``; ``operand_shapes`` are the
    dimensions of its operands in order (the seed first, a block map's
    items next where the call has one, then ``q`` and ``k`` as the first
    two of rank 4: ``(B, H, Lq, D)``, ``(B, H, Lk, D)``).  ``mapped_pairs``:
    the (query, key) pairs a head of a mapped call scores, as the program
    states them; None where it states none, and then a mapped call gives
    None."""
    q, k = [s for s in operand_shapes if len(s) == 4][:2]
    B, H, Lq, D = q
    pairs = B * Lq * k[2]
    if map_items(operand_shapes) is not None:
        if not mapped_pairs or mapped_pairs > pairs:
            return None
        pairs = mapped_pairs
    return 2.0 * H * pairs * D * len(PRODUCTS[kernel])
