"""Matmul operations of one call of each named attention kernel, from the
shapes of its operands as the trace event's own HLO line gives them.

Every product a kernel performs is one ``(Lq x D) x (D x Lk)`` matmul (or a
transpose of it) per batch row and head: ``2 * B * H * Lq * Lk * D``
operations.  Which products each kernel performs is read from its body
(``unicore_tpu/ops/flash_attention.py``, ``attention_fullrow.py``); the
backward kernels compute the scores again, and that work is counted: a
kernel's roofline share is about the kernel as written, unlike
``train_mfu_pct``, which counts only what the algorithm needs.  No block
is skipped for padding, so every call does all of them.
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


def matmul_flops(kernel, operand_shapes):
    """Operations of one call of ``kernel``; ``operand_shapes`` are the
    dimensions of its operands in order (the seed first, then ``q`` and
    ``k`` as the first two of rank 4: ``(B, H, Lq, D)``, ``(B, H, Lk, D)``)."""
    q, k = [s for s in operand_shapes if len(s) == 4][:2]
    B, H, Lq, D = q
    return 2.0 * B * H * Lq * k[2] * D * len(PRODUCTS[kernel])
