"""Chunked state-space scan: Mamba-2's SSD (Dao & Gu 2024, "Transformers
are SSMs", arXiv:2405.21060, listing 1) as XLA's own products.

Per head ``h`` of head dim ``P`` with its group's ``B_t, C_t`` of state
size ``N``::

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T        (P, N)
    y_t = h_t C_t + D * x_t

:func:`ssd_scan` computes it in chunks of ``chunk`` tokens: inside a chunk
by matrix products against the chunk's lower-triangular decay matrix, and
between chunks by the same recurrence over one ``(P, N)`` state per chunk
(``L / chunk`` steps of a ``lax.scan``, not ``L``).  Autodiff gives the
backward: the same products transposed, and the chunk recurrence run from
the last chunk to the first.  :func:`ssd_recurrence` is the recurrence
token by token, the form the tests hold the chunked one to.

Decays and their cumulative sums are float32 whatever the inputs are; the
products take their operands in ``x``'s dtype and accumulate in float32.
"""

import jax
import jax.numpy as jnp


def _heads_to_groups(h, g):
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    return h // g


def ssd_scan(x, dt, A, B, C, D=None, chunk=128):
    """``x`` (b, L, H, P); ``dt`` (b, L, H), already positive (softplus
    applied); ``A`` (H,), negative; ``B``, ``C`` (b, L, G, N) with
    ``H % G == 0`` (head ``h`` reads group ``h // (H / G)``); ``D`` (H,)
    or None.  Returns ``y`` (b, L, H, P) in ``x``'s dtype.  ``L`` need not
    be a multiple of ``chunk``: the tail is padded with ``dt = 0`` tokens
    (no decay, no input) and cut off again."""
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R = _heads_to_groups(H, G)
    Q = int(chunk)
    pad = (-L) % Q
    if pad:
        widths = lambda a: ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
        x, dt, B, C = (jnp.pad(a, widths(a)) for a in (x, dt, B, C))
    nc = (L + pad) // Q
    dtype = x.dtype
    f32 = jnp.float32

    with jax.named_scope("ssd_scan"):
        # (b, nc, Q, ...) chunks; heads as (G, R) so a group's B, C are
        # shared by its R heads inside the products
        xc = x.reshape(b, nc, Q, G, R, P)
        dtc = dt.astype(f32).reshape(b, nc, Q, G, R)
        Bc = B.reshape(b, nc, Q, G, N)
        Cc = C.reshape(b, nc, Q, G, N)
        dA = dtc * A.astype(f32).reshape(G, R)          # <= 0
        cs = jnp.cumsum(dA, axis=2)                     # (b, nc, Q, G, R)
        xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)

        # -- inside a chunk: y_i += sum_{j<=i} exp(cs_i - cs_j) (C_i.B_j) dt_j x_j
        # (the (Q, Q) axes last: they are the tiles the chip works in)
        scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                            preferred_element_type=f32)
        cs_h = jnp.moveaxis(cs, 2, -1)                  # (b, nc, G, R, Q)
        seg = cs_h[..., :, None] - cs_h[..., None, :]   # (b, nc, G, R, i, j)
        tril = jnp.tril(jnp.ones((Q, Q), bool))
        decay = jnp.where(tril, jnp.exp(jnp.where(tril, seg, 0.0)), 0.0)
        weights = (decay * scores[:, :, :, None]).astype(dtype)
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp", weights, xdt,
                       preferred_element_type=f32)

        # -- each chunk's own contribution to the state at its end
        to_end = jnp.exp(cs[:, :, -1:] - cs)            # (b, nc, Q, G, R)
        states = jnp.einsum(
            "bcjgn,bcjgrp->bcgrpn", Bc,
            (xdt.astype(f32) * to_end[..., None]).astype(dtype),
            preferred_element_type=f32,
        )                                               # (b, nc, G, R, P, N)

        # -- between chunks: the recurrence over chunk states
        chunk_decay = jnp.exp(cs[:, :, -1])             # (b, nc, G, R)

        def step(h, inp):
            a, s = inp
            return a[..., None, None] * h + s, h        # emits the state BEFORE the chunk

        _, before = jax.lax.scan(
            step, jnp.zeros((b, G, R, P, N), f32),
            (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)),
        )
        before = jnp.moveaxis(before, 0, 1)             # (b, nc, G, R, P, N)

        # -- what the state carried into the chunk gives each of its tokens
        y = y + jnp.einsum(
            "bcign,bcgrpn->bcigrp", Cc, before.astype(dtype),
            preferred_element_type=f32,
        ) * jnp.exp(cs)[..., None]

        y = y.reshape(b, nc * Q, H, P)
        if D is not None:
            y = y + x.astype(f32) * D.astype(f32)[:, None]
        return y[:, :L].astype(dtype)


def ssd_recurrence(x, dt, A, B, C, D=None):
    """The same function token by token in float32 (``L`` steps of a
    ``lax.scan``): what :func:`ssd_scan` is tested against.  Not for a
    timed path."""
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R = _heads_to_groups(H, G)
    f32 = jnp.float32
    xs = x.astype(f32).reshape(b, L, G, R, P)
    dts = dt.astype(f32).reshape(b, L, G, R)
    a = jnp.exp(dts * A.astype(f32).reshape(G, R))

    def step(h, inp):
        a_t, dt_t, x_t, B_t, C_t = inp
        h = a_t[..., None, None] * h + jnp.einsum(
            "bgrp,bgn->bgrpn", dt_t[..., None] * x_t, B_t
        )
        return h, jnp.einsum("bgrpn,bgn->bgrp", h, C_t)

    t_first = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(
        step, jnp.zeros((b, G, R, P, N), f32),
        (t_first(a), t_first(dts), t_first(xs), t_first(B.astype(f32)),
         t_first(C.astype(f32))),
    )
    y = jnp.moveaxis(y, 0, 1).reshape(b, L, H, P)
    if D is not None:
        y = y + x.astype(f32) * D.astype(f32)[:, None]
    return y.astype(x.dtype)
