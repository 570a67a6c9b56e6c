"""Rotary position embedding (Su et al., RoFormer, arXiv:2104.09864), the
rotate-half pairing most decoders use: channel ``i`` of the first half of a
head is paired with channel ``i`` of the second half, and the pair is
turned by the angle ``position * theta ** (-2 i / D)``.

A model that scales its frequencies states them as a table
(:func:`rope_table`, from a published ``rope_parameters`` group): the
pairs' frequencies and the factor ``c`` that scales both cosine and sine,
and with them ``q`` and ``k`` (YaRN's attention factor; Peng et al.,
arXiv:2309.00071).

A model may rotate part of a head only (``partial_rotary_factor``): the
first ``rotary_dim`` channels are paired and turned as a head of that size
would be (channel ``i`` with ``i + rotary_dim / 2``, the table made for
``rotary_dim`` in the head size's place), and the channels after them pass
through, neither turned nor scaled."""

import math

import jax
import jax.numpy as jnp
import numpy as np


def rope_table(rope_parameters, head_dim):
    """``(inv_freq, c)`` of one ``rope_parameters`` group: the ``head_dim /
    2`` pair frequencies (float32, made on the host in float64) and the
    factor on cosine and sine.  A group that states a
    ``partial_rotary_factor`` rotates the head's first ``rotary_dim =
    head_dim x factor`` channels: everything below is then of
    ``rotary_dim`` in ``head_dim``'s place (as Hugging Face reads it), and
    the table holds ``rotary_dim / 2`` pairs.

    ``rope_type`` ``default``: ``inv_freq_i = theta ^ (-2 i / D)``, ``c`` 1.
    ``yarn`` (as Hugging Face's ``_compute_yarn_parameters`` with
    ``truncate``): with ``e_i`` the default frequencies and ``n_i = e_i /
    factor``, ``dim(r) = D ln(original / (2 pi r)) / (2 ln theta)`` the pair
    that turns ``r`` times over the original context, ``low =
    floor(dim(beta_fast))`` and ``high = ceil(dim(beta_slow))`` clipped to
    the pairs there are, and ``ramp_i = clip((i - low) / (high - low), 0,
    1)``: ``inv_freq_i = n_i ramp_i + e_i (1 - ramp_i)`` (pairs below
    ``low`` untouched, above ``high`` divided by ``factor``), ``c`` the
    stated ``attention_factor``, else ``0.1 ln(factor) + 1``."""
    rp = dict(rope_parameters)
    kind = rp.get("rope_type", "default")
    theta = float(rp["rope_theta"])
    head_dim = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    half = head_dim // 2
    i = np.arange(half, dtype=np.float64)
    plain = theta ** (-2.0 * i / head_dim)
    if kind == "default":
        return plain.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(
            f"rope_type {kind!r} is not one of 'default', 'yarn'")
    factor = float(rp["factor"])
    original = float(rp["original_max_position_embeddings"])

    def dim(turns):
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(float(rp.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim(float(rp.get("beta_slow", 1)))), head_dim - 1)
    if low == high:
        high += 0.001  # no division by zero (as the public code has it)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    inv_freq = plain / factor * ramp + plain * (1.0 - ramp)
    c = rp.get("attention_factor")
    if c is None:
        c = 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(c)


def apply_rotary(x, positions, theta=None, table=None):
    """``x`` (..., L, D) with ``D`` even, ``positions`` (L,) (or anything
    that broadcasts against ``x``'s leading axes, ending in ``L``).  The
    frequencies are ``theta``'s, or a ``table``'s (:func:`rope_table`:
    ``(inv_freq, c)``, cosine and sine both scaled by ``c``).  A table of
    fewer than ``D / 2`` pairs rotates the first ``2 len(inv_freq)``
    channels and passes the rest through.  The angles, sines and the
    rotation itself are float32; the result is rounded to ``x``'s dtype."""
    with jax.named_scope("rotary"):
        turned = x.shape[-1] if table is None else 2 * len(table[0])
        passed = None
        if turned < x.shape[-1]:
            x, passed = x[..., :turned], x[..., turned:]
        half = turned // 2
        if table is None:
            inv_freq = theta ** (
                -jnp.arange(half, dtype=jnp.float32) * 2.0 / turned
            )
        else:
            inv_freq = jnp.asarray(table[0], jnp.float32)
        angle = positions.astype(jnp.float32)[..., None] * inv_freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        if table is not None and table[1] != 1.0:
            cos, sin = cos * table[1], sin * table[1]
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)
        if passed is not None:
            out = jnp.concatenate([out, passed], axis=-1)
        return out
