"""Rotary position embedding (Su et al., RoFormer, arXiv:2104.09864), the
rotate-half pairing most decoders use: channel ``i`` of the first half of a
head is paired with channel ``i`` of the second half, and the pair is
turned by the angle ``position * theta ** (-2 i / D)``."""

import jax
import jax.numpy as jnp


def apply_rotary(x, positions, theta):
    """``x`` (..., L, D) with ``D`` even, ``positions`` (L,) (or anything
    that broadcasts against ``x``'s leading axes, ending in ``L``).  The
    angles, sines and the rotation itself are float32; the result is
    rounded to ``x``'s dtype."""
    with jax.named_scope("rotary"):
        half = x.shape[-1] // 2
        inv_freq = theta ** (
            -jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1]
        )
        angle = positions.astype(jnp.float32)[..., None] * inv_freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )
        return out.astype(x.dtype)
