"""Rotary position embedding (Su et al., RoFormer), rotate-half pairing:
dimension ``i`` of a head turns with dimension ``i + head_dim / 2`` by
the angle ``pos * theta ** (-2 i / head_dim)``. Nothing is stored: the
angles are computed from the positions asked for, in float32.
"""

from __future__ import annotations

import jax.numpy as jnp


def rotary_angles(positions, head_dim, theta=10000.0):
    """``(cos, sin)`` of shape ``positions.shape + (head_dim,)``."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / head_dim)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """Turn ``x`` (..., head_dim) by the angles; ``cos``/``sin`` broadcast
    against it. float32 in, float32 out."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin
