"""Rotary position embeddings."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rotary_dims: Optional[int] = None) -> jax.Array:
    """Rotate ``x`` of shape (..., seq, heads, head_dim) by ``positions``
    of shape (..., seq).

    ``rotary_dims`` < head_dim rotates only the leading ``rotary_dims``
    of each head (partial rotary, as StableLM 2), with the frequencies and
    the rotate-half pairing of a head that size; the rest of the head
    passes through unchanged."""
    hd = x.shape[-1]
    if rotary_dims is not None and rotary_dims < hd:
        rot = apply_rope(x[..., :rotary_dims], positions, theta)
        return jnp.concatenate([rot, x[..., rotary_dims:]], axis=-1)
    dtype = x.dtype
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., s, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]              # (..., s, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)
