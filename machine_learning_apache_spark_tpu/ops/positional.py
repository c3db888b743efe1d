"""Sinusoidal positional encoding.

The reference recomputes the full PE table on **every forward call** and
device-transfers it each time (``transformer.py:33-42``, ``:60`` — quirk noted
at SURVEY.md C15). Here the table is computed once per (length, dim) at trace
time and baked into the compiled program as a constant — zero per-step cost
under jit.
"""

from __future__ import annotations

import functools

import numpy as np
import jax.numpy as jnp


@functools.lru_cache(maxsize=32)
def _table(length: int, dim: int) -> np.ndarray:
    # Same formula as transformer.py:33-42: even channels sin, odd cos, with
    # the 10000^(2i/d) frequency schedule.
    position = np.arange(length, dtype=np.float32)[:, None]
    div = np.exp(np.arange(0, dim, 2, dtype=np.float32) * (-np.log(10000.0) / dim))
    table = np.zeros((length, dim), dtype=np.float32)
    table[:, 0::2] = np.sin(position * div)
    table[:, 1::2] = np.cos(position * div[: dim // 2])
    return table


def sinusoidal_encoding(length: int, dim: int, dtype=jnp.float32) -> jnp.ndarray:
    """``[length, dim]`` sinusoidal table (``PositionalEncoding``,
    ``transformer.py:27-42``), cached host-side and constant-folded by XLA."""
    return jnp.asarray(_table(length, dim), dtype=dtype)


@functools.lru_cache(maxsize=32)
def _rotary_table(length: int, rotary_dim: int, theta: float) -> np.ndarray:
    inv_freq = theta ** (-np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim)
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return np.stack([np.cos(angles), np.sin(angles)]).astype(np.float32)


def rotary_embedding(
    x: jnp.ndarray, *, rotary_dim: int | None = None, theta: float = 10000.0
) -> jnp.ndarray:
    """Rotary positions (Su et al., arXiv:2104.09864) on the first
    ``rotary_dim`` channels of every head of ``x [B, H, S, d]``; the rest
    pass through (a partial rotary factor of ``rotary_dim / d``). Channel
    ``i < rotary_dim / 2`` pairs with ``i + rotary_dim / 2`` (the
    "rotate-half" layout) and turns by ``position * theta^(-2i/rotary_dim)``.
    Positions are ``0..S-1``; the angles are a trace-time constant, and the
    rotation is done in float32."""
    d = x.shape[-1]
    rotary_dim = d if rotary_dim is None else rotary_dim
    if rotary_dim % 2 or not 0 < rotary_dim <= d:
        raise ValueError(f"rotary_dim {rotary_dim} must be even and <= {d}")
    cos, sin = _rotary_table(x.shape[-2], rotary_dim, float(theta))
    half = rotary_dim // 2
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:rotary_dim].astype(jnp.float32)
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rotary_dim:]], axis=-1)


def rotary_embedding_at(
    x: jnp.ndarray, positions: jnp.ndarray, *, theta: float = 10000.0
) -> jnp.ndarray:
    """Rotary positions on every channel of ``x [..., d]`` at the given
    ``positions`` (broadcastable to ``x.shape[:-1]``): what a served model
    needs, whose chunk or decode step starts anywhere in the sequence. Same
    rotate-half layout as ``rotary_embedding``; the angles are worked out in
    float32 where they are used."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = jnp.asarray(
        theta ** (-np.arange(0, d, 2, dtype=np.float64) / d), jnp.float32
    )
    angles = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1
    ).astype(x.dtype)
