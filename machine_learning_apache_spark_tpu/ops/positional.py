"""Positional encodings: sinusoidal tables, rotary positions, and YaRN's
stretched rotary frequencies.

The reference recomputes the full PE table on **every forward call** and
device-transfers it each time (``transformer.py:33-42``, ``:60`` — quirk noted
at SURVEY.md C15). Here the table is computed once per (length, dim) at trace
time and baked into the compiled program as a constant — zero per-step cost
under jit.
"""

from __future__ import annotations

import functools
import math

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


def yarn_inv_freq(dim: int, *, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies (Peng et al., arXiv:2309.00071, as DeepSeek-V3
    applies them) for ``dim`` rotated channels: a pair ``i`` turns at
    ``f_i = theta^(-2i/dim)`` where it completes more than ``beta_fast``
    turns over the ``original`` context, at ``f_i / factor`` where it
    completes fewer than ``beta_slow``, and in between at the mix given by a
    linear ramp over the pair's index between the two correction dims.
    Returns ``[dim / 2]`` float64."""
    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``m = 0.1 mscale ln(factor) + 1``
    (1 where the context is not stretched); DeepSeek-V3 multiplies the
    softmax scale by ``m^2``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embedding_at(
    x: jnp.ndarray, positions: jnp.ndarray, *, theta: float = 10000.0,
    inv_freq: np.ndarray | None = None,
) -> jnp.ndarray:
    """Rotary positions on every channel of ``x [..., d]`` at the given
    ``positions`` (broadcastable to ``x.shape[:-1]``): what a served model
    needs, whose chunk or decode step starts anywhere in the sequence. Same
    rotate-half layout as ``rotary_embedding``; the angles are worked out in
    float32 where they are used. ``inv_freq [d / 2]`` (``yarn_inv_freq``)
    replaces the plain ``theta^(-2i/d)``."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = jnp.asarray(
        theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        if inv_freq is None else inv_freq,
        jnp.float32,
    )
    angles = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1
    ).astype(x.dtype)
