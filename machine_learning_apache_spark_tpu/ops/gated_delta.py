"""Gated delta rule — the linear-attention recurrence of Gated DeltaNet
(Yang, Kautz & Hatamizadeh, arXiv:2412.06464), in chunks.

A value head keeps a state ``S [dk, dv]``. At position ``t``, with key
``k_t``, query ``q_t`` (both already L2-normalised, the query scaled), value
``v_t``, log-decay ``g_t <= 0`` and write strength ``beta_t`` in (0, 1):

    S   <- exp(g_t) S
    u_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

``gated_delta_recurrent`` is that loop, one position a ``lax.scan`` step: the
oracle of the tests and the bring-up smoke, and what a decode step would run.

``gated_delta_rule`` is the training path. Positions are taken ``chunk`` at a
time. Inside a chunk, with ``G_i`` the running sum of ``g`` and
``A[i, j] = beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i``, the writes
``u`` solve the unit lower-triangular system ``(I + A) u = beta (v - exp(G)
k S0)`` (the WY representation of the product of the chunk's Householder-like
factors). With ``T = (I + A)^-1`` applied by forward substitution, in float32,

    u_i   = T (beta v) - T (beta exp(G) k) S0        # ``value`` - ``w`` S0
    o_i   = exp(G_i) q_i S0 + tril(q k^T exp(G_i - G_j)) u
    S_end = exp(G_C) S0 + (exp(G_C - G_i) k_i)^T u

Everything but the state hand-off is batched over all chunks; one
``lax.scan`` over the chunks carries the float32 state, and a second batched
pass forms the outputs from each chunk's starting state. The backward pass
is the transpose of the same chunked program (JAX autodiff through the scan
and the triangular solve): no per-token loop in either direction.

Products take operands in the inputs' dtype and accumulate in float32;
decays, the solve and the state are float32 throughout. With float32 inputs every
product runs at ``Precision.HIGHEST`` (a float32 product on a TPU is
otherwise bfloat16 passes), which is what the tests and ``chip_smoke.py``
compare against the recurrence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu import telemetry

DEFAULT_CHUNK = 64


def record_dispatch(site: str, impl: str, reason: str, **shape) -> None:
    """Trace-time breadcrumb, as ``ops.attention.record_dispatch``: one
    ``ops.gated_delta_dispatch`` annotation per site per traced program."""
    telemetry.annotate(
        "ops.gated_delta_dispatch", site=site, impl=impl, reason=reason, **shape
    )


def _expand_heads(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """``[B, T, Hk, d] -> [B, T, heads, d]``: a key head serves
    ``heads // Hk`` consecutive value heads."""
    if x.shape[2] == heads:
        return x
    if heads % x.shape[2]:
        raise ValueError(
            f"{heads} value heads are not a multiple of {x.shape[2]} key heads"
        )
    return jnp.repeat(x, heads // x.shape[2], axis=2)


def gated_delta_recurrent(q, k, v, g, beta, *, initial_state=None):
    """The recurrence, one position a step, in float32 at full precision.

    ``q, k [B, T, Hk, dk]``, ``v [B, T, Hv, dv]``, ``g, beta [B, T, Hv]``.
    Returns ``(o [B, T, Hv, dv] float32, final state [B, Hv, dk, dv])``.
    """
    heads = v.shape[2]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    q, k = f32(_expand_heads(q, heads)), f32(_expand_heads(k, heads))
    v, g, beta = f32(v), f32(g), f32(beta)
    hi = jax.lax.Precision.HIGHEST
    state = (
        jnp.zeros((v.shape[0], heads, q.shape[-1], v.shape[-1]), jnp.float32)
        if initial_state is None else f32(initial_state)
    )

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, d] / [B, H]
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=hi)
        )
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=hi)

    time_major = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    state, out = jax.lax.scan(
        step, state, tuple(map(time_major, (q, k, v, g, beta)))
    )
    return jnp.moveaxis(out, 0, 1), state


def _solve_unit_lower(a: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """``(I + a)^-1 rhs`` for strictly lower-triangular ``a [..., C, C]`` by
    forward substitution (XLA's triangular solve), in float32. Substitution
    is backward-stable whatever the keys; the product form ``(I - a)(I +
    a^2)(I + a^4)...`` is not: with keys as alike as a positive activation
    leaves them (mean cosine 0.5 and up) its powers of ``a`` reach 1e5 and
    cancel, and read 1e6 times the true gradient on the chip."""
    return jax.lax.linalg.triangular_solve(
        a + jnp.eye(a.shape[-1], dtype=a.dtype), rhs,
        left_side=True, lower=True, unit_diagonal=True,
    )


def gated_delta_rule(
    q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK, initial_state=None,
    site: str = "gated_delta",
):
    """The chunked form (module docstring); same operands as
    ``gated_delta_recurrent``. Returns ``(o [B, T, Hv, dv] in v's dtype,
    final state [B, Hv, dk, dv] float32)``. ``T`` need not be a multiple of
    ``chunk``: the tail is padded with positions that write nothing."""
    b, t, heads, dv = v.shape
    dk = q.shape[-1]
    dtype = v.dtype
    precision = (
        jax.lax.Precision.HIGHEST if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    n = -(-t // chunk)
    record_dispatch(
        site, "chunked_scan",
        f"lax.scan over {n} chunks of {chunk}, WY form inside a chunk",
        batch=b, length=t, heads=heads, dk=dk, dv=dv, dtype=str(dtype),
    )
    pad = n * chunk - t

    def chunks(x):
        """``[B, T, H, ...] -> [B, H, n, chunk, ...]``."""
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q = chunks(_expand_heads(q, heads).astype(dtype))
    k = chunks(_expand_heads(k, heads).astype(dtype))
    v = chunks(v)
    g = chunks(g.astype(jnp.float32))        # [B, H, n, C]
    beta = chunks(beta.astype(jnp.float32))  # [B, H, n, C]

    def dot(spec, x, y):
        return jnp.einsum(
            spec, x, y, precision=precision,
            preferred_element_type=jnp.float32,
        )

    big_g = jnp.cumsum(g, axis=-1)                      # G_i within a chunk
    # exp(G_i - G_j) for i >= j; the difference is <= 0 there, and masked
    # before the exponential elsewhere (G_i - G_j > 0 could overflow).
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    diff = big_g[..., :, None] - big_g[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)

    kk = dot("bhnid,bhnjd->bhnij", k, k)
    a = jnp.where(strict, kk * decay * beta[..., :, None], 0.0)
    # One solve for both right-hand sides: T (beta v) and T (beta e^G k).
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    rhs = jnp.concatenate(
        [f32(v), f32(k) * jnp.exp(big_g)[..., None]], axis=-1
    ) * beta[..., None]
    solved = _solve_unit_lower(a, rhs).astype(dtype)
    value, w = solved[..., :dv], solved[..., dv:]
    g_end = big_g[..., -1]                                          # [B,H,n]
    k_tail = (k * jnp.exp(g_end[..., None] - big_g)[..., None]).astype(dtype)

    state0 = (
        jnp.zeros((b, heads, dk, dv), jnp.float32)
        if initial_state is None else initial_state.astype(jnp.float32)
    )

    def hand_off(s, xs):
        w_i, value_i, k_tail_i, g_end_i = xs
        u = value_i - dot("bhik,bhkv->bhiv", w_i, s.astype(dtype))
        u = u.astype(dtype)
        s_next = s * jnp.exp(g_end_i)[..., None, None] + dot(
            "bhik,bhiv->bhkv", k_tail_i, u
        )
        return s_next, (s.astype(dtype), u)

    chunk_major = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    final, (starts, u) = jax.lax.scan(
        hand_off, state0, tuple(map(chunk_major, (w, value, k_tail, g_end)))
    )
    starts = jnp.moveaxis(starts, 0, 2)  # [B, H, n, dk, dv]
    u = jnp.moveaxis(u, 0, 2)            # [B, H, n, C, dv]

    q_decayed = (q * jnp.exp(big_g)[..., None]).astype(dtype)
    qk = dot("bhnid,bhnjd->bhnij", q, k) * decay          # i >= j kept
    out = dot("bhnid,bhndv->bhniv", q_decayed, starts) + dot(
        "bhnij,bhnjv->bhniv", qk.astype(dtype), u
    )
    out = jnp.moveaxis(out, 1, 3).reshape(b, n * chunk, heads, dv)
    return out[:, :t].astype(dtype), final
