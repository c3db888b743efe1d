"""Lightning attention — linear attention with a fixed decay a head
(Qin et al., "Lightning Attention-2", arXiv:2401.04658), for a served model:
a chunked prefill that takes and returns the state, and a one-token step.

A head keeps a float32 state ``S [dk, dv]``. At position ``t``, with query
``q_t``, key ``k_t``, value ``v_t`` and the head's decay ``lam = exp(-s)``:

    S_t = lam S_{t-1} + k_t^T v_t          o_t = (q_t / sqrt(dk)) S_t

``lightning_attention_recurrent`` is that loop, one position a ``lax.scan``
step: the oracle of the tests.

``lightning_attention`` takes the positions ``chunk`` at a time. Inside a
chunk, with ``i`` the position in it and ``S0`` the state it starts from,

    o_i   = lam^(i+1) (q_i / sqrt(dk)) S0 + sum_{j<=i} lam^(i-j) (q_i . k_j) v_j / sqrt(dk)
    S_end = lam^n S0 + sum_{j<n} lam^(n-1-j) k_j^T v_j

where ``n`` is the number of the chunk's positions that are real
(``n_valid``: a prompt's last chunk is padded, and what lies past the prompt
neither enters the state nor decays it). The in-chunk products take operands
in the inputs' dtype and accumulate in float32; everything that touches the
state is float32 at ``Precision.HIGHEST``.

``lightning_attention_step`` is one position for every row of a decode batch.

Which path a site took is its ``ops.lightning_dispatch`` record. There is one
path today (``xla_chunked`` / ``xla_step``); a Pallas kernel would be chosen
here by shape and backend, as ``ops.gated_delta`` chooses its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu import telemetry

DEFAULT_CHUNK = 256
_HIGHEST = jax.lax.Precision.HIGHEST


def record_dispatch(site: str, impl: str, reason: str, **shape) -> None:
    """Trace-time breadcrumb, as ``ops.attention.record_dispatch``."""
    telemetry.annotate(
        "ops.lightning_dispatch", site=site, impl=impl, reason=reason, **shape
    )


def decay_slopes(heads: int) -> jnp.ndarray:
    """Lightning Attention's convention: ``s_h = 2^(-8 h / H)``, h = 1..H;
    a head's decay a position is ``exp(-s_h)``."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / heads)


def lightning_attention_recurrent(q, k, v, slopes, *, initial_state=None):
    """The recurrence, a position a step. ``q, k, v [B, T, H, d]``; returns
    ``(o [B, T, H, dv] float32, final state [B, H, dk, dv] float32)``."""
    b, _, h, dk = q.shape
    dv = v.shape[-1]
    lam = jnp.exp(-slopes.astype(jnp.float32))[None, :, None, None]
    state = (
        jnp.zeros((b, h, dk, dv), jnp.float32)
        if initial_state is None else initial_state.astype(jnp.float32)
    )
    f32 = lambda x: jnp.swapaxes(x.astype(jnp.float32), 0, 1)  # noqa: E731

    def step(s, xs):
        qt, kt, vt = xs
        s = lam * s + jnp.einsum("bhd,bhe->bhde", kt, vt, precision=_HIGHEST)
        o = jnp.einsum("bhd,bhde->bhe", qt * dk ** -0.5, s, precision=_HIGHEST)
        return s, o

    state, o = jax.lax.scan(step, state, (f32(q), f32(k), f32(v)))
    return jnp.swapaxes(o, 0, 1), state


def lightning_attention(
    q, k, v, slopes, *, initial_state=None, n_valid=None,
    chunk: int = DEFAULT_CHUNK, site: str = "lightning",
):
    """Chunked lightning attention. ``q, k, v [B, T, H, d]`` in one dtype,
    ``slopes [H]``, ``initial_state [B, H, dk, dv]`` (zeros when None),
    ``n_valid [B]`` the real positions of each row (all of them when None).
    Returns ``(o [B, T, H, dv] float32, final state float32)``; outputs at
    positions past ``n_valid`` mean nothing."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    pad = -t % chunk
    record_dispatch(
        site, "xla_chunked", "the one path", batch=b, length=t, heads=h,
        head_dim=dk, chunk=chunk, dtype=str(q.dtype),
    )
    if pad:
        q, k, v = (jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)]) for x in (q, k, v))
    n = (t + pad) // chunk
    n_valid = (
        jnp.full((b,), t, jnp.int32) if n_valid is None
        else jnp.asarray(n_valid, jnp.int32)
    )
    state = (
        jnp.zeros((b, h, dk, dv), jnp.float32)
        if initial_state is None else initial_state.astype(jnp.float32)
    )
    # [n, B, H, C, d]
    chunks = lambda x: x.reshape(b, n, chunk, h, -1).transpose(1, 0, 3, 2, 4)  # noqa: E731
    s = slopes.astype(jnp.float32)
    pos = jnp.arange(chunk)
    diff = pos[:, None] - pos[None, :]
    within = jnp.where(
        diff >= 0, jnp.exp(-s[:, None, None] * jnp.maximum(diff, 0)), 0.0
    )  # [H, C, C]: lam^(i-j) on and under the diagonal
    from_start = jnp.exp(-s[:, None] * (pos + 1))[None, :, :, None]  # lam^(i+1)
    scale = dk ** -0.5

    def one(state, xs):
        qc, kc, vc, start = xs
        real = jnp.clip(n_valid - start, 0, chunk)  # [B]
        keep = pos[None, :] < real[:, None]  # [B, C]
        kc = jnp.where(keep[:, None, :, None], kc, 0)
        scores = jnp.einsum(
            "bhid,bhjd->bhij", qc, kc, preferred_element_type=jnp.float32
        ) * (within * scale)
        intra = jnp.einsum(
            "bhij,bhje->bhie", scores.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32,
        )
        inter = jnp.einsum(
            "bhid,bhde->bhie", qc.astype(jnp.float32) * (from_start * scale),
            state, precision=_HIGHEST,
        )
        # lam^(real-1-j) for j < real; nought past the prompt
        to_end = jnp.where(
            keep[:, None, :],
            jnp.exp(-s[None, :, None] * jnp.maximum(
                real[:, None, None] - 1 - pos[None, None, :], 0
            )),
            0.0,
        )
        carried = jnp.exp(-s[None, :] * real[:, None])[..., None, None] * state
        state = carried + jnp.einsum(
            "bhjd,bhje->bhde", kc.astype(jnp.float32) * to_end[..., None],
            vc.astype(jnp.float32), precision=_HIGHEST,
        )
        return state, intra + inter

    state, o = jax.lax.scan(
        one, state,
        (chunks(q), chunks(k), chunks(v), jnp.arange(n, dtype=jnp.int32) * chunk),
    )
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, dv)
    return o[:, :t], state


def lightning_attention_step(q, k, v, slopes, state, *, site: str = "lightning_step"):
    """One position for every row. ``q, k, v [R, H, d]``, ``state [R, H, dk,
    dv]`` float32. Returns ``(o [R, H, dv] float32, new state)``."""
    record_dispatch(
        site, "xla_step", "the one path", rows=q.shape[0], heads=q.shape[1],
        head_dim=q.shape[2], dtype=str(q.dtype),
    )
    lam = jnp.exp(-slopes.astype(jnp.float32))[None, :, None, None]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    state = lam * state + kf[..., :, None] * vf[..., None, :]
    o = jnp.einsum(
        "rhd,rhde->rhe", q.astype(jnp.float32) * q.shape[-1] ** -0.5, state,
        precision=_HIGHEST,
    )
    return o, state
