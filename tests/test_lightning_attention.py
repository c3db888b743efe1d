"""``ops.lightning_attention``: the chunked prefill and the one-token step
against the per-token recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from machine_learning_apache_spark_tpu.ops.lightning_attention import (
    decay_slopes,
    lightning_attention,
    lightning_attention_recurrent,
    lightning_attention_step,
)

B, T, H, D = 2, 40, 4, 8


def _inputs(dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)).astype(dtype) for kk in keys[:3])
    state = jax.random.normal(keys[3], (B, H, D, D))
    return q, k, v, state


def test_slopes_follow_the_convention():
    s = np.asarray(decay_slopes(32))
    assert s[0] == pytest.approx(2 ** -0.25) and s[-1] == pytest.approx(2 ** -8)
    assert (np.diff(s) < 0).all()


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("with_state", [False, True])
def test_chunked_equals_the_recurrence(chunk, with_state):
    q, k, v, state = _inputs()
    s = decay_slopes(H)
    initial = state if with_state else None
    o_ref, s_ref = lightning_attention_recurrent(q, k, v, s, initial_state=initial)
    o, s_new = lightning_attention(q, k, v, s, initial_state=initial, chunk=chunk)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(s_new, s_ref, atol=2e-5)


def test_positions_past_n_valid_neither_enter_nor_decay_the_state():
    q, k, v, state = _inputs()
    s = decay_slopes(H)
    n_valid = jnp.array([23, T])
    o, s_new = lightning_attention(
        q, k, v, s, initial_state=state, n_valid=n_valid, chunk=16
    )
    o_ref, s_ref = lightning_attention_recurrent(
        q[:1, :23], k[:1, :23], v[:1, :23], s, initial_state=state[:1]
    )
    np.testing.assert_allclose(o[0, :23], o_ref[0], atol=2e-5)
    np.testing.assert_allclose(s_new[0], s_ref[0], atol=2e-5)
    _, s_full = lightning_attention_recurrent(q, k, v, s, initial_state=state)
    np.testing.assert_allclose(s_new[1], s_full[1], atol=2e-5)


def test_chunk_after_chunk_carries_the_state():
    """Two calls, the second starting from the first's state, equal one."""
    q, k, v, state = _inputs()
    s = decay_slopes(H)
    o_ref, s_ref = lightning_attention_recurrent(q, k, v, s, initial_state=state)
    o1, s1 = lightning_attention(q[:, :16], k[:, :16], v[:, :16], s,
                                 initial_state=state, chunk=16)
    o2, s2 = lightning_attention(q[:, 16:], k[:, 16:], v[:, 16:], s,
                                 initial_state=s1, chunk=16)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o_ref, atol=2e-5)
    np.testing.assert_allclose(s2, s_ref, atol=2e-5)


def test_the_step_is_one_position_of_the_recurrence():
    q, k, v, state = _inputs()
    s = decay_slopes(H)
    o_ref, _ = lightning_attention_recurrent(q, k, v, s, initial_state=state)
    cur = state
    for t in range(3):
        o, cur = lightning_attention_step(q[:, t], k[:, t], v[:, t], s, cur)
        np.testing.assert_allclose(o, o_ref[:, t], atol=2e-5)


def test_bfloat16_inputs_keep_a_float32_state():
    q, k, v, state = _inputs(jnp.bfloat16)
    s = decay_slopes(H)
    o, s_new = lightning_attention(q, k, v, s, initial_state=state, chunk=16)
    assert o.dtype == jnp.float32 and s_new.dtype == jnp.float32
    o_ref, s_ref = lightning_attention_recurrent(q, k, v, s, initial_state=state)
    np.testing.assert_allclose(o, o_ref, atol=0.15)
    np.testing.assert_allclose(s_new, s_ref, atol=1e-4)
