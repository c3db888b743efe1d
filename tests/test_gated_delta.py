"""The chunked gated delta rule against its per-token recurrence."""

import jax
import jax.numpy as jnp
import pytest

from machine_learning_apache_spark_tpu.ops.gated_delta import (
    gated_delta_recurrent,
    gated_delta_rule,
)


def _operands(seed, *, length, key_heads, value_heads, decay, b=2, dk=16, dv=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, length, key_heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, length, key_heads, dk)))
    v = jax.random.normal(ks[2], (b, length, value_heads, dv))
    g = -decay * jax.random.uniform(ks[3], (b, length, value_heads), minval=0.2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, value_heads)))
    return q, k, v, g, beta


# decay 1e-3: exp(g) near 1 (the state is kept); 8.0: near 0 (forgotten at once)
@pytest.mark.parametrize("decay,length,chunk,key_heads,value_heads", [
    (1e-3, 50, 16, 2, 4), (0.1, 50, 16, 3, 3), (8.0, 50, 16, 2, 4),
    (1e-3, 100, 64, 2, 4), (0.1, 64, 64, 2, 4), (8.0, 100, 64, 2, 4),
    (0.1, 33, 64, 2, 4), (1e-3, 130, 64, 1, 2),
])
def test_chunked_matches_recurrence_values_and_gradients(
    decay, length, chunk, key_heads, value_heads
):
    args = _operands(
        length + chunk, length=length, key_heads=key_heads,
        value_heads=value_heads, decay=decay,
    )
    out, state = gated_delta_rule(*args, chunk=chunk)
    want, want_state = gated_delta_recurrent(*args)
    assert out.shape == want.shape == args[2].shape
    assert jnp.allclose(out, want, atol=1e-4, rtol=1e-4)
    assert jnp.allclose(state, want_state, atol=1e-4, rtol=1e-4)

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)[0])) + jnp.sum(fn(*a)[1] ** 2)

    got = jax.grad(
        scalar(lambda *a: gated_delta_rule(*a, chunk=chunk)), argnums=range(5)
    )(*args)
    ref = jax.grad(scalar(gated_delta_recurrent), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, ref):
        assert jnp.allclose(a, b, atol=1e-4, rtol=1e-4), name


@pytest.mark.parametrize("shift", [1.0, 4.0])
def test_keys_alike_as_a_positive_activation_leaves_them(shift):
    """Keys with a common component (mean cosine 0.5 and 0.9): the chunk's
    triangular system is ill-conditioned there, and forming its inverse as a
    product of powers loses every digit (read on the chip, PR 26)."""
    q, k, v, g, beta = _operands(
        11, length=128, key_heads=2, value_heads=4, decay=0.01, dk=32, dv=16
    )
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    k = unit(jax.nn.silu(k * 32 ** 0.5 + shift))
    cos = jnp.einsum("btd,bsd->bts", k[:, :, 0], k[:, :, 0])
    assert float(jnp.mean(cos)) > 0.45
    args = (q, k, v, g, beta)
    out, _ = gated_delta_rule(*args, chunk=64)
    want, _ = gated_delta_recurrent(*args)
    assert jnp.allclose(out, want, atol=1e-4, rtol=1e-4)
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a)[0])))  # noqa: E731
    got = jax.grad(loss(lambda *a: gated_delta_rule(*a, chunk=64)), argnums=range(5))(*args)
    ref = jax.grad(loss(gated_delta_recurrent), argnums=range(5))(*args)
    for a, b in zip(got, ref):
        assert jnp.allclose(a, b, atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-6)


def test_initial_state_continues_a_sequence():
    args = _operands(7, length=48, key_heads=2, value_heads=4, decay=0.05)
    whole, final = gated_delta_rule(*args, chunk=16)
    head = [a[:, :20] for a in args]
    tail = [a[:, 20:] for a in args]
    first, state = gated_delta_rule(*head, chunk=16)
    second, final2 = gated_delta_rule(*tail, chunk=16, initial_state=state)
    assert jnp.allclose(jnp.concatenate([first, second], 1), whole, atol=1e-5)
    assert jnp.allclose(final2, final, atol=1e-5)


def test_bfloat16_operands_stay_near_the_float32_recurrence():
    # bfloat16 carries 8 bits of mantissa: products of rounded operands with
    # float32 accumulation and a float32 state land within a few 2^-8.
    args = _operands(3, length=96, key_heads=2, value_heads=4, decay=0.05)
    cast = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    out, _ = gated_delta_rule(*cast, chunk=32)
    want, _ = gated_delta_recurrent(*cast)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))) < 0.05


def test_value_heads_must_be_a_multiple_of_key_heads():
    args = _operands(1, length=8, key_heads=2, value_heads=3, decay=0.1)
    with pytest.raises(ValueError, match="multiple"):
        gated_delta_rule(*args, chunk=8)
