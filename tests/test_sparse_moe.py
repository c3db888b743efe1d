"""Dropless top-k routing over a share of the experts (``DroplessMoE``)
against the dense form: every expert applied to every token, times a gate
weight that is zero where the token did not choose it."""

import jax
import jax.numpy as jnp
import pytest

from machine_learning_apache_spark_tpu.models.moe import DroplessMoE, route_top_k

D, F, E, K = 16, 8, 32, 4


def _layer(held=None, **kw):
    return DroplessMoE(
        d_model=D, expert_hidden=F, shared_hidden=F, num_experts=E, top_k=K,
        experts_held=held, **kw,
    )


@pytest.fixture(scope="module")
def setup():
    x = jax.random.normal(jax.random.key(0), (3, 20, D))
    params = _layer().init(jax.random.key(1), x)["params"]
    return x, params


def _share(params, first, count):
    cut = dict(params)
    for name in ("w_gate", "w_up", "w_down"):
        cut[name] = params[name][first:first + count]
    return cut


def _shared_expert(params, tokens):
    h = jax.nn.silu(tokens @ params["shared_gate"]) * (tokens @ params["shared_up"])
    return jax.nn.sigmoid(tokens @ params["shared_router"]) * (h @ params["shared_down"])


def _dense(params, x, first=0, count=E):
    tokens = x.reshape(-1, D)
    _, experts, weights = route_top_k(tokens @ params["router"], K)
    out = jnp.zeros_like(tokens)
    for e in range(first, first + count):
        gate = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        h = jax.nn.silu(tokens @ params["w_gate"][e]) * (tokens @ params["w_up"][e])
        out = out + gate[:, None] * (h @ params["w_down"][e])
    return (out + _shared_expert(params, tokens)).reshape(x.shape)


@pytest.mark.parametrize("held", [None, (0, 8), (8, 8), (30, 2)])
def test_dropless_matches_dense_values_and_gradients(setup, held):
    x, params = setup
    first, count = held or (0, E)
    cut = _share(params, first, count)

    def sparse(p, x):
        return _layer(held).apply({"params": p}, x)[0]

    def dense(p, x):
        full = dict(params, **{k: params[k].at[first:first + count].set(p[k])
                               for k in ("w_gate", "w_up", "w_down")})
        full.update({k: p[k] for k in p if k not in ("w_gate", "w_up", "w_down")})
        return _dense(full, x, first, count)

    assert jnp.allclose(sparse(cut, x), dense(cut, x), atol=1e-5)
    loss = lambda fn: (lambda p, x: jnp.sum(jnp.sin(fn(p, x))))  # noqa: E731
    got = jax.grad(loss(sparse), argnums=(0, 1))(cut, x)
    want = jax.grad(loss(dense), argnums=(0, 1))(cut, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("renormalize", [True, False])
def test_top_k_weights(renormalize):
    logits = jax.random.normal(jax.random.key(2), (50, E)) * 3
    probs, experts, weights = route_top_k(logits, K, renormalize=renormalize)
    assert experts.shape == weights.shape == (50, K)
    picked = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalize:
        assert jnp.allclose(jnp.sum(weights, -1), 1.0, atol=1e-6)
        assert jnp.allclose(weights, picked / picked.sum(-1, keepdims=True), atol=1e-6)
    else:
        assert jnp.allclose(weights, picked)
    assert jnp.all(picked >= jnp.sort(probs, -1)[:, -K][:, None] - 1e-7)


@pytest.mark.parametrize("held", [(0, 8), None])
def test_no_token_is_dropped_when_every_token_chooses_the_same_experts(setup, held):
    """A router forced onto experts 0..K-1: every one of the N * K
    assignments is local to a share that holds them, four times what the
    share expects, so the layer takes its full-size path; nothing is
    dropped and the result is still the dense form's."""
    x, params = setup
    forced = dict(params)
    forced["router"] = params["router"].at[:, :K].set(0.0) * 0.0
    forced["router"] = forced["router"].at[:, :K].add(
        jnp.abs(x.reshape(-1, D)).mean(0)[:, None] * jnp.sign(x.reshape(-1, D).sum(0))[:, None]
    )
    tokens = x.reshape(-1, D)
    _, experts, _ = route_top_k(tokens @ forced["router"], K)
    first, count = held or (0, E)
    out, stats = _layer(held).apply({"params": _share(forced, first, count)}, x)
    local = int(jnp.sum((experts >= first) & (experts < first + count)))
    assert float(stats["assignments_local"]) == local
    assert float(stats["assignments_computed"]) == local
    assert jnp.allclose(out, _dense(forced, x, first, count), atol=1e-5)
    if held:
        fast_rows = 256 * -(-int(1.5 * tokens.shape[0] * K * count / E) // 256)
        assert local > min(fast_rows, tokens.shape[0] * K) or local == tokens.shape[0] * K


def test_the_shares_add_up_to_the_whole_layer(setup):
    """Four shares of 8 of the 32 experts, the shared expert counted once,
    equal the uncut layer."""
    x, params = setup
    whole, whole_stats = _layer().apply({"params": params}, x)
    shared = _shared_expert(params, x.reshape(-1, D)).reshape(x.shape)
    total, local = shared, 0.0
    for first in range(0, E, 8):
        out, stats = _layer((first, 8)).apply(
            {"params": _share(params, first, 8)}, x
        )
        total = total + (out - shared)
        local += float(stats["assignments_local"])
        assert jnp.allclose(stats["aux"], whole_stats["aux"])  # over all experts
    assert jnp.allclose(total, whole, atol=1e-5)
    assert local == float(whole_stats["assignments_local"]) == x.shape[0] * x.shape[1] * K


def test_stats_and_aux(setup):
    x, params = setup
    _, stats = _layer((4, 8)).apply({"params": _share(params, 4, 8)}, x)
    n = x.shape[0] * x.shape[1]
    assert float(stats["tokens_held_mean"]) == pytest.approx(
        float(stats["assignments_local"]) / 8
    )
    assert float(stats["tokens_held_max"]) >= float(stats["tokens_held_mean"])
    probs, experts, _ = route_top_k(x.reshape(-1, D) @ params["router"], K)
    share = jnp.bincount(experts.reshape(-1), length=E) / (n * K)
    assert float(stats["aux"]) == pytest.approx(
        float(E * jnp.sum(share * probs.mean(0))), rel=1e-5
    )


def test_experts_held_outside_the_router_is_refused(setup):
    x, _ = setup
    with pytest.raises(ValueError, match="experts_held"):
        _layer((28, 8)).init(jax.random.key(0), x)
