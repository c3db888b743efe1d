"""``models.hybrid_lm`` against the benchmark's plain reference on seeded
weights, and the recipe through ``fit``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, weights_hybrid_lm
from benchmark.reference import hybrid_lm as ref
from machine_learning_apache_spark_tpu.models.hybrid_lm import HybridLM
from machine_learning_apache_spark_tpu.ops.positional import rotary_embedding
from machine_learning_apache_spark_tpu.recipes import make_lm_loss, train_lm

kind = manifest.load_kind("train_lm")


def _cfg(**over):
    cfg = manifest.load_config(manifest.load_manifest(), "qwen3_next_80b_a3b")
    kind.toy(cfg, {}, {})
    # two layers, one of each mixer: half the compile of a whole period
    cfg.update(num_layers=2, full_attention_interval=2, compute_dtype="float32")
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def seeded():
    cfg = _cfg()
    params = weights_hybrid_lm.make_params(2**31 + 5, cfg)
    rows = np.random.default_rng(0).integers(0, cfg["vocab_size"], (4, 25)).astype(np.int32)
    return cfg, params, rows


def _model(cfg, **over):
    over.setdefault("scan_chunk", 8)  # rows of 24 positions: three chunks
    return HybridLM(dataclasses.replace(kind.model_config(cfg), **over))


def test_logits_match_the_reference_in_float32(seeded):
    cfg, params, rows = seeded
    got, stats = _model(cfg).apply({"params": params}, rows[:, :-1])
    want = ref.logits(params, cfg, rows[:, :-1])
    assert got.shape == (4, 24, cfg["vocab_size"])
    assert jnp.allclose(got, want, atol=2e-4, rtol=2e-4)
    assert float(stats["assignments_local"]) == float(stats["assignments_computed"])


def _program_loss_and_grads(cfg, params, rows, **over):
    loss_fn = make_lm_loss(_model(cfg, **over))
    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, rows, jax.random.key(0)
    )
    return loss, grads, aux


@pytest.mark.parametrize("block_rows", [1, 4])
def test_loss_and_gradients_match_the_reference_in_float32(seeded, block_rows):
    """The reference takes the batch in row blocks with the whole batch's
    auxiliary term; the program takes it whole."""
    cfg, params, rows = seeded
    loss, grads, aux = _program_loss_and_grads(cfg, params, rows)
    want_loss, want = ref.loss_and_grads(params, cfg, rows, block_rows=block_rows)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(aux) == {
        "moe_aux", "moe_tokens_held_mean", "moe_tokens_held_max",
        "moe_assignments_local", "moe_assignments_computed",
    }
    got_norms, want_norms = ref.leaf_norms(grads), ref.leaf_norms(want)
    assert got_norms.keys() == want_norms.keys()
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(want)
    ):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        assert float(jnp.max(jnp.abs(a - b))) < 2e-3 * scale, path


def test_bfloat16_compute_stays_near_the_float32_reference(seeded):
    # bfloat16 operands (8 bits of mantissa, float32 accumulation) through
    # two layers: logits of unit scale move by a hundredth in the mean; a
    # token whose tenth-best expert flips under the rounding moves a few of
    # them by tenths. The loss moves by well under a per cent, a gradient
    # leaf's norm by a few per cent (the chip's limits, at the real widths,
    # are in the cell's file).
    cfg, params, rows = seeded
    bf16 = dict(cfg, compute_dtype="bfloat16")
    got, _ = _model(bf16).apply({"params": params}, rows[:, :-1])
    want = ref.logits(params, cfg, rows[:, :-1])
    gap = jnp.abs(got - want)
    assert float(jnp.mean(gap)) < 0.02 and float(jnp.max(gap)) < 0.6
    loss, grads, _ = _program_loss_and_grads(bf16, params, rows)
    want_loss, want_grads = ref.loss_and_grads(params, cfg, rows, block_rows=4)
    assert float(loss) == pytest.approx(float(want_loss), rel=5e-3)
    got_norms, want_norms = ref.leaf_norms(grads), ref.leaf_norms(want_grads)
    median = float(np.median(list(want_norms.values())))
    gaps = [abs(got_norms[k] - v) / max(v, median) for k, v in want_norms.items()]
    assert float(np.median(gaps)) < 0.02 and max(gaps) < 0.2


def test_remat_on_and_off_agree(seeded):
    cfg, params, rows = seeded
    loss_a, grads_a, _ = _program_loss_and_grads(cfg, params, rows, remat=True)
    loss_b, grads_b, _ = _program_loss_and_grads(cfg, params, rows, remat=False)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)):
        assert jnp.allclose(a, b, atol=1e-5, rtol=1e-4)


def test_blockwise_head_loss_equals_the_loss_of_the_whole_logits(seeded):
    cfg, params, rows = seeded
    model = _model(cfg, loss_block_tokens=36)  # 96 positions: a padded tail
    (total, count), _ = model.apply({"params": params}, rows[:, :-1], rows[:, 1:])
    logits, _ = model.apply({"params": params}, rows[:, :-1])
    logp = jax.nn.log_softmax(logits)
    want = -jnp.sum(jnp.take_along_axis(logp, rows[:, 1:, None], -1))
    assert float(count) == rows[:, 1:].size
    assert float(total) == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("interval,layers", [(4, 4), (2, 4), (4, 8)])
def test_layer_pattern(interval, layers):
    cfg = _cfg(num_layers=layers, full_attention_interval=interval)
    params = weights_hybrid_lm.make_params(1, cfg)
    shapes = jax.eval_shape(
        lambda: _model(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, shapes
    )
    for i in range(layers):
        mixer = shapes[f"layer_{i}"]["mixer"]
        full = (i + 1) % interval == 0
        assert ("q_proj" in mixer) == full and ("in_proj_qkvz" in mixer) == (not full)


def test_partial_rotary():
    x = jax.random.normal(jax.random.key(0), (2, 3, 10, 16))
    out = rotary_embedding(x, rotary_dim=4, theta=1e7)
    assert jnp.array_equal(out[..., 4:], x[..., 4:])          # untouched channels
    assert jnp.allclose(out[:, :, 0], x[:, :, 0])              # position 0
    assert not jnp.allclose(out[:, :, 1:, :4], x[:, :, 1:, :4])
    pair = lambda t: jnp.sqrt(t[..., 0] ** 2 + t[..., 2] ** 2)  # noqa: E731
    assert jnp.allclose(pair(out), pair(x), atol=1e-5)         # a rotation
    # scores depend on the distance only
    q = rotary_embedding(jnp.broadcast_to(x[:, :, :1], x.shape), rotary_dim=16)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, q)
    assert jnp.allclose(scores[..., 2, 5], scores[..., 4, 7], atol=1e-4)
    assert jnp.allclose(ref.rotary(x, 4, 1e7), out, atol=1e-6)
    with pytest.raises(ValueError):
        rotary_embedding(x, rotary_dim=5)


def test_two_epochs_through_train_lm_lower_the_loss():
    out = train_lm(
        epochs=2, synthetic_n=40, seq_len=32, batch_size=4, vocab_size=64,
        hidden_size=32, num_heads=2, head_dim=16, linear_key_dim=8,
        linear_value_dim=8, num_experts=8, experts_per_token=2,
        expert_hidden=16, shared_expert_hidden=16, learning_rate=3e-3,
        num_layers=2, full_attention_interval=2,
        use_mesh=False,
    )
    first, last = out["history"][0], out["history"][-1]
    assert last["loss"] < first["loss"]
    assert first["moe_assignments_local"] == first["moe_assignments_computed"]
    assert np.isfinite(out["test_loss"])
