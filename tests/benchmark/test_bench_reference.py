"""The plain float32 reference against ``models.transformer`` at small size
on the CPU: forward logits, loss and gradients (dropout masks included), the
Adam steps, and prefill + paged decode against the full forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as manifest_mod, run as bench_run, weights
from benchmark.reference import transformer as ref

CFG = dict(src_vocab_size=50, trg_vocab_size=60, d_model=32, ffn_hidden=64,
           num_heads=4, num_layers=2, dropout=0.1, max_len=16, pad_id=0,
           sos_id=1, eos_id=2)


@pytest.fixture(scope="module")
def setup():
    from machine_learning_apache_spark_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )

    params = weights.make_params(2**31 + 5, CFG)
    model = Transformer(TransformerConfig(
        src_vocab_size=50, trg_vocab_size=60, d_model=32, ffn_hidden=64,
        num_heads=4, num_layers=2, dropout=0.1, max_len=16,
        dtype=jnp.float32,
    ))
    rs = np.random.default_rng(0)
    src = rs.integers(4, 50, (6, 12)).astype(np.int32)
    trg = rs.integers(4, 60, (6, 13)).astype(np.int32)
    src[0, 8:] = 0
    trg[1, 9:] = 0
    return model, params, src, trg


def test_weights_have_the_models_tree(setup):
    import flax.linen as nn

    model, params, src, trg = setup
    init = nn.unbox(model.init(jax.random.key(0), src[:2], trg[:2, :-1])["params"])
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, params)
    assert weights.parameter_count(CFG) == sum(
        x.size for x in jax.tree.leaves(params)
    )
    again = weights.make_params(2**31 + 5, CFG)
    assert all(
        bool((a == b).all())
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again))
    )
    other = weights.make_params(2**31 + 6, CFG)
    assert not bool((other["lm_head"]["kernel"] == params["lm_head"]["kernel"]).all())
    stop = weights.make_params(1, CFG, suppress_stop=True)["lm_head"]["bias"]
    assert float(stop[0]) == float(stop[2]) == weights.STOP_BIAS


def test_forward_logits_agree(setup):
    model, params, src, trg = setup
    with ref.precise():
        program = model.apply({"params": params}, src, trg[:, :-1])
        plain = ref.forward(params, CFG, jnp.asarray(src), jnp.asarray(trg[:, :-1]))
    assert float(jnp.max(jnp.abs(program - plain))) < 1e-4


def test_loss_and_gradients_agree_with_dropout_on(setup):
    from machine_learning_apache_spark_tpu.recipes.translation import (
        make_translation_loss,
    )

    model, params, src, trg = setup
    key = jax.random.key(7)
    with ref.precise():
        (loss, _), grads = jax.value_and_grad(
            make_translation_loss(model, 0), has_aux=True
        )(params, (src, trg), key)
        plain_loss, plain_grads = ref.loss_and_grads(
            params, CFG, jnp.asarray(src), jnp.asarray(trg),
            step_key=key, block_rows=2,
        )
        no_drop, _ = ref.loss_and_grads(
            params, CFG, jnp.asarray(src), jnp.asarray(trg), block_rows=6,
        )
    assert float(loss) == pytest.approx(float(plain_loss), rel=1e-5)
    assert abs(float(no_drop) - float(loss)) > 1e-3, "the masks matter"
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12)),
        grads, plain_grads,
    )))
    assert worst < 1e-4


def test_adam_steps_agree_with_the_recipes_optimizer(setup):
    from machine_learning_apache_spark_tpu.train.state import make_optimizer
    import optax

    _, params, _, _ = setup
    tx = make_optimizer("adam", 1e-3, b1=0.9, b2=0.98, eps=1e-9)
    state = tx.init(params)
    mine, adam = params, ref.adam_init(params)
    theirs = params
    for k in range(3):
        grads = jax.tree.map(lambda p: jnp.sin(p * (k + 1)), params)
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        mine, adam = ref.adam_step(mine, grads, adam, lr=1e-3, b2=0.98, eps=1e-9)
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), mine, theirs
    )))
    assert worst < 1e-6


def test_leaf_norms_split_fused_projections():
    tree = {"qkv": {"bias": jnp.concatenate(
        [jnp.ones(4), jnp.zeros(4), 2 * jnp.ones(4)])}, "b": jnp.ones(3)}
    norms = ref.leaf_norms(tree, block=4)
    assert norms == {"b": pytest.approx(3 ** 0.5), "qkv/bias#0": 2.0,
                     "qkv/bias#1": 0.0, "qkv/bias#2": 4.0}


@pytest.mark.serving
def test_prefill_and_paged_decode_agree_with_the_full_forward(tmp_path):
    """The engine in float32 serves exactly the reference's greedy tokens:
    every served token is the reference's best at its position."""
    result = bench_run.run_cell(
        "big_serve_batch", seed=11, seconds=0.5, trace=False,
        require_chip=False, rehearse=True,
        manifest=manifest_mod.with_put_off(
            manifest_mod.load_manifest(), "big_serve_batch"),
        config_overrides={"compute_dtype": "float32"}, out_dir=str(tmp_path),
    )
    assert result["correct"] is True
    assert result["compared"]["served_gap_max"]["value"] <= 1e-4
    assert result["attempted"] > 0 and result["failed"] == 0
