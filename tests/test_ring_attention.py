"""Ring attention tests: parity with dense attention (values and grads) on
the 8-virtual-device CPU mesh, causal and full, with and without a batch
axis — the sequence-parallel property the reference entirely lacks
(SURVEY.md §5 long-context)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from machine_learning_apache_spark_tpu.ops.attention import (
    scaled_dot_product_attention,
)
from machine_learning_apache_spark_tpu.ops.masks import make_causal_mask
from machine_learning_apache_spark_tpu.parallel import make_mesh
from machine_learning_apache_spark_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS
from machine_learning_apache_spark_tpu.parallel.ring_attention import (
    ring_attention,
)


def qkv(b=2, h=4, s=32, d=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh({SEQ_AXIS: 8})


@pytest.fixture(scope="module")
def dp_sp_mesh():
    return make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})


class TestRingParity:
    def test_full_attention_matches_dense(self, seq_mesh):
        q, k, v = qkv()
        dense = scaled_dot_product_attention(q, k, v)
        ring = ring_attention(q, k, v, seq_mesh)
        np.testing.assert_allclose(ring, dense, atol=1e-5)

    def test_causal_matches_dense(self, seq_mesh):
        q, k, v = qkv()
        mask = make_causal_mask(q.shape[2])
        dense = scaled_dot_product_attention(q, k, v, mask)
        ring = ring_attention(q, k, v, seq_mesh, causal=True)
        np.testing.assert_allclose(ring, dense, atol=1e-5)

    def test_dp_sp_mesh(self, dp_sp_mesh):
        q, k, v = qkv(b=4, s=16)
        dense = scaled_dot_product_attention(q, k, v)
        ring = ring_attention(q, k, v, dp_sp_mesh)
        np.testing.assert_allclose(ring, dense, atol=1e-5)

    def test_gradients_match_dense(self, seq_mesh):
        q, k, v = qkv(s=16)

        def dense_loss(q, k, v):
            return (scaled_dot_product_attention(
                q, k, v, make_causal_mask(q.shape[2])
            ) ** 2).sum()

        def ring_loss(q, k, v):
            return (ring_attention(q, k, v, seq_mesh, causal=True) ** 2).sum()

        g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        for gd, gr in zip(g_dense, g_ring):
            np.testing.assert_allclose(gr, gd, atol=1e-4)

    def test_mesh_with_unused_axes(self):
        """A dp×tp×sp mesh (axes beyond the specs) must work — the natural
        combined mesh once tensor parallelism is in play."""
        from machine_learning_apache_spark_tpu.parallel.mesh import MODEL_AXIS

        mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 2, SEQ_AXIS: 2})
        q, k, v = qkv(b=4, s=16)
        np.testing.assert_allclose(
            ring_attention(q, k, v, mesh),
            scaled_dot_product_attention(q, k, v),
            atol=1e-5,
        )

    def test_no_batch_axis(self, seq_mesh):
        q, k, v = qkv()
        np.testing.assert_allclose(
            ring_attention(q, k, v, seq_mesh, batch_axis=None),
            scaled_dot_product_attention(q, k, v),
            atol=1e-5,
        )

    def test_jit_compiles_once(self, seq_mesh):
        q, k, v = qkv()
        f = jax.jit(lambda q, k, v: ring_attention(q, k, v, seq_mesh))
        np.testing.assert_allclose(
            f(q, k, v), scaled_dot_product_attention(q, k, v), atol=1e-5
        )


class TestRingKvValid:
    def test_kv_valid_matches_dense(self, seq_mesh):
        """Per-key padding validity rides the ring; parity with the dense
        padding-masked path."""
        q, k, v = qkv(s=32)
        lengths = jnp.asarray([20, 32])
        kv_valid = jnp.arange(32)[None, :] < lengths[:, None]
        dense = scaled_dot_product_attention(q, k, v, kv_valid[:, None, None, :])
        ring = ring_attention(q, k, v, seq_mesh, kv_valid=kv_valid)
        np.testing.assert_allclose(ring, dense, atol=1e-5)

    def test_kv_valid_with_causal(self, seq_mesh):
        q, k, v = qkv(s=32)
        from machine_learning_apache_spark_tpu.ops.masks import combine_masks

        kv_valid = jnp.arange(32)[None, :] < jnp.asarray([24, 32])[:, None]
        dense_mask = combine_masks(
            make_causal_mask(32), kv_valid[:, None, None, :]
        )
        dense = scaled_dot_product_attention(q, k, v, dense_mask)
        ring = ring_attention(
            q, k, v, seq_mesh, causal=True, kv_valid=kv_valid
        )
        np.testing.assert_allclose(ring, dense, atol=1e-5)

    def test_fully_padded_row_emits_zeros(self, seq_mesh):
        q, k, v = qkv(s=16)
        kv_valid = jnp.stack([jnp.zeros(16, bool), jnp.ones(16, bool)])
        ring = ring_attention(q, k, v, seq_mesh, kv_valid=kv_valid)
        np.testing.assert_array_equal(np.asarray(ring)[0], 0.0)

    def test_kv_valid_bad_shape_rejected(self, seq_mesh):
        q, k, v = qkv(s=16)
        with pytest.raises(ValueError, match="kv_valid"):
            ring_attention(
                q, k, v, seq_mesh, kv_valid=jnp.ones((2, 8), bool)
            )


class TestSequenceParallelDispatch:
    """``sequence_parallel(mesh)`` routes zoo self-attention through the
    ring with NO model change (VERDICT round-2 item 4)."""

    def test_dot_product_attention_dispatches(self, dp_sp_mesh):
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
            sequence_parallel,
        )

        q, k, v = qkv(b=4, s=16)
        kv_valid = jnp.arange(16)[None, :] < jnp.asarray([10, 16, 12, 16])[:, None]
        dense = dot_product_attention(
            q, k, v, causal=True, kv_valid=kv_valid, use_pallas=False
        )
        with sequence_parallel(dp_sp_mesh):
            ring = dot_product_attention(q, k, v, causal=True, kv_valid=kv_valid)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=1e-5)

    def test_context_wins_over_the_shape_gate(self, dp_sp_mesh, monkeypatch):
        """On a TPU a site this short would go to the dense path by its
        shape; an active sequence-parallel context is asked first."""
        from machine_learning_apache_spark_tpu import telemetry
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
            sequence_parallel,
        )

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q, k, v = qkv(b=4, s=16)
        with sequence_parallel(dp_sp_mesh):
            ring = dot_product_attention(q, k, v, causal=True)
        noted = [
            e.attrs for e in telemetry.get_log().snapshot()
            if e.name == "ops.attention_dispatch"
        ][-1]
        assert (noted["site"], noted["impl"]) == ("dot_product", "ring")
        dense = scaled_dot_product_attention(q, k, v, make_causal_mask(16))
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=1e-5)

    def test_ragged_batch_falls_through(self, dp_sp_mesh):
        """A batch that doesn't fill the mesh's data axis (evaluate's ragged
        tail) must fall through to the dense path, not crash shard_map."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
            sequence_parallel,
        )

        q, k, v = qkv(b=3, s=16)  # 3 rows on a data=2 axis
        with sequence_parallel(dp_sp_mesh):
            got = dot_product_attention(q, k, v)
        expected = scaled_dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)

    def test_cross_attention_falls_through(self, dp_sp_mesh):
        """Sq != Sk must NOT hit the ring (cross-attention site)."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
            sequence_parallel,
        )

        q, _, _ = qkv(b=4, s=8)
        k, v = qkv(b=4, s=16)[:2]
        with sequence_parallel(dp_sp_mesh):
            got = dot_product_attention(q, k, v)
        expected = scaled_dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)

    def test_missing_axis_rejected(self):
        from machine_learning_apache_spark_tpu.ops.attention import (
            sequence_parallel,
        )

        mesh = make_mesh({DATA_AXIS: 8})
        with pytest.raises(ValueError, match="seq"):
            with sequence_parallel(mesh):
                pass

    def test_transformer_trains_on_dp_sp_mesh(self, dp_sp_mesh):
        """The MT Transformer trains under sequence_parallel on a dp×sp mesh
        with no model change, matching the dp-only loss trajectory."""
        from machine_learning_apache_spark_tpu.models import (
            Transformer,
            TransformerConfig,
        )
        from machine_learning_apache_spark_tpu.ops.attention import (
            sequence_parallel,
        )
        from machine_learning_apache_spark_tpu.train.losses import (
            masked_token_cross_entropy,
        )
        from machine_learning_apache_spark_tpu.train.state import (
            TrainState,
            make_optimizer,
        )

        import flax.linen as nn

        cfg = TransformerConfig(
            src_vocab_size=50, trg_vocab_size=60, d_model=16, ffn_hidden=32,
            num_heads=4, num_layers=1, max_len=16, dropout=0.0,
        )
        model = Transformer(cfg)
        rng = jax.random.key(0)
        src = jax.random.randint(rng, (4, 16), 1, 50, dtype=jnp.int32)
        trg = jax.random.randint(rng, (4, 17), 1, 60, dtype=jnp.int32)
        params = nn.unbox(model.init(rng, src, trg[:, :-1])["params"])

        def loss_fn(params, src, trg):
            logits = model.apply(
                {"params": params}, src, trg[:, :-1], deterministic=True
            )
            return masked_token_cross_entropy(logits, trg[:, 1:], cfg.pad_id)

        def train(n_steps, use_sp):
            state = TrainState.create(
                apply_fn=model.apply,
                params=params,
                tx=make_optimizer("adam", 1e-2),
            )

            @jax.jit
            def step(state, src, trg):
                loss, grads = jax.value_and_grad(loss_fn)(state.params, src, trg)
                return state.apply_gradients(grads), loss

            losses = []
            for _ in range(n_steps):
                if use_sp:
                    from machine_learning_apache_spark_tpu.ops.attention import (
                        sequence_parallel,
                    )

                    with sequence_parallel(dp_sp_mesh):
                        state, loss = step(state, src, trg)
                else:
                    state, loss = step(state, src, trg)
                losses.append(float(loss))
            return losses

        sp_losses = train(4, use_sp=True)
        dp_losses = train(4, use_sp=False)
        np.testing.assert_allclose(sp_losses, dp_losses, rtol=1e-4)
        assert sp_losses[-1] < sp_losses[0]


class TestRingValidation:
    def test_indivisible_seq_rejected(self, seq_mesh):
        q, k, v = qkv(s=30)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention(q, k, v, seq_mesh)

    def test_cross_shapes_rejected(self, seq_mesh):
        q, _, _ = qkv(s=16)
        _, k, v = qkv(s=32)
        with pytest.raises(ValueError, match="self-attention-shaped"):
            ring_attention(q, k, v, seq_mesh)


class TestLongContextTraining:
    def test_seq2048_train_step_on_sp_mesh(self):
        """One full fwd+bwd train step at sequence length 2048 on a seq=8
        mesh with remat — the long-context training capability (ring
        attention shards the S² work/memory, jax.checkpoint bounds layer
        activations). The reference caps sequences at 200 by construction
        (SURVEY.md §5)."""
        import dataclasses

        import flax.linen as nn

        from machine_learning_apache_spark_tpu.models import (
            Transformer,
            TransformerConfig,
        )
        from machine_learning_apache_spark_tpu.ops.attention import (
            sequence_parallel,
        )
        from machine_learning_apache_spark_tpu.train.losses import (
            masked_token_cross_entropy,
        )

        S = 2048
        cfg = TransformerConfig(
            src_vocab_size=50, trg_vocab_size=60, d_model=32, ffn_hidden=64,
            num_heads=4, num_layers=1, max_len=S, dropout=0.0, remat=True,
        )
        model = Transformer(cfg)
        src = jax.random.randint(jax.random.key(0), (2, S), 1, 50, dtype=jnp.int32)
        trg = jax.random.randint(jax.random.key(1), (2, S + 1), 1, 60, dtype=jnp.int32)
        params = nn.unbox(model.init(jax.random.key(2), src[:, :8], trg[:, :8])["params"])

        def loss_fn(p):
            logits = model.apply(
                {"params": p}, src, trg[:, :-1], deterministic=True
            )
            return masked_token_cross_entropy(logits, trg[:, 1:], cfg.pad_id)

        mesh = make_mesh({SEQ_AXIS: 8})
        with sequence_parallel(mesh, batch_axis=DATA_AXIS):
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
            loss = float(loss)
        assert np.isfinite(loss)
        assert all(
            np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads)
        )
