"""ops layer tests: mask truth tables, positional encoding, attention numerics.

Models the reference's implicit checks (SURVEY.md §4): causal-mask truth table
vs ``pytorch_machine_translator.py:102-104`` (polarity corrected), attention
vs a naive softmax reference, flash kernel vs the fused-XLA path.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from machine_learning_apache_spark_tpu.ops import (
    combine_masks,
    make_attention_mask,
    make_causal_mask,
    make_padding_mask,
    scaled_dot_product_attention,
    sinusoidal_encoding,
)
from machine_learning_apache_spark_tpu.ops.pallas_attention import flash_attention


class TestMasks:
    def test_causal_truth_table(self):
        m = make_causal_mask(4)[0, 0]
        # Row i may attend columns <= i — tril, the corrected polarity of the
        # reference's (tril == 0) masked-set.
        expected = np.tril(np.ones((4, 4), dtype=bool))
        np.testing.assert_array_equal(np.asarray(m), expected)

    def test_causal_shape(self):
        assert make_causal_mask(7).shape == (1, 1, 7, 7)

    def test_padding_mask(self):
        toks = jnp.array([[5, 3, 0, 0], [1, 0, 0, 0]])
        m = make_padding_mask(toks, pad_id=0)
        assert m.shape == (2, 1, 1, 4)
        np.testing.assert_array_equal(
            np.asarray(m[:, 0, 0]), [[True, True, False, False], [True, False, False, False]]
        )

    def test_attention_mask_rectangular(self):
        # Different query/key lengths — the Q8 capability.
        qv = jnp.array([[True, True, False]])
        kv = jnp.array([[True, False, True, True, False]])
        m = make_attention_mask(qv, kv)
        assert m.shape == (1, 1, 3, 5)
        assert bool(m[0, 0, 0, 0]) and not bool(m[0, 0, 0, 1])
        assert not bool(m[0, 0, 2, 0])  # padded query row attends nothing

    def test_segment_mask_block_diagonal(self):
        from machine_learning_apache_spark_tpu.ops.masks import (
            make_segment_mask,
        )

        seg = jnp.array([[1, 1, 2, 2, 0]])
        m = make_segment_mask(seg, seg)
        assert m.shape == (1, 1, 5, 5)
        got = np.asarray(m[0, 0])
        expected = np.zeros((5, 5), bool)
        expected[:2, :2] = True  # segment 1 block
        expected[2:4, 2:4] = True  # segment 2 block
        # row/col 4 (segment 0 = pad) attends and is attended by nothing
        np.testing.assert_array_equal(got, expected)

    def test_segment_mask_rectangular(self):
        from machine_learning_apache_spark_tpu.ops.masks import (
            make_segment_mask,
        )

        q = jnp.array([[1, 2, 2]])
        k = jnp.array([[2, 2, 1, 0, 1]])
        m = make_segment_mask(q, k)[0, 0]
        np.testing.assert_array_equal(
            np.asarray(m),
            [[False, False, True, False, True],
             [True, True, False, False, False],
             [True, True, False, False, False]],
        )

    def test_combine(self):
        causal = make_causal_mask(4)
        pad = make_padding_mask(jnp.array([[1, 1, 0, 0]]))
        both = combine_masks(causal, pad)
        assert both.shape == (1, 1, 4, 4)
        assert not bool(both[0, 0, 3, 2])  # padding wins
        assert not bool(both[0, 0, 0, 1])  # causality wins
        assert combine_masks(None, None) is None
        assert combine_masks(causal, None) is causal


class TestPositional:
    def test_formula(self):
        pe = np.asarray(sinusoidal_encoding(50, 16))
        pos, i = 7, 3
        np.testing.assert_allclose(
            pe[pos, 2 * i], np.sin(pos / 10000 ** (2 * i / 16)), rtol=1e-5
        )
        np.testing.assert_allclose(
            pe[pos, 2 * i + 1], np.cos(pos / 10000 ** (2 * i / 16)), rtol=1e-5
        )

    def test_first_row(self):
        pe = np.asarray(sinusoidal_encoding(10, 8))
        np.testing.assert_allclose(pe[0, 0::2], 0.0, atol=1e-7)
        np.testing.assert_allclose(pe[0, 1::2], 1.0, atol=1e-7)


def _naive_attention(q, k, v, mask=None):
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if mask is not None:
        s = np.where(mask, s, -1e30)
    w = np.exp(s - s.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", w, v)


class TestAttention:
    def test_matches_naive(self, rng):
        q = rng.standard_normal((2, 3, 5, 8)).astype(np.float32)
        k = rng.standard_normal((2, 3, 7, 8)).astype(np.float32)
        v = rng.standard_normal((2, 3, 7, 8)).astype(np.float32)
        out = scaled_dot_product_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(out), _naive_attention(q, k, v), atol=1e-5)

    def test_masked_positions_ignored(self, rng):
        q = rng.standard_normal((1, 1, 2, 4)).astype(np.float32)
        k = rng.standard_normal((1, 1, 3, 4)).astype(np.float32)
        v = rng.standard_normal((1, 1, 3, 4)).astype(np.float32)
        mask = jnp.array([[[[True, True, False], [True, True, False]]]])
        out = scaled_dot_product_attention(*map(jnp.asarray, (q, k, v)), mask)
        # Changing the masked key/value must not change the output.
        k2, v2 = k.copy(), v.copy()
        k2[0, 0, 2] += 100.0
        v2[0, 0, 2] -= 50.0
        out2 = scaled_dot_product_attention(*map(jnp.asarray, (q, k2, v2)), mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)

    def test_weights_sum_to_one(self, rng):
        from machine_learning_apache_spark_tpu.ops import multi_head_attention_weights

        q = jnp.asarray(rng.standard_normal((2, 2, 4, 8)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 2, 6, 8)), dtype=jnp.float32)
        w = multi_head_attention_weights(q, k)
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)


class TestAttentionImplOverride:
    """``ops.attention_impl``: the benchmarking hook that pins auto
    dispatch to the dense or flash path (the long-context bench measures
    the Pallas kernel against the dense core it replaces with it)."""

    def _spy(self, monkeypatch):
        import machine_learning_apache_spark_tpu.ops.pallas_attention as pa

        calls = []

        def fake_flash(q, k, v, **kw):
            calls.append(kw)
            return scaled_dot_product_attention(q, k, v)

        monkeypatch.setattr(pa, "flash_attention", fake_flash)
        return calls

    def test_forced_flash_dispatches_to_kernel(self, rng, monkeypatch):
        from machine_learning_apache_spark_tpu.ops.attention import (
            attention_impl,
            dot_product_attention,
        )

        calls = self._spy(monkeypatch)
        q = jnp.asarray(rng.standard_normal((1, 2, 8, 4)), dtype=jnp.float32)
        dot_product_attention(q, q, q, causal=True)  # auto on CPU → dense
        assert calls == []
        with attention_impl("flash"):
            dot_product_attention(q, q, q, causal=True)
        assert len(calls) == 1
        # Context restored: auto again.
        dot_product_attention(q, q, q, causal=True)
        assert len(calls) == 1

    def test_forced_dense_and_explicit_arg_wins(self, rng, monkeypatch):
        from machine_learning_apache_spark_tpu.ops.attention import (
            attention_impl,
            dot_product_attention,
        )

        calls = self._spy(monkeypatch)
        q = jnp.asarray(rng.standard_normal((1, 2, 8, 4)), dtype=jnp.float32)
        with attention_impl("dense"):
            dot_product_attention(q, q, q, causal=True)
            assert calls == []
            # An explicit use_pallas argument overrides the context.
            dot_product_attention(q, q, q, causal=True, use_pallas=True)
            assert len(calls) == 1

    def test_dense_mask_never_flash(self, rng, monkeypatch):
        # A dense mask cannot stream through the blockwise kernel — the
        # forced-flash context must not break that invariant.
        from machine_learning_apache_spark_tpu.ops.attention import (
            attention_impl,
            dot_product_attention,
        )

        calls = self._spy(monkeypatch)
        q = jnp.asarray(rng.standard_normal((1, 2, 8, 4)), dtype=jnp.float32)
        with attention_impl("flash"):
            dot_product_attention(q, q, q, mask=make_causal_mask(8))
        assert calls == []

    def test_bad_impl_rejected(self):
        from machine_learning_apache_spark_tpu.ops.attention import (
            attention_impl,
        )

        with pytest.raises(ValueError, match="dense.*flash|flash.*dense"):
            with attention_impl("fast"):
                pass


def _last_dot_product_dispatch():
    from machine_learning_apache_spark_tpu import telemetry

    return [
        e.attrs for e in telemetry.get_log().snapshot()
        if e.kind == "annotation" and e.name == "ops.attention_dispatch"
        and e.attrs["site"] == "dot_product"
    ][-1]


def _reference_attention(q, k, v, mask):
    """Float32 attention with nothing of the package in it: the product,
    the mask, the softmax, plain autodiff."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _sub_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _sub_eqns(inner)


class TestFlashGate:
    """``dot_product_attention``'s auto-dispatch on TPU: a structured-mask
    site runs the flash kernel from ``FLASH_MIN_SCORES`` scores a head and
    the rematerialized dense path under it, chosen from the shapes alone."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        """The dispatcher believes it is on a TPU; the kernel is a spy that
        answers with the dense path (nothing here compiles for Mosaic)."""
        import machine_learning_apache_spark_tpu.ops.pallas_attention as pa

        calls = []

        def fake_flash(q, k, v, **kw):
            calls.append(kw)
            return scaled_dot_product_attention(q, k, v)

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pa, "flash_attention", fake_flash)
        return calls

    @pytest.mark.parametrize(
        "q_len,kv_len,impl",
        [
            (200, 200, "xla_dense"),   # ref_train_1chip's sites
            (256, 256, "xla_dense"),   # big_train_dp4's
            (1, 200, "xla_dense"),     # a KV-cache decode step
            (511, 512, "xla_dense"),   # 512 scores under
            (128, 2047, "xla_dense"),  # 128 under, rectangular
            (512, 512, "pallas_flash"),   # exactly at
            (2048, 128, "pallas_flash"),  # exactly at, rectangular
            (512, 4096, "pallas_flash"),
        ],
    )
    def test_scores_a_head_choose_the_side(self, on_tpu, q_len, kv_len, impl):
        from machine_learning_apache_spark_tpu.ops.attention import (
            FLASH_MIN_SCORES,
            dot_product_attention,
        )

        q = jnp.ones((1, 1, q_len, 8), jnp.float32)
        k = jnp.ones((1, 1, kv_len, 8), jnp.float32)
        dot_product_attention(q, k, k, kv_valid=jnp.ones((1, kv_len), bool))
        noted = _last_dot_product_dispatch()
        assert noted["impl"] == impl
        sign = ">=" if impl == "pallas_flash" else "<"
        assert noted["reason"] == (
            f"{q_len}x{kv_len} scores {sign} {FLASH_MIN_SCORES}"
        )
        assert len(on_tpu) == (impl == "pallas_flash")

    @pytest.mark.parametrize(
        "seq,forced,impl,reason",
        [
            (16, dict(use_pallas=True), "pallas_flash", "caller-selected"),
            (16, dict(ctx="flash"), "pallas_flash", "attention_impl('flash')"),
            (512, dict(use_pallas=False), "xla_dense", "caller-selected"),
            (512, dict(ctx="dense"), "xla_dense", "attention_impl('dense')"),
            (512, dict(mask=True), "xla_dense", "dense mask"),
            (512, dict(mask=True, ctx="flash"), "xla_dense", "dense mask"),
        ],
    )
    def test_forced_paths_win_over_the_gate(
        self, on_tpu, seq, forced, impl, reason
    ):
        import contextlib

        from machine_learning_apache_spark_tpu.ops.attention import (
            attention_impl,
            dot_product_attention,
        )

        q = jnp.ones((1, 1, seq, 8), jnp.float32)
        ctx = (
            attention_impl(forced["ctx"]) if "ctx" in forced
            else contextlib.nullcontext()
        )
        with ctx:
            dot_product_attention(
                q, q, q,
                mask=make_causal_mask(seq) if forced.get("mask") else None,
                causal=not forced.get("mask"),
                use_pallas=forced.get("use_pallas"),
            )
        noted = _last_dot_product_dispatch()
        assert (noted["impl"], noted["reason"]) == (impl, reason)
        assert len(on_tpu) == (impl == "pallas_flash")

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("use_valid", [False, True])
    def test_gated_grads_match_float32_reference(
        self, rng, on_tpu, causal, use_valid
    ):
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
        )

        b, h, s, d = 2, 2, 64, 16
        q, k, v = (
            jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
            for _ in range(3)
        )
        kv_valid = jnp.asarray(rng.random((b, s)) < 0.8) if use_valid else None
        if use_valid:
            kv_valid = kv_valid.at[:, 0].set(True)  # no fully masked row
        mask = combine_masks(
            make_causal_mask(s) if causal else None,
            kv_valid[:, None, None, :] if use_valid else None,
        )

        def grads(fn):
            return jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)
            )(q, k, v)

        gated = grads(lambda q, k, v: dot_product_attention(
            q, k, v, causal=causal, kv_valid=kv_valid
        ))
        assert _last_dot_product_dispatch()["impl"] == "xla_dense"
        assert on_tpu == []
        reference = grads(lambda q, k, v: _reference_attention(q, k, v, mask))
        for name, a, e in zip("qkv", gated, reference):
            scale = float(jnp.max(jnp.abs(e))) + 1e-9
            err = float(jnp.max(jnp.abs(a - e))) / scale
            assert err < 1e-4, f"d{name} relative error {err}"

    def test_dense_site_saves_no_score_matrix(self, on_tpu):
        """The residuals of a structured-mask site on the dense path, gated
        or forced, are what the kernel's dense backward kept — q, k, v,
        kv_valid — and never a [B, H, Sq, Sk] array; a dense-mask site,
        plain autodiff, does save them."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
        )

        b, h, sq, sk, d = 2, 3, 40, 56, 8
        q = jnp.ones((b, h, sq, d), jnp.bfloat16)
        k = jnp.ones((b, h, sk, d), jnp.bfloat16)
        kv_valid = jnp.ones((b, sk), bool)

        def residual_shapes(**kw):
            kw.setdefault("kv_valid", kv_valid)
            _, vjp = jax.vjp(
                lambda q, k, v: dot_product_attention(q, k, v, **kw), q, k, k
            )
            return [x.shape for x in jax.tree.leaves(vjp)]

        gated = residual_shapes()
        assert _last_dot_product_dispatch()["reason"].startswith("40x56 scores <")
        forced = residual_shapes(use_pallas=False)
        assert _last_dot_product_dispatch()["reason"] == "caller-selected"
        for saved in (gated, forced):
            assert (b, h, sq, d) in saved and (b, h, sk, d) in saved
            assert not [shape for shape in saved if shape[-2:] == (sq, sk)]
        masked = residual_shapes(
            kv_valid=None, mask=jnp.ones((b, 1, sq, sk), bool)
        )
        assert (b, h, sq, sk) in masked

    def test_score_product_is_float32_for_bfloat16_operands(self):
        """``dot_product_attention``'s dense path keeps QK^T in float32 as
        the kernel does, and its backward feeds its two products the
        operands' dtype, as the kernels do; the pure-jnp core under the
        paged decode keeps the compute dtype."""
        from machine_learning_apache_spark_tpu.ops import (
            multi_head_attention_weights,
        )
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
        )

        q = jnp.ones((2, 2, 24, 8), jnp.bfloat16)
        k = jnp.ones((2, 2, 40, 8), jnp.bfloat16)
        scores = (2, 2, 24, 40)

        def dots(fn, *args):
            return [
                e for e in _sub_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
                if e.primitive.name == "dot_general"
            ]

        def dense(q, k, v):
            return dot_product_attention(q, k, v, causal=True, use_pallas=False)

        assert dense(q, k, k).dtype == jnp.bfloat16
        assert {
            e.outvars[0].aval.shape: e.outvars[0].aval.dtype
            for e in dots(dense, q, k, k)
        } == {scores: jnp.float32, (2, 2, 24, 8): jnp.bfloat16}
        assert [
            e.outvars[0].aval.dtype
            for e in dots(multi_head_attention_weights, q, k)
        ] == [jnp.bfloat16]

        backward = dots(
            lambda q, k, v, g: jax.vjp(dense, q, k, v)[1](g),
            q, k, k, jnp.ones((2, 2, 24, 8), jnp.bfloat16),
        )
        fed_scores = [
            e for e in backward
            if scores in [v.aval.shape for v in e.invars]
        ]
        assert len(fed_scores) == 4  # the forward's PV, then dv, dq and dk
        for e in fed_scores:
            assert {v.aval.dtype for v in e.invars} == {jnp.dtype(jnp.bfloat16)}


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla_path(self, rng, causal):
        q = jnp.asarray(rng.standard_normal((2, 2, 67, 16)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 2, 67, 16)), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 2, 67, 16)), dtype=jnp.float32)
        mask = make_causal_mask(67) if causal else None
        expected = scaled_dot_product_attention(q, k, v, mask)
        got = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-3)

    def test_cross_lengths(self, rng):
        q = jnp.asarray(rng.standard_normal((1, 2, 20, 8)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 150, 8)), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 150, 8)), dtype=jnp.float32)
        expected = scaled_dot_product_attention(q, k, v)
        got = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-3)

    def test_rectangular_causal(self, rng):
        # Decode-style: few queries over a long key history; bottom-right
        # aligned diagonal must match the mask-based XLA path.
        from machine_learning_apache_spark_tpu.ops.attention import dot_product_attention

        q = jnp.asarray(rng.standard_normal((1, 2, 4, 8)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 20, 8)), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 20, 8)), dtype=jnp.float32)
        expected = scaled_dot_product_attention(q, k, v, make_causal_mask(4, 20))
        got_xla = dot_product_attention(q, k, v, causal=True, use_pallas=False)
        got_flash = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got_xla), np.asarray(expected), atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_flash), np.asarray(expected), atol=2e-3)

    def test_kv_valid_matches_padding_mask(self, rng):
        """Per-key validity streamed through the kernel == dense padding
        mask (the MT model's src/cross mask case)."""
        b, h, s, d = 2, 2, 40, 8
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        lengths = jnp.asarray([25, 40])
        kv_valid = jnp.arange(s)[None, :] < lengths[:, None]
        expected = scaled_dot_product_attention(
            q, k, v, kv_valid[:, None, None, :]
        )
        got = flash_attention(q, k, v, kv_valid=kv_valid, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-3)

    def test_kv_valid_with_causal(self, rng):
        b, h, s, d = 2, 2, 24, 8
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        k, v = q * 0.9, q * 1.1
        kv_valid = jnp.arange(s)[None, :] < jnp.asarray([[16], [24]])[:, 0][:, None]
        from machine_learning_apache_spark_tpu.ops.masks import combine_masks

        dense = combine_masks(make_causal_mask(s), kv_valid[:, None, None, :])
        expected = scaled_dot_product_attention(q, k, v, dense)
        got = flash_attention(
            q, k, v, causal=True, kv_valid=kv_valid, interpret=True
        )
        # Every query row (including real rows past the key-padding boundary,
        # which attend only keys 0..15 — the causal∧kv_valid interaction)
        # has key 0 valid, so the dense reference is well-defined everywhere:
        # compare the full tensors.
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), atol=2e-3
        )

    def test_fully_masked_rows_emit_zeros(self, rng):
        """A batch row with zero valid keys must emit zeros, never
        mean-of-V (the exp(-inf - -inf) = 1 accumulator trap)."""
        b, h, s, d = 2, 2, 16, 8
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        kv_valid = jnp.stack(
            [jnp.zeros(s, bool), jnp.ones(s, bool)]
        )  # batch 0: nothing valid
        got = flash_attention(q, q, q, kv_valid=kv_valid, interpret=True)
        np.testing.assert_array_equal(np.asarray(got)[0], 0.0)
        # batch 1 unaffected
        expected = scaled_dot_product_attention(q[1:], q[1:], q[1:])
        np.testing.assert_allclose(
            np.asarray(got)[1:], np.asarray(expected), atol=2e-3
        )

    def test_kv_valid_bad_shape_rejected(self, rng):
        q = jnp.ones((2, 2, 8, 8))
        with pytest.raises(ValueError, match="kv_valid"):
            flash_attention(
                q, q, q, kv_valid=jnp.ones((2, 9), bool), interpret=True
            )

    def test_dot_product_attention_structured_dispatch(self, rng):
        """kv_valid + causal through the public entry point (XLA path) ==
        hand-built dense mask."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
        )
        from machine_learning_apache_spark_tpu.ops.masks import combine_masks

        q = jnp.asarray(rng.standard_normal((2, 2, 12, 8)), dtype=jnp.float32)
        kv_valid = jnp.arange(12)[None, :] < jnp.asarray([8, 12])[:, None]
        dense = combine_masks(make_causal_mask(12), kv_valid[:, None, None, :])
        expected = scaled_dot_product_attention(q, q, q, dense)
        got = dot_product_attention(
            q, q, q, causal=True, kv_valid=kv_valid, use_pallas=False
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)

    def test_multi_block(self, rng):
        # Sequence long enough to exercise >1 q and k block.
        q = jnp.asarray(rng.standard_normal((1, 1, 300, 8)), dtype=jnp.float32)
        k, v = q + 0.1, q - 0.1
        expected = scaled_dot_product_attention(q, k, v, make_causal_mask(300))
        got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-3)


class TestFlashBackward:
    """The Pallas flash-2 backward (blockwise dq/dk/dv from saved lse):
    grads must match the dense XLA path on shapes above the pallas-backward
    threshold, across structured-mask configurations."""

    SHAPE = (1, 2, 512, 32)  # 512×512 scores ≥ FLASH_MIN_SCORES

    def _grads(self, fn, *args):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)
        )(*args)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("use_valid", [False, True])
    def test_grads_match_dense(self, rng, causal, use_valid):
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
        )
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            _use_pallas_bwd,
        )

        b, h, s, d = self.SHAPE
        assert _use_pallas_bwd(s, s), "shape must exercise the pallas backward"
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        kv_valid = (
            jnp.asarray(rng.random((b, s)) < 0.8) if use_valid else None
        )
        flash = self._grads(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, kv_valid=kv_valid, interpret=True
            ),
            q, k, v,
        )
        dense = self._grads(
            lambda q, k, v: dot_product_attention(
                q, k, v, causal=causal, kv_valid=kv_valid, use_pallas=False
            ),
            q, k, v,
        )
        for name, a, e in zip("qkv", flash, dense):
            scale = float(jnp.max(jnp.abs(e))) + 1e-9
            err = float(jnp.max(jnp.abs(a - e))) / scale
            assert err < 1e-4, f"d{name} relative error {err}"

    def test_masked_key_grads_are_zero(self, rng):
        """dk/dv at kv_valid=False positions must be exactly zero — the
        output doesn't depend on masked keys, so neither may the grads."""
        b, h, s, d = self.SHAPE
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=jnp.float32)
        kv_valid = jnp.arange(s)[None, :] < (s // 2)
        kv_valid = jnp.broadcast_to(kv_valid, (b, s))
        _, dk, dv = self._grads(
            lambda q, k, v: flash_attention(
                q, k, v, kv_valid=kv_valid, interpret=True
            ),
            q, q * 0.9, q * 1.1,
        )
        np.testing.assert_array_equal(np.asarray(dk)[:, :, s // 2 :], 0.0)
        np.testing.assert_array_equal(np.asarray(dv)[:, :, s // 2 :], 0.0)

    def test_small_shapes_use_dense_fallback(self, rng):
        """Below the threshold the dense recompute path must stay exact."""
        q = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), dtype=jnp.float32)
        flash = self._grads(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True),
            q, q + 0.1, q - 0.1,
        )
        dense = self._grads(
            lambda q, k, v: scaled_dot_product_attention(
                q, k, v, make_causal_mask(64)
            ),
            q, q + 0.1, q - 0.1,
        )
        for a, e in zip(flash, dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=1e-4)


class TestFlashPerShardLaunch:
    """A Mosaic custom call is opaque to the SPMD partitioner (on the chip
    an unwrapped kernel under a sharded batch fails to compile), so under
    ``kernel_mesh(mesh)`` the launchers run inside a ``shard_map`` over the
    mesh's data/model axes. On the CPU mesh the kernels interpret, but the
    wrapping is the same program structure the chip compiles."""

    def _mesh(self):
        import jax
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))

    @pytest.mark.parametrize(
        "seq,spec",
        [(64, ("data",)), (512, ("data", "model")), (512, ())],
        ids=["batch-sharded", "batch+heads-pallas-bwd", "replicated"],
    )
    def test_sharded_inputs_match_unsharded(self, seq, spec):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from machine_learning_apache_spark_tpu.ops.attention import (
            kernel_mesh,
        )
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            flash_attention,
        )

        mesh = self._mesh()
        b, h, d = 8, 2, 32
        q, k, v = (
            jax.random.normal(jax.random.key(i), (b, h, seq, d), jnp.float32)
            for i in range(3)
        )
        valid = jnp.arange(seq)[None, :] < (seq - 7 * jnp.arange(b)[:, None])

        def loss(q, k, v, valid):
            out = flash_attention(
                q, k, v, causal=True, kv_valid=valid, interpret=True
            )
            return (out ** 2).sum()

        want = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v, valid)
        sharded = NamedSharding(mesh, P(*spec))
        args = (
            *(jax.device_put(a, sharded) for a in (q, k, v)),
            jax.device_put(valid, NamedSharding(mesh, P(*spec[:1]))),
        )
        f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        with kernel_mesh(mesh):  # tracing happens inside
            got = f(*args)
            text = f.lower(*args).compile().as_text()
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
        if spec == ("data", "model"):
            # inputs already laid out the way the launch shards them:
            # nothing is gathered, each device works on its own block
            assert "all-gather" not in text
            assert got[0].sharding.is_equivalent_to(sharded, 4)

    def test_inside_a_manual_shard_map_the_kernel_is_called_directly(self):
        """The ZeRO-1 / data-parallel steps are already fully manual; the
        launcher must not nest a second shard_map over the same axes."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from machine_learning_apache_spark_tpu.ops.attention import (
            kernel_mesh,
        )
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            flash_attention,
        )

        mesh = self._mesh()
        q = jax.random.normal(jax.random.key(0), (8, 2, 64, 32), jnp.float32)
        body = lambda q: flash_attention(q, q, q, causal=True, interpret=True)
        spec = P("data", "model")
        with kernel_mesh(mesh):
            got = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=spec, out_specs=spec
            ))(q)
        np.testing.assert_allclose(got, body(q), rtol=1e-5, atol=1e-6)


def _flash_tile_records(q_shape):
    """The ``flash_tiles`` dispatch records whose padded, flattened query
    operand is ``q_shape``."""
    from machine_learning_apache_spark_tpu import telemetry

    return [
        e.attrs for e in telemetry.get_log().snapshot()
        if e.kind == "annotation" and e.name == "ops.attention_dispatch"
        and e.attrs["site"] == "flash_tiles"
        and tuple(e.attrs["q"]) == tuple(q_shape)
    ]


class TestFlashTiling:
    """The tiles of the three flash kernels come from the launch's shapes
    (``_choose_tiling``): what the chooser picks, that any tiling computes
    the same attention, and that a launch says which one it ran."""

    @pytest.mark.parametrize("length", [200, 256])
    @pytest.mark.parametrize("d_pad,itemsize", [(128, 2), (128, 4), (256, 2)])
    def test_short_site_is_one_tile_a_side(self, length, d_pad, itemsize):
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            _choose_tiling,
        )

        t = _choose_tiling(length, length, d_pad, itemsize)
        assert (t.q_pad, t.k_pad) == (256, 256)
        assert t.fwd == t.dq == t.dkv == (256, 256)

    @pytest.mark.parametrize("length", [4096, 8192, 1100, 2304])
    @pytest.mark.parametrize(
        "d_pad,itemsize", [(128, 2), (128, 4), (256, 2), (256, 4)]
    )
    def test_tiles_fit_the_budget_and_divide_the_padded_sides(
        self, length, d_pad, itemsize
    ):
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            VMEM_BUDGET,
            _choose_tiling,
            _vmem_bytes,
        )

        t = _choose_tiling(length, length, d_pad, itemsize)
        base = -(-length // 128) * 128
        for pad in (t.q_pad, t.k_pad):
            # a side grows by at most an eighth over its padding to 128
            assert base <= pad <= base + base // 8
        for kernel in ("fwd", "dq", "dkv"):
            bq, bk = getattr(t, kernel)
            assert bq % 128 == 0 and bk % 128 == 0
            # one q_pad for all three kernels: the backward reads the
            # forward's lse at that length
            assert t.q_pad % bq == 0 and t.k_pad % bk == 0
            assert _vmem_bytes(kernel, bq, bk, d_pad, itemsize) <= VMEM_BUDGET
        if length >= 4096:
            # long sites leave the 128 x 128 tile behind in every kernel
            assert min(min(getattr(t, k)) for k in ("fwd", "dq", "dkv")) >= 256

    @pytest.mark.parametrize(
        "q_len,kv_len,block_q,block_k,want_pads,want_tile",
        [
            (300, 300, 128, 128, (384, 384), (128, 128)),
            (4096, 4096, 256, 512, (4096, 4096), (256, 512)),
            (20, 150, 128, 128, (24, 256), (24, 128)),  # clamped as it was
            (1100, 1100, 512, 128, (1536, 1152), (512, 128)),
        ],
    )
    def test_explicit_tiles_are_honoured(
        self, q_len, kv_len, block_q, block_k, want_pads, want_tile
    ):
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            _choose_tiling,
        )

        t = _choose_tiling(q_len, kv_len, 128, 4, block_q, block_k)
        assert (t.q_pad, t.k_pad) == want_pads
        assert t.fwd == t.dq == t.dkv == want_tile

    def test_one_side_given_the_other_chosen(self):
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            _choose_tiling,
        )

        t = _choose_tiling(4096, 4096, 128, 2, block_q=128)
        assert {t.fwd[0], t.dq[0], t.dkv[0]} == {128}
        assert min(t.fwd[1], t.dq[1], t.dkv[1]) >= 512

    def test_short_query_side_keeps_its_multiple_of_eight(self):
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            _choose_tiling,
        )

        t = _choose_tiling(67, 67, 128, 4)
        assert (t.q_pad, t.k_pad) == (72, 128)
        assert t.fwd == t.dq == t.dkv == (72, 128)
        t = _choose_tiling(1, 4096, 128, 2)  # a decode step over a history
        assert t.q_pad == 8 and t.fwd[0] == 8 and t.fwd[1] >= 512

    # (q_len, kv_len, heads, d, causal, kv_valid, tiles, against the dense
    # path too). Lengths of 1,024 and more give several tiles a side under
    # the chooser or under the explicit rectangles.
    PARITY = {
        "chosen-causal-1024x128": (1024, 1024, 2, 128, True, False, None, True),
        "chosen-full-1024x128": (1024, 1024, 2, 128, False, False, None, True),
        "chosen-causal-1024x256": (1024, 1024, 1, 256, True, False, None, True),
        "chosen-causal-2048": (2048, 2048, 1, 32, True, False, None, True),
        "chosen-causal-valid": (1024, 1024, 2, 32, True, True, None, True),
        "chosen-full-valid-1100": (1100, 1100, 2, 32, False, True, None, True),
        "chosen-causal-1100": (1100, 1100, 2, 32, True, False, None, True),
        "chosen-causal-q640-kv1024": (640, 1024, 2, 32, True, False, None, True),
        # query rows before the diagonal's start see no key: the kernel
        # emits zeros there and the dense path an average, so kernel only
        "chosen-causal-q1024-kv640": (1024, 640, 2, 32, True, False, None, False),
        "256x512-causal": (1024, 1024, 2, 32, True, False, (256, 512), True),
        "512x256-causal-q768": (768, 1024, 2, 32, True, False, (512, 256), True),
        "256x256-causal-1100": (1100, 1100, 1, 32, True, False, (256, 256), True),
        "512x256-full": (1024, 1024, 1, 32, False, False, (512, 256), True),
    }

    @pytest.mark.parametrize("case", list(PARITY))
    def test_any_tiling_is_the_same_attention(self, rng, case):
        """Forward and ``jax.grad`` of the chosen (or a rectangular) tiling
        against explicit 128 x 128 tiles and against the dense path."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            dot_product_attention,
        )

        q_len, kv_len, h, d, causal, use_valid, tiles, dense = self.PARITY[case]
        q = jnp.asarray(rng.standard_normal((1, h, q_len, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, h, kv_len, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, h, kv_len, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((1, h, q_len, d)), jnp.float32)
        kv_valid = (
            jnp.asarray(rng.random((1, kv_len)) < 0.8).at[:, 0].set(True)
            if use_valid else None
        )
        block_q, block_k = tiles or (None, None)

        def both(attend):
            def loss(q, k, v):
                out = attend(q, k, v)
                return jnp.sum(out * w), out

            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True
            )(q, k, v)
            return out, grads

        def flash(bq, bk):
            return both(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, kv_valid=kv_valid, block_q=bq,
                block_k=bk, interpret=True,
            ))

        got = flash(block_q, block_k)
        refs = [flash(128, 128)]
        if dense:
            refs.append(both(lambda q, k, v: dot_product_attention(
                q, k, v, causal=causal, kv_valid=kv_valid, use_pallas=False
            )))
        for want in refs:
            np.testing.assert_allclose(got[0], want[0], atol=2e-5)
            for name, a, e in zip("qkv", got[1], want[1]):
                scale = float(jnp.max(jnp.abs(e))) + 1e-9
                err = float(jnp.max(jnp.abs(a - e))) / scale
                assert err < 1e-4, f"d{name} relative error {err}"

    def test_launch_records_tiles_grid_and_vmem(self, rng):
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            _choose_tiling,
            _vmem_bytes,
        )

        # a shape no other test launches: the record is left while tracing
        b, h, s, d = 1, 3, 520, 24
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        jax.grad(lambda q: jnp.sum(
            flash_attention(q, q, q, causal=True, interpret=True)
        ))(q)
        t = _choose_tiling(s, s, 128, 4)
        records = _flash_tile_records((b * h, t.q_pad, 128))
        forward = [r for r in records if r["impl"] == "forward"][-1]
        backward = [r for r in records if r["impl"] == "backward"][-1]
        bq, bk = t.fwd
        grid = (b * h, t.q_pad // bq, t.k_pad // bk)
        assert forward["kernels"]["flash_fwd"] == dict(
            block_q=bq, block_k=bk, grid=grid,
            vmem_bytes=_vmem_bytes("fwd", bq, bk, 128, 4),
        )
        assert forward["reason"].startswith(
            f"flash_fwd {bq}x{bk} grid {'x'.join(map(str, grid))} vmem "
        )
        assert forward["reason"].endswith(
            f"of [{b * h},{t.q_pad},128] x [{b * h},{t.k_pad},128] "
            "float32 causal"
        )
        assert set(backward["kernels"]) == {"flash_bwd_dq", "flash_bwd_dkv"}
        bq, bk = t.dkv
        assert backward["kernels"]["flash_bwd_dkv"]["grid"] == (
            b * h, t.k_pad // bk, t.q_pad // bq
        )
        assert "flash_bwd_dq " in backward["reason"]
        assert "; flash_bwd_dkv " in backward["reason"]

    def test_per_shard_launch_chooses_on_the_shard(self):
        """Under ``kernel_mesh`` the launcher runs inside the ``shard_map``:
        the record's grid is the shard's B/n x H/m heads, its tiles those of
        the shard's lengths."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from machine_learning_apache_spark_tpu.ops.attention import (
            kernel_mesh,
        )
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            _choose_tiling,
        )

        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        b, h, s, d = 8, 2, 528, 40  # a shape of this test's own
        q = jax.device_put(
            jax.random.normal(jax.random.key(0), (b, h, s, d), jnp.float32),
            NamedSharding(mesh, P("data", "model")),
        )
        with kernel_mesh(mesh):
            jax.jit(jax.grad(lambda q: jnp.sum(
                flash_attention(q, q, q, causal=True, interpret=True)
            )))(q)
        t = _choose_tiling(s, s, 128, 4)
        shard_rows = (b // 4) * (h // 2)
        records = _flash_tile_records((shard_rows, t.q_pad, 128))
        assert {r["impl"] for r in records} == {"forward", "backward"}
        for r in records:
            for kernel in r["kernels"].values():
                assert kernel["grid"][0] == shard_rows
        assert not _flash_tile_records((b * h, t.q_pad, 128))


class TestRaggedPagedAttention:
    """Decode-step attention over a paged KV store: the XLA gather
    fallback (CPU tier-1 route), the Pallas kernel in interpret mode, and
    a naive per-row dense reference must all agree over arbitrary
    raggedness — zero-length rows, partial pages, full tables, shared
    prefix pages."""

    R, H, DH, PAGE, P = 5, 2, 8, 4, 6  # rows, heads, head_dim, page, pages/row

    def _setup(self, seed=0):
        rng = np.random.default_rng(seed)
        d_model = self.H * self.DH
        num_pages = 1 + self.R * self.P
        k_pages = rng.normal(size=(num_pages, self.PAGE, d_model))
        v_pages = rng.normal(size=(num_pages, self.PAGE, d_model))
        # ragged lengths: inactive, sub-page, exact page, mid-table, full
        lengths = np.array(
            [0, 1, self.PAGE, 2 * self.PAGE + 3, self.P * self.PAGE],
            np.int32,
        )
        table = np.zeros((self.R, self.P), np.int32)
        next_page = 1
        for r in range(self.R):
            used = -(-int(lengths[r]) // self.PAGE)
            for p in range(used):
                table[r, p] = next_page
                next_page += 1
        query = rng.normal(size=(self.R, self.H, self.DH))
        cur_k = rng.normal(size=(self.R, d_model))
        cur_v = rng.normal(size=(self.R, d_model))
        f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
        return (
            f32(query), f32(k_pages), f32(v_pages),
            jnp.asarray(table), jnp.asarray(lengths),
            f32(cur_k), f32(cur_v),
        )

    def _dense_reference(self, q, k_pages, v_pages, table, lengths,
                         cur_k, cur_v):
        q, k_pages, v_pages = map(np.asarray, (q, k_pages, v_pages))
        table, lengths = np.asarray(table), np.asarray(lengths)
        out = np.zeros_like(q)
        for r in range(self.R):
            ln = int(lengths[r])
            rows_k = np.concatenate(
                [k_pages[table[r, p]] for p in range(self.P)]
            )[:ln]
            rows_v = np.concatenate(
                [v_pages[table[r, p]] for p in range(self.P)]
            )[:ln]
            if cur_k is not None:
                rows_k = np.concatenate([rows_k, np.asarray(cur_k)[r : r + 1]])
                rows_v = np.concatenate([rows_v, np.asarray(cur_v)[r : r + 1]])
            if rows_k.shape[0] == 0:
                continue  # inactive row, no current token: zeros
            for h in range(self.H):
                sl = slice(h * self.DH, (h + 1) * self.DH)
                s = rows_k[:, sl] @ q[r, h] / np.sqrt(self.DH)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[r, h] = p @ rows_v[:, sl]
        return out

    @pytest.mark.parametrize("with_cur", [True, False])
    def test_fallback_matches_dense_reference(self, with_cur):
        from machine_learning_apache_spark_tpu.ops.attention import (
            ragged_paged_attention,
        )

        q, kp, vp, tbl, lens, ck, cv = self._setup()
        if not with_cur:
            ck = cv = None
        got = ragged_paged_attention(
            q, kp, vp, tbl, lens, cur_k=ck, cur_v=cv, use_pallas=False
        )
        want = self._dense_reference(q, kp, vp, tbl, lens, ck, cv)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)

    @pytest.mark.parametrize("with_cur", [True, False])
    def test_kernel_interpret_matches_fallback(self, with_cur):
        """The Pallas kernel (interpret mode on CPU) and the XLA gather
        fallback are the same function — the bit-equivalence contract
        that lets CPU tier-1 stand in for the TPU path."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            ragged_paged_attention,
        )

        q, kp, vp, tbl, lens, ck, cv = self._setup(seed=1)
        if not with_cur:
            ck = cv = None
        fb = ragged_paged_attention(
            q, kp, vp, tbl, lens, cur_k=ck, cur_v=cv, use_pallas=False
        )
        kern = ragged_paged_attention(
            q, kp, vp, tbl, lens, cur_k=ck, cur_v=cv,
            use_pallas=True, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(fb), atol=2e-5
        )

    def test_inactive_row_emits_zeros(self):
        from machine_learning_apache_spark_tpu.ops.attention import (
            ragged_paged_attention,
        )

        q, kp, vp, tbl, lens, _, _ = self._setup()
        out = ragged_paged_attention(q, kp, vp, tbl, lens, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(out[0]), 0.0)

    def test_shared_prefix_pages_give_identical_outputs(self):
        """Two rows whose block tables point at the same physical pages
        (prefix sharing) attend identical KV — the numerical basis for
        refcounted page reuse."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            ragged_paged_attention,
        )

        q, kp, vp, tbl, lens, _, _ = self._setup()
        tbl = np.asarray(tbl).copy()
        lens = np.asarray(lens).copy()
        tbl[1] = tbl[4]  # row 1 shares row 4's pages
        lens[1] = lens[4]
        q = jnp.asarray(np.asarray(q).copy())
        q = q.at[1].set(q[4])
        out = ragged_paged_attention(
            q, kp, vp, jnp.asarray(tbl), jnp.asarray(lens), use_pallas=False
        )
        np.testing.assert_allclose(
            np.asarray(out[1]), np.asarray(out[4]), atol=1e-6
        )

    # -- quantized (int8) pages ----------------------------------------------

    def _quantize_pages(self, pages):
        """Per-page absmax int8 quantization, per-slot scale layout —
        the same scheme the paged runtime writes: one scale per page,
        broadcast to every slot so the kernel's [page_size] scale row
        dequantizes either granularity."""
        pages = np.asarray(pages)
        absmax = np.abs(pages).max(axis=(1, 2))
        scale = np.maximum(absmax / 127.0, 1e-30).astype(np.float32)
        q = np.clip(
            np.round(pages / scale[:, None, None]), -127, 127
        ).astype(np.int8)
        slot_scale = np.broadcast_to(
            scale[:, None], pages.shape[:2]
        ).astype(np.float32)
        return jnp.asarray(q), jnp.asarray(np.ascontiguousarray(slot_scale))

    def test_int8_quantization_round_trip_bound(self):
        """Dequantized int8 pages sit within half a quantization step
        (absmax/254) of the fp32 original — the error budget every
        downstream accuracy claim rests on."""
        _, kp, _, _, _, _, _ = self._setup()
        qk, ks = self._quantize_pages(kp)
        deq = np.asarray(qk, np.float32) * np.asarray(ks)[..., None]
        err = np.abs(deq - np.asarray(kp))
        step = np.abs(np.asarray(kp)).max(axis=(1, 2)) / 127.0
        assert (err <= step[:, None, None] * 0.5 + 1e-7).all()

    def test_int8_scales_must_come_in_pairs(self):
        from machine_learning_apache_spark_tpu.ops.attention import (
            ragged_paged_attention,
        )

        q, kp, vp, tbl, lens, _, _ = self._setup()
        qk, ks = self._quantize_pages(kp)
        qv, _ = self._quantize_pages(vp)
        with pytest.raises(ValueError, match="k_scale and v_scale"):
            ragged_paged_attention(
                q, qk, qv, tbl, lens, k_scale=ks, use_pallas=False
            )

    @pytest.mark.parametrize("with_cur", [True, False])
    def test_int8_fallback_matches_dequantized_reference(self, with_cur):
        """int8 pages + per-slot scales through the fallback must equal
        the dense reference run on the dequantized fp32 pages — in-
        kernel dequantization is positioned before the dots, so the two
        orderings agree to float rounding."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            ragged_paged_attention,
        )

        q, kp, vp, tbl, lens, ck, cv = self._setup(seed=2)
        if not with_cur:
            ck = cv = None
        qk, ks = self._quantize_pages(kp)
        qv, vs = self._quantize_pages(vp)
        got = ragged_paged_attention(
            q, qk, qv, tbl, lens, cur_k=ck, cur_v=cv,
            k_scale=ks, v_scale=vs, use_pallas=False,
        )
        deq_k = jnp.asarray(
            np.asarray(qk, np.float32) * np.asarray(ks)[..., None]
        )
        deq_v = jnp.asarray(
            np.asarray(qv, np.float32) * np.asarray(vs)[..., None]
        )
        want = self._dense_reference(q, deq_k, deq_v, tbl, lens, ck, cv)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)

    @pytest.mark.parametrize("with_cur", [True, False])
    def test_int8_kernel_interpret_matches_fallback(self, with_cur):
        """The Pallas kernel's in-kernel dequant (interpret mode) and
        the XLA fallback's gather-then-dequant are the same function on
        int8 pages — extending the CPU-stands-in-for-TPU contract to
        the quantized plane."""
        from machine_learning_apache_spark_tpu.ops.attention import (
            ragged_paged_attention,
        )

        q, kp, vp, tbl, lens, ck, cv = self._setup(seed=3)
        if not with_cur:
            ck = cv = None
        qk, ks = self._quantize_pages(kp)
        qv, vs = self._quantize_pages(vp)
        fb = ragged_paged_attention(
            q, qk, qv, tbl, lens, cur_k=ck, cur_v=cv,
            k_scale=ks, v_scale=vs, use_pallas=False,
        )
        kern = ragged_paged_attention(
            q, qk, qv, tbl, lens, cur_k=ck, cur_v=cv,
            k_scale=ks, v_scale=vs, use_pallas=True, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(fb), atol=2e-5
        )
