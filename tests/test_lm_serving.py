"""The decoder-only runtime (``serving/lm_runtime.py``) and ``LanguageModel``
behind ``ServingEngine``, at a toy size on the CPU, against the plain float32
reference (``benchmark/reference/sala_lm.py``)."""

import copy

import jax
import numpy as np
import pytest

from benchmark import manifest, weights_sala_lm
from benchmark.kinds import serve_lm
from benchmark.reference import sala_lm as ref
from machine_learning_apache_spark_tpu.inference import LanguageModel
from machine_learning_apache_spark_tpu.serving import ServingEngine
from machine_learning_apache_spark_tpu.serving.lm_runtime import LMDecodeRuntime
from machine_learning_apache_spark_tpu.serving.paged_runtime import (
    PagedDecodeRuntime,
)
from machine_learning_apache_spark_tpu.serving.queue import ServeRequest

NEW = 8


@pytest.fixture(scope="module")
def toy():
    """The cell's own configuration at the rehearsal's widths, float32 so
    that the comparison with the reference is to rounding."""
    m = manifest.load_manifest()
    cfg = copy.deepcopy(manifest.load_config(m, "minicpm_sala_9b"))
    serve_lm.toy(cfg, copy.deepcopy(manifest.load_traffic("open_loop_doc_qa")), {})
    cfg["weight_dtype"] = "float32"
    cfg["eos_token_id"] = None
    params = weights_sala_lm.make_params(2**31 + 5, cfg)
    return cfg, weights_sala_lm.model_config(cfg), params


def _runtime(toy, **kw):
    cfg, model, params = toy
    kw = dict(dict(
        max_active=3, max_context=448, max_new_tokens=NEW, prefill_chunk=32,
        steps_per_launch=4, num_pages=160, snapshot_capacity=4,
    ), **kw)
    return LMDecodeRuntime(model, params, **kw)


def _request(ids):
    return ServeRequest(text="", ids=np.asarray(ids, np.int32), submit_time=0.0)


def _decode(rt, prompts, rows=None):
    """Admit ``prompts`` on ``rows``, launch to the end; tokens, logits and
    admissions a prompt."""
    rows = list(range(len(prompts))) if rows is None else rows
    reqs = [_request(p) for p in prompts]
    admitted = [rt.admit(r, row) for r, row in zip(reqs, rows)]
    logits = {r.id: [] for r in reqs}
    tokens = {}
    row_of = {r.id: row for r, row in zip(reqs, rows)}
    while rt.any_active():
        assert rt.grow() == []
        active = [req for _, req in rt.active_rows()]
        result = rt.launch(logits_of=rows)
        got = np.asarray(rt.captured[0])
        for req in active:
            logits[req.id].append(got[:, rows.index(row_of[req.id])])
        for req, ids, row, _ in result.completed:
            rt.retire(row)
            tokens[req.id] = np.asarray(ids)
    return (
        [tokens[r.id] for r in reqs],
        [np.concatenate(logits[r.id])[:NEW] for r in reqs],
        admitted,
    )


def _reference_logits(toy, prompt, served):
    cfg, _, params = toy
    n = len(prompt)
    with jax.default_matmul_precision("highest"):
        logits, _ = ref.forward(
            params, cfg, np.concatenate([prompt, served[:-1]]),
            np.arange(n - 1, n - 1 + len(served)), t_max=448, block=64,
            dense=n + NEW < cfg["sparse_config"]["dense_len"], query_rows=16,
        )
    return logits


@pytest.mark.parametrize("length", [40, 150, 301])
def test_prefill_then_decode_equals_the_references_full_forward(toy, length):
    """Through pages and state, on both sides of ``dense_len`` (128)."""
    rt = _runtime(toy)
    prompt = np.random.default_rng(length).integers(0, 128, length)
    (tokens,), (logits,), _ = _decode(rt, [prompt])
    assert len(tokens) == NEW
    want = _reference_logits(toy, prompt, tokens)
    np.testing.assert_allclose(logits, want, atol=5e-5)
    np.testing.assert_array_equal(np.argmax(want, -1), tokens)


def test_a_prefix_hit_gives_the_logits_of_a_cold_prefill(toy):
    rng = np.random.default_rng(0)
    doc = rng.integers(0, 128, 200)
    ask = np.concatenate([doc, rng.integers(0, 128, 17)])
    cold = _runtime(toy, snapshot_capacity=0)
    (cold_tokens,), (cold_logits,), (cold_admit,) = _decode(cold, [ask])
    assert cold_admit[0] == "miss"
    warm = _runtime(toy)
    _decode(warm, [doc])  # leaves the document's snapshot
    assert warm.prefix_cache.stats()["entries"] == 1
    pages_of_doc = warm.mem_pool.in_use
    (tokens,), (logits,), (admit,) = _decode(warm, [ask])
    assert admit[0] == "hit" and admit[1] == 32  # one chunk for the rest
    assert warm.counters["resumed_tokens"] == 192  # the last page boundary
    np.testing.assert_array_equal(tokens, cold_tokens)
    np.testing.assert_allclose(logits, cold_logits, atol=5e-5)
    # the document's pages were shared, not copied: the hit held only its own
    assert warm.mem_pool.high_water <= pages_of_doc + 6


def test_two_rows_sharing_a_documents_pages_do_not_disturb_each_other(toy):
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 128, 160)
    asks = [np.concatenate([doc, rng.integers(0, 128, n)]) for n in (9, 30)]
    alone = []
    for ask in asks:
        rt = _runtime(toy)
        _decode(rt, [doc])
        alone.append(_decode(rt, [ask]))
    rt = _runtime(toy)
    _decode(rt, [doc])
    tokens, logits, admitted = _decode(rt, asks, rows=[2, 0])
    assert [a[0] for a in admitted] == ["hit", "hit"]
    shared = rt.prefix_cache.stats()["resident_pages"]
    assert shared >= 160 // 8 - 1
    for i in range(2):
        np.testing.assert_array_equal(tokens[i], alone[i][0][0])
        np.testing.assert_allclose(logits[i], alone[i][1][0], atol=5e-5)
    assert rt.stats()["active_rows"] == 0
    # once the rows have retired only the snapshots hold pages
    assert rt.mem_pool.in_use == len({
        p for e in rt.prefix_cache._entries.values() for p in e["pages"]
    })


def test_one_launch_program_for_any_occupancy(toy):
    from machine_learning_apache_spark_tpu.utils.compilation_cache import (
        jit_cache_size,
    )

    rt = _runtime(toy)
    assert rt.warmup() == 4
    counts = [jit_cache_size(f) for f in rt.jit_fns()]
    assert counts == [1, 1, 1, 1]
    rng = np.random.default_rng(2)
    _decode(rt, [rng.integers(0, 128, 50)])
    _decode(rt, [rng.integers(0, 128, n) for n in (20, 170, 333)])
    _decode(rt, [rng.integers(0, 128, 90), rng.integers(0, 128, 31)], rows=[1, 2])
    assert [jit_cache_size(f) for f in rt.jit_fns()] == counts


def test_snapshots_are_evicted_lru_and_their_pages_freed(toy):
    rt = _runtime(toy, snapshot_capacity=2)
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, 128, 100) for _ in range(3)]
    for d in docs:
        _decode(rt, [d])
    stats = rt.prefix_cache.stats()
    assert stats["entries"] == 2 and stats["evictions"] == 1
    assert rt.prefix_cache.match_length(np.asarray(docs[0], np.int32), 99) == 0
    assert rt.prefix_cache.match_length(np.asarray(docs[2], np.int32), 99) == 96
    assert rt.mem_pool.in_use == stats["resident_pages"]


def test_reset_keeps_the_compiled_programs(toy):
    rt = _runtime(toy)
    rt.warmup()
    req = _request(np.arange(60) % 128)
    rt.admit(req, 0)
    assert rt.reset() == [req]
    assert rt.mem_pool.in_use == 0 and not rt.any_active()
    from machine_learning_apache_spark_tpu.utils.compilation_cache import (
        jit_cache_size,
    )

    _decode(rt, [np.arange(60) % 128])
    assert [jit_cache_size(f) for f in rt.jit_fns()] == [1, 1, 1, 1]


def test_the_serving_launch_hands_back_no_logits(toy):
    """The launch the engine runs returns tokens alone; ``launch(logits_of=)``
    is a program of its own, compiled when first asked for, and both emit the
    same tokens."""
    from machine_learning_apache_spark_tpu.utils.compilation_cache import (
        jit_cache_size,
    )

    rt = _runtime(toy)
    prompt = np.arange(75) % 128
    rt.admit(_request(prompt), 1)
    plain = []
    while rt.any_active():
        assert rt.grow() == []
        for _, ids, row, _ in rt.launch().completed:
            rt.retire(row)
            plain = ids
        assert rt.captured is None
    assert rt._logits_launch_fn is None
    (tokens,), (logits,), _ = _decode(rt, [prompt], rows=[2])
    np.testing.assert_array_equal(tokens, plain)
    np.testing.assert_array_equal(np.argmax(logits, -1), plain)
    assert [jit_cache_size(f) for f in rt.jit_fns()] == [1, 1, 1, 1]
    assert rt._logits_launch_fn not in rt.jit_fns()


def test_two_engines_of_one_bundle_keep_their_own_contexts(toy):
    """``serve`` leaves nothing on the bundle: the second engine's context
    does not reach the first's."""
    _, model, params = toy
    lm = LanguageModel(model, params)
    assert lm.max_positions == model.max_positions == 524288
    kw = dict(max_active=1, max_new_tokens=NEW, prefill_chunk=32,
              steps_per_launch=4, start=False)
    wide = lm.serve(max_context=256, **kw)
    narrow = lm.serve(max_context=128, **kw)
    assert wide.runtime.max_context == 256 and wide.boundaries == (256 - NEW,)
    assert narrow.runtime.max_context == 128
    with pytest.raises(ValueError, match="exceeds the model's max_len"):
        lm.serve(max_context=model.max_positions + NEW + 1, **kw)


def test_the_engine_serves_a_language_model_through_the_same_loop(toy):
    _, model, params = toy
    lm = LanguageModel(model, params)
    rng = np.random.default_rng(4)
    doc = rng.integers(0, 128, 160)
    prompts = [doc, np.concatenate([doc, rng.integers(0, 128, 21)]),
               np.concatenate([doc, rng.integers(0, 128, 9)]),
               rng.integers(0, 128, 30)]
    oracle = lm(prompts, max_new_tokens=NEW, prefill_chunk=32,
                steps_per_launch=4)
    with lm.serve(max_context=448, max_active=3, max_new_tokens=NEW,
                  prefill_chunk=32, steps_per_launch=4, num_pages=160,
                  prefix_cache_size=4, prefill_budget=64) as eng:
        assert isinstance(eng, ServingEngine)
        assert isinstance(eng.runtime, LMDecodeRuntime)
        first = eng.submit(prompts[0]).result(timeout=120)
        rest = [eng.submit(p) for p in prompts[1:]]
        outs = [first] + [r.result(timeout=120) for r in rest]
        assert eng.recompiles_after_warmup == 0
        assert eng.compile_count() == len(eng.runtime.jit_fns())
        assert [r.trace.attrs("admit")["kind"] for r in rest] == ["hit", "hit", "miss"]
        stats = eng.runtime.stats()
        assert stats["active_rows"] == 0 and stats["resumed_tokens"] == 2 * 152
        assert eng.metrics.check_conservation()["completed"] == 4
    for want, got in zip(oracle, outs):
        np.testing.assert_array_equal(want, got)


def test_the_programs_own_initialiser_gives_a_servable_model(toy):
    """``init_params`` (no benchmark weights): same tree, bfloat16 by
    default, served and one-shot alike."""
    import dataclasses

    import jax.numpy as jnp

    from machine_learning_apache_spark_tpu.models import sala_lm

    _, model, params = toy
    model = dataclasses.replace(model, dtype=jnp.dtype("bfloat16"))
    own = sala_lm.init_params(model, jax.random.key(3))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert own["lm_head"].dtype == jnp.bfloat16
    lm = LanguageModel(model, own)
    prompt = np.arange(70) % 128
    (answer,) = lm([prompt], max_new_tokens=5, prefill_chunk=32,
                   steps_per_launch=4)
    assert answer.shape == (5,) and answer.dtype == np.int32


def test_a_prompt_past_the_context_is_refused_at_submit(toy):
    _, model, params = toy
    lm = LanguageModel(model, params)
    with lm.serve(max_context=128, max_active=1, max_new_tokens=NEW,
                  prefill_chunk=32, steps_per_launch=4) as eng:
        with pytest.raises(ValueError, match="beyond the largest"):
            eng.submit(np.zeros(121, np.int32))


def test_the_bundles_build_their_own_runtimes(make_tiny_translator):
    """One engine, two runtimes: the encoder-decoder bundle still gets the
    two-store runtime, through the same ``make_runtime`` the engine calls."""
    translator, texts = make_tiny_translator(8)
    eng = translator.serve(start=False, boundaries=(8, 16), max_active=2,
                           max_new_tokens=4)
    assert isinstance(eng.runtime, PagedDecodeRuntime)
    assert eng.runtime.prefill_cost([1, 2, 3]) == eng.prefill_chunk
    with eng:
        out = eng.submit(texts[0]).result(timeout=120)
    assert out == translator([texts[0]], max_new_tokens=4)[0]
