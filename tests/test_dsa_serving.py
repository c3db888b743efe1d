"""DeepSeek-V3.2-Exp's block served (``models.dsa_lm`` through
``serving/lm_runtime.py`` and ``LanguageModel``), at a toy size on the CPU,
against the plain float32 reference (``benchmark/reference/dsa_lm.py``): the
index top-k smaller than the contexts, so that the selection matters; 8 of
16 router outputs held, in 2 groups."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, weights_dsa_lm
from benchmark.kinds import serve_dsa_lm
from benchmark.reference import dsa_lm as ref
from machine_learning_apache_spark_tpu.inference import LanguageModel
from machine_learning_apache_spark_tpu.models import dsa_lm, moe
from machine_learning_apache_spark_tpu.ops import dsa_index, latent_attention
from machine_learning_apache_spark_tpu.serving.lm_runtime import LMDecodeRuntime
from machine_learning_apache_spark_tpu.serving.queue import ServeRequest

NEW = 8


@pytest.fixture(scope="module")
def toy():
    """The cell's own configuration at the rehearsal's widths, float32 so
    that the comparison with the reference is to rounding."""
    m = manifest.load_manifest()
    cfg = copy.deepcopy(manifest.load_config(m, "deepseek_v32_exp"))
    serve_dsa_lm.toy(cfg, copy.deepcopy(manifest.load_traffic("open_loop_doc_qa_dsa")), {})
    cfg["weight_dtype"] = "float32"
    cfg["eos_token_id"] = None
    params = weights_dsa_lm.make_params(2**31 + 7, cfg)
    return cfg, weights_dsa_lm.model_config(cfg), params


def _runtime(toy, **kw):
    _, model, params = toy
    kw = dict(dict(
        max_active=3, max_context=448, max_new_tokens=NEW, prefill_chunk=32,
        steps_per_launch=4, num_pages=160, snapshot_capacity=4,
    ), **kw)
    return LMDecodeRuntime(model, params, **kw)


def _decode(rt, prompts):
    """Admit ``prompts`` on rows 0.., launch to the end; tokens, logits and
    admissions a prompt."""
    rows = list(range(len(prompts)))
    reqs = [ServeRequest(text="", ids=np.asarray(p, np.int32), submit_time=0.0)
            for p in prompts]
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
            logits[req.id].append(got[:, row_of[req.id]])
        for req, ids, row, _ in result.completed:
            rt.retire(row)
            tokens[req.id] = np.asarray(ids)
    return (
        [tokens[r.id] for r in reqs],
        [np.concatenate(logits[r.id])[:NEW] for r in reqs],
        admitted,
    )


def _reference_logits(toy, prompt, served, **variant):
    cfg, _, params = toy
    n = len(prompt)
    doc = prompt[: n * 2 // 3]  # the rest and the served tokens fit a block
    kw = dict(t_max=640, block=128, key_block=64, capacity=16, query_rows=16)
    with jax.default_matmul_precision("highest"):
        rows = ref.document_rows(params, cfg, doc, **kw)
        ((logits, _, _),) = ref.forward(
            params, cfg, rows, len(doc),
            [(np.concatenate([prompt[len(doc):], served[:-1]]),
              np.arange(n - 1, n - 1 + len(served)), None)], **kw, **variant,
        )
    return logits


@pytest.mark.parametrize("length", [30, 150, 301])
def test_prefill_then_decode_equals_the_references_full_forward(toy, length):
    """Through the latent and index pages, with contexts under and over the
    index top-k (48): compared on logits."""
    rt = _runtime(toy)
    prompt = np.random.default_rng(length).integers(0, 128, length)
    (tokens,), (logits,), _ = _decode(rt, [prompt])
    assert len(tokens) == NEW
    want = _reference_logits(toy, prompt, tokens)
    np.testing.assert_allclose(logits, want, atol=2e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.argmax(want, -1), tokens)
    # the selection matters: attending everything gives other logits
    off = _reference_logits(toy, prompt, tokens, select="all")
    if length > 48:
        assert np.max(np.abs(off - want)) > 1e-2


def test_absorbed_equals_up_projected_attention():
    """``ops.latent_attention`` over latent rows against softmax attention
    with every head's keys and values up-projected from them."""
    rng = np.random.default_rng(0)
    n, h, kv, dn, dr, dv, k = 3, 4, 16, 8, 4, 8, 10
    q_nope = jnp.asarray(rng.normal(size=(n, h, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(n, h, dr)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(n, k, kv + dr)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(size=(kv, h, dn)), jnp.float32)
    w_uv = jnp.asarray(rng.normal(size=(kv, h, dv)), jnp.float32)
    valid = jnp.asarray(rng.random((n, k)) < 0.7).at[:, 0].set(True)
    scale = 0.3
    with jax.default_matmul_precision("highest"):
        q = jnp.concatenate([latent_attention.absorb_query(q_nope, w_uk), q_rope], -1)
        o = latent_attention.expand_output(
            latent_attention.attend_rows(q, rows, valid, kv_rank=kv, scale=scale),
            w_uv, jnp.float32,
        )
        keys = jnp.einsum("nkc,chd->nkhd", rows[..., :kv], w_uk)
        values = jnp.einsum("nkc,chd->nkhd", rows[..., :kv], w_uv)
        s = (jnp.einsum("nhd,nkhd->nhk", q_nope, keys)
             + jnp.einsum("nhr,nkr->nhk", q_rope, rows[..., kv:])) * scale
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        want = jnp.einsum("nhk,nkhd->nhd", jax.nn.softmax(s, -1), values)
    np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [1, 5, 64, 300])
def test_the_index_selection_is_the_exact_top_k(k):
    """``select_top`` against a sort, with fewer selectable positions than
    ``k`` in some rows, ties and negative scores among them; the plane rows
    it gives are those of its positions, through one map for every query
    and through a map a query."""
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(4, 256)).astype(np.float32)
    scores[1, 100:] = -np.inf  # 100 selectable
    scores[2, 10:20] = scores[2, 5]  # ties
    shared = rng.permutation(1000)[None, :256].astype(np.int32)
    each = np.stack([rng.permutation(1000)[:256] for _ in range(4)]).astype(np.int32)
    for rows_map in (shared, each):
        got, rows, valid = map(np.asarray, dsa_index.select_top(
            jnp.asarray(scores), k, jnp.asarray(rows_map)
        ))
        for r in range(4):
            finite = np.flatnonzero(np.isfinite(scores[r]))
            order = finite[np.argsort(-scores[r, finite], kind="stable")][:k]
            want = np.sort(order)
            assert valid[r].sum() == len(want)
            np.testing.assert_array_equal(got[r][valid[r]], want)
            np.testing.assert_array_equal(
                rows[r][valid[r]], rows_map[r % len(rows_map)][want]
            )
            assert list(got[r][valid[r]]) == sorted(got[r][valid[r]])


def test_the_grouped_router_follows_the_published_rule():
    """``route_sigmoid_grouped`` against a numpy transcription of DeepSeek-V3's
    ``noaux_tc``: 8 experts in 2 groups, the better group kept, top 3."""
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(20, 8)).astype(np.float32)
    bias = rng.normal(scale=0.3, size=8).astype(np.float32)
    s, experts, weights = moe.route_sigmoid_grouped(
        jnp.asarray(logits), jnp.asarray(bias), 3, groups=2, groups_kept=1,
        scale=2.5,
    )
    score = 1 / (1 + np.exp(-logits))
    for t in range(20):
        biased = score[t] + bias
        groups = biased.reshape(2, 4)
        best = np.argmax(np.sort(groups, -1)[:, -2:].sum(-1))
        masked = np.full(8, -np.inf)
        masked[best * 4:(best + 1) * 4] = biased[best * 4:(best + 1) * 4]
        want = np.argsort(-masked)[:3]
        np.testing.assert_array_equal(np.asarray(experts[t]), want)
        w = score[t, want] / score[t, want].sum() * 2.5
        np.testing.assert_allclose(np.asarray(weights[t]), w, rtol=1e-6)


def test_the_held_shares_add_up_to_the_uncut_layer(toy):
    """Two chips' shares of the expert layer (experts 0-3 and 4-7 of 8) plus
    the shared expert counted once equal the reference's whole layer, every
    expert held."""
    cfg, model, params = toy
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(24, cfg["hidden_size"])),
                    jnp.float32)
    whole_cfg = dict(cfg, router_width=8, experts_held=[0, 8])
    moe_p = dict(p["moe"], router=p["moe"]["router"][:, :8], bias=p["moe"]["bias"][:8])
    whole_p = dict(p, moe=moe_p)
    with jax.default_matmul_precision("highest"):
        want = ref.feed_forward(whole_p, whole_cfg, x, group_limit=True,
                                capacity=24, matmul=ref.f32_matmul) - x
        hidden = ref.rms_norm(x, p["post_norm"], cfg["rms_norm_eps"])
        parts, shared = [], None
        for first in (0, 4):
            share = dict(moe_p, **{
                name: moe_p[name][first:first + 4]
                for name in ("w_gate", "w_up", "w_down")
            })
            shard = dataclasses.replace(model, num_experts=8, experts_held=(first, 4))
            out, _ = dsa_lm._ffn(dict(p, moe=share), shard, x)
            shared = moe.shared_swiglu(hidden, moe_p["shared_gate"], moe_p["shared_up"],
                                       moe_p["shared_down"], jnp.float32)
            parts.append(out - shared)
        got = parts[0] + parts[1] + shared
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_a_document_resumed_from_its_pages_gives_the_logits_of_a_cold_prefill(toy):
    rng = np.random.default_rng(8)
    doc = rng.integers(0, 128, 200)
    ask = np.concatenate([doc, rng.integers(0, 128, 17)])
    cold = _runtime(toy, snapshot_capacity=0)
    (cold_tokens,), (cold_logits,), (cold_admit,) = _decode(cold, [ask])
    assert cold_admit[0] == "miss"
    warm = _runtime(toy)
    assert len(warm.jit_fns()) == 2  # no state to save or restore
    _decode(warm, [doc])
    (tokens,), (logits,), (admit,) = _decode(warm, [ask])
    assert admit[0] == "hit" and warm.counters["resumed_tokens"] == 192
    np.testing.assert_array_equal(tokens, cold_tokens)
    np.testing.assert_allclose(logits, cold_logits, atol=5e-5)
    assert warm.counters["launches_unequal"] == 0
    assert warm.counters["moe_assignments_local"] == warm.counters[
        "moe_assignments_computed"] > 0


def test_the_engine_serves_the_model_through_the_same_loop(toy):
    _, model, params = toy
    lm = LanguageModel(model, params)
    rng = np.random.default_rng(9)
    doc = rng.integers(0, 128, 160)
    prompts = [doc, np.concatenate([doc, rng.integers(0, 128, 21)]),
               rng.integers(0, 128, 30)]
    oracle = lm(prompts, max_new_tokens=NEW, prefill_chunk=32, steps_per_launch=4)
    with lm.serve(max_context=448, max_active=3, max_new_tokens=NEW,
                  prefill_chunk=32, steps_per_launch=4, num_pages=160,
                  prefix_cache_size=4, prefill_budget=64) as eng:
        first = eng.submit(prompts[0]).result(timeout=120)
        rest = [eng.submit(p) for p in prompts[1:]]
        outs = [first] + [r.result(timeout=120) for r in rest]
        assert eng.recompiles_after_warmup == 0
        assert eng.compile_count() == len(eng.runtime.jit_fns()) == 2
        assert [r.trace.attrs("admit")["kind"] for r in rest] == ["hit", "miss"]
    for want, got in zip(oracle, outs):
        np.testing.assert_array_equal(want, got)


def test_a_missed_prompt_one_past_whole_chunks_is_served(toy):
    """Its prefill fills whole chunks, and the positions prefilled (64 of a
    65-token prompt) are what the admission counts as real: counting the
    whole prompt put more real tokens than computed slots on the ledger,
    which refused the launch."""
    _, model, params = toy
    lm = LanguageModel(model, params)
    prompt = np.random.default_rng(10).integers(0, 128, 65)
    oracle = lm([prompt], max_new_tokens=NEW, prefill_chunk=32, steps_per_launch=4)
    with lm.serve(max_context=448, max_active=1, max_new_tokens=NEW,
                  prefill_chunk=32, steps_per_launch=4, num_pages=160,
                  prefix_cache_size=4) as eng:
        req = eng.submit(prompt)
        out = req.result(timeout=120)
        admit = req.trace.attrs("admit")
        assert admit["kind"] == "miss" and admit["prefill_tokens"] == 64
        assert eng.metrics.ledger()["failed"] == 0
    np.testing.assert_array_equal(out, oracle[0])


def test_the_programs_own_initialiser_gives_the_benchmarks_tree(toy):
    _, model, params = toy
    own = dsa_lm.init_params(model, jax.random.key(3))
    mine = dict(params)
    mine.pop("logit_bias", None)
    assert jax.tree.structure(own) == jax.tree.structure(mine)
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, mine)
