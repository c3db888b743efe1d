"""``ops.sparse_block_attention`` over a paged store against the plain
reference's selection and dense masked softmax (``benchmark/reference/
sala_lm.py``), with contexts on both sides of ``dense_len``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sala_lm as ref
from machine_learning_apache_spark_tpu.ops.sparse_block_attention import (
    SparseSpec,
    gather_pages,
    page_rows,
    sparse_decode,
    sparse_prefill,
    unit_means,
)

G, HG, D = 2, 2, 16
SPEC = SparseSpec(block=8, stride=2, topk=4, window_blocks=2, init_blocks=1,
                  dense_len=64)
SIZES = dict(stride=2, kernel=4, block=8, topk=4, window=2, init=1, dense_len=64)
T_MAX = 256
PAGES = 40


def _store(seed, length):
    """Keys and values of ``length`` positions laid into shuffled pages."""
    rng = np.random.default_rng(seed)
    keys = rng.standard_normal((T_MAX, G, D)).astype(np.float32)
    values = rng.standard_normal((T_MAX, G, D)).astype(np.float32)
    keys[length:] = values[length:] = 0.0
    n_pages = -(-length // SPEC.block)
    table = np.zeros(T_MAX // SPEC.block + 4, np.int32)
    table[:n_pages] = rng.permutation(np.arange(1, PAGES))[:n_pages]
    k_store = jnp.zeros((G * PAGES * SPEC.block, D))
    v_store = jnp.zeros((G * PAGES * SPEC.block, D))
    u_store = jnp.zeros((G * PAGES * SPEC.units, D))
    pos = np.arange(n_pages * SPEC.block)
    rows = page_rows(k_store, G, SPEC.block, table[pos // SPEC.block],
                     pos % SPEC.block).reshape(-1)
    heads_first = lambda a: jnp.swapaxes(jnp.asarray(a[: len(pos)]), 0, 1)  # noqa: E731
    k_store = k_store.at[rows].set(heads_first(keys).reshape(-1, D))
    v_store = v_store.at[rows].set(heads_first(values).reshape(-1, D))
    units = pos[:: SPEC.stride] // SPEC.stride
    unit_rows = page_rows(u_store, G, SPEC.units, table[units // SPEC.units],
                          units % SPEC.units).reshape(-1)
    u_store = u_store.at[unit_rows].set(
        unit_means(heads_first(keys), SPEC.stride).reshape(-1, D)
    )
    return keys, values, jnp.asarray(table), k_store, v_store, u_store


def _reference(q, keys, values, t, dense):
    taken = ref.select_blocks(q, jnp.asarray(keys), t, SIZES, dense, "topk")
    mask = jnp.repeat(taken, SPEC.block, axis=-1) & (
        jnp.arange(T_MAX)[None, None, :] <= t[:, None, None]
    )
    s = jnp.einsum("nghd,sgd->nghs", q, keys) * D ** -0.5
    s = jnp.where(mask[:, :, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nghs,sgd->nghd", w, values), np.asarray(taken)


def test_gather_pages_reads_what_page_rows_wrote():
    keys, _, table, k_store, _, _ = _store(0, 100)
    got = gather_pages(k_store, jnp.broadcast_to(table[:3], (G, 3)), SPEC.block)
    want = jnp.swapaxes(jnp.asarray(keys[:24]), 0, 1).reshape(G, 3, SPEC.block, D)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("length,dense", [(150, False), (203, False), (40, True)])
def test_decode_selection_and_output_equal_the_reference(length, dense):
    keys, values, table, k_store, v_store, u_store = _store(length, length)
    rng = np.random.default_rng(1)
    q = jnp.asarray(1.6 * rng.standard_normal((3, G, HG, D)), jnp.float32)
    t = jnp.array([length - 1, length - 9, max(length - 30, 0)], jnp.int32)
    o, chosen = sparse_decode(
        q, k_store, v_store, u_store, jnp.broadcast_to(table, (3, len(table))),
        t, jnp.full((3,), dense), SPEC,
    )
    o_ref, taken = _reference(q, keys, values, t, dense)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    if not dense:
        for n in range(3):
            for g in range(G):
                mine = {int(i) for i in np.asarray(chosen[n, g]) if i >= 0}
                assert mine == set(np.nonzero(taken[n, g])[0].tolist())
                assert len(mine) == min(SPEC.topk, int(t[n]) // SPEC.block + 1)


def test_a_dense_row_beside_sparse_rows_takes_the_wide_gather():
    """One launch shape for both: the dense row attends everything, the
    sparse rows keep their top-k."""
    keys, values, table, k_store, v_store, u_store = _store(7, 200)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((2, G, HG, D)), jnp.float32)
    t = jnp.array([60, 199], jnp.int32)
    dense = jnp.array([True, False])
    o, _ = sparse_decode(
        q, k_store, v_store, u_store, jnp.broadcast_to(table, (2, len(table))),
        t, dense, SPEC,
    )
    for n in range(2):
        o_ref, _ = _reference(q[n: n + 1], keys, values, t[n: n + 1], bool(dense[n]))
        np.testing.assert_allclose(o[n], o_ref[0], atol=2e-5)


@pytest.mark.parametrize("dense", [False, True])
def test_prefill_chunk_equals_the_reference(dense):
    length = 56 if dense else 176
    keys, values, table, k_store, v_store, u_store = _store(3, length)
    rng = np.random.default_rng(4)
    start = length - 16
    q = jnp.asarray(rng.standard_normal((16, G, HG, D)), jnp.float32)
    t = start + jnp.arange(16, dtype=jnp.int32)
    o = sparse_prefill(
        q, k_store, v_store, u_store, table, t, jnp.bool_(dense), SPEC
    )
    o_ref, _ = _reference(q, keys, values, t, dense)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)


def test_forced_blocks_are_always_taken():
    """Block 0 and the window hold whatever the scores say."""
    keys, values, table, k_store, v_store, u_store = _store(5, 240)
    q = jnp.zeros((1, G, HG, D), jnp.float32)  # flat scores: ties everywhere
    t = jnp.array([239], jnp.int32)
    _, chosen = sparse_decode(
        q, k_store, v_store, u_store, table[None], t, jnp.array([False]), SPEC
    )
    own = 239 // SPEC.block
    for g in range(G):
        mine = set(np.asarray(chosen[0, g]).tolist())
        assert {0, own, own - 1} <= mine and len(mine) == SPEC.topk


def test_prefill_runs_only_the_query_blocks_that_hold_a_real_query():
    """A padded chunk: the real queries read as in a whole chunk, the
    blocks of padding alone are left at zero (they were not run)."""
    keys, values, table, k_store, v_store, u_store = _store(6, 176)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((32, G, HG, D)), jnp.float32)
    t = 144 + jnp.arange(32, dtype=jnp.int32)
    whole = sparse_prefill(q, k_store, v_store, u_store, table, t,
                           jnp.bool_(False), SPEC)
    part = sparse_prefill(q, k_store, v_store, u_store, table, t,
                          jnp.bool_(False), SPEC, real=jnp.int32(11))
    np.testing.assert_array_equal(part[:16], whole[:16])
    assert not np.asarray(part[16:]).any() and np.asarray(whole[16:]).any()
