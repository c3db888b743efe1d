"""The token indexer's paged decode kernel (``ops.pallas_dsa_index``,
interpreted: the backend here is the CPU) against the XLA scan of
``ops.dsa_index.paged_scores``, the dispatch between them, and the pages each
counts as read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from machine_learning_apache_spark_tpu import telemetry
from machine_learning_apache_spark_tpu.ops import dsa_index

PAGE, BLOCK, HEADS, D = 16, 128, 16, 128  # 8 pages a pass
PASSES = 3
WIDTH = PASSES * BLOCK // PAGE  # pages a table
NUM_PAGES = 200

# Positions ``t`` of the rows; 0 stands for a row not active as well.
CASES = {
    # 32 rows as a decode launch leaves them: most not active, the rest
    # ending mid-page (37), at the end of a page (47), one past it (48), at
    # the end of a pass (127), one past it (128), in the second pass (200)
    # and at the table's last position (383), one at its first position (0)
    "cell-like": [0] * 22 + [37, 0, 47, 48, 127, 128, 200, 383, 5, 0],
    # every row past one pass, one row alone reaching the last
    "two-passes": [130, 255, 256, 140, 383, 129, 200, 250],
    # rows not a multiple of 8: one grid step holds them all
    "six-rows": [0, 15, 16, 100, 300, 0],
}


def _site(rows_t, dtype, seed=0):
    rng = np.random.default_rng(seed)
    t = np.asarray(rows_t, np.int32)
    tables = np.zeros((len(t), WIDTH), np.int32)  # NULL_PAGE tails
    for r, pos in enumerate(t):
        if pos:  # a document's consecutive pages, then a question's anywhere
            n = pos // PAGE + 1
            doc = n * 2 // 3 if r % 2 else 0
            start = rng.integers(1, NUM_PAGES - doc)
            tables[r, :doc] = np.arange(start, start + doc)
            tables[r, doc:n] = rng.choice(np.arange(1, NUM_PAGES), n - doc,
                                          replace=False)
    plane = jnp.asarray(rng.standard_normal((NUM_PAGES * PAGE, D)), dtype)
    q = jnp.asarray(rng.standard_normal((len(t), HEADS, D)), dtype)
    w = dsa_index.index_weights(
        jnp.asarray(rng.standard_normal((len(t), HEADS))), HEADS, D
    )
    return q, w, plane, jnp.asarray(tables), jnp.asarray(t)


def _take_kernel(monkeypatch):
    monkeypatch.setattr(dsa_index, "_kernel_refusal", lambda *a, **k: None)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_paged_kernel_scores_and_selects_as_the_xla_scan(
    monkeypatch, case, dtype
):
    """The kernel's ``[N, T]`` scores are the XLA scan's to float32
    rounding, ``-inf`` in the same places (past each row's position and in
    every pass past it); ``select`` gives the same positions, plane rows and
    ``valid`` through either path; and each path counts the pages it read."""
    q, w, plane, tables, t = _site(CASES[case], dtype)
    kw = dict(page=PAGE, block=BLOCK)
    want = np.asarray(dsa_index.paged_scores(q, w, plane, tables, t, **kw))
    picked = dsa_index.select(q, w, plane, tables, t, **kw, topk=64, site="x")
    assert dsa_index.pages_read(q, plane, tables, t, **kw) == (
        len(CASES[case]) * (max(CASES[case]) // BLOCK + 1) * (BLOCK // PAGE),
    ) * 2

    _take_kernel(monkeypatch)
    from machine_learning_apache_spark_tpu.ops import pallas_dsa_index

    got = np.asarray(pallas_dsa_index.scan_scores(
        q, w, plane, tables, t, **kw, interpret=True
    ))
    assert got.shape == want.shape == (len(t), PASSES * BLOCK)
    finite = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), finite)
    np.testing.assert_array_equal(got[~finite], want[~finite])
    assert finite.sum() == int(np.sum(np.asarray(t) + 1))
    np.testing.assert_allclose(
        got[finite], want[finite], rtol=1e-6,
        atol=1e-6 * float(np.max(np.abs(want[finite]))),
    )
    through_kernel = dsa_index.select(q, w, plane, tables, t, **kw, topk=64,
                                      site="x")
    for a, b in zip(picked, through_kernel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    read, padded = dsa_index.pages_read(q, plane, tables, t, **kw)
    assert read == sum(x // PAGE + 1 for x in CASES[case])
    assert padded == len(CASES[case]) * (max(CASES[case]) // BLOCK + 1) * 8


def _dispatches():
    return [
        e.attrs for e in telemetry.get_log().snapshot()
        if e.kind == "annotation" and e.name == "ops.dsa_index_dispatch"
    ]


@pytest.mark.parametrize("site,backend,dtype,want", [
    ("decode", "cpu", "bfloat16", "xla_scan (backend cpu"),
    ("prefill", "tpu", "bfloat16", "xla_scan (one table for every query"),
    ("decode", "tpu", "float32", "xla_scan (float32 queries over float32 keys"),
    ("decode", "tpu", "bfloat16", "pallas_paged (dsa_index_scan: passes of 128"),
])
def test_the_dispatch_follows_what_the_site_shows(
    monkeypatch, site, backend, dtype, want
):
    """A site takes the kernel on the TPU with a table a row and bfloat16
    operands; a prefill chunk's shared table, a CPU and other dtypes keep
    the XLA scan. Traced only: the TPU cases are never run here."""
    q, w, plane, tables, t = _site(CASES["two-passes"], dtype)
    if site == "prefill":
        tables = tables[0]
    monkeypatch.setattr(dsa_index, "_backend", lambda: backend)
    telemetry.get_log().clear()
    jax.eval_shape(
        lambda *a: dsa_index.select(*a, page=PAGE, block=BLOCK, topk=64,
                                    site=site),
        q, w, plane, tables, t,
    )
    (seen,) = _dispatches()
    assert f"{seen['impl']} ({seen['reason']}".startswith(want), seen
    assert seen["site"] == site
    if seen["impl"] == "pallas_paged":
        assert "grid 1 x 8 rows" in seen["reason"]
