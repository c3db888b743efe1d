"""``correct`` comes out false when the timed path is broken underneath.

Each test skips the harness's look for a chip and drives the rest of a run
at toy widths with one fault planted in the program: a step that returns
its state unchanged; half of the batch left out, the mean taken over the
rest; the exchange between chips left out (every chip keeps its own
shard's gradient); a served token altered where it is produced.
"""

import pytest

from benchmark import manifest as manifest_mod, run as bench_run

# big_serve_batch was put off by PR 23 (PERF.md): its files are written and
# its manifest entries wait in benchmark/put_off_big_serve_batch.json.
MANIFEST = manifest_mod.with_put_off(
    manifest_mod.load_manifest(), "big_serve_batch"
)


@pytest.fixture
def _run(tmp_path):
    def run(cell, **kw):
        return bench_run.run_cell(
            cell, seed=2**31 + 97, seconds=0.4, trace=False,
            require_chip=False, rehearse=True, manifest=MANIFEST,
            out_dir=str(tmp_path), **kw,
        )
    return run


def _over(result):
    return {k for k, v in result["compared"].items() if v["value"] > v["limit"]}


def test_step_that_returns_its_state_unchanged(monkeypatch, _run):
    from machine_learning_apache_spark_tpu.train.state import TrainState

    monkeypatch.setattr(TrainState, "apply_gradients", lambda self, grads: self)
    result = _run("ref_train_1chip")
    assert result["correct"] is False
    assert {"grad1_worst_leaf", "change3_worst_leaf"} <= _over(result)
    assert result["compared"]["change3_worst_leaf"]["value"] == pytest.approx(1.0)


def _loss_over_leading_rows(monkeypatch, share):
    from machine_learning_apache_spark_tpu.recipes import translation

    make = translation.make_translation_loss

    def broken(model, pad_id, **kw):
        inner = make(model, pad_id, **kw)

        def loss_fn(params, batch, rng):
            src, trg = batch
            n = src.shape[0] // share
            return inner(params, (src[:n], trg[:n]), rng)

        return loss_fn

    monkeypatch.setattr(translation, "make_translation_loss", broken)


def test_half_of_the_batch_left_out(monkeypatch, _run):
    _loss_over_leading_rows(monkeypatch, 2)
    result = _run("ref_train_1chip")
    assert result["correct"] is False
    assert "grad1_worst_leaf" in _over(result)


def test_exchange_between_chips_left_out(monkeypatch, _run):
    """Without the all-reduce a chip applies its own shard's gradient: what
    chip 0 then holds is the step over the first quarter of the rows."""
    _loss_over_leading_rows(monkeypatch, 4)
    result = _run("big_train_dp4")
    assert result["correct"] is False
    assert "grad1_worst_leaf" in _over(result)


@pytest.mark.serving
def test_served_token_altered_where_it_is_produced(monkeypatch, _run):
    from machine_learning_apache_spark_tpu.serving.paged_runtime import (
        PagedDecodeRuntime,
    )

    launch = PagedDecodeRuntime.launch

    def altered(self):
        result = launch(self)
        for _, ids, _, _ in result.completed:
            if len(ids) > 2:
                ids[2] = 4 + (ids[2] - 3) % 70  # another word of the toy vocabulary
        return result

    monkeypatch.setattr(PagedDecodeRuntime, "launch", altered)
    result = _run("big_serve_batch")
    assert result["correct"] is False
    assert "served_gap_max" in _over(result)


@pytest.mark.serving
def test_served_answer_cut_short(monkeypatch, _run):
    from machine_learning_apache_spark_tpu.serving.paged_runtime import (
        PagedDecodeRuntime,
    )

    launch = PagedDecodeRuntime.launch

    def cut(self):
        result = launch(self)
        for _, ids, _, _ in result.completed:
            del ids[-1:]
        return result

    monkeypatch.setattr(PagedDecodeRuntime, "launch", cut)
    result = _run("big_serve_steady")
    assert result["correct"] is False
    assert "served_len_short" in _over(result)
