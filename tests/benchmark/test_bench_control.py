"""The control of the ``correct`` decision, at a size a test run can hold.

The control is the plain reference put in the program's place and computed
in the nearest precision below the bfloat16 that both configurations state.
One rule for both: int8 (per-row and per-column absmax), the lower precision
that the chip's matrix unit has a mode for (393 TOP/s against 197 TFLOP/s)
and that the program already has a path in (the int8 KV store), so the step
that would tempt a later PR. The float8 (e4m3) reference is read beside it
and given its verdict too. PERF.md has the readings on the chip at the
cells' own sizes; here the same code runs at test widths with the test's own
limits, set between the two readings the same way: the program comes out
correct, the control does not.
"""

import pytest

from benchmark import compare, manifest as manifest_mod, run as bench_run

# big_serve_batch was put off by PR 23 (PERF.md): its files are written and
# its manifest entries wait in benchmark/put_off_big_serve_batch.json.
MANIFEST = manifest_mod.with_put_off(
    manifest_mod.load_manifest(), "big_serve_batch"
)

SERVE_SIZE = dict(
    config_overrides=dict(
        trg_vocab_size=8000, d_model=64, ffn_hidden=128,
        engine=dict(max_new_tokens=12),
    ),
    cell_overrides=dict(
        # every request that the window finished (100-200 of them): the mean
        # gap rests on a few dozen near-ties and wants a sample that large
        compare_requests=10000, reference_block_rows=64,
        # at this size (CPU, PR 23, seeds 1-2): served_gap_max program
        # 0.022-0.037, float8 0.243-0.339, int8 0.058-0.073 (not separated);
        # served_gap_mean program 0.00028-0.00033, int8 0.00097-0.0015,
        # float8 0.020-0.025
        limits=dict(served_gap_max=0.12, served_gap_mean=0.0006,
                    served_len_short=0),
    ),
)
TRAIN_SIZE = dict(
    config_overrides=dict(
        d_model=64, ffn_hidden=128, src_vocab_size=512, trg_vocab_size=512,
    ),
    mix_overrides=dict(rows_per_chip=32, src_len=16, trg_len=16),
    cell_overrides=dict(
        reference_block_rows=16,
        # program read 0.026-0.030 / 0.019-0.023, the int8 control
        # 0.127-0.165 / 0.052-0.064, half the batch 0.33-0.41 / 0.17-0.18 on
        # seeds 1-3 at this size (CPU, PR 23)
        # ... and grad1_median_leaf program 0.0018, int8 0.054, half 0.074
        limits=dict(grad1_worst_leaf=0.07, grad1_median_leaf=0.01,
                    change3_worst_leaf=0.15),
    ),
)


@pytest.mark.serving
@pytest.mark.parametrize("seed", [1, 2])
def test_served_model_control_in_int8_is_not_correct(seed, tmp_path):
    result = bench_run.run_cell(
        "big_serve_batch", seed=seed, seconds=0.6, trace=False,
        require_chip=False, rehearse=True, control=("all",), manifest=MANIFEST,
        out_dir=str(tmp_path), **SERVE_SIZE,
    )
    assert result["correct"] is True, result["compared"]
    for stand_in in ("control_int8", "control_fp8"):
        control = result["control"][stand_in]
        assert control["correct"] is False and control["over"], control
    control = result["control"]["control_int8"]
    assert "served_gap_mean" in control["over"]
    assert control["served_gap_mean"] >= 3 * result["compared"]["served_gap_mean"]["value"]


def test_training_control_in_int8_and_planted_faults_are_not_correct(tmp_path):
    result = bench_run.run_cell(
        "ref_train_1chip", seed=1, seconds=0.3, trace=False,
        require_chip=False, rehearse=True,
        control=("control_int8", "fault_half_batch", "fault_state_unchanged"),
        out_dir=str(tmp_path), **TRAIN_SIZE,
    )
    assert result["correct"] is True, result["compared"]
    assert set(result["control"]) == {
        "control_int8", "fault_half_batch", "fault_state_unchanged"}
    for stand_in, numbers in result["control"].items():
        assert numbers["correct"] is False and numbers["over"], (
            f"{stand_in} passed every number: {numbers}")
    assert result["control"]["fault_state_unchanged"]["change3_worst_leaf"] == pytest.approx(1.0)
    grad = result["compared"]["grad1_worst_leaf"]["value"]
    assert result["control"]["control_int8"]["grad1_worst_leaf"] >= 3 * grad
    assert "grad1_median_leaf" in result["control"]["control_int8"]["over"]
    assert "change3_median_leaf" in result["control"]["control_int8"]["not_compared"]


def test_an_unknown_stand_in_is_an_error():
    with pytest.raises(KeyError, match="no stand-in"):
        compare.chosen(("control_int4",), {"control_int8": None})
    assert compare.chosen(("all",), {"a": 1, "b": 2}) == ["a", "b"]
