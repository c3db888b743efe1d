"""The DeepSeek-V3.2-Exp serving cell (``dsv32_serve_doc_qa_64k``, kind
``serve_dsa_lm``): rehearsed on the CPU at the kind's toy widths with its
controls and planted fault, its manifest entries, its configuration against
the catalog row, its needed-operations count against a hand count, and its
readers on a synthetic run and on a run of a program without them."""

import types

import pytest

from benchmark import flops_dsa_lm, manifest, run as bench_run, weights_dsa_lm
from benchmark.kinds import serve_dsa_lm

CELL = "dsv32_serve_doc_qa_64k"
M = manifest.load_manifest()
NEW_METRICS = {
    "mla_attn_ms.steady", "mla_attn_roofline.steady", "dsa_index_ms.steady",
    "dsa_index_roofline.steady", "moe_route_ms.steady", "moe_experts_ms.steady",
    "moe_experts_roofline.steady", "index_selected_share.steady",
}
# The toy rehearses with float32 weights: at width 64 in bfloat16 the rows
# of its documents drift apart layer by layer (6 % by the third) and the
# program's mean gap (0.07-0.32 over two seeds) sits beside the int8
# reference's (0.22-0.54). In float32 the program reads rounding alone: a
# gap of 2e-6, first-layer rows 2e-7 apart, no selection outside the
# tolerance; int8 0.33, 0.0088 and 0.011.
FLOAT32 = dict(weight_dtype="float32")
LIMITS = dict(served_gap_mean=0.01, served_gap_p90=0.02, latent_gap_layer0=0.001,
              selected_outside_share=0.005, served_len_short=0, replay_diverged=0,
              moe_launches_unequal=0)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    return bench_run.run_cell(
        CELL, seed=2**31 + 13, seconds=1.5, trace=True, require_chip=False,
        rehearse=True, control=("all",), config_overrides=FLOAT32,
        cell_overrides=dict(limits=LIMITS),
        out_dir=str(tmp_path_factory.mktemp("serve_dsa_lm_cell")),
    )


def test_manifest_has_the_cell_and_no_problems():
    assert manifest.problems(M) == []
    cell = manifest.find_cell(M, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["config"] == "deepseek_v32_exp"
    entry = next(c for c in M["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == [
        "num_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
    ]
    e2e = {m["name"] for m in manifest.metrics_for(M, CELL, "end_to_end")}
    assert e2e == {"latency_p50_ms", "setup_s"}
    names = {m["name"] for m in manifest.metrics_for(M, CELL, "per_layer")}
    assert NEW_METRICS <= names and "launch_mfu.steady" in names
    # sala's cell's own (their workloads lists are held to it alone)
    for name in ("state_restore_ms.steady", "kv_selected_share.steady",
                 "prefix_hit_token_share.steady"):
        assert name not in names
    for m in M["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "latency_p50_ms"


def test_the_configuration_keeps_the_catalog_rows_numbers():
    """Every number of the catalog row's config is in the file under its
    key, those that differ are the ones ``reduced`` names, and no width is
    among them."""
    row = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=3, hidden_size=7168,
        index_head_dim=128, index_n_heads=64, index_topk=2048,
        intermediate_size=18432, kv_lora_rank=512, max_position_embeddings=163840,
        moe_intermediate_size=2048, moe_layer_freq=1, n_group=8,
        n_routed_experts=256, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=128, num_experts_per_tok=8, num_hidden_layers=61,
        num_key_value_heads=128, num_nextn_predict_layers=1, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-06,
        rope_theta=10000, routed_scaling_factor=2.5, tie_word_embeddings=False,
        topk_group=4, v_head_dim=128, vocab_size=129280,
    )
    cfg = manifest.load_config(M, "deepseek_v32_exp")
    entry = next(c for c in M["configs"] if c["name"] == "deepseek_v32_exp")
    differ = {k for k, v in row.items() if cfg[k] != v}
    assert differ | {"num_layers"} == set(entry["reduced"])
    assert cfg["num_layers"] == 5 and cfg["num_hidden_layers"] == 61
    assert cfg["rope_scaling"] == dict(
        beta_fast=32, beta_slow=1, factor=40, mscale=1, mscale_all_dim=1,
        original_max_position_embeddings=4096, type="yarn",
    )
    assert cfg["router_width"] == 256 and cfg["experts_held"] == [0, 16]
    assert cfg["vocab_size"] * 8 == 129280
    for key in ("published", "assumed", "departures", "deployment", "architecture"):
        assert cfg[key]
    # 4,636 M parameters held here, as the issue counts them
    assert round(weights_dsa_lm.parameter_count(cfg) / 1e6) == 4636


def test_needed_operations_against_a_hand_count():
    cfg = manifest.load_config(M, "deepseek_v32_exp")
    f, b = flops_dsa_lm.mla_step_cost(cfg, 47000)
    assert f == 2.0 * 128 * 2048 * 1088 and b == 2 * 2048 * 576
    assert flops_dsa_lm.mla_step_cost(cfg, 99)[1] == 2 * 100 * 576
    f, b = flops_dsa_lm.index_step_cost(cfg, 47000)
    assert f == 2.0 * 64 * 128 * 47001 and b == 256 * 47001
    f, b = flops_dsa_lm.experts_cost(cfg, 10, 16)
    assert b == 10 * 3 * 7168 * 2048 * 2 and f == 16 * 6 * 7168 * 2048
    mla = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
           + 128 * 128 * 7168)
    index = 1536 * 64 * 128 + 7168 * 128 + 7168 * 64
    expert = 7168 * 256 + 3 * 7168 * 2048 * (1 + 8 * 16 / 256)
    assert flops_dsa_lm.linear_token_flops(cfg) == pytest.approx(2.0 * (
        5 * (mla + index) + 3 * 7168 * 18432 + 4 * expert + 7168 * 16160
    ))


def test_rehearsed_cell_is_correct_and_counts_its_window(rehearsed):
    assert rehearsed["correct"] is True
    assert rehearsed["failed"] == 0 and rehearsed["attempted"] > 5
    assert rehearsed["device"]["platform"] == "cpu"
    compared = rehearsed["compared"]
    assert set(compared) == set(LIMITS)
    assert compared["replay_diverged"]["value"] == 0
    assert compared["moe_launches_unequal"]["value"] == 0
    metrics = rehearsed["metrics"]
    assert metrics["recompiles_in_window.steady"]["value"] == 0
    # the toy's index top-k (48) is under every document's length
    assert 0.0 < metrics["index_selected_share.steady"]["value"] < 0.5
    for name in NEW_METRICS - {"index_selected_share.steady"} | {"launch_mfu.steady"}:
        assert name not in metrics  # a chip's numbers, or none at all


def test_each_stand_in_is_told_apart(rehearsed):
    """Every stand-in fails a limit, read over the program's own sampled
    requests."""
    control = rehearsed["control"]
    assert set(control) == set(serve_dsa_lm.STAND_INS)
    requests = {control[name]["requests"] for name in serve_dsa_lm.STAND_INS}
    assert len(requests) == 1 and requests.pop() > 1
    for name in serve_dsa_lm.STAND_INS:
        assert control[name]["correct"] is False, name
        assert {"served_gap_mean", "selected_outside_share"} <= set(control[name]["over"])
    # the first layer's rows: the precision alone moves them
    for name in ("control_int8", "control_fp8"):
        assert "latent_gap_layer0" in control[name]["over"], name


def test_a_page_overwritten_under_a_live_row_is_refused(tmp_path):
    """``fault_live_page``: the replay of the window's tokens refuses the
    run."""
    result = bench_run.run_cell(
        CELL, seed=2**31 + 14, seconds=1.5, trace=False, require_chip=False,
        rehearse=True, control=(serve_dsa_lm.LIVE_FAULT,),
        config_overrides=FLOAT32, cell_overrides=dict(limits=LIMITS),
        out_dir=str(tmp_path),
    )
    fault = result["control"][serve_dsa_lm.LIVE_FAULT]
    assert fault["planted"]["steps_left"] > 0
    assert result["correct"] is False and fault["correct"] is False
    assert "replay_diverged" in fault["over"] and fault["replay_diverged"] >= 1


def test_readers_return_none_on_a_program_without_the_counters():
    """On the parent's checkout (no scope or counter of this PR) every new
    reader leaves its metric out and none raises."""
    run = types.SimpleNamespace(
        counters={}, events=[], trace_data=None, window_s=1.0, chips=1,
        setup_s=None, mix={}, note=lambda msg: None,
    )
    for name in NEW_METRICS:
        assert manifest.load_reader(name)(run) is None, name


def test_readers_on_a_synthetic_run():
    run = types.SimpleNamespace(
        counters=dict(scope_ms={"lm.mla": 4.0, "lm.dsa.index": 9.0,
                                "lm.moe.route": 1.5, "lm.moe.experts": 7.0},
                      selected_share_sum=4.3, selected_share_n=100),
        note=lambda msg: None, chips=1,
    )
    assert manifest.load_reader("index_selected_share.steady")(run) == 0.043
    assert manifest.load_reader("mla_attn_ms.steady")(run) == 4.0
    assert manifest.load_reader("dsa_index_ms.steady")(run) == 9.0
    assert manifest.load_reader("moe_route_ms.steady")(run) == 1.5
    assert manifest.load_reader("moe_experts_ms.steady")(run) == 7.0
    # a share of a roofline needs the chip's peaks: none off a TPU
    run.counters["mla_cost_per_launch"] = (1e9, 1e8)
    assert manifest.load_reader("mla_attn_roofline.steady")(run) is None


def test_the_reference_takes_a_near_tie_selection_and_no_other():
    """``reference.dsa_lm.adopted``: a selection that swaps the reference's
    last pick for a position scored within the tolerance is attended; one
    that takes a position far under it is not, and counts as outside."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import dsa_lm as ref

    scores = jnp.asarray([[5.0, 4.0, 3.001, 3.0, 1.0, 0.0, -jnp.inf]] * 3)
    causal = jnp.isfinite(scores)
    own = jnp.asarray([[1, 1, 1, 0, 0, 0, 0]] * 3, bool)
    adopt = jnp.asarray([
        [1, 1, 0, 1, 0, 0, 0],  # the near tie
        [1, 1, 0, 0, 0, 1, 0],  # a position far under the third score
        [0, 0, 0, 0, 0, 0, 0],  # none given
    ], bool)
    taken, (inversion, outside, picks) = ref.adopted(
        scores, causal, own, jnp.full(3, 3.001), adopt, 0.05
    )
    np.testing.assert_array_equal(taken, [adopt[0], own[1], own[2]])
    assert float(inversion[0]) < 0.05 < float(inversion[1])
    np.testing.assert_array_equal(outside, [0, 1, 0])
    np.testing.assert_array_equal(picks, [3, 3, 0])
