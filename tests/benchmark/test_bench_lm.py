"""The language-model cell (``q3next_train_full_4k``, kind ``train_lm``):
rehearsed on the CPU at toy widths, its control and planted faults, its
needed-operations count against a hand count, and its readers on a
synthetic run."""

import types

import pytest

from benchmark import flops_hybrid_lm, manifest, run as bench_run, weights_hybrid_lm

CELL = "q3next_train_full_4k"
M = manifest.load_manifest()

# A toy's leaves are a few dozen numbers, so the toy's limits are its own
# (as tests/benchmark/test_bench_control.py sets them): at this size on the
# CPU (PR 26, seeds 1-3) the program read grad1_median_leaf 0.0008-0.0011
# and the int8 reference 0.0035-0.0045; routed to one expert, the
# reference's worst leaf reads 0.5-0.8 against the program's 0.01-0.03.
LIMITS = dict(grad1_worst_leaf=0.1, grad1_median_leaf=0.002, change3_worst_leaf=0.1)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    return bench_run.run_cell(
        CELL, seed=2**31 + 7, seconds=0.5, trace=True, require_chip=False,
        rehearse=True, control=("control_int8", "fault_top1_only", "fault_state_unchanged"),
        cell_overrides=dict(limits=LIMITS),
        out_dir=str(tmp_path_factory.mktemp("lm_cell")),
    )


def test_manifest_has_the_cell_and_no_problems():
    assert manifest.problems(M) == []
    cell = manifest.find_cell(M, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    entry = next(c for c in M["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    names = {m["name"] for m in manifest.metrics_for(M, CELL, "per_layer")}
    assert names == {
        "train_step_ms_p50", "train_step_mfu", "loader_wait_ms_p50",
        "gdn_scan_ms", "gdn_scan_roofline", "moe_experts_ms",
        "moe_experts_roofline", "moe_route_ms", "expert_load_max_over_mean",
        "lm_flash_roofline",
    }
    assert "flash_fwd_roofline" not in names


def test_the_configuration_keeps_the_published_widths():
    cfg = manifest.load_config(M, "qwen3_next_80b_a3b")
    published = dict(
        hidden_size=2048, head_dim=256, num_attention_heads=16,
        num_key_value_heads=2, partial_rotary_factor=0.25, rope_theta=10000000,
        full_attention_interval=4, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        num_experts_per_tok=10, norm_topk_prob=True, rms_norm_eps=1e-6,
        router_width=512,
    )
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 32, 18992)
    assert cfg["experts_held"] == [0, 32] and cfg["published"]["num_experts"] == 512
    assert cfg["assumed"] and cfg["departures"] and "16 chips" in cfg["deployment"]
    # the parameter table of the file, from the shapes the weights are made in
    n = weights_hybrid_lm.parameter_count(cfg)
    assert round(n / 1e6, 1) == cfg["parameters_millions"]["held_here_total"]


def test_rehearsal_is_correct_and_reports_the_cells_metrics(rehearsed):
    assert rehearsed["correct"] is True, rehearsed["compared"]
    assert set(rehearsed["compared"]) == set(LIMITS)
    assert rehearsed["attempted"] > 0 and rehearsed["failed"] == 0
    reported = set(rehearsed["metrics"])
    assert {"train_step_ms_p50", "expert_load_max_over_mean"} <= reported
    # off the chip there is no peak to take a share of and no device plane
    assert not any("mfu" in n or "roofline" in n for n in reported)
    assert rehearsed["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0


@pytest.mark.parametrize("stand_in,over", [
    ("control_int8", "grad1_median_leaf"),
    ("fault_top1_only", "grad1_worst_leaf"),
    ("fault_state_unchanged", "change3_worst_leaf"),
])
def test_control_and_planted_faults_are_not_correct(rehearsed, stand_in, over):
    report = rehearsed["control"][stand_in]
    assert report["correct"] is False and over in report["over"], report


def test_untraced_rehearsal_reports_the_end_to_end_metrics(tmp_path, capfd):
    result = bench_run.run_cell(
        CELL, seed=5, seconds=0.3, trace=False, require_chip=False,
        rehearse=True, cell_overrides=dict(limits=LIMITS), out_dir=str(tmp_path),
    )
    err = capfd.readouterr().err
    assert set(result["metrics"]) == {"setup_s", "train_tokens_per_s_per_chip"}
    assert result["correct"] is True, result["compared"]
    assert "gated delta site gated_delta_net: chunked_scan" in err
    assert "attention site dot_product" in err
    assert "assignments local" in err
    assert "steps in which they differ: 0" in err
    assert "by log lap across the window" in err


@pytest.mark.parametrize("local,computed,want", [
    (40.0, 40.0, 0.0),
    (40.0, 24.0, 1.0),  # a segment skipped or cut short
    (40.0, 41.0, 1.0),
])
def test_the_step_verdict_tells_a_step_whose_counters_differ(local, computed, want):
    import jax.numpy as jnp

    kind = manifest.load_kind("train_lm")
    metrics = {
        "moe_assignments_local": jnp.float32(local),
        "moe_assignments_computed": jnp.float32(computed),
    }
    loss = kind.with_step_verdict(lambda p, b, r: (jnp.float32(2.0), metrics))
    value, out = loss(None, None, None)
    assert float(value) == 2.0 and float(out["moe_steps_unequal"]) == want
    assert float(out["moe_assignments_local"]) == local


def test_laps_of_local_assignments_come_out_of_the_running_means():
    kind = manifest.load_kind("train_lm")
    run = _synthetic_run()
    # laps of 4 steps, the window opens after step 8: a lap of the warm-up,
    # then laps whose steps read 100, 104 and 120 assignments each
    so_far = [(8, 90.0), (12, 100.0), (16, 102.0), (20, (400 + 416 + 480) / 12)]
    kind._report_laps(run, so_far, first_window_step=8)
    assert run.counters["moe_assignments_local_by_lap"] == pytest.approx([100, 104, 120])
    warm_up_only = _synthetic_run()
    kind._report_laps(warm_up_only, so_far[:1], first_window_step=8)
    assert "moe_assignments_local_by_lap" not in warm_up_only.counters


def test_needed_operations_against_a_hand_count():
    cfg = dict(
        hidden_size=8, num_layers=4, full_attention_interval=4, head_dim=4,
        num_attention_heads=2, num_key_value_heads=1, linear_num_key_heads=1,
        linear_num_value_heads=2, linear_key_head_dim=4, linear_value_head_dim=4,
        router_width=16, moe_intermediate_size=2,
        shared_expert_intermediate_size=2, vocab_size=32, experts_held=[0, 4],
    )
    s, rows, local = 16, 2, 40.0
    gdn = 2 * 8 * (4 + 4 + 8 + 8 + 4) + 2 * 8 * 8 + 6 * 2 * 4 * 4
    attn = 2 * 8 * (16 + 4 + 4) + 2 * 8 * 8 + 2 * 2 * 16 * 4 * 2 / 2
    every = 2 * 8 * 16 + 3 * 2 * 8 * 2 + 2 * 8
    head = 2 * 8 * 32
    tokens = rows * s
    want = 3 * (tokens * (3 * gdn + attn + 4 * every + head) + local * 3 * 2 * 8 * 2)
    assert flops_hybrid_lm.train_step_flops(cfg, rows, s, local) == pytest.approx(want)
    f, b = flops_hybrid_lm.scan_cost_per_step(cfg, rows, s)
    assert f == 3 * tokens * 3 * 6 * 2 * 4 * 4
    assert b == 2 * tokens * 3 * (2 * (2 * 4 + 2 * 2 * 4) + 4 * 2 * 2)
    f, b = flops_hybrid_lm.experts_cost_per_step(cfg, local)
    assert f == 3 * local * 3 * 2 * 8 * 2 and b == 2 * 2 * 4 * 4 * 3 * 8 * 2
    f, b = flops_hybrid_lm.flash_cost_per_step(cfg, rows, s)
    assert f == 3 * (2 * 2 * rows * 2 * s * s * 4 / 2)
    # the real cell, a token forward: about 0.43 GFLOP (ISSUE 26's reckoning)
    real = manifest.load_config(M, "qwen3_next_80b_a3b")
    per_token = flops_hybrid_lm.forward_flops_per_token(real, 4096, 4 * 10 * 32 / 512)
    assert 0.40e9 < per_token < 0.46e9


def _synthetic_run(**counters):
    notes = []
    run = types.SimpleNamespace(
        counters=counters, trace_data=None, chips=1, window_s=20.0,
        note=notes.append, events=[], cfg={}, mix={},
    )
    return run


@pytest.mark.parametrize("metric,counters,want", [
    ("gdn_scan_ms", {"scope_ms": {"lm.gdn_scan": 12.5}}, 12.5),
    ("moe_experts_ms", {"scope_ms": {"lm.moe.experts": 3.0}}, 3.0),
    ("moe_route_ms", {"scope_ms": {"lm.moe.route": 0.75}}, 0.75),
    ("expert_load_max_over_mean",
     {"moe_tokens_held_mean": 320.0, "moe_tokens_held_max": 368.0}, 1.15),
    # nothing to read: a program without the scope, an untraced run
    ("gdn_scan_ms", {}, None),
    ("moe_experts_ms", {"scope_ms": {"lm.moe.experts": 0.0}}, None),
    ("moe_route_ms", {"scope_ms": {}}, None),
    ("expert_load_max_over_mean", {}, None),
    # off the chip a share of a peak is left out, never 0
    ("gdn_scan_roofline",
     {"scope_ms": {"lm.gdn_scan": 12.5}, "scan_cost_per_step": (1e12, 1e9)}, None),
    ("moe_experts_roofline", {"experts_cost_per_step": (1e12, 1e9)}, None),
    ("lm_flash_roofline", {"lm_flash_cost_per_step": (1e12, 1e9)}, None),
])
def test_readers_on_a_synthetic_run(metric, counters, want):
    got = manifest.load_reader(metric)(_synthetic_run(**counters))
    assert got == (pytest.approx(want) if want is not None else None)


def test_roofline_share_from_counts_and_peaks(monkeypatch):
    from benchmark import lm_readers, readers

    monkeypatch.setattr(readers, "chip_peaks", lambda run: {
        "bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9})
    run = _synthetic_run(cost=(2e12, 4e9))  # 10 ms of compute, 5 ms of bytes
    assert lm_readers.roofline_percent(run, "cost", 40.0) == pytest.approx(25.0)
    assert lm_readers.roofline_percent(run, "cost", None) is None
    assert lm_readers.roofline_percent(run, "missing", 40.0) is None


def test_scope_seconds_reads_the_recorded_trace_and_survives_a_bad_file(tmp_path):
    import os

    from benchmark import scope_trace

    recorded = os.path.join(os.path.dirname(__file__), "recorded_v5e.xplane.pb")
    found, runs, step_s = scope_trace.scope_seconds(
        recorded, ["self_attn", "ffn", "lm.gdn_scan"], r"^jit_step\("
    )
    assert runs >= 1 and found["self_attn"] > found["ffn"] > 0
    assert step_s > found["self_attn"] + found["ffn"]
    assert found["lm.gdn_scan"] == 0.0
    assert scope_trace.scope_seconds(recorded, ["lm.gdn_scan"], r"^jit_step\(") is None
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\x0a\xff\xff\xff")
    notes = []
    assert scope_trace.scope_seconds(str(bad), ["x"], "y", note=notes.append) is None
    assert notes
