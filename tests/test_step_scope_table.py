"""``tools/step_scope_table.py``: a train step's device time by named scope
out of a recorded v5e trace, and where the operations under no scope sit."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "tests", "benchmark", "recorded_v5e.xplane.pb")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "step_scope_table", os.path.join(ROOT, "tools", "step_scope_table.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_disjoint_scopes_and_the_rest_sum_to_the_step(tool):
    table = tool.step_table(RECORDED, ["self_attn", "ffn"], min_ms=0.0)
    assert table["runs"] >= 1 and not table["under_two"]
    assert table["scopes_ms"]["self_attn"] > table["scopes_ms"]["ffn"] > 0
    whole = (sum(table["scopes_ms"].values()) + table["none_ms"]
             + table["no_operation_ms"])
    assert whole == pytest.approx(table["step_ms"], rel=1e-6)
    # every operation under none of them is in one place and, at no floor,
    # listed by name
    assert sum(ms for _, ms, _ in table["none_places"]) == pytest.approx(table["none_ms"])
    assert sum(ms for _, ms, _ in table["none_ops"]) == pytest.approx(table["none_ms"])


def test_nested_scopes_are_named_and_counted_twice(tool):
    table = tool.step_table(RECORDED, ["decoder", "cross_attn"], min_ms=0.0)
    assert table["under_two"]
    assert set(table["under_two"].values()) == {"decoder+cross_attn"}
    whole = (sum(table["scopes_ms"].values()) + table["none_ms"]
             + table["no_operation_ms"])
    assert whole > table["step_ms"]


def test_no_run_of_the_module_gives_none(tool):
    assert tool.step_table(RECORDED, ["ffn"], module_pattern=r"^jit_absent\(") is None


@pytest.mark.parametrize("tf_op,want", [
    ("", "(no op_name)"),
    ("ragged-dot-none:", "(ragged-dot-none)"),
    ("jit(step)/add:", "step: add"),
    ("jit(step)/jvp(HybridLM)/layer_2/layer_2.expert_half/moe/closed_call/"
     "while/body/closed_call:",
     "forward: layer_N/layer_N.expert_half/moe/closed_call/while/body/closed_call"),
    ("jit(step)/transpose(jvp(HybridLM))/layer_0/jvp(HybridLM)/layer_0/checkpoint/"
     "layer_0.expert_half/moe/while/body/closed_call/add_any:",
     "backward: layer_N/checkpoint/layer_N.expert_half/moe/while/body/closed_call/add_any"),
])
def test_place_of_an_operation(tool, tf_op, want):
    assert tool.place(tf_op) == want


def test_the_other_scopes_are_the_programs(tool):
    from machine_learning_apache_spark_tpu.models.hybrid_lm import RESIDUAL_NORM
    from machine_learning_apache_spark_tpu.train.state import UPDATE_SCOPE

    assert tool.OTHER_SCOPES == (UPDATE_SCOPE, "lm.embed", RESIDUAL_NORM)
