"""Where the hybrid language model's train step puts its operations: the
``op_name`` of every instruction of a toy step compiled here, the path that
a device trace shows as each operation's ``tf_op`` and that
``benchmark/scope_trace.py`` sums by scope. The optimizer's update is under
``train.update``, the embedding under ``lm.embed``, the block's norms and
residual adds under ``lm.residual_norm``, and no instruction under two of
the step's scopes: those the ``train_lm`` kind collects and these three."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest, weights_hybrid_lm
from benchmark.kinds import train_lm
from machine_learning_apache_spark_tpu.models.hybrid_lm import RESIDUAL_NORM, HybridLM
from machine_learning_apache_spark_tpu.recipes import make_lm_loss
from machine_learning_apache_spark_tpu.train.loop import make_train_step
from machine_learning_apache_spark_tpu.train.state import (
    UPDATE_SCOPE,
    TrainState,
    make_optimizer,
)

INSTRUCTION = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\((.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
STEP_SCOPES = (*train_lm.SCOPES, UPDATE_SCOPE, "lm.embed", RESIDUAL_NORM)


@dataclasses.dataclass
class Instruction:
    opcode: str
    op_name: str
    operands: list
    called: str | None  # a fusion's computation
    index: int | None  # a tuple element's


def _scope_rx(scope):
    return re.compile(r"(^|/)" + re.escape(scope) + r"(/|$|:)")


@pytest.fixture(scope="module")
def step():
    """The compiled toy step's instructions by name, each computation's
    root (the entry's under ``"ENTRY"``), and the number of the state's
    leaves: the step's first outputs."""
    cfg = manifest.load_config(manifest.load_manifest(), "qwen3_next_80b_a3b")
    train_lm.toy(cfg, {}, {})
    model = HybridLM(dataclasses.replace(train_lm.model_config(cfg), scan_chunk=16))
    params = jax.eval_shape(lambda: weights_hybrid_lm.make_params(1, cfg))
    state = jax.eval_shape(
        lambda p: TrainState.create(
            apply_fn=model.apply, params=p, tx=make_optimizer("adam", 1e-3)
        ),
        params,
    )
    text = make_train_step(make_lm_loss(model)).lower(
        state, jax.ShapeDtypeStruct((2, 33), jnp.int32), jax.random.key(0)
    ).compile().as_text()
    instructions, roots, computation = {}, {}, None
    for line in text.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            entry = line.startswith("ENTRY ")
            computation = "ENTRY" if entry else line.split()[0].lstrip("%")
            continue
        m = INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        rest = m[4].split(", metadata=")[0]
        called = re.search(r"calls=%([\w.\-]+)", rest)
        index = re.search(r"index=(\d+)", rest)
        op_name = OP_NAME.search(line)
        instructions[m[2]] = Instruction(
            m[3], op_name[1] if op_name else "",
            re.findall(r"%([\w.\-]+)", rest.split("calls=")[0]),
            called[1] if called else None, int(index[1]) if index else None,
        )
        if m[1]:
            roots[computation] = m[2]
    return instructions, roots, len(jax.tree.leaves(state))


def _producer(instructions, roots, name):
    """The instruction that computes ``name``'s value: through bitcasts,
    copies and tuple elements, into fusions down to their roots."""
    while True:
        ins = instructions[name]
        if ins.opcode in ("bitcast", "copy", "reshape"):
            name = ins.operands[0]
        elif ins.opcode == "get-tuple-element":
            source = instructions[ins.operands[0]]
            if source.opcode == "fusion":
                source = instructions[roots[source.called]]
            if source.opcode != "tuple":
                return name
            name = source.operands[ins.index]
        elif ins.opcode == "fusion":
            name = roots[ins.called]
        else:
            return name


def test_every_parameter_and_moment_is_updated_under_the_update_scope(step):
    instructions, roots, leaves = step
    outputs = instructions[roots["ENTRY"]].operands
    update = _scope_rx(UPDATE_SCOPE)
    found = {}
    for name in outputs[:leaves]:
        producer = _producer(instructions, roots, name)
        found[name] = instructions[producer].op_name
    outside = {k: v for k, v in found.items() if not update.search(v)}
    assert len(found) == leaves and not outside, outside


def test_the_embedding_and_its_gradient_are_under_lm_embed(step):
    instructions, _, _ = step
    embed = _scope_rx("lm.embed")
    gathers, scatters = (
        [v for v in instructions.values() if v.opcode == op and embed.search(v.op_name)]
        for op in ("gather", "scatter")
    )
    assert gathers and scatters
    # every other gather or scatter is another scope's: the experts' token
    # gathers and weighted scatter-add (the router's), the loss's labels
    others = [_scope_rx(s) for s in STEP_SCOPES if s != "lm.embed"]
    for v in instructions.values():
        if v.opcode in ("gather", "scatter") and not embed.search(v.op_name):
            assert any(rx.search(v.op_name) for rx in others), v.op_name


@pytest.mark.parametrize("norm", ["input_norm", "post_norm", "final_norm"])
def test_the_block_and_final_norms_are_under_lm_residual_norm(step, norm):
    instructions, _, _ = step
    residual = _scope_rx(RESIDUAL_NORM)
    named = [v for v in instructions.values() if f"/{norm}/" in v.op_name]
    assert any(v.opcode == "rsqrt" for v in named), norm
    assert all(residual.search(v.op_name) for v in named)


def test_the_residual_adds_are_under_lm_residual_norm(step):
    instructions, _, _ = step
    adds = [
        v for v in instructions.values()
        if v.opcode == "add" and re.search(r"lm\.residual_norm/add(_any)?$", v.op_name)
    ]
    assert adds


def test_no_instruction_is_under_two_of_the_kinds_scopes(step):
    instructions, _, _ = step
    rx = {s: _scope_rx(s) for s in STEP_SCOPES}
    seen = set()
    for v in instructions.values():
        hit = [s for s, r in rx.items() if r.search(v.op_name)]
        assert len(hit) <= 1, (v.opcode, v.op_name, hit)
        seen.update(hit)
    # every one of them is somewhere in the toy's step
    assert seen == set(STEP_SCOPES)
