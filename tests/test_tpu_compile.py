"""The flash kernels and the Gated DeltaNet chunk kernels compiled for a
described TPU v5e, no chip attached: what interpret mode cannot see (a tile
over the kernel's VMEM, a block Mosaic refuses), at the widths the
benchmark's cell runs and at the shapes that share the launchers. Nothing runs; a compile that passes is not a chip run.

The topology is described inside a fixture, after collection, and every such
test lives in this one file: only the worker that is given the file loads
the TPU compiler.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from machine_learning_apache_spark_tpu.ops import gated_delta
from machine_learning_apache_spark_tpu.ops.pallas_attention import (
    flash_attention,
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (batch, heads, q_len, kv_len, head_dim, dtype, causal, kv_valid)
SITES = {
    "q3next-4096x256-bf16-causal": (4, 16, 4096, 4096, 256, "bfloat16", True, False),
    "long-8192x64-bf16-causal": (1, 16, 8192, 8192, 64, "bfloat16", True, False),
    "1100x128-f32-valid": (2, 4, 1100, 1100, 128, "float32", False, True),
    "q640-kv1024-bf16-causal-valid": (2, 4, 640, 1024, 128, "bfloat16", True, True),
}


@pytest.mark.parametrize("site", list(SITES))
def test_chosen_tiles_compile_forward_and_backward(one_chip, site):
    b, h, q_len, kv_len, d, dtype, causal, use_valid = SITES[site]
    q = jax.ShapeDtypeStruct((b, h, q_len, d), dtype, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, h, kv_len, d), dtype, sharding=one_chip)
    valid = (
        (jax.ShapeDtypeStruct((b, kv_len), jnp.bool_, sharding=one_chip),)
        if use_valid else ()
    )

    def loss(q, k, v, *valid):
        out = flash_attention(
            q, k, v, causal=causal, kv_valid=valid[0] if valid else None
        )
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k, *valid
    ).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"%{name}" in text, f"{name} is not in the compiled program"


# (batch, length, key heads, value heads, dk = dv, dtype)
SCAN_SITES = {
    "q3next-4x4096x32x128-bf16": (4, 4096, 16, 32, 128, "bfloat16"),
    "smoke-2x200x4x128-f32": (2, 200, 2, 4, 128, "float32"),
    "3x130x3x256-bf16": (3, 130, 3, 3, 256, "bfloat16"),
}


@pytest.mark.parametrize("site", list(SCAN_SITES))
def test_gated_delta_chunk_kernels_compile(one_chip, monkeypatch, site):
    """The dispatch is told the backend is a TPU (here it would observe the
    CPU); the shapes then pass its gate on their own, and the program holds
    the primal's and the saving forward's kernel and the backward's, and
    the kernel that builds the in-chunk inverse. Nothing of XLA's
    triangular-solve expansion is left (until PR 32 the program held its
    ``InvertDiagBlocksLowerTriangular`` custom calls, 10.9 ms each at the
    benchmark's site)."""
    b, t, hk, hv, d, dtype = SCAN_SITES[site]
    monkeypatch.setattr(gated_delta, "_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((b, t, hk, d), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, t, hv, d), dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct((b, t, hv), jnp.float32, sharding=one_chip)

    def loss(*a):
        first, _ = gated_delta.gated_delta_rule(*a)  # the primal
        out, final = jax.checkpoint(gated_delta.gated_delta_rule)(*a)
        return jnp.sum((first + out).astype(jnp.float32)) + jnp.sum(final)

    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        q, q, v, g, g
    ).compile().as_text()
    for name in ("gdn_chunk_fwd", "gdn_chunk_bwd", "gdn_inverse"):
        assert f"%{name}" in text, f"{name} is not in the compiled program"
    for gone in ("InvertDiagBlocksLowerTriangular", "triangular-solve",
                 "triangular_solve"):
        assert gone not in text, f"{gone} is in the compiled program"


def _served_program(one_chip, config: str, program: str):
    """A served language model's hot program (``launch`` or ``prefill``) at
    the benchmark configuration's published widths and engine sizes, lowered
    for the described chip, and the configuration's engine sizes."""
    import importlib

    from benchmark import manifest
    from machine_learning_apache_spark_tpu.serving.lm_runtime import (
        LMDecodeRuntime,
    )

    kind = {"minicpm_sala_9b": "sala_lm", "deepseek_v32_exp": "dsa_lm"}[config]
    weights = importlib.import_module(f"benchmark.weights_{kind}")
    model_module = importlib.import_module(
        f"machine_learning_apache_spark_tpu.models.{kind}"
    )
    cfg = manifest.load_config(manifest.load_manifest(), config)
    model, engine = weights.model_config(cfg), cfg["engine"]
    runtime = object.__new__(LMDecodeRuntime)  # the programs, no planes
    runtime.cfg, runtime._donate = model, True
    runtime.steps_per_launch = engine["steps_per_launch"]
    runtime.max_new_tokens = engine["max_new_tokens"]
    rows, chunk = engine["max_active"], engine["prefill_chunk"]
    width = -(-engine["max_context"] // 64) + chunk // 64

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = described(jax.eval_shape(lambda: weights.make_params(1, cfg)))
    cache = described(jax.eval_shape(
        lambda: model_module.new_cache(model, rows=rows, num_pages=engine["num_pages"])
    ))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    flag = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_, sharding=one_chip)  # noqa: E731
    if program == "launch":
        lowered = runtime._make_launch().lower(
            params, cache, i32(rows), i32(rows), i32(rows), flag(rows),
            i32(rows, width), flag(rows),
        )
    else:
        lowered = runtime._make_prefill().lower(
            params, cache, i32(chunk), i32(width), i32(), i32(), i32(), flag()
        )
    return lowered, engine


@pytest.mark.parametrize("program", ["launch", "prefill"])
def test_served_language_model_programs_compile_in_place(one_chip, program):
    """The decoder-only runtime's two hot programs at the benchmark's
    published widths and engine sizes (``minicpm_sala_9b``): they fit the
    chip, the page store is updated in place (no copy of a whole plane: the
    first layout, ``[heads, pages, page, d]``, cost four such copies a layer
    a step), and no softmax maximum became a row-wide ``reduce-window``."""
    lowered, engine = _served_program(one_chip, "minicpm_sala_9b", program)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12e9
    assert memory.alias_size_in_bytes > 1.6e9  # the cache is donated
    text = compiled.as_text()
    plane = f"bf16[{2 * engine['num_pages'] * 64},128]"
    assert f"= {plane}" in text
    assert not [
        line for line in text.splitlines()
        if " copy(" in line and f"= {plane}" in line
    ]
    assert "reduce-window" not in text


@pytest.mark.parametrize("program", ["launch", "prefill"])
def test_served_latent_attention_programs_compile_in_place(one_chip, program):
    """``models.dsa_lm``'s two hot programs at the benchmark's published
    widths and engine sizes (``deepseek_v32_exp``): they fit the chip beside
    the weights and pages, the latent and index planes are written in place
    (no copy of a whole plane: latent rows of 576 lanes cost five 582 MB
    copies a launch, hence rows of 640), and the index scan forms no
    ``[queries, 64 heads, context]`` array (538 MB a layer in the launch)."""
    lowered, engine = _served_program(one_chip, "deepseek_v32_exp", program)
    rows, chunk = engine["max_active"], engine["prefill_chunk"]
    width = -(-engine["max_context"] // 64) + chunk // 64
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14e9
    assert memory.alias_size_in_bytes > 3.5e9  # the cache is donated
    text = compiled.as_text()
    positions = engine["num_pages"] * 64
    for plane in (f"bf16[{positions},640]", f"bf16[{positions},128]"):
        assert f"= {plane}" in text
        assert not [
            line for line in text.splitlines()
            if " copy(" in line and f"= {plane}" in line
        ], plane
    queries = rows if program == "launch" else 64
    context = -(-width // 64) * 64 * 64  # the table in whole passes of 4,096
    assert f"f32[{queries},64,{context}]" not in text
    assert f"f32[{queries},64,4096]" in text  # one pass of the scan


def test_the_decode_launch_scans_the_index_in_the_paged_kernel(one_chip, monkeypatch):
    """The decode launch traced for the TPU (the backend here is the CPU,
    so the test says which it is) takes ``dsa_index_scan`` at every layer,
    fits the chip, still writes both planes in place, and gathers no pass
    of every row's index keys (``bf16[2048,64,128]`` at 32 rows)."""
    from machine_learning_apache_spark_tpu.ops import dsa_index

    monkeypatch.setattr(dsa_index, "_backend", lambda: "tpu")
    lowered, engine = _served_program(one_chip, "deepseek_v32_exp", "launch")
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14e9
    text = compiled.as_text()
    assert "%dsa_index_scan" in text
    positions = engine["num_pages"] * 64
    for plane in (f"bf16[{positions},640]", f"bf16[{positions},128]"):
        assert not [
            line for line in text.splitlines()
            if " copy(" in line and f"= {plane}" in line
        ], plane
    rows, per_pass = engine["max_active"], 4096 // 64
    assert f"bf16[{rows * per_pass},64,128]" not in text
    assert f"f32[{rows},64,4096]" not in text


@pytest.mark.parametrize("program", ["launch", "prefill"])
def test_the_sala_programs_do_not_see_the_index_dispatch(one_chip, monkeypatch, program):
    """``minicpm_sala_9b``'s two hot programs lower to the same text
    whichever path the token indexer's dispatch would take: nothing of
    ``ops.dsa_index`` reaches them."""
    from machine_learning_apache_spark_tpu.ops import dsa_index

    before = _served_program(one_chip, "minicpm_sala_9b", program)[0].as_text()
    monkeypatch.setattr(dsa_index, "_backend", lambda: "tpu")
    after = _served_program(one_chip, "minicpm_sala_9b", program)[0].as_text()
    assert after == before
    assert "dsa_index" not in after


def _q3next_period_step(one_chip):
    """``q3next_train_full_4k``'s train step for the described chip, one
    period (three Gated DeltaNet layers and a gated attention layer, the
    experts behind them) at reduced widths and a row of 512 positions:
    the scan's and the flash kernels' gates pass, as on the chip."""
    from benchmark import manifest, weights_hybrid_lm
    from benchmark.kinds import train_lm
    from machine_learning_apache_spark_tpu.models.hybrid_lm import HybridLM
    from machine_learning_apache_spark_tpu.ops.attention import attention_impl
    from machine_learning_apache_spark_tpu.recipes import make_lm_loss
    from machine_learning_apache_spark_tpu.train.loop import make_train_step
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    cfg = manifest.load_config(manifest.load_manifest(), "qwen3_next_80b_a3b")
    cfg.update(
        vocab_size=512, hidden_size=256, num_attention_heads=2,
        num_key_value_heads=1, head_dim=128, linear_num_key_heads=1,
        linear_num_value_heads=2, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, router_width=32,
        experts_held=[0, 4], num_experts_per_tok=4,
    )
    model = HybridLM(train_lm.model_config(cfg))
    opt = cfg["optimizer"]
    tx = make_optimizer(opt["name"], opt["learning_rate"], b1=opt["b1"],
                        b2=opt["b2"], eps=opt["eps"])
    params = jax.eval_shape(lambda: weights_hybrid_lm.make_params(1, cfg))
    state = jax.eval_shape(
        lambda p: TrainState.create(apply_fn=model.apply, params=p, tx=tx), params
    )
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), state
    )
    step = make_train_step(train_lm.with_step_verdict(make_lm_loss(model)))
    with attention_impl("flash"):  # the backend here is the CPU
        lowered = step.lower(
            state, jax.ShapeDtypeStruct((1, 513), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        )
    return lowered.compile().as_text()


def _instructions(text: str) -> str:
    """The computations of a compiled module without their metadata (the
    ``op_name`` a scope writes, source lines), every instruction and
    computation renamed by the order in which it first appears (XLA numbers
    a name after the names it has met, which may be metadata's): the
    instructions alone."""
    text = text[min(text.index("\n%"), text.index("\nENTRY")):]
    text = re.sub(r",? metadata=\{[^{}]*\}", "", text)
    names = {}
    return re.sub(
        r"%[\w.\-]+",
        lambda m: names.setdefault(m[0], f"%v{len(names)}"), text,
    )


def test_the_train_steps_scopes_compile_to_the_same_instructions(one_chip, monkeypatch):
    """A named scope is metadata: the hybrid language model's train step
    compiled with its scopes (the model's ``lm.*``, the optimizer's
    ``train.update``) and with ``jax.named_scope`` a null context holds the
    same instructions, fusions and schedule, and differs in ``op_name``
    alone. So the scopes cost nothing when the step is not traced."""
    import contextlib

    monkeypatch.setattr(gated_delta, "_backend", lambda: "tpu")
    scoped = _q3next_period_step(one_chip)
    for scope in ("train.update", "lm.embed", "lm.residual_norm", "lm.gdn_scan"):
        assert f"/{scope}/" in scoped, scope
    assert "%gdn_chunk_fwd" in scoped and "%flash_fwd" in scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    unscoped = _q3next_period_step(one_chip)
    assert "lm.residual_norm" not in unscoped and "train.update" not in unscoped
    assert _instructions(scoped) == _instructions(unscoped)
