"""Weights of the MiniCPM-SALA block from the seed: one jitted call, on the
device, in the configuration's ``weight_dtype`` (bfloat16), and the program's
model configuration built from the configuration's file.

The tree has the names and shapes ``models.sala_lm`` reads; nothing is taken
from the program's own initialiser, and the reference is handed the same
arrays (it reads them as float32). Distributions (the configuration file
lists them under ``assumed``):

- projection kernels normal with variance 1 / fan_in;
- embedding normal(0, 1 / scale_emb): times ``scale_emb`` the residual
  stream starts at unit RMS, as a muP-parametrised model's does (at unit
  variance the stream would be 12 times every branch's output and no layer
  would show in the logits);
- norm weights 1 + normal(0, 0.02), non-zero noise so that a norm handled
  wrongly shows; the sparse layers' ``q_norm`` / ``k_norm`` weights are
  centred on ``qk_gain`` instead (1.6: scores of deviation 2.6, so that a
  query's attention rests on tens of keys, not thousands, and what the
  selector leaves out shows in the output);
- where the configuration names an ``eos_token_id``, ``logit_bias`` holds
  ``STOP_BIAS`` at that id and 0 elsewhere: seed-made weights would end an
  answer where chance puts it, and the mix fixes the answer's length.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NORM_STD = 0.02
STOP_BIAS = -30.0
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def mixer_types(cfg: dict) -> list[str]:
    return list(cfg["mixer_types"][: cfg["num_layers"]])


def shapes(cfg: dict) -> list[tuple[tuple, tuple, str]]:
    """(path, shape, kind) of every leaf, in a fixed order."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    gd = cfg["num_key_value_heads"] * cfg["head_dim"]
    ld = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    out = [
        (("embedding",), (cfg["vocab_size"], d), "embedding"),
        (("lm_head",), (d, cfg["vocab_size"]), "kernel"),
        (("final_norm",), (d,), "norm"),
    ]
    for i, kind in enumerate(mixer_types(cfg)):
        at = lambda *names: ("layers", i, *names)  # noqa: E731
        out += [(at("input_norm"), (d,), "norm"), (at("post_norm"), (d,), "norm")]
        if kind == SPARSE:
            out += [
                (at("mixer", "q"), (d, hd), "kernel"),
                (at("mixer", "k"), (d, gd), "kernel"),
                (at("mixer", "v"), (d, gd), "kernel"),
                (at("mixer", "gate"), (d, hd), "kernel"),
                (at("mixer", "o"), (hd, d), "kernel"),
                (at("mixer", "q_norm"), (cfg["head_dim"],), "qk_gain"),
                (at("mixer", "k_norm"), (cfg["head_dim"],), "qk_gain"),
            ]
        else:
            out += [
                (at("mixer", name), (d, ld), "kernel")
                for name in ("q", "k", "v", "gate")
            ]
            out += [
                (at("mixer", "o"), (ld, d), "kernel"),
                (at("mixer", "q_norm"), (cfg["lightning_head_dim"],), "norm"),
                (at("mixer", "k_norm"), (cfg["lightning_head_dim"],), "norm"),
                (at("mixer", "out_norm"), (cfg["lightning_head_dim"],), "norm"),
            ]
        out += [
            (at("mlp", "gate"), (d, f), "kernel"),
            (at("mlp", "up"), (d, f), "kernel"),
            (at("mlp", "down"), (f, d), "kernel"),
        ]
    return out


def parameter_count(cfg: dict) -> int:
    return sum(math.prod(shape) for _, shape, _ in shapes(cfg))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make(key, spec: tuple, dtype: str, scale_emb: float, qk_gain: float):
    leaves = []
    for k, (_, shape, kind) in zip(jax.random.split(key, len(spec)), spec):
        noise = jax.random.normal(k, shape, jnp.float32)
        if kind == "kernel":
            leaves.append((noise * shape[0] ** -0.5).astype(dtype))
        elif kind == "embedding":
            leaves.append((noise / scale_emb).astype(dtype))
        elif kind == "qk_gain":
            leaves.append(qk_gain + NORM_STD * noise)
        else:
            leaves.append(1.0 + NORM_STD * noise)
    return leaves


def make_params(seed: int, cfg: dict) -> dict:
    """The parameter tree for ``cfg`` from ``seed`` (which may exceed 32
    signed bits: it is folded into the key as two 31-bit halves)."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF), int(seed) >> 31
    )
    spec = tuple(shapes(cfg))
    leaves = _make(
        key, spec, cfg["weight_dtype"], float(cfg["scale_emb"]),
        float(cfg["weights"]["qk_gain"]),
    )
    params: dict = {"layers": [
        {"mixer": {}, "mlp": {}} for _ in mixer_types(cfg)
    ]}
    for (path, _, _), leaf in zip(spec, leaves):
        node = params
        for name in path[:-1]:
            node = node[name]
        node[path[-1]] = leaf
    if cfg.get("eos_token_id") is not None:
        params["logit_bias"] = jnp.zeros(
            (cfg["vocab_size"],), jnp.float32
        ).at[cfg["eos_token_id"]].set(STOP_BIAS)
    return params


def model_config(cfg: dict):
    """The program's ``SalaLMConfig`` for a configuration's file."""
    from machine_learning_apache_spark_tpu.models.sala_lm import SalaLMConfig
    from machine_learning_apache_spark_tpu.ops.sparse_block_attention import (
        SparseSpec,
    )

    s = cfg["sparse_config"]
    if s["kernel_size"] != 2 * s["kernel_stride"]:
        raise ValueError("the program's unit means need kernel = 2 strides")
    return SalaLMConfig(
        vocab_size=cfg["vocab_size"], mixer_types=tuple(mixer_types(cfg)),
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        lightning_heads=cfg["lightning_nh"],
        lightning_head_dim=cfg["lightning_head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        scale_emb=float(cfg["scale_emb"]), scale_depth=cfg["scale_depth"],
        depth_for_scale=cfg["num_hidden_layers"],
        dim_model_base=cfg["dim_model_base"],
        max_positions=cfg["max_position_embeddings"],
        sparse=SparseSpec(
            block=s["block_size"], stride=s["kernel_stride"], topk=s["topk"],
            window_blocks=s["window_size"] // s["block_size"],
            init_blocks=s["init_blocks"], dense_len=s["dense_len"],
        ),
        eos_id=cfg.get("eos_token_id"),
        dtype=jnp.dtype(cfg["weight_dtype"]),
    )
