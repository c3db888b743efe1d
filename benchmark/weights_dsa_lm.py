"""Weights of the DeepSeek-V3.2-Exp block from the seed: one jitted call, on
the device, in the configuration's ``weight_dtype`` (bfloat16), and the
program's model configuration built from the configuration's file.

The tree has the names and shapes ``models.dsa_lm`` reads; nothing is taken
from the program's own initialiser, and the reference is handed the same
arrays (it reads them as float32). Distributions (the configuration file
lists them under ``assumed``):

- projection kernels, the router and the experts normal with variance 1 /
  fan_in;
- embedding normal(0, 1);
- norm weights (and the index keys' LayerNorm weight) 1 + normal(0, 0.02),
  the LayerNorm's bias normal(0, 0.02): non-zero noise so that a norm handled
  wrongly shows;
- the router's correction bias normal(0, ``router_bias_std``), float32;
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


def shapes(cfg: dict) -> list[tuple[tuple, tuple, str]]:
    """(path, shape, kind) of every leaf, in a fixed order."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    held = cfg["experts_held"][1]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    out = [
        (("embedding",), (cfg["vocab_size"], d), "embedding"),
        (("lm_head",), (d, cfg["vocab_size"]), "kernel"),
        (("final_norm",), (d,), "norm"),
    ]
    for i in range(cfg["num_layers"]):
        at = lambda *names: ("layers", i, *names)  # noqa: E731
        out += [
            (at("input_norm"), (d,), "norm"), (at("post_norm"), (d,), "norm"),
            (at("attn", "wq_a"), (d, ql), "kernel"),
            (at("attn", "q_norm"), (ql,), "norm"),
            (at("attn", "wq_b"), (ql, h * (dn + dr)), "kernel"),
            (at("attn", "wkv_a"), (d, kvl + dr), "kernel"),
            (at("attn", "kv_norm"), (kvl,), "norm"),
            (at("attn", "wkv_b"), (kvl, h * (dn + dv)), "kernel"),
            (at("attn", "wo"), (h * dv, d), "kernel"),
            (at("index", "wq_b"), (ql, hi * di), "kernel"),
            (at("index", "wk"), (d, di), "kernel"),
            (at("index", "k_norm"), (di,), "norm"),
            (at("index", "k_bias"), (di,), "bias"),
            (at("index", "weights_proj"), (d, hi), "kernel"),
        ]
        if i < cfg["first_k_dense_replace"]:
            out += [
                (at("mlp", "gate"), (d, f), "kernel"),
                (at("mlp", "up"), (d, f), "kernel"),
                (at("mlp", "down"), (f, d), "kernel"),
            ]
        else:
            out += [
                (at("moe", "router"), (d, cfg["router_width"]), "kernel"),
                (at("moe", "bias"), (cfg["router_width"],), "router_bias"),
                (at("moe", "w_gate"), (held, d, fe), "kernel"),
                (at("moe", "w_up"), (held, d, fe), "kernel"),
                (at("moe", "w_down"), (held, fe, d), "kernel"),
                (at("moe", "shared_gate"), (d, fs), "kernel"),
                (at("moe", "shared_up"), (d, fs), "kernel"),
                (at("moe", "shared_down"), (fs, d), "kernel"),
            ]
    return out


def parameter_count(cfg: dict) -> int:
    return sum(math.prod(shape) for _, shape, _ in shapes(cfg))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, spec: tuple, dtype: str, bias_std: float):
    leaves = []
    for k, (_, shape, kind) in zip(jax.random.split(key, len(spec)), spec):
        noise = jax.random.normal(k, shape, jnp.float32)
        if kind == "kernel":
            leaves.append((noise * shape[-2] ** -0.5).astype(dtype))
        elif kind == "embedding":
            leaves.append(noise.astype(dtype))
        elif kind == "router_bias":
            leaves.append(bias_std * noise)
        elif kind == "bias":
            leaves.append(NORM_STD * noise)
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
        key, spec, cfg["weight_dtype"], float(cfg["weights"]["router_bias_std"])
    )
    params: dict = {"layers": [{} for _ in range(cfg["num_layers"])]}
    for (path, _, _), leaf in zip(spec, leaves):
        node = params
        for name in path[:-1]:
            node = node[name] if isinstance(name, int) else node.setdefault(name, {})
        node[path[-1]] = leaf
    if cfg.get("eos_token_id") is not None:
        params["logit_bias"] = jnp.zeros(
            (cfg["vocab_size"],), jnp.float32
        ).at[cfg["eos_token_id"]].set(STOP_BIAS)
    return params


def model_config(cfg: dict):
    """The program's ``DSALMConfig`` for a configuration's file."""
    from machine_learning_apache_spark_tpu.models.dsa_lm import DSALMConfig

    rope = cfg["rope_scaling"]
    if rope["mscale"] != rope["mscale_all_dim"]:
        raise ValueError("the program scales the rotary's cos and sin by 1")
    return DSALMConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_layers"],
        first_dense=cfg["first_k_dense_replace"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        index_heads=cfg["index_n_heads"], index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], num_experts=cfg["router_width"],
        experts_held=tuple(cfg["experts_held"]),
        experts_per_token=cfg["num_experts_per_tok"],
        expert_groups=cfg["n_group"], groups_kept=cfg["topk_group"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_theta"]), rope_factor=float(rope["factor"]),
        rope_original=rope["original_max_position_embeddings"],
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        mscale_all_dim=float(rope["mscale_all_dim"]),
        rms_eps=cfg["rms_norm_eps"],
        max_positions=cfg["max_position_embeddings"],
        eos_id=cfg.get("eos_token_id"), dtype=jnp.dtype(cfg["weight_dtype"]),
        **cfg.get("program", {}),
    )
