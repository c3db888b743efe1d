"""Weights of the hybrid language model from the seed: one jitted call, on
the device, in float32 (``weights.py`` does the same for the encoder-decoder).

The tree has the names and shapes ``models.hybrid_lm.HybridLM`` reads; nothing
is taken from the program's own initialiser, so the reference and the program
are handed the same benchmark-made numbers. Distributions (the configuration
file lists them under ``assumed``):

- projection kernels normal with variance 1 / fan_in (stacked expert kernels
  a matrix each); ``in_proj_ba`` at half that deviation, so that the gates
  ``beta`` and ``g`` stay spread by the learnt offsets, not by the input;
- embedding normal(0, 1): the residual stream starts at unit RMS;
- ``(1 + w)`` norm weights ``w`` normal(0, 0.02), the gated norm's plain
  weight 1 + normal(0, 0.02): non-zero, so that a norm handled wrongly shows;
- the convolution normal with variance 1 / kernel width;
- ``A_log = log(A)``, ``A`` uniform in [0.5, 2]; ``dt_bias`` the inverse
  softplus of a step log-uniform in [0.002, 0.05]: the per-token decay
  ``exp(-A * softplus(a + dt_bias))`` then spans about 0.9 to 0.999 across
  heads, so the state is neither forgotten at once nor never.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.flops_hybrid_lm import is_full_attention
from benchmark.weights import _nest

NORM_STD = 0.02


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, kind) for a configuration's widths."""
    d = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    held = cfg["experts_held"][1]
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    out = {
        "embedding": ((cfg["vocab_size"], d), "embedding"),
        "lm_head": ((d, cfg["vocab_size"]), "kernel"),
        "final_norm/w": ((d,), "offset"),
    }
    for i in range(cfg["num_layers"]):
        p = f"layer_{i}"
        out[f"{p}/input_norm/w"] = ((d,), "offset")
        out[f"{p}/post_norm/w"] = ((d,), "offset")
        m = f"{p}/mixer"
        if is_full_attention(cfg, i):
            out[f"{m}/q_proj"] = ((d, h * 2 * dh), "kernel")
            out[f"{m}/k_proj"] = ((d, hkv * dh), "kernel")
            out[f"{m}/v_proj"] = ((d, hkv * dh), "kernel")
            out[f"{m}/o_proj"] = ((h * dh, d), "kernel")
            out[f"{m}/q_norm/w"] = ((dh,), "offset")
            out[f"{m}/k_norm/w"] = ((dh,), "offset")
        else:
            out[f"{m}/in_proj_qkvz"] = ((d, 2 * key_dim + 2 * value_dim), "kernel")
            out[f"{m}/in_proj_ba"] = ((d, 2 * hv), "half_kernel")
            out[f"{m}/conv"] = (
                (cfg["linear_conv_kernel_dim"], 2 * key_dim + value_dim), "kernel",
            )
            out[f"{m}/A_log"] = ((hv,), "a_log")
            out[f"{m}/dt_bias"] = ((hv,), "dt_bias")
            out[f"{m}/norm/w"] = ((dv,), "scale")
            out[f"{m}/out_proj"] = ((value_dim, d), "kernel")
        e = f"{p}/moe"
        out[f"{e}/router"] = ((d, cfg["router_width"]), "kernel")
        out[f"{e}/w_gate"] = ((held, d, f), "kernel")
        out[f"{e}/w_up"] = ((held, d, f), "kernel")
        out[f"{e}/w_down"] = ((held, f, d), "kernel")
        out[f"{e}/shared_gate"] = ((d, fs), "kernel")
        out[f"{e}/shared_up"] = ((d, fs), "kernel")
        out[f"{e}/shared_down"] = ((fs, d), "kernel")
        out[f"{e}/shared_router"] = ((d, 1), "kernel")
    return out


def parameter_count(cfg: dict) -> int:
    return sum(math.prod(shape) for shape, _ in shapes(cfg).values())


@functools.partial(jax.jit, static_argnums=1)
def _make(key, spec: tuple):
    flat = {}
    for k, (name, shape, kind) in zip(jax.random.split(key, len(spec)), spec):
        if kind == "a_log":
            value = jnp.log(jax.random.uniform(k, shape, jnp.float32, 0.5, 2.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(0.002), math.log(0.05)
            ))
            value = dt + jnp.log(-jnp.expm1(-dt))
        else:
            noise = jax.random.normal(k, shape, jnp.float32)
            if kind in ("kernel", "half_kernel"):
                value = noise / jnp.sqrt(jnp.float32(shape[-2]))
                if kind == "half_kernel":
                    value = 0.5 * value
            elif kind == "embedding":
                value = noise
            elif kind == "scale":
                value = 1.0 + NORM_STD * noise
            else:
                value = NORM_STD * noise
        flat[name] = value
    return _nest(flat)


def make_params(seed: int, cfg: dict):
    """The parameter tree for ``cfg`` from ``seed``. ``seed`` may exceed 32
    signed bits; it is folded into the key as two 31-bit halves."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF), int(seed) >> 31
    )
    spec = tuple(
        (name, shape, kind) for name, (shape, kind) in shapes(cfg).items()
    )
    return _make(key, spec)
