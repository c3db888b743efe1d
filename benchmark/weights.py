"""Weights from the seed: one jitted call, on the device, in float32 (the
type the program trains and serves from; its compute type is bfloat16).

The tree has the names and shapes ``models.transformer.Transformer`` reads;
nothing is taken from the program's own initialiser, so the reference and
the program are handed the same benchmark-made numbers. Distributions are
this file's choice: kernels normal with variance 1/fan_in, embeddings
normal(0, ``embed_std``), biases and LayerNorm offsets small and non-zero so
that a bias handled wrongly shows, LayerNorm scales near one.

``suppress_stop`` puts -30 on the output bias of the pad and end-of-sentence
ids: with it no request ends early, so the work of a serving window does not
depend on the seed (ISSUE 23, "the work must not depend on the seed").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EMBED_STD = 0.3
BIAS_STD = 0.02
STOP_BIAS = -30.0


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, kind) for a configuration's widths."""
    d, f = cfg["d_model"], cfg["ffn_hidden"]
    out: dict = {}

    def dense(prefix, fan_in, fan_out):
        out[prefix + "/kernel"] = ((fan_in, fan_out), "kernel")
        out[prefix + "/bias"] = ((fan_out,), "bias")

    def norm(prefix):
        out[prefix + "/scale"] = ((d,), "scale")
        out[prefix + "/bias"] = ((d,), "bias")

    for side, vocab in (("encoder", cfg["src_vocab_size"]),
                        ("decoder", cfg["trg_vocab_size"])):
        out[f"{side}/embed/embed/embedding"] = ((vocab, d), "embedding")
        for i in range(cfg["num_layers"]):
            p = f"{side}/layer_{i}"
            dense(p + "/self_attn/qkv", d, 3 * d)
            dense(p + "/self_attn/out", d, d)
            norm(p + "/ln1")
            if side == "decoder":
                dense(p + "/cross_attn/q", d, d)
                dense(p + "/cross_attn/kv", d, 2 * d)
                dense(p + "/cross_attn/out", d, d)
                norm(p + "/ln2")
            dense(p + "/ffn/up", d, f)
            dense(p + "/ffn/down", f, d)
            norm(p + ("/ln3" if side == "decoder" else "/ln2"))
    dense("lm_head", d, cfg["trg_vocab_size"])
    return out


def parameter_count(cfg: dict) -> int:
    n = 0
    for shape, _ in shapes(cfg).values():
        size = 1
        for s in shape:
            size *= s
        n += size
    return n


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, value in flat.items():
        node = tree
        *parents, leaf = name.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = value
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec: tuple, suppress_stop: tuple):
    flat = {}
    keys = jax.random.split(key, len(spec))
    for k, (name, shape, kind) in zip(keys, spec):
        noise = jax.random.normal(k, shape, jnp.float32)
        if kind == "kernel":
            value = noise / jnp.sqrt(jnp.float32(shape[0]))
        elif kind == "embedding":
            value = EMBED_STD * noise
        elif kind == "scale":
            value = 1.0 + BIAS_STD * noise
        else:
            value = BIAS_STD * noise
        if name == "lm_head/bias" and suppress_stop:
            value = value.at[jnp.asarray(suppress_stop)].set(STOP_BIAS)
        flat[name] = value
    return _nest(flat)


def make_params(seed: int, cfg: dict, *, suppress_stop: bool = False):
    """The parameter tree for ``cfg`` from ``seed``. ``seed`` may exceed 32
    signed bits; it is folded into the key as two 31-bit halves."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF), int(seed) >> 31
    )
    spec = tuple(
        (name, shape, kind) for name, (shape, kind) in shapes(cfg).items()
    )
    stop = (cfg["pad_id"], cfg["eos_id"]) if suppress_stop else ()
    return _make(key, spec, stop)
