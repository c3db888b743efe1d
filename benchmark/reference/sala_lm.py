"""Plain float32 reference of the MiniCPM-SALA block: lightning
(linear-attention) and InfLLM-V2 block-sparse attention layers in the
configuration's ``mixer_types`` order, a dense SwiGLU after each, MiniCPM's
``scale_emb`` / ``scale_depth`` / ``dim_model_base`` scalings.

Straight ``jax.numpy``: no kernel, no cache, no pages, no chunk algebra and
no import of the program under test. One request at a time, the **whole**
sequence (document, question and served tokens) in one teacher-forced pass,
following ``benchmark/configs/minicpm_sala_9b.json`` ("architecture"):

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``; ``h = x + c Mixer(RMSNorm(x))``,
  ``y = h + c MLP(RMSNorm(h))``, ``c = scale_depth / sqrt(num_hidden_layers)``
  (the published depth); embedding times ``scale_emb``; final norm, hidden
  over ``hidden_size / dim_model_base``, untied head (plus the harness's
  ``logit_bias`` where the weights carry one).
- lightning layer by its **per-token recurrence** ``S_t = exp(-s_h) S_{t-1} +
  k_t^T v_t``, ``o_t = (q_t / sqrt(d)) S_t``, one position a ``lax.scan``
  step; QK-norm, rotary (rotate-half, theta ``rope_theta``), output norm a
  head and sigmoid output gate.
- sparse layer: compressed keys ``kc_j = mean(k[16 j : 16 j + 32])``, the
  exact softmax over the visible ones, summed over the KV head's 16 query
  heads, max-pooled to blocks (kernel 5, stride 4, padding 1), block 0 and
  the query's own block with the 31 before it forced, the 64 highest taken;
  then a **dense masked softmax over all positions**, the mask being the
  tokens of the blocks the reference's own selection names. A request whose
  whole length stays under ``dense_len`` attends every position.

It is computed in blocks of ``block`` tokens so that 65k positions fit
beside the weights: a lightning layer carries its state from block to block
(it is a recurrence), a sparse layer first writes every position's K and V
(``[t_max, 2, 128]`` float32, 68 MB at 66,560) and then attends a block of
queries at a time. Every jitted piece has shapes that depend on ``block`` and
``t_max`` alone, so one set of programs serves every request of a process.
The weights are the program's bfloat16 arrays read as float32 (the
configuration states bfloat16 weights); every product is float32 at
``highest``.

``matmul`` is a parameter of every projection so that a control can put a
lower precision in its place; ``select="window_only"`` (the forced blocks
alone, no top-k) and ``zero_state_at`` (every lightning state zeroed on
reaching that position, as a prefix hit that restored nothing would) are the
planted faults.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.transformer import (  # noqa: F401  (re-exported)
    f32_matmul,
    fp8_matmul,
    lowp_matmul,
    on_device,
)

NEG = -1e30
HI = jax.lax.Precision.HIGHEST
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
MATMULS = {"f32": f32_matmul, "int8": lowp_matmul, "fp8": fp8_matmul}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def rotary(x, positions, theta: float):
    """``x [T, H, d]`` at ``positions [T]``: channel ``i < d / 2`` pairs with
    ``i + d / 2`` and turns by ``position * theta^(-2i/d)`` (float32
    angles)."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(
        theta ** (-np.arange(0, d, 2, dtype=np.float64) / d), jnp.float32
    )
    angles = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _sizes(cfg):
    s = cfg["sparse_config"]
    return dict(
        stride=s["kernel_stride"], kernel=s["kernel_size"], block=s["block_size"],
        topk=s["topk"], window=s["window_size"] // s["block_size"],
        init=s["init_blocks"], dense_len=s["dense_len"],
    )


def _mlp(p, x, matmul):
    f = lambda w: w.astype(jnp.float32)  # noqa: E731
    return matmul(
        jax.nn.silu(matmul(x, f(p["gate"]))) * matmul(x, f(p["up"])), f(p["down"])
    )


def _after_mixer(p, cfg, x, mixed, matmul):
    c = cfg["scale_depth"] / cfg["num_hidden_layers"] ** 0.5
    h = x + c * mixed
    return h + c * _mlp(p["mlp"], rms_norm(h, p["post_norm"], cfg["rms_norm_eps"]), matmul)


def lightning_block(p, cfg, x, state, start, zero_at, matmul):
    """One block of tokens through a lightning layer and its MLP. ``x [B,
    D]``, ``state [H, d, d]``; returns ``(y [B, D], state)``."""
    heads, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps, m = cfg["rms_norm_eps"], p["mixer"]
    f = lambda w: w.astype(jnp.float32)  # noqa: E731
    n = x.shape[0]
    positions = start + jnp.arange(n)
    hidden = rms_norm(x, p["input_norm"], eps)
    split = lambda t: t.reshape(n, heads, d)  # noqa: E731
    q = rms_norm(split(matmul(hidden, f(m["q"]))), m["q_norm"], eps)
    k = rms_norm(split(matmul(hidden, f(m["k"]))), m["k_norm"], eps)
    v = split(matmul(hidden, f(m["v"])))
    q = rotary(q, positions, cfg["rope_theta"])
    k = rotary(k, positions, cfg["rope_theta"])
    slopes = jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)
    lam = jnp.exp(-slopes)[:, None, None]

    def step(s, xs):
        qt, kt, vt, pos = xs
        s = jnp.where(pos == zero_at, 0.0, s)
        s = lam * s + kt[:, :, None] * vt[:, None, :]
        return s, jnp.einsum("hd,hde->he", qt * d ** -0.5, s, precision=HI)

    state, o = jax.lax.scan(step, state, (q, k, v, positions))
    o = rms_norm(o, m["out_norm"], eps).reshape(n, heads * d)
    o = o * jax.nn.sigmoid(matmul(hidden, f(m["gate"])))
    return _after_mixer(p, cfg, x, matmul(o, f(m["o"])), matmul), state


def kv_block(p, cfg, x, matmul):
    """Keys (normed) and values of one block of a sparse layer: ``[B, G, d]``."""
    g, d = cfg["num_key_value_heads"], cfg["head_dim"]
    eps, m = cfg["rms_norm_eps"], p["mixer"]
    n = x.shape[0]
    hidden = rms_norm(x, p["input_norm"], eps)
    k = matmul(hidden, m["k"].astype(jnp.float32)).reshape(n, g, d)
    v = matmul(hidden, m["v"].astype(jnp.float32)).reshape(n, g, d)
    return rms_norm(k, m["k_norm"], eps), v


def select_blocks(q, keys, t, sz, dense, select):
    """The selection of queries ``q [N, G, Hg, d]`` at positions ``t [N]``
    over ``keys [T, G, d]``: a bool mask ``[N, G, T / block]``."""
    d = q.shape[-1]
    stride, block, m = sz["stride"], sz["block"], sz["block"] // sz["stride"]
    n_tok = keys.shape[0]
    units = keys.reshape(n_tok // stride, stride, *keys.shape[1:])
    # windows of kernel = 2 strides: kc_j = mean(k[stride j : stride j + kernel])
    kc = 0.5 * (jnp.mean(units, 1) + jnp.roll(jnp.mean(units, 1), -1, axis=0))
    j = jnp.arange(kc.shape[0])
    visible = (stride * j[None, :] + sz["kernel"] - 1 <= t[:, None]) & (
        j[None, :] < kc.shape[0] - 1
    )
    s = jnp.einsum("nghd,ugd->nghu", q, kc, precision=HI) * d ** -0.5
    s = jnp.where(visible[:, None, None, :], s, NEG)
    p = jnp.where(visible[:, None, None, :], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    r = p.sum(2)  # [N, G, U]
    n_blocks = n_tok // block
    i = jnp.arange(n_blocks)
    # block i: compressed keys m i - 1 .. m i + m - 1
    padded = jnp.pad(r, [(0, 0), (0, 0), (1, 0)])
    windows = jnp.stack(
        [padded[..., m * i + off] for off in range(m + 1)], axis=-1
    )
    b = windows.max(-1)  # [N, G, n_blocks]
    own = (t // block)[:, None]
    forced = (i[None, :] < sz["init"]) | (i[None, :] > own - sz["window"])
    reach = i[None, :] <= own
    if select == "window_only":
        return jnp.broadcast_to((forced & reach)[:, None, :], b.shape)
    score = jnp.where(forced[:, None, :], jnp.inf, b)
    score = jnp.where(reach[:, None, :], score, -jnp.inf)
    top, idx = jax.lax.top_k(score, min(sz["topk"], n_blocks))
    taken = (
        (idx[..., None] == i) & (top > -jnp.inf)[..., None]
    ).any(-2)
    return jnp.where(dense, jnp.broadcast_to(reach[:, None, :], b.shape), taken)


def sparse_block(p, cfg, x, keys, values, start, dense, select, query_rows, matmul):
    """One block of tokens through a sparse layer and its MLP, given every
    position's ``keys`` / ``values [T, G, d]``. Returns ``(y [B, D], taken
    [B, G, T / block] bool)``."""
    sz = _sizes(cfg)
    heads, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, m = cfg["rms_norm_eps"], p["mixer"]
    f = lambda w: w.astype(jnp.float32)  # noqa: E731
    n = x.shape[0]
    hidden = rms_norm(x, p["input_norm"], eps)
    q = matmul(hidden, f(m["q"])).reshape(n, g, heads // g, d)
    q = rms_norm(q, m["q_norm"], eps)
    t = start + jnp.arange(n)
    key_pos = jnp.arange(keys.shape[0])

    def rows(xs):
        qx, tx = xs
        taken = select_blocks(qx, keys, tx, sz, dense, select)
        mask = jnp.repeat(taken, sz["block"], axis=-1) & (
            key_pos[None, None, :] <= tx[:, None, None]
        )
        s = jnp.einsum("nghd,sgd->nghs", qx, keys, precision=HI) * d ** -0.5
        s = jnp.where(mask[:, :, None, :], s, NEG)
        w = jnp.where(mask[:, :, None, :], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
        return jnp.einsum("nghs,sgd->nghd", w, values, precision=HI), taken

    cut = lambda a: a.reshape(n // query_rows, query_rows, *a.shape[1:])  # noqa: E731
    o, taken = jax.lax.map(rows, (cut(q), cut(t)))
    o = o.reshape(n, heads * d) * jax.nn.sigmoid(matmul(hidden, f(m["gate"])))
    y = _after_mixer(p, cfg, x, matmul(o, f(m["o"])), matmul)
    return y, taken.reshape(n, *taken.shape[2:])


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, matmul_name: str, select: str, query_rows: int):
    cfg = json.loads(cfg_json)
    matmul = MATMULS[matmul_name]
    return dict(
        lightning=jax.jit(
            lambda p, x, s, start, zero_at: lightning_block(
                p, cfg, x, s, start, zero_at, matmul
            )
        ),
        kv=jax.jit(lambda p, x: kv_block(p, cfg, x, matmul)),
        sparse=jax.jit(
            lambda p, x, k, v, start, dense: sparse_block(
                p, cfg, x, k, v, start, dense, select, query_rows, matmul
            )
        ),
        head=jax.jit(lambda params, x: head(params, cfg, x, matmul)),
    )


def head(params, cfg, x, matmul=f32_matmul):
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    x = x / (cfg["hidden_size"] / cfg["dim_model_base"])
    logits = matmul(x, params["lm_head"].astype(jnp.float32))
    if "logit_bias" in params:
        logits = logits + params["logit_bias"]
    return logits


def forward(params, cfg, tokens, out_positions, *, t_max: int, block: int,
            dense: bool, matmul: str = "f32", select: str = "topk",
            zero_state_at: int = -1, query_rows: int = 128):
    """Logits ``[len(out_positions), V]`` of the whole sequence ``tokens``
    (a 1-D int array) at ``out_positions`` (each the logits that predict the
    next position), and the blocks every sparse layer's selection took there:
    a bool array ``[sparse layers, len(out_positions), G, t_max / block
    size]``. ``t_max`` (a multiple of ``block``) bounds the sequence;
    ``dense`` is whether the request attends everything."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    if n > t_max or t_max % block:
        raise ValueError(f"{n} tokens, t_max {t_max}, block {block}")
    out_positions = np.asarray(out_positions, np.int64)
    prog = _programs(
        json.dumps(cfg, sort_keys=True), matmul, select, min(query_rows, block)
    )
    n_blocks = -(-n // block)
    padded = np.zeros(n_blocks * block, np.int32)
    padded[:n] = tokens
    x = params["embedding"][jnp.asarray(padded)].astype(jnp.float32) * cfg["scale_emb"]
    g, d = cfg["num_key_value_heads"], cfg["head_dim"]
    heads, dl = cfg["lightning_nh"], cfg["lightning_head_dim"]
    taken_all = []
    kinds = cfg["mixer_types"][: cfg["num_layers"]]
    for p, kind in zip(params["layers"], kinds):
        blocks = []
        if kind == LIGHTNING:
            state = jnp.zeros((heads, dl, dl), jnp.float32)
            for b in range(n_blocks):
                y, state = prog["lightning"](
                    p, x[b * block:(b + 1) * block], state,
                    jnp.int32(b * block), jnp.int32(zero_state_at),
                )
                blocks.append(y)
        else:
            keys = jnp.zeros((t_max, g, d), jnp.float32)
            values = jnp.zeros((t_max, g, d), jnp.float32)
            for b in range(n_blocks):
                k, v = prog["kv"](p, x[b * block:(b + 1) * block])
                keys = jax.lax.dynamic_update_slice_in_dim(keys, k, b * block, 0)
                values = jax.lax.dynamic_update_slice_in_dim(values, v, b * block, 0)
            taken = []
            for b in range(n_blocks):
                y, tk = prog["sparse"](
                    p, x[b * block:(b + 1) * block], keys, values,
                    jnp.int32(b * block), jnp.bool_(dense),
                )
                blocks.append(y)
                inside = out_positions[
                    (out_positions >= b * block) & (out_positions < (b + 1) * block)
                ]
                if len(inside):
                    taken.append(np.asarray(tk)[inside - b * block])
            taken_all.append(np.concatenate(taken))
        x = jnp.concatenate(blocks)
    logits = prog["head"](params, x[jnp.asarray(out_positions)])
    taken = (
        np.stack(taken_all) if taken_all
        else np.zeros((0, len(out_positions), g, 0), bool)
    )
    return np.asarray(logits), taken
