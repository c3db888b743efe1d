"""Plain float32 reference of the hybrid language-model block: Gated DeltaNet
and gated softmax-attention layers in a period, a top-k expert layer after
each, next-token loss, gradients and Adam.

Straight ``jax.numpy``: no kernel, no chunk algebra, no sort or gather in the
expert layer, and no import of the program under test. It follows the
``qwen3_next`` block as the configuration's file spells it out
(``benchmark/configs/qwen3_next_80b_a3b.json``, "architecture"):

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``; pre-norm residual
  blocks ``h = x + Mixer(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``.
- Gated DeltaNet by its **per-token recurrence** (``S <- exp(g) S``,
  ``u = beta (v - S^T k)``, ``S <- S + k u^T``, ``o = S^T q``), one position a
  ``lax.scan`` step. The scan is nested, ``RECURRENCE_SEGMENT`` positions in
  an inner scan under ``jax.checkpoint``: the backward pass otherwise keeps
  every position's state, 4096 x [32, 128, 128] float32 = 8.6 GB a row.
- Gated attention as a dense masked softmax over all positions, each KV head
  serving ``num_attention_heads / num_key_value_heads`` query heads.
- The expert layer as **every held expert applied to every token, times a
  gate weight that is zero where the token did not choose it**; the same
  ``experts_held`` share as the program; ``top_k`` is a parameter so that a
  planted fault can route to one expert instead of ten.
- The router's auxiliary loss ``E * sum_e f_e p_e`` is over the whole batch
  while the gradient is taken in blocks of rows: ``f`` (the share of
  assignments an expert, which carries no gradient) is counted in a first
  pass over all blocks and handed to each block's gradient, in which
  ``p``'s sum over the block's tokens is linear.

Departure from the published model, the program's too: no multi-token
prediction module. Layout choices that are this repository's (the published
checkpoint interleaves them a key head): ``in_proj_qkvz`` columns are
``[q | k | v | z]``, ``in_proj_ba`` columns ``[b | a]``, ``q_proj`` columns
are ``[head, (query, gate), head_dim]``.

``matmul`` is a parameter of every projection so that the control of the
``correct`` decision can put a lower precision in its place; the leaf norms
and the low-precision products are shared with ``reference/transformer.py``.
Adam is that file's update too, written again in numpy on the host
(``adam_step_host``): four float32 copies of this model do not fit the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.transformer import (  # noqa: F401  (re-exported)
    f32_matmul,
    fp8_matmul,
    leaf_norms,
    lowp_matmul,
    on_device,
)

NEG = -1e30
RECURRENCE_SEGMENT = 64
HI = jax.lax.Precision.HIGHEST


def rms_norm(x, w, eps, *, offset=True):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * ((1.0 + w) if offset else w)


def rotary(x, rotary_dim: int, theta: float):
    """``x [B, H, S, d]``: channel ``i < rotary_dim / 2`` pairs with
    ``i + rotary_dim / 2`` and turns by ``position * theta^(-2i/rotary_dim)``."""
    s = x.shape[-2]
    inv_freq = theta ** (-np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim)
    angles = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos, sin = np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)
    half = rotary_dim // 2
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def delta_recurrence(q, k, v, g, beta):
    """``q, k, v [B, T, H, d]`` (one key a value head already), ``g, beta
    [B, T, H]`` -> ``o [B, T, H, dv]``."""
    b, t, h, dk = q.shape
    seg = RECURRENCE_SEGMENT if t % RECURRENCE_SEGMENT == 0 else t

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HI))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HI)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(step, s, xs)

    def segments(x):  # [B, T, ...] -> [T / seg, seg, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(t // seg, seg, *x.shape[1:])

    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(segment, state, tuple(map(segments, (q, k, v, g, beta))))
    return jnp.moveaxis(out.reshape(t, b, h, -1), 0, 1)


def gated_delta_net(p, x, cfg, matmul):
    b, s, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    qkvz = matmul(x, p["in_proj_qkvz"])
    qkv, z = qkvz[..., :2 * key_dim + value_dim], qkvz[..., 2 * key_dim + value_dim:]
    ba = matmul(x, p["in_proj_ba"])
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    width = p["conv"].shape[0]
    padded = jnp.pad(qkv, [(0, 0), (width - 1, 0), (0, 0)])
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * p["conv"][j] for j in range(width)))
    q = qkv[..., :key_dim].reshape(b, s, hk, dk)
    k = qkv[..., key_dim:2 * key_dim].reshape(b, s, hk, dk)
    v = qkv[..., 2 * key_dim:].reshape(b, s, hv, dv)
    unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6
    )
    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(unit(k), hv // hk, axis=2)
    o = delta_recurrence(q, k, v, g, beta)
    o = rms_norm(o, p["norm"]["w"], cfg["rms_norm_eps"], offset=False)
    o = o * jax.nn.silu(z.reshape(b, s, hv, dv))
    return matmul(o.reshape(b, s, value_dim), p["out_proj"])


def gated_attention(p, x, cfg, matmul):
    b, s, _ = x.shape
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    qg = matmul(x, p["q_proj"]).reshape(b, s, h, 2, dh)
    q, gate = qg[..., 0, :], qg[..., 1, :]
    k = matmul(x, p["k_proj"]).reshape(b, s, hkv, dh)
    v = matmul(x, p["v_proj"]).reshape(b, s, hkv, dh)
    q = rms_norm(q, p["q_norm"]["w"], eps).transpose(0, 2, 1, 3)
    k = rms_norm(k, p["k_norm"]["w"], eps).transpose(0, 2, 1, 3)
    rotary_dim = int(dh * cfg["partial_rotary_factor"])
    q = rotary(q, rotary_dim, float(cfg["rope_theta"]))
    k = rotary(k, rotary_dim, float(cfg["rope_theta"]))
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), h // hkv, axis=1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def attend(qkv):
        """The query heads of one KV head of one row: the [.., S, S] scores
        of every head and row at once, kept for the backward, would be
        gigabytes."""
        q, k, v = qkv  # [h / hkv, S, dh]
        scores = matmul(q, k.transpose(0, 2, 1)) * dh ** -0.5
        scores = jnp.where(causal, scores, NEG)
        return matmul(jax.nn.softmax(scores, axis=-1), v)

    groups = lambda t: t.reshape(b * hkv, h // hkv, s, dh)  # noqa: E731
    attn = jax.lax.map(attend, (groups(q), groups(k), groups(v)))
    attn = attn.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
    attn = attn * jax.nn.sigmoid(gate)
    return matmul(attn.reshape(b, s, h * dh), p["o_proj"])


def route(p, tokens, cfg, matmul, top_k):
    """``(probs [N, E], gate [N, held])``: the router's softmax over all
    experts and, for each held expert, the renormalised weight of the tokens
    that chose it among their ``top_k`` (zero elsewhere)."""
    first, held = cfg["experts_held"]
    probs = jax.nn.softmax(matmul(tokens, p["router"]), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    chosen = experts[..., None] == (first + jnp.arange(held))  # [N, k, held]
    return probs, experts, jnp.sum(jnp.where(chosen, weights[..., None], 0.0), axis=1)


def moe(p, x, cfg, matmul, top_k, share):
    """The expert layer's output and its auxiliary term: with ``share``
    (``f [E]``, the whole batch's share of assignments an expert)
    ``E * sum_e f_e * sum_tokens probs_e``, to be divided by the batch's
    tokens by the caller; with ``share=None`` the assignment counts ``[E]``
    themselves (the first pass)."""
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    probs, experts, gate = route(p, tokens, cfg, matmul, top_k)

    @jax.checkpoint  # an expert's hidden activations are recomputed, not kept
    def expert(w_gate, w_up, w_down, weight):
        h = jax.nn.silu(matmul(tokens, w_gate)) * matmul(tokens, w_up)
        return weight[:, None] * matmul(h, w_down)

    def one_expert(out, xs):
        return out + expert(*xs), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(tokens),
        (p["w_gate"], p["w_up"], p["w_down"], gate.T),
    )
    h = jax.nn.silu(matmul(tokens, p["shared_gate"])) * matmul(tokens, p["shared_up"])
    out = out + jax.nn.sigmoid(matmul(tokens, p["shared_router"])) * matmul(
        h, p["shared_down"]
    )
    e = probs.shape[-1]
    if share is None:
        aux = jnp.sum(jax.nn.one_hot(experts.reshape(-1), e, dtype=jnp.float32), axis=0)
    else:
        aux = e * jnp.sum(share * jnp.sum(probs, axis=0))
    return out.reshape(b, s, d), aux


def is_full_attention(cfg, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def hidden_states(params, cfg, tokens, matmul=f32_matmul, top_k=None, shares=None):
    """The final-normed hidden states ``[B, S, d]`` and a list, one a layer,
    of the expert layer's auxiliary term (see ``moe``)."""
    top_k = top_k or cfg["num_experts_per_tok"]
    eps = cfg["rms_norm_eps"]
    x = params["embedding"][tokens]
    aux = []
    # Each half of a layer is recomputed in the backward pass
    # (``jax.checkpoint``): the same mathematics, and the float32
    # intermediates of four layers at 4096 positions do not fit otherwise.
    for i in range(cfg["num_layers"]):
        p = params[f"layer_{i}"]
        mixer = gated_attention if is_full_attention(cfg, i) else gated_delta_net

        @jax.checkpoint
        def mixer_half(p, x, mixer=mixer):
            return x + mixer(p["mixer"], rms_norm(x, p["input_norm"]["w"], eps), cfg, matmul)

        @jax.checkpoint
        def expert_half(p, x, share):
            out, term = moe(
                p["moe"], rms_norm(x, p["post_norm"]["w"], eps), cfg, matmul,
                top_k, share,
            )
            return x + out, term

        x = mixer_half(p, x)
        x, term = expert_half(p, x, None if shares is None else shares[i])
        aux.append(term)
    return rms_norm(x, params["final_norm"]["w"], eps), aux


def logits(params, cfg, tokens, matmul=f32_matmul, top_k=None):
    hidden, _ = hidden_states(params, cfg, tokens, matmul, top_k)
    return matmul(hidden, params["lm_head"])


def make_block_fns(cfg, *, matmul=f32_matmul, top_k=None):
    """``counts(params, rows) -> [L, E]`` assignment counts of a block of
    token rows ``[R, S + 1]``, and ``grad(params, rows, shares, tokens) ->
    (loss share, gradient share)``: the block's part of the batch's mean
    cross-entropy plus the weighted auxiliary term, ``tokens`` being the
    batch's number of scored positions."""
    weight = cfg["router_aux_loss_coef"]

    @jax.jit
    def counts(params, rows):
        _, aux = hidden_states(params, cfg, rows[:, :-1], matmul, top_k)
        return jnp.stack(aux)

    def block_loss(params, rows, shares, tokens):
        hidden, aux = hidden_states(
            params, cfg, rows[:, :-1], matmul, top_k, shares
        )

        @jax.checkpoint
        def row_nll(head, hidden, labels):  # one row's [S, V] logits at a time
            logp = jax.nn.log_softmax(matmul(hidden, head), axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))

        nll = jnp.sum(jax.lax.map(
            lambda xs: row_nll(params["lm_head"], *xs), (hidden, rows[:, 1:])
        ))
        return (nll + weight * jnp.mean(jnp.stack(aux))) / tokens

    return counts, jax.jit(jax.value_and_grad(block_loss))


def adam_step_host(params, grads, state, *, lr, b1, b2, eps):
    """Kingma & Ba's update as ``reference/transformer.py:adam_step`` writes
    it, in numpy on the host and in place: parameters, gradients and both
    moments of this configuration are 10 GB together, which the device
    cannot spare beside a block's working memory. ``params`` and ``grads``
    are device trees; the moments live in ``state`` as numpy trees (made on
    the first call). Returns the new device parameters and state."""
    t = state["t"] + 1
    c1, c2 = np.float32(1 - b1**t), np.float32(1 - b2**t)
    host = lambda tree: [np.array(x) for x in jax.tree.leaves(jax.device_get(tree))]  # noqa: E731
    treedef = jax.tree.structure(params)
    p, g = host(params), host(grads)
    m = state["m"] or [np.zeros_like(x) for x in p]
    v = state["v"] or [np.zeros_like(x) for x in p]
    for p_i, g_i, m_i, v_i in zip(p, g, m, v):
        m_i *= np.float32(b1)
        m_i += np.float32(1 - b1) * g_i
        g_i *= g_i
        v_i *= np.float32(b2)
        v_i += np.float32(1 - b2) * g_i
        np.divide(v_i, c2, out=g_i)
        np.sqrt(g_i, out=g_i)
        g_i += np.float32(eps)
        np.divide(m_i, g_i, out=g_i)
        p_i -= (np.float32(lr) / c1) * g_i
    return jax.device_put(treedef.unflatten(p)), {"m": m, "v": v, "t": t}


def loss_and_grads(params, cfg, batch, *, block_rows, rows=None, block_fns=None,
                   matmul=f32_matmul, top_k=None):
    """Mean next-token loss (with the auxiliary term) of ``batch [B, S + 1]``
    and its gradient, ``block_rows`` rows at a time. ``rows`` (a slice)
    restricts the batch: a planted fault."""
    counts, grad = block_fns or make_block_fns(cfg, matmul=matmul, top_k=top_k)
    n = batch.shape[0]
    lo, hi = (0, n) if rows is None else (rows.start or 0, rows.stop or n)
    blocks = [
        jnp.asarray(batch[start:min(start + block_rows, hi)])
        for start in range(lo, hi, block_rows)
    ]
    tokens = (hi - lo) * (batch.shape[1] - 1)
    top_k = top_k or cfg["num_experts_per_tok"]
    shares = sum(counts(params, block) for block in blocks) / (tokens * top_k)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    loss, grads = 0.0, None
    for block in blocks:
        part, g = grad(params, block, shares, jnp.float32(tokens))
        loss = loss + part
        grads = g if grads is None else add(grads, g)
    return loss, grads

