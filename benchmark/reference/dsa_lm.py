"""Plain float32 reference of DeepSeek-V3.2-Exp's block as the benchmark
runs it (``benchmark/configs/deepseek_v32_exp.json``, "architecture"): the
token indexer with its own float32 scores and ``lax.top_k``, MLA over the
positions its selection names, a dense SwiGLU in the leading layers and, in
the rest, the grouped sigmoid router with each held expert applied to the
tokens routed to it, times its weight, plus the shared expert.

Straight ``jax.numpy``: no kernel, no cache, no pages and no import of the
program under test. The weights are the program's bfloat16 arrays read as
float32; every product is float32 at ``highest``. A document is computed in
blocks of ``block`` queries, layer by layer (``document_rows``): its
attention in the **absorbed** form over the latent rows ``[c_kv | k_rope]``
each query selected, gathered ``query_rows`` queries at a time
(``attention_selected``). A request is computed from a start position on
(``forward``: its question and served tokens, one block through all layers)
over the document's rows, its attention in the **up-projected** form, a
**dense masked softmax** over keys in blocks of ``key_block`` with a running
maximum and sum (never a ``[queries, heads, context]`` array; each key
block's ``k_h = W_UK,h c_kv`` and ``v_h = W_UV,h c_kv`` up-projected where
it is used); where the request comes with a selection made elsewhere, a
query attends it if it is a near-tie of its own (``adopted``). A held expert
takes the ``capacity`` tokens of a block it was given (the rest of the block
at weight 0), or the whole block where more were routed to it.

Variants (the stand-ins that ``--control`` reads): ``matmul`` is every
projection's product (``int8`` / ``fp8``: ``reference.transformer``'s
rounded products); ``select="all"`` attends every earlier position at a
request's steps (the indexer's selection off); ``group_limit=False`` takes
the top 8 of all 256 experts (the router's group limit off).
"""

from __future__ import annotations

import functools
import json
import math

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
MATMULS = {"f32": f32_matmul, "int8": lowp_matmul, "fp8": fp8_matmul}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def yarn(cfg):
    """(inverse frequencies ``[rope_dim / 2]``, attention temperature ``m``)
    of the configuration's ``rope_scaling`` (YaRN, as DeepSeek-V3 states
    it)."""
    r, s = cfg["qk_rope_head_dim"], cfg["rope_scaling"]
    base, factor = float(cfg["rope_theta"]), float(s["factor"])

    def dim_at(turns):
        return r * math.log(s["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (
            2 * math.log(base)
        )

    low = max(math.floor(dim_at(s["beta_fast"])), 0)
    high = min(math.ceil(dim_at(s["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    freq = base ** (-np.arange(0, r, 2) / r)
    freq = freq / factor * ramp + freq * (1 - ramp)
    m = 0.1 * s["mscale_all_dim"] * math.log(factor) + 1.0
    return freq, m


def rotate(x, positions, freq):
    """Rotate-half on every channel of ``x [..., r]`` at ``positions``
    (broadcastable to ``x.shape[:-1]``)."""
    r = x.shape[-1]
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _f(w):
    return w.astype(jnp.float32)


def layer_keys(p, cfg, x, start, matmul):
    """Latent rows ``[B, kv_rank + rope]`` and index keys ``[B, di]`` of a
    block of a layer's inputs ``x [B, D]`` at ``start ..``."""
    eps, a, ix = cfg["rms_norm_eps"], p["attn"], p["index"]
    kv_rank, rr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    freq, _ = yarn(cfg)
    t = start + jnp.arange(x.shape[0])
    h = rms_norm(x, p["input_norm"], eps)
    kv = matmul(h, _f(a["wkv_a"]))
    latent = jnp.concatenate(
        [rms_norm(kv[:, :kv_rank], a["kv_norm"], eps), rotate(kv[:, kv_rank:], t, freq)],
        axis=-1,
    )
    ik = layer_norm(matmul(h, _f(ix["wk"])), ix["k_norm"], ix["k_bias"], eps)
    ik = jnp.concatenate([rotate(ik[:, :rr], t, freq), ik[:, rr:]], axis=-1)
    return latent, ik


def adopted(scores, causal, own, kth, adopt, tolerance):
    """The positions a block of queries attends, given the reference's own
    index ``scores [B, T]`` and exact top-k ``own`` (its ``kth`` score a
    query) and, where a query comes with one, a selection made elsewhere:
    ``adopt [B, T]`` (a row of False where there is none). A query takes that selection where it is as large as the reference's own
    and no position left out of it scores over a position in it by more
    than ``tolerance`` deviations of the query's scores: a near-tie of the
    exact top-k that rounding may settle either way. Returns ``(taken [B,
    T], (inversion [B], outside [B], picks [B]))``: the depth in deviations
    of the worst such inversion, the picks scored under ``kth`` by more than
    ``tolerance`` deviations, and the picks (0, 0, 0 with no selection)."""
    picked = adopt & causal
    count = jnp.maximum(causal.sum(-1), 1)
    mean = jnp.sum(jnp.where(causal, scores, 0.0), -1) / count
    dev = jnp.sqrt(jnp.sum(jnp.where(causal, jnp.square(scores - mean[:, None]), 0.0), -1) / count)
    dev = jnp.maximum(dev, 1e-30)
    over = jnp.max(jnp.where(causal & ~picked, scores, -jnp.inf), -1)
    under = jnp.min(jnp.where(picked, scores, jnp.inf), -1)
    has = adopt.any(-1)
    inversion = jnp.where(has, jnp.maximum((over - under) / dev, 0.0), 0.0)
    outside = jnp.sum(picked & (scores < (kth - tolerance * dev)[:, None]), -1)
    take = has & (inversion <= tolerance) & (picked.sum(-1) == own.sum(-1))
    taken = jnp.where(take[:, None], picked, own)
    return taken, (inversion, outside, picked.sum(-1))


def _queries(p, cfg, x, t, matmul):
    """MLA's query (``q_nope [B, H, dn]``, rotated ``q_rope [B, H, dr]``)
    and the indexer's queries ``[B, Hi, di]`` and weights ``[B, Hi]`` of a
    block of a layer's inputs ``x [B, D]`` at positions ``t [B]``."""
    eps, a, ix = cfg["rms_norm_eps"], p["attn"], p["index"]
    heads, dn, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"])
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    freq, _ = yarn(cfg)
    n = x.shape[0]
    h = rms_norm(x, p["input_norm"], eps)
    cq = rms_norm(matmul(h, _f(a["wq_a"])), a["q_norm"], eps)
    q = matmul(cq, _f(a["wq_b"])).reshape(n, heads, dn + dr)
    iq = matmul(cq, _f(ix["wq_b"])).reshape(n, hi, di)
    iq = jnp.concatenate([rotate(iq[..., :dr], t[:, None], freq), iq[..., dr:]], -1)
    iw = matmul(h, _f(ix["weights_proj"])) * (hi ** -0.5 * di ** -0.5)
    return q[..., :dn], rotate(q[..., dn:], t[:, None], freq), iq, iw


def index_scores(iq, iw, ikeys, t, *, key_block):
    """The indexer's float32 scores ``[B, T]`` of queries ``iq [B, Hi, di]``
    (weights ``iw [B, Hi]``) at positions ``t [B]`` over every key of
    ``ikeys [T, di]`` at or before them (``-inf`` elsewhere), key block by
    key block through the last query's position."""
    n, t_max = t.shape[0], ikeys.shape[0]

    def block(i, scores):
        keys = jax.lax.dynamic_slice_in_dim(ikeys, i * key_block, key_block, 0)
        s = jnp.einsum("nhd,kd->nhk", iq, keys, precision=HI)
        s = jnp.einsum("nhk,nh->nk", jax.nn.relu(s), iw, precision=HI)
        return jax.lax.dynamic_update_slice_in_dim(scores, s, i * key_block, 1)

    scores = jax.lax.fori_loop(
        0, jnp.max(t) // key_block + 1, block, jnp.full((n, t_max), -jnp.inf)
    )
    return jnp.where(jnp.arange(t_max)[None, :] <= t[:, None], scores, -jnp.inf)


def attention(p, cfg, x, start, latents, ikeys, adopt, *, select, tolerance,
              key_block, matmul):
    """MLA over a block of queries ``x [B, D]`` at ``start ..``, given every
    position's latent rows and index keys (``[T, ...]``, filled through the
    block's end) and a selection to adopt (``adopted``), as a dense masked
    softmax in the up-projected form. Returns ``(x + MLA, taken [B, T]
    bool, the adoption's numbers)``."""
    a = p["attn"]
    n, t_max = x.shape[0], latents.shape[0]
    heads, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kv_rank = cfg["kv_lora_rank"]
    _, m = yarn(cfg)
    t = start + jnp.arange(n)
    q_nope, q_rope, iq, iw = _queries(p, cfg, x, t, matmul)
    scores = index_scores(iq, iw, ikeys, t, key_block=key_block)
    causal = jnp.arange(t_max)[None, :] <= t[:, None]
    if select == "all":
        taken = causal
        stats = (jnp.zeros(n), jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32))
    else:
        top, idx = jax.lax.top_k(scores, min(cfg["index_topk"], t_max))
        own = jnp.zeros((n, t_max), bool).at[jnp.arange(n)[:, None], idx].set(True)
        taken, stats = adopted(
            scores, causal, own & causal, top[:, -1], adopt, tolerance
        )

    # dense masked softmax, up-projected, key block by key block
    w_kv = _f(a["wkv_b"])  # [kv_rank, heads * (dn + dv)]
    w_kv3 = w_kv.reshape(kv_rank, heads, dn + dv)
    w_uk = w_kv3[..., :dn].reshape(kv_rank, heads * dn)
    w_uv = w_kv3[..., dn:].reshape(kv_rank, heads * dv)
    scale = (dn + dr) ** -0.5 * m * m

    def step(i, carry):
        top, total, acc = carry
        c = jax.lax.dynamic_slice_in_dim(latents, i * key_block, key_block, 0)
        k_nope = matmul(c[:, :kv_rank], w_uk).reshape(key_block, heads, dn)
        v = matmul(c[:, :kv_rank], w_uv).reshape(key_block, heads, dv)
        s = (
            jnp.einsum("nhd,khd->nhk", q_nope, k_nope, precision=HI)
            + jnp.einsum("nhr,kr->nhk", q_rope, c[:, kv_rank:], precision=HI)
        ) * scale
        mask = jax.lax.dynamic_slice_in_dim(taken, i * key_block, key_block, 1)
        s = jnp.where(mask[:, None, :], s, NEG)
        new_top = jnp.maximum(top, s.max(-1))
        weight = jnp.where(mask[:, None, :], jnp.exp(s - new_top[..., None]), 0.0)
        fade = jnp.exp(top - new_top)
        acc = acc * fade[..., None] + jnp.einsum("nhk,khd->nhd", weight, v, precision=HI)
        return new_top, total * fade + weight.sum(-1), acc

    blocks = (start + n + key_block - 1) // key_block
    _, total, acc = jax.lax.fori_loop(0, blocks, step, (
        jnp.full((n, heads), NEG, jnp.float32), jnp.zeros((n, heads), jnp.float32),
        jnp.zeros((n, heads, dv), jnp.float32),
    ))
    o = (acc / jnp.maximum(total, 1e-30)[..., None]).reshape(n, heads * dv)
    return x + matmul(o, _f(a["wo"])), taken, stats


def attention_selected(p, cfg, x, start, latents, ikeys, *, key_block,
                       query_rows, matmul):
    """MLA over a block of queries ``x [B, D]`` at ``start ..`` in the
    absorbed form (``q~_h = W_UK,h^T q_nope,h`` against ``c_kv``, the output
    ``W_UV,h`` times the weighted sum of ``c_kv``) over the latent rows the
    reference's own selection names, ``query_rows`` queries at a time: the
    documents' path. The same mathematics as ``attention``'s (the CPU tests
    hold the two forms equal), reading 2,048 rows a query where a dense
    softmax reads every position before it. Returns ``x + MLA``."""
    a = p["attn"]
    n = x.shape[0]
    heads, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kv_rank, k = cfg["kv_lora_rank"], min(cfg["index_topk"], latents.shape[0])
    _, m = yarn(cfg)
    t = start + jnp.arange(n)
    q_nope, q_rope, iq, iw = _queries(p, cfg, x, t, matmul)
    w_kv3 = _f(a["wkv_b"]).reshape(kv_rank, heads, dn + dv)
    q_lat = jnp.einsum("nhd,chd->nhc", q_nope, w_kv3[..., :dn], precision=HI)
    scale = (dn + dr) ** -0.5 * m * m

    def rows(i, out):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, i * query_rows, query_rows, 0)

        scores = index_scores(cut(iq), cut(iw), ikeys, cut(t), key_block=key_block)
        top, idx = jax.lax.top_k(scores, k)
        valid = (top > -jnp.inf)[:, None, :]
        c = latents[idx]  # [query_rows, k, kv_rank + dr]
        s = (
            jnp.einsum("nhc,nkc->nhk", cut(q_lat), c[..., :kv_rank], precision=HI)
            + jnp.einsum("nhr,nkr->nhk", cut(q_rope), c[..., kv_rank:], precision=HI)
        ) * scale
        s = jnp.where(valid, s, NEG)
        weight = jnp.where(valid, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        weight = weight / weight.sum(-1, keepdims=True)
        o = jnp.einsum("nhk,nkc->nhc", weight, c[..., :kv_rank], precision=HI)
        return jax.lax.dynamic_update_slice_in_dim(out, o, i * query_rows, 0)

    o = jax.lax.fori_loop(
        0, n // query_rows, rows, jnp.zeros((n, heads, kv_rank), jnp.float32)
    )
    o = jnp.einsum("nhc,chd->nhd", o, w_kv3[..., dn:], precision=HI)
    return x + matmul(o.reshape(n, heads * dv), _f(a["wo"]))


def route(cfg, s, bias, group_limit: bool):
    """The chosen experts ``[N, k]`` and their weights of sigmoid scores
    ``s [N, E]``: top-k of ``s + bias`` within the best groups (each scored
    by its two largest), weights ``s`` renormalised times the scale."""
    n, e = s.shape
    k, groups = cfg["num_experts_per_tok"], cfg["n_group"]
    biased = s + bias
    if group_limit:
        per_group = jnp.sort(biased.reshape(n, groups, e // groups), -1)[..., -2:].sum(-1)
        kept = jnp.argsort(-per_group, -1)[:, : cfg["topk_group"]]
        keep = (jnp.arange(groups)[None, None, :] == kept[..., None]).any(1)
        biased = jnp.where(jnp.repeat(keep, e // groups, -1), biased, -jnp.inf)
    _, chosen = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(s, chosen, -1)
    return chosen, w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def _swiglu(x, g, u, d, matmul):
    return matmul(jax.nn.silu(matmul(x, _f(g))) * matmul(x, _f(u)), _f(d))


def feed_forward(p, cfg, x, *, group_limit, capacity, matmul):
    """The feed-forward half over a block ``x [B, D]``: ``x + FFN``."""
    h = rms_norm(x, p["post_norm"], cfg["rms_norm_eps"])
    if "mlp" in p:
        return x + _swiglu(h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"], matmul)
    m = p["moe"]
    first, held = cfg["experts_held"]
    s = jax.nn.sigmoid(matmul(h, _f(m["router"])))
    chosen, w = route(cfg, s, m["bias"], group_limit)
    # weight of each held expert a token (0 where it was not chosen)
    weight = jnp.sum(
        jnp.where(chosen[..., None] == first + jnp.arange(held), w[..., None], 0.0), 1
    )  # [B, held]
    y = _swiglu(h, m["shared_gate"], m["shared_up"], m["shared_down"], matmul)
    cap = min(capacity, x.shape[0])

    def expert(y, xs):
        g, u, d, we = xs
        routed = jnp.argsort(we == 0, stable=True)[:cap]

        def few():
            out = _swiglu(h[routed], g, u, d, matmul) * we[routed][:, None]
            return y.at[routed].add(out)

        def many():
            return y + _swiglu(h, g, u, d, matmul) * we[:, None]

        return jax.lax.cond(jnp.sum(we > 0) <= cap, few, many), None

    y, _ = jax.lax.scan(expert, y, (m["w_gate"], m["w_up"], m["w_down"], weight.T))
    return x + y


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, matmul_name: str, select: str, group_limit: bool,
              key_block: int, capacity: int, tolerance: float, query_rows: int):
    cfg = json.loads(cfg_json)
    matmul = MATMULS[matmul_name]

    def put(plane, rows, start):
        return jax.lax.dynamic_update_slice_in_dim(plane, rows, start, 0)

    # Attention and feed-forward are programs of their own, so that the
    # float32 copies of their weights are never held together.
    return dict(
        keys=jax.jit(lambda p, x, start: layer_keys(p, cfg, x, start, matmul)),
        attend=jax.jit(lambda p, x, start, latents, ikeys, adopt: attention(
            p, cfg, x, start, latents, ikeys, adopt, select=select,
            tolerance=tolerance, key_block=key_block, matmul=matmul,
        )),
        attend_selected=jax.jit(lambda p, x, start, latents, ikeys: attention_selected(
            p, cfg, x, start, latents, ikeys, key_block=key_block,
            query_rows=query_rows, matmul=matmul,
        )),
        ffn=jax.jit(lambda p, x: feed_forward(
            p, cfg, x, group_limit=group_limit, capacity=capacity, matmul=matmul,
        )),
        put=jax.jit(put, donate_argnums=0),
        head=jax.jit(lambda params, x: head(params, cfg, x, matmul)),
    )


def head(params, cfg, x, matmul=f32_matmul):
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = matmul(x, _f(params["lm_head"]))
    if "logit_bias" in params:
        logits = logits + params["logit_bias"]
    return logits


def _checked(t_max, block, key_block, n):
    if t_max % block or t_max % key_block or n + block > t_max:
        raise ValueError(f"{n} positions, t_max {t_max}, block {block}")


def document_rows(params, cfg, document, *, t_max: int, block: int,
                  key_block: int = 512, capacity: int = 256, query_rows: int = 128,
                  matmul: str = "f32", group_limit: bool = True) -> list:
    """Every layer's latent rows and index keys of the positions of
    ``document`` (ids), computed here, a block of ``block`` positions at a
    time, the attention by ``attention_selected``: a list a layer of
    ``(latents [n, kv_rank + rope], keys [n, index_head_dim])`` float32 on
    the host, what ``forward`` takes as the positions before a request's
    own."""
    n = len(document)
    _checked(t_max, block, key_block, n)
    prog = _programs(json.dumps(cfg, sort_keys=True), matmul, "topk",
                     group_limit, key_block, capacity, 0.0, query_rows)
    n_blocks = -(-n // block)
    padded = np.zeros(n_blocks * block, np.int32)
    padded[:n] = document
    x = _f(params["embedding"][jnp.asarray(padded)])
    dl = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    kept = []
    for p in params["layers"]:
        latents = jnp.zeros((t_max, dl), jnp.float32)
        ikeys = jnp.zeros((t_max, cfg["index_head_dim"]), jnp.float32)
        for b in range(n_blocks):
            lat, ik = prog["keys"](p, x[b * block:(b + 1) * block], b * block)
            latents = prog["put"](latents, lat, b * block)
            ikeys = prog["put"](ikeys, ik, b * block)
        for b in range(n_blocks):
            y = prog["attend_selected"](
                p, x[b * block:(b + 1) * block], b * block, latents, ikeys
            )
            x = prog["put"](x, prog["ffn"](p, y), b * block)
        kept.append((np.asarray(latents[:n]), np.asarray(ikeys[:n])))
    return kept


def forward(params, cfg, rows, start: int, requests, *, t_max: int, block: int,
            key_block: int = 512, capacity: int = 256, matmul: str = "f32",
            query_rows: int = 128, select: str = "topk", group_limit: bool = True,
            tolerance: float = 0.0):
    """Each of ``requests`` (a list of ``(ids from position start on,
    out_positions, adopt)``, the positions counted from 0) computed here
    from ``start`` on, over ``rows``: a list a layer of ``(latents, keys)``
    of the positions before ``start`` (``document_rows``'s; arrays of at
    least ``start`` rows, read as float32). ``adopt`` is None or a selection
    made elsewhere at the out positions, ``[layers, len(out_positions),
    t_max]`` bool, which a query takes in place of its own
    where it is a near-tie of it (``adopted``, within ``tolerance``).
    Returns, a request, ``(logits [len(out_positions), V], taken [layers,
    len(out_positions), t_max] bool, (inversion, outside, picks) [layers,
    len(out_positions)])``: the logits that predict the position after each
    out position, the positions each layer attended there, and the
    adoption's numbers. ``t_max`` (a multiple of ``block`` and of
    ``key_block``) bounds ``start`` + a block."""
    _checked(t_max, block, key_block, start)
    prog = _programs(json.dumps(cfg, sort_keys=True), matmul, select,
                     group_limit, key_block, capacity, float(tolerance), query_rows)

    def planes(latents, keys):
        out = []
        for a in (latents, keys):
            a = jnp.asarray(a[:start], jnp.float32)
            out.append(jnp.zeros((t_max, a.shape[-1]), jnp.float32).at[:start].set(a))
        return out

    if not rows:  # nothing before ``start``
        widths = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["index_head_dim"])
        rows = [tuple(np.zeros((start, w), np.float32) for w in widths)] * len(params["layers"])
    kept = [planes(*r) for r in rows]
    out = []
    for ids, out_positions, adopt in requests:
        tail = np.zeros(block, np.int32)
        tail[:len(ids)] = ids
        x = _f(params["embedding"][jnp.asarray(tail)])
        rel = np.asarray(out_positions) - start
        taken_all, stats_all = [], []
        for layer, (p, (latents, ikeys)) in enumerate(zip(params["layers"], kept)):
            given = np.zeros((block, t_max), bool)
            if adopt is not None:
                given[rel] = adopt[layer]
            lat, ik = prog["keys"](p, x, start)
            y, taken, stats = prog["attend"](
                p, x, start, jax.lax.dynamic_update_slice_in_dim(latents, lat, start, 0),
                jax.lax.dynamic_update_slice_in_dim(ikeys, ik, start, 0),
                jnp.asarray(given),
            )
            x = prog["ffn"](p, y)
            taken_all.append(np.asarray(taken[rel]))
            stats_all.append([np.asarray(a[rel]) for a in stats])
        logits = prog["head"](params, x[jnp.asarray(rel)])
        out.append((np.asarray(logits), np.stack(taken_all),
                    tuple(np.stack(a) for a in zip(*stats_all))))
    return out
