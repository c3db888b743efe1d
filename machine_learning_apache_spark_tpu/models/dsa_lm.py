"""Decoder-only language model of DeepSeek-V3.2-Exp's block, written for
serving: latent attention (MLA) read in its absorbed form over a paged latent
store, DeepSeek Sparse Attention's token indexer choosing the positions each
query reads, and a feed-forward that is dense in the leading layers and a
mixture of experts behind a grouped sigmoid router in the rest.

The block, pre-norm, no bias (``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``):

    h = x + MLA(RMSNorm(x))        y = h + FFN(RMSNorm(h))

**MLA** (``num_heads`` heads): ``c_q = RMSNorm(x W_DQ)``; ``[q_nope | q_rope]
= c_q W_UQ`` a head, ``q_rope`` rotated; ``[c_kv | k_rope] = x W_DKV``,
``c_kv`` normed, ``k_rope`` rotated and shared by every head. The store keeps
``[c_kv | k_rope]`` a position a layer; attention runs over the positions the
indexer selected, absorbed (``ops.latent_attention``), at the scale
``(dn + dr)^-0.5 * m^2`` (YaRN's temperature ``m``); ``o = W_O concat_h(W_UV,h
o~_h)``.

**Indexer**: ``q^I = c_q W_IQ`` (``index_heads`` of ``index_head_dim``),
``k^I = LayerNorm(x W_IK)``, the first ``qk_rope_dim`` channels of both
rotated; ``w = x W_IW * index_heads^-0.5 * index_head_dim^-0.5``; a query at
``t`` reads the ``index_topk`` positions ``s <= t`` of highest ``sum_j w_j
ReLU(q^I_j . k^I_s)`` (every earlier position while ``t < index_topk``). The
index keys are a second plane on the same block tables (``ops.dsa_index``).

**FFN**: layers before ``first_dense`` a SwiGLU of ``intermediate_size``; the
rest a mixture of SwiGLU experts of ``expert_hidden``: ``models.moe``'s
grouped sigmoid router over ``num_experts`` (``expert_groups`` groups, the
best ``groups_kept``, ``experts_per_token`` experts, weights renormalised
times ``routed_scale``), of which this chip holds ``experts_held`` and
computes their part, plus one shared expert, ungated.

Rotary positions are YaRN's (``ops.positional.yarn_inv_freq``), rotate-half.
Parameters are a plain dict, in ``cfg.dtype``; norms, softmax, the index
scores and the router are float32. Scopes: ``lm.mla`` (the store's writes and
reads and the absorbed attention), ``lm.mla_proj`` (MLA's projections),
``lm.dsa.index`` (the indexer and its selection), ``lm.mlp``,
``lm.moe.route`` / ``lm.moe.experts`` / ``lm.moe.shared``, ``lm.head``.

The serving seam (``serving.lm_runtime``) reads ``new_cache``,
``prefill_chunk``, ``decode_step``, ``page_bytes``, ``state_planes`` (none
here), ``selected_share``, ``COUNTS`` and ``PAIRED_COUNTS``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu.models import moe
from machine_learning_apache_spark_tpu.models.hybrid_lm import _rms
from machine_learning_apache_spark_tpu.ops import dsa_index, latent_attention
from machine_learning_apache_spark_tpu.ops.positional import (
    rotary_embedding_at,
    yarn_inv_freq,
    yarn_mscale,
)

NULL_PAGE = 0
#: A decode step's counters, summed over the expert layers (assignments to
#: held experts as the router chose them and as the grouped products were
#: given them, held experts with any assignment) and over every layer's
#: index scan (pages of keys the path it took fetched, and what the XLA
#: scan fetches at the same step: ``ops.dsa_index.pages_read``), and the
#: pair of them that has to agree in a launch (``serving.lm_runtime``).
COUNTS = ("moe_assignments_local", "moe_assignments_computed",
          "moe_experts_touched", "index_pages_read", "index_pages_padded")
PAIRED_COUNTS = (("moe_assignments_local", "moe_assignments_computed"),)


@dataclasses.dataclass(frozen=True)
class DSALMConfig:
    vocab_size: int
    num_layers: int = 5
    first_dense: int = 1
    hidden_size: int = 7168
    intermediate_size: int = 18432
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    index_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    num_experts: int = 256  # the router's width
    experts_held: tuple[int, int] = (0, 16)
    experts_per_token: int = 8
    expert_groups: int = 8
    groups_kept: int = 4
    expert_hidden: int = 2048
    shared_hidden: int = 2048
    routed_scale: float = 2.5
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    page: int = 64
    index_block: int = 4096  # positions a pass of the index scan
    query_block: int = 64  # prefill queries selected and gathered at a time
    max_positions: int = 163840
    eos_id: int | None = None
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        first, held = self.experts_held
        if not (0 <= first and held > 0 and first + held <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} outside the router")
        if self.num_experts % self.expert_groups:
            raise ValueError("num_experts is not a multiple of expert_groups")

    @property
    def page_size(self) -> int:
        return self.page

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_row(self) -> int:
        """A latent row as stored: ``latent_width`` rounded up to 128 lanes.
        At 576 the TPU compiler copies the whole plane around every write
        and read (read in the launch compiled for a described v5e: five
        582 MB copies); at 640 the writes are in place."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.mscale_all_dim)
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5 * m * m

    def inv_freq(self):
        return yarn_inv_freq(
            self.qk_rope_dim, theta=self.rope_theta, factor=self.rope_factor,
            original=self.rope_original, beta_fast=self.beta_fast,
            beta_slow=self.beta_slow,
        )

    def is_dense(self, total_len: int) -> bool:
        """No request attends everything by a switch: the indexer takes
        every earlier position while there are fewer than ``index_topk``."""
        return False


def init_params(cfg: DSALMConfig, key) -> dict:
    """Seeded parameters: kernels normal with variance 1 / fan_in, embedding
    normal(0, 1), norm weights one, biases zero."""
    d, h = cfg.hidden_size, cfg.num_heads
    held = cfg.experts_held[1]
    keys = iter(jax.random.split(key, 32 * cfg.num_layers + 4))

    def kernel(*shape):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w * shape[-2] ** -0.5).astype(cfg.dtype)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    layers = []
    for i in range(cfg.num_layers):
        layer = dict(
            input_norm=ones(d), post_norm=ones(d),
            attn=dict(
                wq_a=kernel(d, cfg.q_lora_rank), q_norm=ones(cfg.q_lora_rank),
                wq_b=kernel(cfg.q_lora_rank, h * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
                wkv_a=kernel(d, cfg.latent_width), kv_norm=ones(cfg.kv_lora_rank),
                wkv_b=kernel(cfg.kv_lora_rank, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
                wo=kernel(h * cfg.v_head_dim, d),
            ),
            index=dict(
                wq_b=kernel(cfg.q_lora_rank, cfg.index_heads * cfg.index_head_dim),
                wk=kernel(d, cfg.index_head_dim),
                k_norm=ones(cfg.index_head_dim),
                k_bias=jnp.zeros((cfg.index_head_dim,), jnp.float32),
                weights_proj=kernel(d, cfg.index_heads),
            ),
        )
        if i < cfg.first_dense:
            f = cfg.intermediate_size
            layer["mlp"] = dict(gate=kernel(d, f), up=kernel(d, f), down=kernel(f, d))
        else:
            f, fs = cfg.expert_hidden, cfg.shared_hidden
            layer["moe"] = dict(
                router=kernel(d, cfg.num_experts),
                bias=jnp.zeros((cfg.num_experts,), jnp.float32),
                w_gate=kernel(held, d, f), w_up=kernel(held, d, f),
                w_down=kernel(held, f, d),
                shared_gate=kernel(d, fs), shared_up=kernel(d, fs),
                shared_down=kernel(fs, d),
            )
        layers.append(layer)
    return dict(
        embedding=jax.random.normal(
            next(keys), (cfg.vocab_size, d), jnp.float32
        ).astype(cfg.dtype),
        lm_head=kernel(d, cfg.vocab_size),
        final_norm=ones(d),
        layers=layers,
    )


# -- the serving seam ------------------------------------------------------------


def new_cache(cfg: DSALMConfig, *, rows: int, num_pages: int, device=None) -> dict:
    """Zeroed planes for ``num_pages`` pages of ``cfg.page`` positions (page 0
    is the null page): a latent plane ``[pages * page, latent_row]`` (``[c_kv
    | k_rope]`` and zeros to the row's end) and an index plane ``[pages *
    page, index_head_dim]`` a layer. Nothing a row: ``rows`` is not read."""
    z = lambda width: jnp.zeros(  # noqa: E731
        (num_pages * cfg.page, width), cfg.dtype, device=device
    )
    return dict(
        latent=[z(cfg.latent_row) for _ in range(cfg.num_layers)],
        index=[z(cfg.index_head_dim) for _ in range(cfg.num_layers)],
    )


def page_bytes(cfg: DSALMConfig) -> int:
    item = jnp.dtype(cfg.dtype).itemsize
    return cfg.num_layers * cfg.page * (cfg.latent_row + cfg.index_head_dim) * item


def state_planes(cfg: DSALMConfig) -> list:
    """Shapes of the fixed-size state a row keeps beside the pages: none."""
    return []


def selected_share(cfg: DSALMConfig, chosen, pos, dense):
    """Positions a step's attention read over the positions its row's
    context held, the mean over layers: ``chosen [layers, R, topk]`` (-1
    where a slot is empty), ``pos [R]`` -> ``[R]``."""
    taken = jnp.sum(chosen >= 0, axis=-1).astype(jnp.float32).mean(axis=0)
    return taken / (pos + 1.0)


# -- the block ---------------------------------------------------------------------


def _norm(x, w, eps):
    return _rms(x, eps) * w.astype(jnp.float32)


def _layer_norm(x, w, b, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _rotate_first(x, positions, cfg):
    """YaRN rotary on the first ``qk_rope_dim`` channels of ``x [..., d]``."""
    r = cfg.qk_rope_dim
    turned = rotary_embedding_at(x[..., :r], positions, inv_freq=cfg.inv_freq())
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


def _projections(p, cfg, hidden, positions):
    """MLA's and the indexer's inputs of ``hidden [N, D]`` (normed, in the
    compute dtype) at ``positions [N]``: the absorbed query ``[N, H, kv_rank
    + rope_dim]``, the new latent rows ``[N, kv_rank + rope_dim]``, the index
    queries ``[N, Hi, di]``, weights ``[N, Hi]`` and keys ``[N, di]`` (and
    ``W_UV``); a latent row is padded with zeros to ``latent_row``."""
    a, ix = p["attn"], p["index"]
    n, h = hidden.shape[0], cfg.num_heads
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    with jax.named_scope("lm.mla_proj"):
        c_q = _norm(hidden @ a["wq_a"], a["q_norm"], cfg.rms_eps).astype(cfg.dtype)
        q = (c_q @ a["wq_b"]).reshape(n, h, dn + dr)
        q_rope = rotary_embedding_at(
            q[..., dn:], positions[:, None], inv_freq=cfg.inv_freq()
        )
        kv = (hidden @ a["wkv_a"]).astype(jnp.float32)
        c_kv = _norm(kv[:, :cfg.kv_lora_rank], a["kv_norm"], cfg.rms_eps)
        k_rope = rotary_embedding_at(
            kv[:, cfg.kv_lora_rank:], positions, inv_freq=cfg.inv_freq()
        )
        latent = jnp.concatenate(
            [c_kv, k_rope, jnp.zeros((n, cfg.latent_row - cfg.latent_width))],
            axis=-1,
        ).astype(cfg.dtype)
        w_ukv = a["wkv_b"].reshape(cfg.kv_lora_rank, h, dn + cfg.v_head_dim)
        q_lat = latent_attention.absorb_query(q[..., :dn], w_ukv[..., :dn])
        query = jnp.concatenate([q_lat, q_rope.astype(cfg.dtype)], axis=-1)
    with jax.named_scope("lm.dsa.index"):
        di = cfg.index_head_dim
        iq = (c_q @ ix["wq_b"]).reshape(n, cfg.index_heads, di)
        iq = _rotate_first(iq, positions[:, None], cfg).astype(cfg.dtype)
        ik = _layer_norm(hidden @ ix["wk"], ix["k_norm"], ix["k_bias"], cfg.rms_eps)
        ik = _rotate_first(ik, positions, cfg).astype(cfg.dtype)
        iw = dsa_index.index_weights(
            hidden @ ix["weights_proj"], cfg.index_heads, di
        )
    return query, latent, iq, iw, ik, w_ukv[..., dn:]


def _attn_out(p, cfg, o_latent, w_uv):
    """``o~ [N, H, kv_rank]`` -> the block's output ``[N, D]`` float32."""
    with jax.named_scope("lm.mla_proj"):
        o = latent_attention.expand_output(o_latent, w_uv, cfg.dtype)
        o = o.reshape(o.shape[0], -1).astype(cfg.dtype)
        return (o @ p["attn"]["wo"]).astype(jnp.float32)


def _ffn(p, cfg, x):
    """The feed-forward half's output for ``x [N, D]`` (the residual) and
    its counters: ``(out [N, D] float32, counts)``."""
    hidden = _norm(x, p["post_norm"], cfg.rms_eps).astype(cfg.dtype)
    if "mlp" in p:
        m = p["mlp"]
        with jax.named_scope("lm.mlp"):
            h = jax.nn.silu(hidden @ m["gate"]) * (hidden @ m["up"])
            return (h.astype(cfg.dtype) @ m["down"]).astype(jnp.float32), {}
    m = p["moe"]
    first, held = cfg.experts_held
    with jax.named_scope("lm.moe.route"):
        logits = jnp.dot(
            hidden.astype(jnp.float32), m["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        _, experts, weights = moe.route(
            logits, cfg.experts_per_token, kind="sigmoid_grouped",
            bias=m["bias"], groups=cfg.expert_groups,
            groups_kept=cfg.groups_kept, scale=cfg.routed_scale,
        )
        plan = moe.local_assignments(
            experts, weights, first=first, held=held,
            num_experts=cfg.num_experts,
        )
    out, computed = moe.grouped_experts(
        hidden, plan, m["w_gate"], m["w_up"], m["w_down"],
        k=cfg.experts_per_token, num_experts=cfg.num_experts,
    )
    with jax.named_scope("lm.moe.shared"):
        out = out + moe.shared_swiglu(
            hidden, m["shared_gate"], m["shared_up"], m["shared_down"], cfg.dtype
        )
    counts = dict(
        moe_assignments_local=jnp.sum(plan["local"]).astype(jnp.int32),
        moe_assignments_computed=computed.astype(jnp.int32),
        moe_experts_touched=jnp.sum(plan["sizes"] > 0).astype(jnp.int32),
    )
    return out, counts


def _embed(params, tokens):
    return params["embedding"][tokens].astype(jnp.float32)


def _head(params, cfg, x):
    with jax.named_scope("lm.head"):
        x = _norm(x, params["final_norm"], cfg.rms_eps)
        logits = jnp.dot(
            x.astype(cfg.dtype), params["lm_head"],
            preferred_element_type=jnp.float32,
        )
        if "logit_bias" in params:
            logits = logits + params["logit_bias"].astype(jnp.float32)
        return logits


def prefill_chunk(params, cfg: DSALMConfig, cache: dict, tokens, table, row,
                  start, length, dense):
    """Positions ``start .. start + C - 1`` of one request (``tokens [C]``,
    of which those before ``length`` are real): writes their latent rows and
    index keys into the pages ``table [Pmax]`` names and returns the new
    cache. Queries are selected, gathered and attended ``query_block`` at a
    time, and the blocks that hold padding alone are not run. ``row`` and
    ``dense`` are the seam's and unread. No logits: the launch's first step
    takes the prompt's last token."""
    del row, dense
    c = tokens.shape[0]
    page, qb = cfg.page, min(cfg.query_block, c)
    positions = start + jnp.arange(c, dtype=jnp.int32)
    real = jnp.clip(length - start, 0, c)
    chunk_pages = jax.lax.dynamic_slice(table, (start // page,), (c // page,))
    at = jnp.repeat(chunk_pages, page) * page + positions % page  # [C]
    cache = {name: list(planes) for name, planes in cache.items()}
    x = _embed(params, tokens)
    for i, p in enumerate(params["layers"]):
        hidden = _norm(x, p["input_norm"], cfg.rms_eps).astype(cfg.dtype)
        query, latent, iq, iw, ik, w_uv = _projections(p, cfg, hidden, positions)
        with jax.named_scope("lm.dsa.index"):
            cache["index"][i] = latent_attention.write_rows(cache["index"][i], at, ik)
        with jax.named_scope("lm.mla"):
            cache["latent"][i] = latent_attention.write_rows(
                cache["latent"][i], at, latent
            )
        index_plane, latent_plane = cache["index"][i], cache["latent"][i]

        def block(b, out):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, b * qb, qb, 0)  # noqa: E731
            tx = cut(positions)
            with jax.named_scope("lm.dsa.index"):
                _, rows, valid = dsa_index.select(
                    cut(iq), cut(iw), index_plane, table, tx, page=page,
                    block=cfg.index_block, topk=cfg.index_topk,
                    site="dsa_index_prefill",
                )
            with jax.named_scope("lm.mla"):
                o = latent_attention.attend_selected(
                    cut(query), latent_plane, rows, valid,
                    kv_rank=cfg.kv_lora_rank, scale=cfg.softmax_scale,
                    site="mla_prefill",
                )
            return jax.lax.dynamic_update_slice_in_dim(out, o, b * qb, 0)

        o = jax.lax.fori_loop(
            0, (real + qb - 1) // qb, block,
            jnp.zeros((c, cfg.num_heads, cfg.kv_lora_rank), jnp.float32),
        )
        x = x + _attn_out(p, cfg, o, w_uv)
        out, _ = _ffn(p, cfg, x)
        x = x + out
    return cache


def decode_step(params, cfg: DSALMConfig, cache: dict, token, pos, tables,
                active, dense):
    """One position for every row: ``token [R]`` at ``pos [R]``, block
    tables ``tables [R, Pmax]``; rows not ``active`` write to the null page
    and their outputs mean nothing. Returns ``(logits [R, V] float32, new
    cache, chosen [layers, R, index_topk] (-1 where a slot is empty),
    counts)``, the counts summed over the expert layers (assignments local
    and computed, and held experts with any assignment) and over every
    layer's index scan (pages read, and pages the XLA scan reads)."""
    del dense
    page = cfg.page
    slot = jnp.take_along_axis(tables, (pos // page)[:, None], axis=1)[:, 0]
    at = jnp.where(active, slot, NULL_PAGE) * page + pos % page
    t = jnp.where(active, pos, 0)
    cache = {name: list(planes) for name, planes in cache.items()}
    x = _embed(params, token)
    chosen_all, counts = [], {}
    for i, p in enumerate(params["layers"]):
        hidden = _norm(x, p["input_norm"], cfg.rms_eps).astype(cfg.dtype)
        query, latent, iq, iw, ik, w_uv = _projections(p, cfg, hidden, pos)
        with jax.named_scope("lm.dsa.index"):
            cache["index"][i] = latent_attention.write_rows(cache["index"][i], at, ik)
            chosen, rows, valid = dsa_index.select(
                iq, iw, cache["index"][i], tables, t, page=page,
                block=cfg.index_block, topk=cfg.index_topk,
                site="dsa_index_decode",
            )
            read, padded = dsa_index.pages_read(
                iq, cache["index"][i], tables, t, page=page, block=cfg.index_block
            )
        layer_counts = dict(index_pages_read=read, index_pages_padded=padded)
        with jax.named_scope("lm.mla"):
            cache["latent"][i] = latent_attention.write_rows(
                cache["latent"][i], at, latent
            )
            o = latent_attention.attend_selected(
                query, cache["latent"][i], rows, valid,
                kv_rank=cfg.kv_lora_rank, scale=cfg.softmax_scale, site="mla_decode",
            )
        chosen_all.append(jnp.where(valid, chosen, -1))
        x = x + _attn_out(p, cfg, o, w_uv)
        out, moe_counts = _ffn(p, cfg, x)
        for name, value in {**layer_counts, **moe_counts}.items():
            counts[name] = counts.get(name, 0) + value
        x = x + out
    return _head(params, cfg, x), cache, jnp.stack(chosen_all), counts
