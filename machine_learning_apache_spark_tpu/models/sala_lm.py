"""Decoder-only language model of sparse-attention and linear-attention
layers, written for serving: a chunk of one request's prompt (``prefill_chunk``)
and one position of every row (``decode_step``), both over a paged store.

The block, pre-norm, no bias anywhere (``RMSNorm(x) = x / sqrt(mean(x^2) +
eps) * w``):

    h = x + c * Mixer_i(RMSNorm(x))        y = h + c * MLP(RMSNorm(h))

with ``c = scale_depth / sqrt(depth_for_scale)``, ``MLP(x) = down(silu(gate(x))
* up(x))``. The embedding's output is multiplied by ``scale_emb``; a final
RMSNorm, then the hidden state divided by ``hidden_size / dim_model_base``,
precede the untied head. ``Mixer_i`` is named by ``cfg.mixer_types[i]``:

- ``"lightning-attn"``: ``q, k, v = x W_q, x W_k, x W_v`` a head; RMSNorm a
  head on ``q`` and ``k``; rotary positions on both; the decayed linear
  attention of ``ops.lightning_attention`` (state float32 ``[heads, d, d]``
  a row); ``y = (RMSNorm_head(o) * sigmoid(x W_g)) W_o``.
- ``"minicpm4"``: ``num_heads`` query heads over ``num_kv_heads`` KV heads, no
  positional term; RMSNorm a head on ``q`` and ``k``; the block-sparse
  attention of ``ops.sparse_block_attention`` (a request marked *dense*
  attends everything); ``y = (o * sigmoid(x W_g)) W_o``.

What a request leaves on the device (``new_cache``): for every sparse layer K,
V ``[kv heads * pages * page, d]`` and the selector's unit means ``[kv heads
* pages * page / stride, d]`` (matrices of rows, KV head outermost: see
``ops.sparse_block_attention``), addressed by the row's block table; for every
lightning layer a float32 state ``[rows, heads, d, d]``, one slot a row.

Parameters are a plain dict (``init_params``), in ``cfg.dtype``; norms,
softmax, the selector's scores and the lightning state are float32. The
scopes ``lm.sparse_attn`` (with ``lm.sparse_attn.select`` inside it),
``lm.lightning`` and ``lm.mlp`` name the parts in a device trace.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu.models.hybrid_lm import _rms
from machine_learning_apache_spark_tpu.ops.lightning_attention import (
    decay_slopes,
    lightning_attention,
    lightning_attention_step,
)
from machine_learning_apache_spark_tpu.ops.positional import rotary_embedding_at
from machine_learning_apache_spark_tpu.ops.sparse_block_attention import (
    NULL_PAGE,
    SparseSpec,
    gather_pages,
    page_rows,
    sparse_decode,
    sparse_prefill,
    unit_means,
)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
#: A decode step's counters, and those of a launch that have to agree
#: (``serving.lm_runtime``): none.
COUNTS, PAIRED_COUNTS = (), ()


@dataclasses.dataclass(frozen=True)
class SalaLMConfig:
    vocab_size: int
    mixer_types: tuple[str, ...]
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    depth_for_scale: int = 32  # the published depth, whatever depth is run
    dim_model_base: int = 256
    max_positions: int = 524288  # the published context: prompt and new tokens
    sparse: SparseSpec = SparseSpec()
    eos_id: int | None = None
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        unknown = set(self.mixer_types) - {SPARSE, LIGHTNING}
        if unknown:
            raise ValueError(f"unknown mixer types {sorted(unknown)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads is not a multiple of num_kv_heads")

    @property
    def page_size(self) -> int:
        """A page is the selector's block."""
        return self.sparse.block

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def sparse_layers(self) -> int:
        return sum(m == SPARSE for m in self.mixer_types)

    @property
    def lightning_layers(self) -> int:
        return sum(m == LIGHTNING for m in self.mixer_types)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.depth_for_scale ** 0.5

    def is_dense(self, total_len: int) -> bool:
        """Whether a request of ``total_len`` positions (prompt and new
        tokens) attends everything in its sparse layers."""
        return total_len < self.sparse.dense_len


def init_params(cfg: SalaLMConfig, key) -> dict:
    """Seeded parameters: kernels normal with variance 1 / fan_in, embedding
    normal(0, 1), norm weights one."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 4))

    def kernel(rows, cols):
        w = jax.random.normal(next(keys), (rows, cols), jnp.float32)
        return (w * rows ** -0.5).astype(cfg.dtype)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    layers = []
    for kind in cfg.mixer_types:
        if kind == SPARSE:
            hd, gd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
            mixer = dict(
                q=kernel(d, hd), k=kernel(d, gd), v=kernel(d, gd),
                gate=kernel(d, hd), o=kernel(hd, d),
                q_norm=ones(cfg.head_dim), k_norm=ones(cfg.head_dim),
            )
        else:
            hd = cfg.lightning_heads * cfg.lightning_head_dim
            mixer = dict(
                q=kernel(d, hd), k=kernel(d, hd), v=kernel(d, hd),
                gate=kernel(d, hd), o=kernel(hd, d),
                q_norm=ones(cfg.lightning_head_dim),
                k_norm=ones(cfg.lightning_head_dim),
                out_norm=ones(cfg.lightning_head_dim),
            )
        layers.append(dict(
            input_norm=ones(d), post_norm=ones(d), mixer=mixer,
            mlp=dict(gate=kernel(d, f), up=kernel(d, f), down=kernel(f, d)),
        ))
    return dict(
        embedding=jax.random.normal(
            next(keys), (cfg.vocab_size, d), jnp.float32
        ).astype(cfg.dtype),
        lm_head=kernel(d, cfg.vocab_size),
        final_norm=ones(d),
        layers=layers,
    )


def new_cache(cfg: SalaLMConfig, *, rows: int, num_pages: int, device=None) -> dict:
    """Zeroed device state for ``rows`` rows and ``num_pages`` pages of
    ``cfg.sparse.block`` positions (page 0 is the null page)."""
    spec = cfg.sparse
    g, dh = cfg.num_kv_heads, cfg.head_dim
    h, dl = cfg.lightning_heads, cfg.lightning_head_dim
    z = lambda shape, dtype: jnp.zeros(shape, dtype, device=device)  # noqa: E731
    n = cfg.sparse_layers
    return dict(
        k=[z((g * num_pages * spec.block, dh), cfg.dtype) for _ in range(n)],
        v=[z((g * num_pages * spec.block, dh), cfg.dtype) for _ in range(n)],
        units=[z((g * num_pages * spec.units, dh), cfg.dtype) for _ in range(n)],
        states=[
            z((rows, h, dl, dl), jnp.float32)
            for _ in range(cfg.lightning_layers)
        ],
    )


def page_bytes(cfg: SalaLMConfig) -> int:
    """K, V and unit means of one page in every sparse layer."""
    spec = cfg.sparse
    return cfg.sparse_layers * cfg.num_kv_heads * cfg.head_dim * (
        2 * spec.block + spec.units
    ) * jnp.dtype(cfg.dtype).itemsize


def state_planes(cfg: SalaLMConfig) -> list:
    """Shapes of the fixed-size float32 state a row keeps beside the pages,
    ``cache["states"]``: one ``[heads, d, d]`` a lightning layer."""
    d = cfg.lightning_head_dim
    return [(cfg.lightning_heads, d, d)] * cfg.lightning_layers


def selected_share(cfg: SalaLMConfig, chosen, pos, dense):
    """Key positions a step attended over the positions its row's context
    held, the mean over sparse layers and KV heads (1 for a dense row):
    ``chosen [sparse layers, R, kv heads, topk]`` block indices (-1 where
    none), ``pos [R]`` -> ``[R]``."""
    block = cfg.sparse.block
    held = jnp.clip(
        pos[None, :, None, None] + 1 - chosen * block, 0, block
    )
    held = jnp.where(chosen >= 0, held, 0)
    attended = jnp.sum(held, axis=-1).mean(axis=(0, 2)) if (
        chosen.shape[0]
    ) else jnp.zeros(pos.shape, jnp.float32)
    return jnp.where(dense, 1.0, attended / (pos + 1.0))


def _norm(x, w, eps):
    return _rms(x, eps) * w.astype(jnp.float32)


def _mlp(p, x, dtype):
    with jax.named_scope("lm.mlp"):
        h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
        return (h.astype(dtype) @ p["down"]).astype(jnp.float32)


def _after_mixer(p, cfg, x, mixed):
    """The rest of a block: the mixer's residual, then the MLP's."""
    h = x + cfg.residual_scale * mixed
    hidden = _norm(h, p["post_norm"], cfg.rms_eps).astype(cfg.dtype)
    return h + cfg.residual_scale * _mlp(p["mlp"], hidden, cfg.dtype)


def _embed(params, cfg, tokens):
    return params["embedding"][tokens].astype(jnp.float32) * cfg.scale_emb


def _head(params, cfg, x):
    """Float32 logits of ``x [..., D]`` (the residual stream)."""
    with jax.named_scope("lm.head"):
        x = _norm(x, params["final_norm"], cfg.rms_eps)
        x = x / (cfg.hidden_size / cfg.dim_model_base)
        logits = jnp.dot(
            x.astype(cfg.dtype), params["lm_head"],
            preferred_element_type=jnp.float32,
        )
        if "logit_bias" in params:
            logits = logits + params["logit_bias"].astype(jnp.float32)
        return logits


def _lightning_qkv(p, cfg, x, positions):
    """``x [N, D]`` -> q, k, v ``[N, H, d]`` (normed, rotated) and the gate."""
    h, d = cfg.lightning_heads, cfg.lightning_head_dim
    n = x.shape[0]
    q = (x @ p["q"]).reshape(n, h, d)
    k = (x @ p["k"]).reshape(n, h, d)
    v = (x @ p["v"]).reshape(n, h, d)
    q = _norm(q, p["q_norm"], cfg.rms_eps).astype(cfg.dtype)
    k = _norm(k, p["k_norm"], cfg.rms_eps).astype(cfg.dtype)
    pos = positions[:, None]
    q = rotary_embedding_at(q, pos, theta=cfg.rope_theta)
    k = rotary_embedding_at(k, pos, theta=cfg.rope_theta)
    return q, k, v, x @ p["gate"]


def _lightning_out(p, cfg, o, gate):
    n = o.shape[0]
    o = _norm(o, p["out_norm"], cfg.rms_eps).reshape(n, -1)
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
    return (o.astype(cfg.dtype) @ p["o"]).astype(jnp.float32)


def _sparse_qkv(p, cfg, x):
    h, g, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n = x.shape[0]
    q = (x @ p["q"]).reshape(n, g, h // g, d)  # a KV head's query heads together
    k = (x @ p["k"]).reshape(n, g, d)
    v = (x @ p["v"]).reshape(n, g, d)
    q = _norm(q, p["q_norm"], cfg.rms_eps).astype(cfg.dtype)
    k = _norm(k, p["k_norm"], cfg.rms_eps).astype(cfg.dtype)
    return q, k, v, x @ p["gate"]


def _sparse_out(p, cfg, o, gate):
    n = o.shape[0]
    o = o.reshape(n, -1) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return (o.astype(cfg.dtype) @ p["o"]).astype(jnp.float32)


def prefill_chunk(params, cfg: SalaLMConfig, cache: dict, tokens, table, row,
                  start, length, dense):
    """Positions ``start .. start + C - 1`` of one request (``tokens [C]``,
    of which those before ``length``, the prompt's length, are real): writes
    their K, V and unit means into the pages ``table [Pmax]`` names, carries
    the lightning states of slot ``row`` over them, and returns the new
    cache. ``start`` is a multiple of the page size; ``dense`` says whether
    the request attends everything. No logits: the launch's first step takes
    the prompt's last token."""
    spec = cfg.sparse
    c = tokens.shape[0]
    positions = start + jnp.arange(c, dtype=jnp.int32)
    real = jnp.clip(length - start, 0, c)
    chunk_pages = jax.lax.dynamic_slice(
        table, (start // spec.block,), (c // spec.block,)
    )
    cache = {name: list(planes) for name, planes in cache.items()}
    x = _embed(params, cfg, tokens)
    si = li = 0
    for p, kind in zip(params["layers"], cfg.mixer_types):
        hidden = _norm(x, p["input_norm"], cfg.rms_eps).astype(cfg.dtype)
        m = p["mixer"]
        if kind == SPARSE:
            q, k, v, gate = _sparse_qkv(m, cfg, hidden)
            with jax.named_scope("lm.sparse_attn"):
                g = cfg.num_kv_heads
                rows = page_rows(
                    cache["k"][si], g, spec.block,
                    jnp.repeat(chunk_pages, spec.block), positions % spec.block,
                ).reshape(-1)
                heads_first = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
                kh = heads_first(k)  # [G, C, d]
                k_pages = cache["k"][si].at[rows].set(kh.reshape(g * c, -1))
                v_pages = cache["v"][si].at[rows].set(
                    heads_first(v).reshape(g * c, -1)
                )
                n_units = c // spec.stride
                unit_rows = page_rows(
                    cache["units"][si], g, spec.units,
                    jnp.repeat(chunk_pages, spec.units),
                    jnp.arange(n_units) % spec.units,
                ).reshape(-1)
                units = cache["units"][si].at[unit_rows].set(
                    unit_means(kh, spec.stride).reshape(g * n_units, -1)
                )
                o = sparse_prefill(
                    q, k_pages, v_pages, units, table, positions, dense, spec,
                    real=real,
                )
            cache["k"][si], cache["v"][si], cache["units"][si] = (
                k_pages, v_pages, units
            )
            out = _sparse_out(m, cfg, o, gate)
            si += 1
        else:
            q, k, v, gate = _lightning_qkv(m, cfg, hidden, positions)
            with jax.named_scope("lm.lightning"):
                state = jax.lax.dynamic_index_in_dim(
                    cache["states"][li], row, keepdims=True
                )
                o, state = lightning_attention(
                    q[None], k[None], v[None], decay_slopes(cfg.lightning_heads),
                    initial_state=state, n_valid=real[None], chunk=c,
                    site="lightning_prefill",
                )
                cache["states"][li] = jax.lax.dynamic_update_index_in_dim(
                    cache["states"][li], state[0], row, 0
                )
            out = _lightning_out(m, cfg, o[0], gate)
            li += 1
        x = _after_mixer(p, cfg, x, out)
    return cache


def decode_step(params, cfg: SalaLMConfig, cache: dict, token, pos, tables,
                active, dense):
    """One position for every row: ``token [R]`` at ``pos [R]``, block tables
    ``tables [R, Pmax]``; rows not ``active`` write to the null page and
    their outputs mean nothing. Returns ``(logits [R, V] float32, new
    cache, selected [sparse layers, R, kv heads, topk], counts)``; no
    counters here (``counts`` is empty)."""
    spec = cfg.sparse
    r = token.shape[0]
    page = jnp.take_along_axis(tables, (pos // spec.block)[:, None], axis=1)[:, 0]
    page = jnp.where(active, page, NULL_PAGE)
    slot = pos % spec.block
    cache = {name: list(planes) for name, planes in cache.items()}
    x = _embed(params, cfg, token)
    selected = []
    si = li = 0
    for p, kind in zip(params["layers"], cfg.mixer_types):
        hidden = _norm(x, p["input_norm"], cfg.rms_eps).astype(cfg.dtype)
        m = p["mixer"]
        if kind == SPARSE:
            q, k, v, gate = _sparse_qkv(m, cfg, hidden)
            with jax.named_scope("lm.sparse_attn"):
                g = cfg.num_kv_heads
                rows = page_rows(cache["k"][si], g, spec.block, page, slot)
                heads_first = lambda a: jnp.swapaxes(a, 0, 1).reshape(g * r, -1)  # noqa: E731
                k_pages = cache["k"][si].at[rows.reshape(-1)].set(heads_first(k))
                v_pages = cache["v"][si].at[rows.reshape(-1)].set(heads_first(v))
                # The whole page's unit means again: the units the position
                # has not closed yet are not visible to any query.
                whole = gather_pages(
                    k_pages, jnp.broadcast_to(page[None, :], (g, r)), spec.block
                )  # [G, R, block, d]
                unit_rows = page_rows(
                    cache["units"][si], g, spec.units, page[:, None],
                    jnp.arange(spec.units),
                )  # [G, R, m]
                units = cache["units"][si].at[unit_rows.reshape(-1)].set(
                    unit_means(whole, spec.stride).reshape(g * r * spec.units, -1)
                )
                o, chosen = sparse_decode(
                    q, k_pages, v_pages, units, tables, pos, dense, spec
                )
            cache["k"][si], cache["v"][si], cache["units"][si] = (
                k_pages, v_pages, units
            )
            selected.append(chosen)
            out = _sparse_out(m, cfg, o, gate)
            si += 1
        else:
            q, k, v, gate = _lightning_qkv(m, cfg, hidden, pos)
            with jax.named_scope("lm.lightning"):
                o, cache["states"][li] = lightning_attention_step(
                    q, k, v, decay_slopes(cfg.lightning_heads),
                    cache["states"][li],
                )
            out = _lightning_out(m, cfg, o, gate)
            li += 1
        x = _after_mixer(p, cfg, x, out)
    chosen = (
        jnp.stack(selected) if selected
        else jnp.zeros((0, r, cfg.num_kv_heads, spec.topk), jnp.int32)
    )
    return _head(params, cfg, x), cache, chosen, {}
