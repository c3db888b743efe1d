"""Decoder-only hybrid language model: linear-attention and softmax-attention
layers in a fixed period, every layer followed by a sparse expert layer.

The block, pre-norm throughout, no bias and no dropout anywhere:

    h = x + Mixer_i(RMSNorm(x))        y = h + MoE(RMSNorm(h))

``Mixer_i`` is ``GatedAttention`` where ``(i + 1) % full_attention_interval
== 0`` and ``GatedDeltaNet`` otherwise (three linear layers to one full layer
at the interval of 4). ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``.
Embedding and output head are untied; a final RMSNorm precedes the head.

- ``GatedDeltaNet`` (Yang et al., arXiv:2412.06464): one projection to
  ``[q | k | v | z]`` and one to ``[b | a]``; ``q, k, v`` pass a causal
  depthwise convolution and SiLU; ``q, k`` are L2-normalised a head (``q``
  scaled by ``dk^-0.5``); ``beta = sigmoid(b)``, ``g = -exp(A_log) *
  softplus(a + dt_bias)``; the recurrence runs in chunks
  (``ops.gated_delta.gated_delta_rule``); the output is
  ``out_proj(RMSNorm_head(o) * SiLU(z))`` with a plain-weight norm a head.
- ``GatedAttention``: ``q_proj`` gives query and gate a head; RMSNorm
  ``(1 + w)`` over each head of ``q`` and ``k``; rotary positions on the
  first ``partial_rotary_factor`` of each head; causal attention with grouped
  KV heads through ``ops.attention.dot_product_attention`` (each KV head is
  repeated for its query heads, so the flash kernel sees equal head counts);
  output ``o_proj(attn * sigmoid(gate))``.
- ``models.moe.DroplessMoE``: top-k over all experts, the experts held here.

Compute is ``cfg.dtype`` on float32 parameters; norms, the router, the gates
``g`` / ``beta``, softmax and the recurrence's state are float32. With
``cfg.remat`` each half of a block is rematerialised (``nn.remat``): only
``x`` and ``h`` of every layer outlive the forward pass.

``HybridLM.__call__(tokens)`` returns ``(logits, stats)``; with ``labels`` it
returns ``((sum of next-token cross-entropies, count), stats)`` and takes the
head and the loss ``loss_block_tokens`` positions at a time under
``jax.checkpoint``, so the float32 ``[rows * S, V]`` logits never exist whole.
``stats`` holds the expert layers' ``aux`` (mean over layers),
``tokens_held_mean`` (mean), ``tokens_held_max`` (max) and
``assignments_local`` / ``assignments_computed`` (sums).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu.models.moe import DroplessMoE
from machine_learning_apache_spark_tpu.ops.attention import dot_product_attention
from machine_learning_apache_spark_tpu.ops.gated_delta import (
    DEFAULT_CHUNK,
    gated_delta_rule,
)
from machine_learning_apache_spark_tpu.ops.positional import rotary_embedding


@dataclasses.dataclass(frozen=True)
class HybridLMConfig:
    vocab_size: int
    hidden_size: int = 2048
    num_layers: int = 4
    full_attention_interval: int = 4
    # gated softmax attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated delta net
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    linear_conv_kernel: int = 4
    # No recipe or configuration file sets the two block sizes below; the
    # tests do, to put several scan chunks and a padded tail of the loss's
    # blocks into rows short enough for the CPU.
    scan_chunk: int = DEFAULT_CHUNK
    loss_block_tokens: int = 2048
    # experts
    num_experts: int = 512
    experts_per_token: int = 10
    experts_held: tuple[int, int] | None = None  # (first, count); None = all
    expert_hidden: int = 512
    shared_expert_hidden: int = 512
    norm_topk_prob: bool = True
    router_aux_weight: float = 0.001
    rms_eps: float = 1e-6
    remat: bool = True
    dtype: jnp.dtype = jnp.float32

    def is_full_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0


# The block's two norms, the final norm and the residual adds: a scope of
# their own at the block's level, apart from the mixers' and the expert
# layer's scopes (``q_norm`` / ``k_norm`` and the gated norm sit under those),
# so that no operation of a step is under two of them.
RESIDUAL_NORM = "lm.residual_norm"


def _rms(x, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)`` over the last axis (``w``
    starts at zero), or ``* w`` (starting at one) with ``offset=False``.
    Float32 inside, the input's dtype out."""

    eps: float = 1e-6
    offset: bool = True

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.offset else nn.initializers.ones
        w = self.param("w", init, (x.shape[-1],)).astype(jnp.float32)
        return (_rms(x, self.eps) * ((1.0 + w) if self.offset else w)).astype(x.dtype)


def _decay_init(key, shape, dtype=jnp.float32):
    """``A_log``: ``A`` uniform in [0.5, 2]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 0.5, 2.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias``: the inverse softplus of a step log-uniform in
    [0.002, 0.05], so that a fresh layer's per-token decay ``exp(-A dt)``
    spans about 0.9 to 0.999."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(0.002), jnp.log(0.05)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_depthwise_conv(x, kernel):
    """``y_t = sum_j kernel[j] * x_{t - (K - 1) + j}`` a channel, zeros before
    the sequence. ``x [B, S, C]``, ``kernel [K, C]``; float32 accumulation."""
    width = kernel.shape[0]
    padded = jnp.pad(x, [(0, 0), (width - 1, 0), (0, 0)])
    s = x.shape[1]
    return sum(
        padded[:, j:j + s].astype(jnp.float32) * kernel[j].astype(jnp.float32)
        for j in range(width)
    )


class GatedDeltaNet(nn.Module):
    cfg: HybridLMConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
        key_dim, value_dim = hk * dk, hv * dv
        init = nn.initializers.lecun_normal()
        w_qkvz = self.param("in_proj_qkvz", init, (d, 2 * key_dim + 2 * value_dim))
        w_ba = self.param("in_proj_ba", init, (d, 2 * hv))
        conv = self.param(
            "conv", nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (cfg.linear_conv_kernel, 2 * key_dim + value_dim),
        )
        a_log = self.param("A_log", _decay_init, (hv,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (hv,))
        w_out = self.param("out_proj", init, (value_dim, d))

        with jax.named_scope("lm.gdn_proj_conv"):
            xc = x.astype(cfg.dtype)
            # Two products from the fused matrix's column blocks: slicing
            # the product instead leaves a padded float32 cotangent of the
            # whole [B, S, q|k|v|z] to the backward pass.
            split = 2 * key_dim + value_dim
            w_qkvz = w_qkvz.astype(cfg.dtype)
            qkv, z = xc @ w_qkvz[:, :split], xc @ w_qkvz[:, split:]
            ba = jnp.dot(
                x.astype(jnp.float32), w_ba.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., hv:] + dt_bias.astype(jnp.float32)
            )
            # rounded before the activation: the backward keeps this one
            qkv = jax.nn.silu(causal_depthwise_conv(qkv, conv).astype(cfg.dtype))
            q = qkv[..., :key_dim].reshape(b, s, hk, dk)
            k = qkv[..., key_dim:2 * key_dim].reshape(b, s, hk, dk)
            v = qkv[..., 2 * key_dim:].reshape(b, s, hv, dv).astype(cfg.dtype)
            l2 = lambda t: t * jax.lax.rsqrt(  # noqa: E731
                jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6
            )
            q = (l2(q) * dk ** -0.5).astype(cfg.dtype)
            k = l2(k).astype(cfg.dtype)
        with jax.named_scope("lm.gdn_scan"):
            o, _ = gated_delta_rule(
                q, k, v, g, beta, chunk=cfg.scan_chunk, site="gated_delta_net"
            )
        with jax.named_scope("lm.gdn_proj_conv"):
            o = RMSNorm(cfg.rms_eps, offset=False, name="norm")(o)
            z = z.reshape(b, s, hv, dv)
            o = (o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
            o = o.astype(cfg.dtype).reshape(b, s, value_dim)
            return (o @ w_out.astype(cfg.dtype)).astype(x.dtype)


class GatedAttention(nn.Module):
    cfg: HybridLMConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        init = nn.initializers.lecun_normal()
        w_q = self.param("q_proj", init, (d, h * 2 * dh))
        w_k = self.param("k_proj", init, (d, hkv * dh))
        w_v = self.param("v_proj", init, (d, hkv * dh))
        w_o = self.param("o_proj", init, (h * dh, d))
        with jax.named_scope("lm.attn"):
            xc = x.astype(cfg.dtype)
            w_q = w_q.astype(cfg.dtype).reshape(d, h, 2, dh)  # [.., (query, gate), ..]
            q = jnp.einsum("bsd,dhe->bshe", xc, w_q[:, :, 0])
            gate = jnp.einsum("bsd,dhe->bshe", xc, w_q[:, :, 1])
            k = (xc @ w_k.astype(cfg.dtype)).reshape(b, s, hkv, dh)
            v = (xc @ w_v.astype(cfg.dtype)).reshape(b, s, hkv, dh)
            q = RMSNorm(cfg.rms_eps, name="q_norm")(q)
            k = RMSNorm(cfg.rms_eps, name="k_norm")(k)
            heads_first = lambda t: jnp.swapaxes(t, 1, 2)  # noqa: E731
            rotary_dim = int(dh * cfg.partial_rotary_factor)
            q = rotary_embedding(heads_first(q), rotary_dim=rotary_dim, theta=cfg.rope_theta)
            k = rotary_embedding(heads_first(k), rotary_dim=rotary_dim, theta=cfg.rope_theta)
            # A KV head serves h / hkv consecutive query heads.
            k = jnp.repeat(k, h // hkv, axis=1)
            v = jnp.repeat(heads_first(v), h // hkv, axis=1)
            attn = dot_product_attention(q, k, v, causal=True)
            attn = heads_first(attn).astype(jnp.float32)
            attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32))
            attn = attn.astype(cfg.dtype).reshape(b, s, h * dh)
            return (attn @ w_o.astype(cfg.dtype)).astype(x.dtype)


class HybridBlock(nn.Module):
    """One layer, in two halves: ``h = x + Mixer(RMSNorm(x))`` and
    ``y = h + MoE(RMSNorm(h))`` with the expert layer's stats. Under
    ``cfg.remat`` each half is rematerialised apart (``hybrid_block_class``),
    so the backward pass holds one half's intermediates at a time and only
    ``x`` and ``h`` of every layer outlive the forward."""

    cfg: HybridLMConfig
    full_attention: bool

    def setup(self):
        cfg = self.cfg
        self.input_norm = RMSNorm(cfg.rms_eps)
        self.mixer = (GatedAttention if self.full_attention else GatedDeltaNet)(cfg)
        self.post_norm = RMSNorm(cfg.rms_eps)
        self.moe = DroplessMoE(
            d_model=cfg.hidden_size, expert_hidden=cfg.expert_hidden,
            shared_hidden=cfg.shared_expert_hidden,
            num_experts=cfg.num_experts, top_k=cfg.experts_per_token,
            experts_held=cfg.experts_held, renormalize=cfg.norm_topk_prob,
            dtype=cfg.dtype,
        )

    def mixer_half(self, x):
        with jax.named_scope(RESIDUAL_NORM):
            normed = self.input_norm(x)
        out = self.mixer(normed)
        with jax.named_scope(RESIDUAL_NORM):
            return x + out

    def expert_half(self, h):
        with jax.named_scope(RESIDUAL_NORM):
            normed = self.post_norm(h)
        out, stats = self.moe(normed)
        with jax.named_scope(RESIDUAL_NORM):
            return h + out, stats

    def __call__(self, x):
        return self.expert_half(self.mixer_half(x))


def hybrid_block_class(cfg: HybridLMConfig):
    if not cfg.remat:
        return HybridBlock
    return nn.remat(HybridBlock, methods=("mixer_half", "expert_half"))


def _block_loss(head, hidden, labels, scored, dtype):
    """Sum of the cross-entropies of ``hidden [T, d]`` against ``labels [T]``
    where ``scored``, under ``head [d, V]``: logits in float32 from a
    ``dtype`` product."""
    logits = jnp.dot(
        hidden.astype(dtype), head.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(scored, picked, 0.0))


class HybridLM(nn.Module):
    cfg: HybridLMConfig

    @nn.compact
    def __call__(self, tokens, labels=None):
        cfg = self.cfg
        embedding = self.param(
            "embedding", nn.initializers.normal(1.0),
            (cfg.vocab_size, cfg.hidden_size),
        )
        head = self.param(
            "lm_head", nn.initializers.lecun_normal(),
            (cfg.hidden_size, cfg.vocab_size),
        )
        with jax.named_scope("lm.embed"):
            x = embedding[tokens].astype(cfg.dtype)
        block_cls = hybrid_block_class(cfg)
        per_layer = []
        for i in range(cfg.num_layers):
            x, stats = block_cls(
                cfg, cfg.is_full_attention(i), name=f"layer_{i}"
            )(x)
            per_layer.append(stats)
        stacked = {k: jnp.stack([s[k] for s in per_layer]) for k in per_layer[0]}
        stats = {
            "aux": jnp.mean(stacked["aux"]),
            "tokens_held_mean": jnp.mean(stacked["tokens_held_mean"]),
            "tokens_held_max": jnp.max(stacked["tokens_held_max"]),
            "assignments_local": jnp.sum(stacked["assignments_local"]),
            "assignments_computed": jnp.sum(stacked["assignments_computed"]),
        }
        with jax.named_scope(RESIDUAL_NORM):
            x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        with jax.named_scope("lm.head_loss"):
            if labels is None:
                logits = jnp.dot(
                    x.astype(cfg.dtype), head.astype(cfg.dtype),
                    preferred_element_type=jnp.float32,
                )
                return logits, stats
            n = labels.size
            block = min(cfg.loss_block_tokens, n)
            pad = -n % block  # padded positions score nothing
            flat = jnp.pad(x.reshape(n, -1), [(0, pad), (0, 0)])
            flat_labels = jnp.pad(labels.reshape(n), (0, pad))
            scored = jnp.arange(n + pad) < n
            blocks = lambda t: t.reshape(-1, block, *t.shape[1:])  # noqa: E731
            loss_of = jax.checkpoint(
                lambda hd, h, l, m: _block_loss(hd, h, l, m, cfg.dtype)
            )

            def add_block(total, xs):
                return total + loss_of(head, *xs), None

            total, _ = jax.lax.scan(
                add_block, jnp.float32(0.0),
                (blocks(flat), blocks(flat_labels), blocks(scored)),
            )
            return (total, jnp.float32(labels.size)), stats
