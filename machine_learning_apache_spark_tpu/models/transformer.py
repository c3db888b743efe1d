"""Encoder-decoder Transformer.

Reference: the 11-class ``transformer.py`` module library (C14-C23,
SURVEY.md §2.1) used by the en→de MT driver
(``pytorch_machine_translator.py:120``: d_model=512, ffn=1024, heads=8,
drop=0.1, layers=1, max_seq=200).

Correct-semantics deltas from the reference (SURVEY.md §2.5):
- Q9: masks are boolean (True = attendable) applied ``where(mask, s, -inf)``
  before softmax — never added.
- Q8: cross-attention reshapes Q with the *decoder's* length and K/V with the
  *encoder's*; src/trg sequence lengths are independent.
- C15: positional encodings are a trace-time constant, not recomputed and
  re-transferred per forward.
- C18's hand-rolled LayerNorm is ``nn.LayerNorm`` (same math, fused by XLA).

Structure is post-LN residual (``x = LN(x + drop(sublayer(x)))``) matching
``transformer.py:130-139``. Attention runs through the shared ops core, which
dispatches to the Pallas flash kernel when maskless/causal on TPU.

Tensor-parallel seam: every Dense hidden axis is annotated with the logical
axis names ``("embed", "mlp"/"heads")`` via ``nn.with_partitioning`` — the
``parallel`` package maps these onto the mesh's ``"model"`` axis for TP runs
and to unsharded for single-chip runs.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu.ops.attention import (
    NEG_INF,
    dot_product_attention,
    ragged_paged_attention,
)
from machine_learning_apache_spark_tpu.ops.masks import (
    combine_masks,
    make_causal_mask,
    make_padding_mask,
)
from machine_learning_apache_spark_tpu.ops.positional import sinusoidal_encoding


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Hyperparameters — the reference ctor signature (``transformer.py:256-267``)
    plus compute dtype. Defaults are the MT driver's
    (``pytorch_machine_translator.py:108-117``)."""

    src_vocab_size: int
    trg_vocab_size: int
    d_model: int = 512
    ffn_hidden: int = 1024
    num_heads: int = 8
    num_layers: int = 1
    dropout: float = 0.1
    max_len: int = 200
    pad_id: int = 0
    dtype: jnp.dtype = jnp.float32  # bfloat16 for MXU-native training
    # Extra all-zero-target columns on the LM head so its vocab dim divides
    # a tensor-parallel "model" axis (Megatron-style vocab padding). Logits
    # are sliced back to trg_vocab_size before they leave the model, so
    # losses/decoding are exactly vocab-sized regardless of padding.
    logit_pad: int = 0
    # Rematerialize encoder/decoder layers under autodiff (jax.checkpoint):
    # activations inside each layer are recomputed in the backward instead
    # of saved — O(num_layers) → O(1) layer activations live at once, the
    # FLOPs-for-HBM trade that makes long-context training fit.
    remat: bool = False
    # Mixture-of-experts FFN (models.moe): 0 = dense FFN (the reference's
    # C19); N > 0 replaces every FFN with N switch-routed experts whose
    # weights shard over the mesh "expert" axis. The Switch load-balancing
    # aux losses are sown into the "losses" collection — training code adds
    # moe_aux_weight × their mean to the task loss.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2


def _dense(features: int, cfg: TransformerConfig, name: str, logical_out: str):
    """Dense with TP logical partitioning on (in, out) kernel axes."""
    return nn.Dense(
        features,
        dtype=cfg.dtype,
        name=name,
        kernel_init=nn.with_partitioning(
            nn.initializers.lecun_normal(), ("embed", logical_out)
        ),
    )


class SentenceEmbedding(nn.Module):
    """Token embedding + positional encoding + dropout (C16,
    ``transformer.py:44-62``), with the PE table cached (C15 fix)."""

    vocab_size: int
    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        *,
        deterministic: bool = True,
        position_offset: jnp.ndarray | int = 0,
        positions: jnp.ndarray | None = None,
    ):
        x = nn.Embed(
            self.vocab_size,
            self.cfg.d_model,
            dtype=self.cfg.dtype,
            embedding_init=nn.with_partitioning(
                nn.initializers.normal(stddev=0.02), (None, "embed")
            ),
            name="embed",
        )(tokens)
        # position_offset shifts the PE window for incremental decoding
        # (token t of the generation loop gets PE row t, not 0). The table
        # covers max(cfg.max_len, L) so static sequences longer than max_len
        # keep working; only dynamic offsets are bounded by max_len.
        table = sinusoidal_encoding(
            max(self.cfg.max_len, tokens.shape[-1]),
            self.cfg.d_model,
            self.cfg.dtype,
        )
        if positions is not None:
            # Per-token position ids ([B, S] gather): sequence packing gives
            # each packed segment PE rows restarting at 0, so a segment sees
            # exactly the encoding its pair would see unpacked.
            pe = table[positions]
        else:
            pe = jax.lax.dynamic_slice_in_dim(
                table, position_offset, tokens.shape[-1], axis=0
            )
        x = x + pe
        return nn.Dropout(self.cfg.dropout, deterministic=deterministic)(x)


class MultiHeadAttention(nn.Module):
    """Self- or cross-attention with fused projections.

    Self-attention uses a fused QKV ``Linear(d, 3d)`` like the reference C17
    (``transformer.py:74-83``); cross-attention fuses KV (C21) but — fixing
    Q8 — reshapes each stream with its own length.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        x_q: jnp.ndarray,
        x_kv: jnp.ndarray | None = None,
        mask: jnp.ndarray | None = None,
        *,
        causal: bool = False,
        kv_valid: jnp.ndarray | None = None,
        decode: bool = False,
        deterministic: bool = True,
        paged: dict | None = None,
        paged_cross: bool = False,
        sow_mem_kv: bool = False,
    ) -> jnp.ndarray:
        cfg = self.cfg
        head_dim = cfg.d_model // cfg.num_heads
        b, s_q, _ = x_q.shape

        def split_heads(t, length):
            return t.reshape(b, length, cfg.num_heads, head_dim).transpose(0, 2, 1, 3)

        def out_proj(t):
            return nn.Dense(
                cfg.d_model,
                dtype=cfg.dtype,
                name="out",
                kernel_init=nn.with_partitioning(
                    nn.initializers.lecun_normal(), ("heads", "embed")
                ),
            )(t)

        if paged is not None:
            # Paged ragged decode (serving): ``x_q`` is one position per
            # request row ([R, 1, d]); cached K/V live in the engine's
            # shared page store and are addressed through this call's
            # block table + per-row lengths — see
            # ``ops.attention.ragged_paged_attention``. The projections
            # reuse the exact Dense modules of the padded paths ("qkv" /
            # "q" / "out"), so one set of params serves both modes.
            if paged_cross:
                # Cross-attention over prefilled memory pages; K/V were
                # projected once at prefill (sow_mem_kv below) and
                # scattered into the page store.
                q = _dense(cfg.d_model, cfg, "q", "heads")(x_q)
                ctx = ragged_paged_attention(
                    q[:, 0].reshape(b, cfg.num_heads, head_dim),
                    paged["k_pages"], paged["v_pages"],
                    paged["table"], paged["length"],
                    k_scale=paged.get("k_scale"),
                    v_scale=paged.get("v_scale"),
                )
            else:
                # Self-attention: project this step's Q/K/V, attend the
                # cached pages plus the current position (the causal
                # diagonal), and sow the new K/V so the caller can
                # scatter them into the page store after the step.
                qkv = _dense(3 * cfg.d_model, cfg, "qkv", "heads")(x_q)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                self.sow("paged", "k_new", k[:, 0])
                self.sow("paged", "v_new", v[:, 0])
                ctx = ragged_paged_attention(
                    q[:, 0].reshape(b, cfg.num_heads, head_dim),
                    paged["k_pages"], paged["v_pages"],
                    paged["table"], paged["length"],
                    k_scale=paged.get("k_scale"),
                    v_scale=paged.get("v_scale"),
                    cur_k=k[:, 0], cur_v=v[:, 0],
                )
            return out_proj(ctx.reshape(b, 1, cfg.d_model))

        if x_kv is None:
            qkv = _dense(3 * cfg.d_model, cfg, "qkv", "heads")(x_q)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            s_kv = s_q
        elif decode:
            # Cross-attention decode: the encoder memory is fixed for the
            # whole generation, so its K/V projection is done once — on the
            # cache-priming call — and reused from the cache every step
            # (one [S_src, d]×[d, 2d] matmul per sequence, not per token).
            s_kv = x_kv.shape[1]
            q = _dense(cfg.d_model, cfg, "q", "heads")(x_q)
            if not self.has_variable("cache", "cached_mem_key"):
                kv = _dense(2 * cfg.d_model, cfg, "kv", "heads")(x_kv)
                k, v = jnp.split(kv, 2, axis=-1)
                self.variable("cache", "cached_mem_key", lambda: k)
                self.variable("cache", "cached_mem_value", lambda: v)
            else:
                # The "kv" Dense is skipped entirely on cached steps; all
                # submodules here carry explicit names so the module tree
                # stays stable regardless.
                k = self.variable("cache", "cached_mem_key", None).value
                v = self.variable("cache", "cached_mem_value", None).value
        else:
            s_kv = x_kv.shape[1]
            kv = _dense(2 * cfg.d_model, cfg, "kv", "heads")(x_kv)
            k, v = jnp.split(kv, 2, axis=-1)
            q = _dense(cfg.d_model, cfg, "q", "heads")(x_q)
            if sow_mem_kv:
                # Paged prefill: expose the memory K/V projections so the
                # serving runtime can scatter them into the page store —
                # the once-per-sequence cross-attention projection that
                # the flax decode cache otherwise keeps internal.
                self.sow("paged", "k_mem", k)
                self.sow("paged", "v_mem", v)

        if decode and x_kv is None:
            # Incremental decoding: append this step's K/V (one position per
            # call) to the cache and attend over everything written so far —
            # O(1) projection work per generated token instead of
            # re-projecting the whole prefix (the flax decode-cache pattern).
            is_initialized = self.has_variable("cache", "cached_key")
            cached_k = self.variable(
                "cache", "cached_key",
                jnp.zeros, (b, cfg.max_len, cfg.d_model), k.dtype,
            )
            cached_v = self.variable(
                "cache", "cached_value",
                jnp.zeros, (b, cfg.max_len, cfg.d_model), v.dtype,
            )
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            if not is_initialized:
                # Cache-shape init trace: K/V are this call's (length-1)
                # projections; any caller-passed full-width validity mask
                # does not apply to them.
                kv_valid = None
            else:
                idx = cache_index.value
                cached_k.value = jax.lax.dynamic_update_slice_in_dim(
                    cached_k.value, k, idx, axis=1
                )
                cached_v.value = jax.lax.dynamic_update_slice_in_dim(
                    cached_v.value, v, idx, axis=1
                )
                cache_index.value = idx + s_q
                k, v = cached_k.value, cached_v.value
                s_kv = cfg.max_len
                # Only the filled prefix is attendable (causality within the
                # written positions is implied by generation order); a
                # caller-provided kv_valid further masks positions whose
                # token is pad — matching the naive decoder's trg_valid.
                prefix = jnp.broadcast_to(
                    jnp.arange(cfg.max_len) < idx + s_q, (b, cfg.max_len)
                )
                kv_valid = prefix if kv_valid is None else prefix & kv_valid
                causal = False

        # Structured (causal/kv_valid) masks stream through the Pallas flash
        # kernel on TPU once the site is long enough for it to pay
        # (ops.attention.FLASH_MIN_SCORES scores a head); a shorter site and
        # a dense mask take the fused-XLA path.
        out = dot_product_attention(
            split_heads(q, s_q),
            split_heads(k, s_kv),
            split_heads(v, s_kv),
            mask,
            causal=causal,
            kv_valid=kv_valid,
        )
        out = out.transpose(0, 2, 1, 3).reshape(b, s_q, cfg.d_model)
        return out_proj(out)


class FeedForward(nn.Module):
    """Position-wise FFN (C19, ``transformer.py:104-117``):
    Dense(ffn) → ReLU → Dropout → Dense(d)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, deterministic: bool = True):
        cfg = self.cfg
        h = _dense(cfg.ffn_hidden, cfg, "up", "mlp")(x)
        h = nn.relu(h)
        h = nn.Dropout(cfg.dropout, deterministic=deterministic)(h)
        return nn.Dense(
            cfg.d_model,
            dtype=cfg.dtype,
            name="down",
            kernel_init=nn.with_partitioning(
                nn.initializers.lecun_normal(), ("mlp", "embed")
            ),
        )(h)


def _make_ffn(cfg: TransformerConfig, name: str):
    """Dense FFN, or the switch-routed MoE variant when cfg.moe_experts > 0."""
    if cfg.moe_experts > 0:
        from machine_learning_apache_spark_tpu.models.moe import MoEFeedForward

        return MoEFeedForward(
            d_model=cfg.d_model,
            ffn_hidden=cfg.ffn_hidden,
            num_experts=cfg.moe_experts,
            capacity_factor=cfg.moe_capacity_factor,
            dropout=cfg.dropout,
            dtype=cfg.dtype,
            name=name,
        )
    return FeedForward(cfg, name=name)


class EncoderLayer(nn.Module):
    """Post-LN residual block (C20, ``transformer.py:120-139``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self, x, mask=None, kv_valid=None, deterministic: bool = True,
        token_valid=None,
    ):
        # ``deterministic`` is positional-friendly: nn.remat marks it static
        # by argnum (keyword-only args cannot be static under jax.checkpoint).
        drop = nn.Dropout(self.cfg.dropout, deterministic=deterministic)
        attn = MultiHeadAttention(self.cfg, name="self_attn")(
            x, mask=mask, kv_valid=kv_valid, deterministic=deterministic
        )
        x = nn.LayerNorm(dtype=self.cfg.dtype, name="ln1")(x + drop(attn))
        ffn_kw = (
            # token_valid (always derived from the tokens, independent of
            # any attention-mask override) excludes pad positions from MoE
            # routing — capacity slots and aux statistics alike.
            {"valid": token_valid if token_valid is not None else kv_valid}
            if self.cfg.moe_experts > 0
            else {}
        )
        ffn = _make_ffn(self.cfg, "ffn")(
            x, deterministic=deterministic, **ffn_kw
        )
        return nn.LayerNorm(dtype=self.cfg.dtype, name="ln2")(x + drop(ffn))


class Encoder(nn.Module):
    """Embedding + layer stack (C20's ``Encoder``, ``transformer.py:149-166``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        src_tokens,
        src_mask=None,
        src_valid=None,
        *,
        deterministic: bool = True,
        positions=None,
    ):
        x = SentenceEmbedding(self.cfg.src_vocab_size, self.cfg, name="embed")(
            src_tokens, deterministic=deterministic, positions=positions
        )
        # MoE pad exclusion must not depend on the attention-mask override:
        # derive token validity from the tokens themselves.
        token_valid = (
            src_tokens != self.cfg.pad_id if self.cfg.moe_experts > 0 else None
        )
        # static_argnums counts self at 0; deterministic is arg 4.
        layer_cls = (
            nn.remat(EncoderLayer, static_argnums=(4,))
            if self.cfg.remat
            else EncoderLayer
        )
        for i in range(self.cfg.num_layers):
            x = layer_cls(self.cfg, name=f"layer_{i}")(
                x, src_mask, src_valid, deterministic, token_valid
            )
        return x


class DecoderLayer(nn.Module):
    """Self-attn + cross-attn + FFN, each post-LN residual (C22,
    ``transformer.py:194-224``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        y,
        memory,
        self_mask=None,
        cross_mask=None,
        trg_valid=None,
        memory_valid=None,
        self_causal: bool = False,
        decode: bool = False,
        deterministic: bool = True,
        token_valid=None,
        paged_self: dict | None = None,
        paged_mem: dict | None = None,
        sow_mem_kv: bool = False,
    ):
        # Flags are plain positional-friendly bools so nn.remat can mark
        # them static by argnum (7, 8, 9; self counts at 0). The paged_*
        # kwargs are the serving decode path (never rematerialized).
        drop = nn.Dropout(self.cfg.dropout, deterministic=deterministic)
        attn = MultiHeadAttention(self.cfg, name="self_attn")(
            y,
            mask=self_mask,
            causal=self_causal,
            kv_valid=trg_valid,
            decode=decode,
            deterministic=deterministic,
            paged=paged_self,
        )
        y = nn.LayerNorm(dtype=self.cfg.dtype, name="ln1")(y + drop(attn))
        cross = MultiHeadAttention(self.cfg, name="cross_attn")(
            y,
            memory,
            mask=cross_mask,
            kv_valid=memory_valid,
            decode=decode,
            deterministic=deterministic,
            paged=paged_mem,
            paged_cross=paged_mem is not None,
            sow_mem_kv=sow_mem_kv,
        )
        y = nn.LayerNorm(dtype=self.cfg.dtype, name="ln2")(y + drop(cross))
        ffn_kw = (
            # token_valid is derived from the tokens regardless of mask
            # overrides; it matches y's positions only outside decode (a
            # decode step feeds [B, 1] tokens while validity spans the
            # cache), so the decode path routes its single real token.
            {"valid": None if (decode or paged_self is not None) else (
                token_valid if token_valid is not None else trg_valid
            )}
            if self.cfg.moe_experts > 0
            else {}
        )
        ffn = _make_ffn(self.cfg, "ffn")(
            y, deterministic=deterministic, **ffn_kw
        )
        return nn.LayerNorm(dtype=self.cfg.dtype, name="ln3")(y + drop(ffn))


class Decoder(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        trg_tokens,
        memory,
        self_mask=None,
        cross_mask=None,
        trg_valid=None,
        memory_valid=None,
        *,
        self_causal: bool = False,
        decode: bool = False,
        position_offset: jnp.ndarray | int = 0,
        positions=None,
        deterministic: bool = True,
        paged: dict | None = None,
        sow_mem_kv: bool = False,
    ):
        y = SentenceEmbedding(self.cfg.trg_vocab_size, self.cfg, name="embed")(
            trg_tokens,
            deterministic=deterministic,
            position_offset=position_offset,
            positions=positions,
        )
        # MoE pad exclusion, independent of any attention-mask override.
        token_valid = (
            trg_tokens != self.cfg.pad_id if self.cfg.moe_experts > 0 else None
        )
        # Remat only on the training path: the decode cache is a mutable
        # variable collection, which jax.checkpoint cannot rewind (and the
        # paged/sow serving paths use keyword args remat can't thread).
        layer_cls = (
            nn.remat(DecoderLayer, static_argnums=(7, 8, 9))
            if self.cfg.remat and not decode and paged is None
            and not sow_mem_kv
            else DecoderLayer
        )
        for i in range(self.cfg.num_layers):
            layer_kw = {}
            if paged is not None:
                # Each layer owns one [2, num_pages, page, d] plane of
                # each page store. Self- and cross-attention address
                # *separate* stores: the self store is the decode loop's
                # scan carry (small — grows with generated tokens), the
                # mem store holds prompt cross-KV and is read-only during
                # decode, so it never rides a carry or gets copied.
                layer_kw = dict(
                    paged_self=dict(
                        k_pages=paged["self_pages"][i, 0],
                        v_pages=paged["self_pages"][i, 1],
                        table=paged["self_table"],
                        length=paged["self_len"],
                    ),
                    paged_mem=dict(
                        k_pages=paged["mem_pages"][i, 0],
                        v_pages=paged["mem_pages"][i, 1],
                        table=paged["mem_table"],
                        length=paged["mem_len"],
                    ),
                )
                # Quantized stores ship per-slot dequantization scales
                # ([layers, 2, num_pages, page]) alongside the int8
                # payload; each attention site gets its layer's k/v plane.
                if paged.get("self_scales") is not None:
                    layer_kw["paged_self"]["k_scale"] = (
                        paged["self_scales"][i, 0]
                    )
                    layer_kw["paged_self"]["v_scale"] = (
                        paged["self_scales"][i, 1]
                    )
                if paged.get("mem_scales") is not None:
                    layer_kw["paged_mem"]["k_scale"] = (
                        paged["mem_scales"][i, 0]
                    )
                    layer_kw["paged_mem"]["v_scale"] = (
                        paged["mem_scales"][i, 1]
                    )
            if sow_mem_kv:
                layer_kw["sow_mem_kv"] = True
            y = layer_cls(self.cfg, name=f"layer_{i}")(
                y,
                memory,
                self_mask,
                cross_mask,
                trg_valid,
                memory_valid,
                self_causal,
                decode,
                deterministic,
                token_valid,
                **layer_kw,
            )
        return y


class Transformer(nn.Module):
    """Encoder + Decoder + LM head (C23, ``transformer.py:255-284``).

    ``__call__(src_tokens, trg_tokens)`` builds the three masks from the pad
    id — src self-attn padding, trg causal∧padding, cross (trg queries over
    src keys) — matching the MT driver's mask plumbing
    (``pytorch_machine_translator.py:164-177``) but with the correct
    semantics; explicit masks may be passed to override.
    """

    cfg: TransformerConfig

    def setup(self):
        self.encoder = Encoder(self.cfg)
        self.decoder = Decoder(self.cfg)
        # LM head: d_model → trg vocab (+ TP padding), the reference's
        # Linear(512, |de|) (``transformer.py:271,283``), vocab axis
        # model-sharded under TP.
        self.lm_head = nn.Dense(
            self.cfg.trg_vocab_size + self.cfg.logit_pad,
            dtype=self.cfg.dtype,
            kernel_init=nn.with_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
        )

    def _logits(self, y: jnp.ndarray) -> jnp.ndarray:
        """LM head with the TP vocab padding sliced off."""
        logits = self.lm_head(y)
        if self.cfg.logit_pad:
            logits = logits[..., : self.cfg.trg_vocab_size]
        return logits

    def __call__(
        self,
        src_tokens: jnp.ndarray,
        trg_tokens: jnp.ndarray,
        src_mask: jnp.ndarray | None = None,
        trg_mask: jnp.ndarray | None = None,
        cross_mask: jnp.ndarray | None = None,
        *,
        src_positions: jnp.ndarray | None = None,
        trg_positions: jnp.ndarray | None = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        pad = self.cfg.pad_id
        # Default masks stay *structured* — per-key validity vectors plus a
        # causal flag — so TPU runs stream them through the flash kernel
        # without materializing [B, Sq, Sk] (an explicit dense mask override
        # still takes the fused-XLA path). Sequence packing
        # (``data.packing``) overrides all three masks with block-diagonal
        # segment masks and supplies per-token ``*_positions``.
        src_valid = (src_tokens != pad) if src_mask is None else None
        trg_valid = (trg_tokens != pad) if trg_mask is None else None
        # Cross-attention defaults to masking padded *source* keys whenever
        # the caller did not override cross_mask — independent of whether
        # src_mask was overridden (each attention site keeps its own default).
        memory_valid = (src_tokens != pad) if cross_mask is None else None
        memory = self.encoder(
            src_tokens, src_mask, src_valid, deterministic=deterministic,
            positions=src_positions,
        )
        y = self.decoder(
            trg_tokens,
            memory,
            trg_mask,
            cross_mask,
            trg_valid,
            memory_valid,
            self_causal=trg_mask is None,
            positions=trg_positions,
            deterministic=deterministic,
        )
        return self._logits(y)

    def encode(self, src_tokens, *, deterministic: bool = True):
        return self.encoder(
            src_tokens,
            None,
            src_tokens != self.cfg.pad_id,
            deterministic=deterministic,
        )

    def decode_logits(self, trg_tokens, memory, src_valid):
        """One decoder pass → vocab logits, for the generation loop (no
        dropout; causal + padding via structured masks)."""
        y = self.decoder(
            trg_tokens,
            memory,
            None,
            None,
            trg_tokens != self.cfg.pad_id,
            src_valid,
            self_causal=True,
            deterministic=True,
        )
        return self._logits(y)

    def decode_step(self, token, memory, src_valid, position, trg_valid=None):
        """One incremental step: ``token`` is ``[B, 1]``, self-attention
        K/V come from the mutable ``cache`` collection — O(1) projection
        work per generated token (the KV-cache decoder). ``trg_valid``
        ([B, max_len]) marks which written cache positions hold real (non-
        pad) tokens, mirroring the naive decoder's padding mask."""
        y = self.decoder(
            token,
            memory,
            None,
            None,
            trg_valid,
            src_valid,
            decode=True,
            position_offset=position,
            deterministic=True,
        )
        return self._logits(y)

    def prefill_paged(self, src_tokens):
        """Paged-serving prefill: encode the prompt and project every
        decoder layer's cross-attention K/V over the memory — sown into
        the ``"paged"`` collection (``decoder/layer_i/cross_attn/
        k_mem|v_mem``, each ``[B, S_src, d]``) for the serving runtime to
        scatter into its page store. This is the once-per-sequence work
        the flax decode cache does on its priming call, surfaced so the
        cached K/V can outlive the request (prefix sharing)."""
        src_valid = src_tokens != self.cfg.pad_id
        memory = self.encoder(
            src_tokens, None, src_valid, deterministic=True
        )
        dummy = jnp.full((src_tokens.shape[0], 1), 1, jnp.int32)
        self.decoder(
            dummy, memory, None, None, None, src_valid,
            sow_mem_kv=True, deterministic=True,
        )
        return memory

    def decode_step_paged(
        self, token, self_pages, mem_pages, self_table, self_len,
        mem_table, mem_len, positions,
        self_scales=None, mem_scales=None,
    ):
        """One ragged decode step over the paged KV stores: ``token`` is
        ``[R, 1]`` (one position per request row); ``self_pages`` and
        ``mem_pages`` are ``[layers, 2, num_pages, page, d]`` stores —
        the *self* store holds generated-token K/V (small, mutated every
        step: the decode loop's scan carry), the *mem* store holds the
        prompts' cross-attention K/V (written at prefill, read-only here,
        so the launch program never copies it). The tables/lengths
        address each row's pages in its store, and ``positions``
        (``[R, 1]``) carries each row's own PE index — rows at different
        depths of generation share one program. The step's new
        self-attention K/V are sown into the ``"paged"`` collection
        (``decoder/layer_i/self_attn/k_new|v_new``) for the caller to
        scatter at each row's cursor. Quantized stores (int8 payload)
        pass their per-slot dequantization scales as ``self_scales`` /
        ``mem_scales`` (``[layers, 2, num_pages, page]`` float32);
        ``None`` means that store is full-precision."""
        y = self.decoder(
            token,
            None,
            None,
            None,
            None,
            None,
            paged=dict(
                self_pages=self_pages,
                mem_pages=mem_pages,
                self_table=self_table,
                self_len=self_len,
                mem_table=mem_table,
                mem_len=mem_len,
                self_scales=self_scales,
                mem_scales=mem_scales,
            ),
            positions=positions,
            deterministic=True,
        )
        return self._logits(y)


def greedy_translate(
    model: "Transformer",
    params,
    src_tokens: jnp.ndarray,
    *,
    max_new_tokens: int | None = None,
    sos_id: int = 1,
    eos_id: int = 2,
) -> jnp.ndarray:
    """Greedy decoding for the MT model — the inference path the reference
    never ships (it trains and discards, quirk Q7 / SURVEY.md §5).

    Re-runs the full decoder per emitted token over a fixed-width buffer
    (static shapes; one compile). O(L²) decoder work — the simple faithful
    path; a KV-cache incremental decoder is the documented follow-up.
    Generates exactly ``max_new_tokens`` tokens (default: ``cfg.max_len - 1``)
    after the leading ``sos``; returns ``[B, max_new_tokens + 1]`` int32 ids,
    rows padded after their ``eos``.
    """
    cfg = model.cfg
    pad = cfg.pad_id
    if max_new_tokens is None:
        max_new_tokens = cfg.max_len - 1
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    length = max_new_tokens + 1  # + the sos slot; PE table grows statically
    src_valid = src_tokens != pad
    memory = model.apply(
        {"params": params}, src_tokens, method=Transformer.encode
    )

    ys = jnp.full((src_tokens.shape[0], length), pad, jnp.int32)
    ys = ys.at[:, 0].set(sos_id)
    finished = jnp.zeros(src_tokens.shape[0], bool)

    def step(carry, t):
        ys, finished = carry
        logits = model.apply(
            {"params": params},
            ys,
            memory,
            src_valid,
            method=Transformer.decode_logits,
        )
        nxt = jnp.argmax(logits[:, t, :], axis=-1).astype(jnp.int32)
        nxt = jnp.where(finished, pad, nxt)
        finished = finished | (nxt == eos_id)
        ys = jax.lax.dynamic_update_index_in_dim(ys, nxt, t + 1, axis=1)
        return (ys, finished), None

    (ys, _), _ = jax.lax.scan(step, (ys, finished), jnp.arange(length - 1))
    return ys


def _prime_decode_cache(decode_model, params, memory, src_valid, gen_len, sos_id):
    """Cache-priming call shared by the cached decoders: creates the zeroed
    self-attention K/V buffers AND projects the encoder memory's
    cross-attention K/V once, storing them in the cache. The priming logits
    are discarded; the init trace writes nothing into the self-attention
    cache, so the first real step recomputes sos with identical semantics.
    """
    rows = memory.shape[0]
    _, primed = decode_model.apply(
        {"params": params},
        jnp.full((rows, 1), sos_id, jnp.int32),
        memory,
        src_valid,
        jnp.zeros((), jnp.int32),
        jnp.ones((rows, gen_len), bool),
        method=Transformer.decode_step,
        mutable=["cache"],
    )
    return primed["cache"]


def _validate_max_new_tokens(max_new_tokens, cfg):
    if max_new_tokens is None:
        return cfg.max_len - 1
    if not 1 <= max_new_tokens <= cfg.max_len - 1:
        raise ValueError(
            f"max_new_tokens must be in [1, {cfg.max_len - 1}], got "
            f"{max_new_tokens}"
        )
    return max_new_tokens


def beam_translate(
    model: "Transformer",
    params,
    src_tokens: jnp.ndarray,
    *,
    beam_size: int = 4,
    max_new_tokens: int | None = None,
    length_penalty: float = 0.6,
    sos_id: int = 1,
    eos_id: int = 2,
) -> jnp.ndarray:
    """KV-cache beam search — the inference path the reference never ships,
    taken past greedy.

    TPU-first shape discipline: beams are flat-batched (``B·K`` rows share
    one decode cache), every step is one fused program inside a single
    ``lax.scan`` (top-k over ``K·V``, beam reorder via gather, cache rows
    gathered alongside), and nothing is data-dependently shaped. Finished
    beams extend only with ``pad`` at zero cost; hypothesis selection uses
    the GNMT length penalty ``((5+L)/6)^alpha`` (``length_penalty=0`` scores
    raw log-probs; ``beam_size=1`` reproduces greedy decoding exactly).

    Returns ``[B, max_new_tokens + 1]`` int32 ids (leading ``sos``, rows
    padded after their ``eos``) — the ``greedy_translate`` contract.
    """
    cfg = model.cfg
    pad = cfg.pad_id
    max_new_tokens = _validate_max_new_tokens(max_new_tokens, cfg)
    if beam_size < 1:
        raise ValueError(f"beam_size must be >= 1, got {beam_size}")
    b = src_tokens.shape[0]
    k = beam_size
    gen_len = max_new_tokens + 1
    vocab = cfg.trg_vocab_size

    src_valid = src_tokens != pad
    memory = model.apply(
        {"params": params}, src_tokens, method=Transformer.encode
    )
    # Tile encoder outputs to the flat beam batch: row b*k + j is beam j of
    # sentence b.
    memory = jnp.repeat(memory, k, axis=0)
    src_valid_t = jnp.repeat(src_valid, k, axis=0)

    decode_model = Transformer(dataclasses.replace(cfg, max_len=gen_len))
    cache = _prime_decode_cache(
        decode_model, params, memory, src_valid_t, gen_len, sos_id
    )

    ys = jnp.full((b, k, gen_len), pad, jnp.int32)
    ys = ys.at[:, :, 0].set(sos_id)
    scores = jnp.zeros((b, k), jnp.float32)
    finished = jnp.zeros((b, k), bool)
    lengths = jnp.zeros((b, k), jnp.int32)  # generated tokens incl. eos
    # GNMT-style completed-hypothesis set (capacity 1 — the best): a
    # finished beam can be evicted from the live set by raw-score top-k, so
    # its penalized score/tokens are banked the step it finishes.
    best_score = jnp.full((b,), NEG_INF, jnp.float32)
    best_ys = jnp.full((b, gen_len), pad, jnp.int32)

    def _penalize(score, length):
        return score / ((5.0 + length.astype(jnp.float32)) / 6.0) ** length_penalty

    def reorder_cache(tree, beam_idx):
        def gather(path, leaf):
            # Cross-attention memory K/V (cached_mem_*) are identical across
            # beams of one sentence (tiled from one encode) — gathering them
            # would be pure HBM traffic; scalars (cache_index) likewise ride.
            if any("cached_mem" in str(p) for p in path):
                return leaf
            if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == b * k:
                x = leaf.reshape(b, k, *leaf.shape[1:])
                idx = beam_idx.reshape(b, k, *([1] * (leaf.ndim - 1)))
                x = jnp.take_along_axis(x, idx, axis=1)
                return x.reshape(b * k, *leaf.shape[1:])
            return leaf

        return jax.tree_util.tree_map_with_path(gather, tree)

    def step(carry, t):
        ys, scores, finished, lengths, best_score, best_ys, cache = carry
        token = jax.lax.dynamic_slice_in_dim(ys, t, 1, axis=2)  # [b,k,1]
        logits, updated = decode_model.apply(
            {"params": params, "cache": cache},
            token.reshape(b * k, 1),
            memory,
            src_valid_t,
            t,
            (ys != pad).reshape(b * k, gen_len),
            method=Transformer.decode_step,
            mutable=["cache"],
        )
        logp = jax.nn.log_softmax(
            logits[:, 0, :].astype(jnp.float32), axis=-1
        ).reshape(b, k, vocab)
        # Finished beams extend only with pad, at zero cost.
        pad_only = jnp.full((vocab,), NEG_INF).at[pad].set(0.0)
        logp = jnp.where(finished[:, :, None], pad_only, logp)
        total = scores[:, :, None] + logp  # [b, k, vocab]
        # Step 0: all beams are identical copies of sos — search beam 0 only,
        # or top-k would return k copies of the same hypothesis.
        total = jnp.where(
            (t == 0) & (jnp.arange(k)[None, :, None] > 0), NEG_INF, total
        )
        new_scores, flat_idx = jax.lax.top_k(total.reshape(b, k * vocab), k)
        beam_idx = flat_idx // vocab  # [b, k] which parent beam
        token = (flat_idx % vocab).astype(jnp.int32)

        gathered = lambda x: jnp.take_along_axis(x, beam_idx, axis=1)
        was_finished = gathered(finished)
        ys = jnp.take_along_axis(ys, beam_idx[:, :, None], axis=1)
        ys = jax.lax.dynamic_update_slice_in_dim(
            ys, token[:, :, None], t + 1, axis=2
        )
        lengths = gathered(lengths) + (~was_finished).astype(jnp.int32)
        newly_finished = ~was_finished & (token == eos_id)
        finished = was_finished | (token == eos_id)
        # Bank the best newly finished hypothesis before top-k can evict it.
        cand = jnp.where(newly_finished, _penalize(new_scores, lengths), NEG_INF)
        cand_beam = jnp.argmax(cand, axis=1)  # [b]
        cand_score = jnp.take_along_axis(cand, cand_beam[:, None], axis=1)[:, 0]
        cand_ys = jnp.take_along_axis(
            ys, cand_beam[:, None, None], axis=1
        )[:, 0, :]
        better = cand_score > best_score
        best_score = jnp.where(better, cand_score, best_score)
        best_ys = jnp.where(better[:, None], cand_ys, best_ys)
        cache = reorder_cache(updated["cache"], beam_idx)
        return (
            ys, new_scores, finished, lengths, best_score, best_ys, cache
        ), None

    (ys, scores, finished, lengths, best_score, best_ys, _), _ = jax.lax.scan(
        step,
        (ys, scores, finished, lengths, best_score, best_ys, cache),
        jnp.arange(max_new_tokens),
    )

    # Selection: the banked best finished hypothesis wins when one exists
    # (every finished beam was banked the step it finished, so none is ever
    # lost to eviction); otherwise the best live beam by penalized score.
    live_best = jnp.argmax(_penalize(scores, lengths), axis=1)  # [b]
    live_ys = jnp.take_along_axis(ys, live_best[:, None, None], axis=1)[:, 0, :]
    use_banked = best_score > NEG_INF * 0.5
    return jnp.where(use_banked[:, None], best_ys, live_ys)


def _cached_decode(
    model: "Transformer",
    params,
    src_tokens: jnp.ndarray,
    select_next,
    *,
    max_new_tokens: int | None,
    sos_id: int,
    eos_id: int,
) -> jnp.ndarray:
    """Shared KV-cache decode loop: encode once, prime the cache, then scan
    one-token decoder steps; ``select_next(logits[B, V], t) -> [B] int32``
    is the only policy difference between the greedy and sampling decoders.

    Each step runs the decoder stack on only the new token, appending its
    self-attention K/V to a mutable cache — O(1) decoder work per token vs
    the O(L) full re-decode of ``greedy_translate``. Cross-attention K/V
    over the encoder memory are projected once, on the priming call.
    """
    cfg = model.cfg
    pad = cfg.pad_id
    max_new_tokens = _validate_max_new_tokens(max_new_tokens, cfg)
    b = src_tokens.shape[0]
    src_valid = src_tokens != pad
    memory = model.apply(
        {"params": params}, src_tokens, method=Transformer.encode
    )
    # Cache buffers sized to the generation length, not cfg.max_len — the
    # params are max_len-independent, so a config-shrunk twin of the model
    # right-sizes every layer's K/V cache (and each step's attention span).
    gen_len = max_new_tokens + 1
    decode_model = Transformer(dataclasses.replace(cfg, max_len=gen_len))
    cache = _prime_decode_cache(
        decode_model, params, memory, src_valid, gen_len, sos_id
    )

    ys = jnp.full((b, gen_len), pad, jnp.int32)
    ys = ys.at[:, 0].set(sos_id)
    finished = jnp.zeros(b, bool)

    def step(carry, t):
        ys, finished, cache = carry
        token = jax.lax.dynamic_slice_in_dim(ys, t, 1, axis=1)
        logits, updated = decode_model.apply(
            {"params": params, "cache": cache},
            token,
            memory,
            src_valid,
            t,
            ys != pad,  # pad tokens in the prefix stay unattendable (naive parity)
            method=Transformer.decode_step,
            mutable=["cache"],
        )
        nxt = select_next(logits[:, 0, :], t).astype(jnp.int32)
        nxt = jnp.where(finished, pad, nxt)
        finished = finished | (nxt == eos_id)
        ys = jax.lax.dynamic_update_index_in_dim(ys, nxt, t + 1, axis=1)
        return (ys, finished, updated["cache"]), None

    (ys, _, _), _ = jax.lax.scan(
        step, (ys, finished, cache), jnp.arange(max_new_tokens)
    )
    return ys


def greedy_translate_cached(
    model: "Transformer",
    params,
    src_tokens: jnp.ndarray,
    *,
    max_new_tokens: int | None = None,
    sos_id: int = 1,
    eos_id: int = 2,
) -> jnp.ndarray:
    """KV-cache greedy decoding — ``_cached_decode`` with an argmax policy.
    Same output contract as ``greedy_translate``."""
    return _cached_decode(
        model, params, src_tokens,
        lambda logits, t: jnp.argmax(logits, axis=-1),
        max_new_tokens=max_new_tokens, sos_id=sos_id, eos_id=eos_id,
    )


def _filter_logits(
    logits: jnp.ndarray, temperature: float, top_k: int | None, top_p: float | None
) -> jnp.ndarray:
    """Sampling filters over ``[B, V]`` logits: temperature scaling, then
    top-k truncation, then nucleus (top-p) truncation — masked-out entries
    become NEG_INF so ``jax.random.categorical`` never selects them."""
    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
    if top_k is not None:
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        # top_k >= vocab keeps everything (not an error — mirrors the
        # temperature-only case).
        kth = jax.lax.top_k(logits, min(top_k, logits.shape[-1]))[0][..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p is not None:
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # Keep the smallest prefix whose mass reaches top_p (the first token
        # always survives: its exclusive cumulative mass is 0 < top_p).
        exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
        keep = exclusive_cum < top_p
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, NEG_INF, logits)
    return logits


def sample_translate(
    model: "Transformer",
    params,
    src_tokens: jnp.ndarray,
    rng: jax.Array,
    *,
    max_new_tokens: int | None = None,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    sos_id: int = 1,
    eos_id: int = 2,
) -> jnp.ndarray:
    """Stochastic decoding with temperature / top-k / nucleus filtering —
    ``_cached_decode`` with a filtered-categorical policy (O(1) decoder work
    per token). ``temperature=0`` degrades to greedy argmax. Same output
    contract as the greedy decoders: ``[B, max_new_tokens + 1]`` int32 ids,
    ``sos``-led, rows padded after their ``eos``.
    """
    # Validate filter args eagerly and uniformly (the greedy temperature=0
    # branch must reject bad top_k/top_p exactly like the sampling branch).
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if temperature <= 0.0:  # static: resolved at trace time
        select = lambda logits, t: jnp.argmax(logits, axis=-1)
    else:
        def select(logits, t):
            filtered = _filter_logits(logits, temperature, top_k, top_p)
            return jax.random.categorical(jax.random.fold_in(rng, t), filtered)

    return _cached_decode(
        model, params, src_tokens, select,
        max_new_tokens=max_new_tokens, sos_id=sos_id, eos_id=eos_id,
    )
