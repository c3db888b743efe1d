"""Language-model recipe — next-token training of ``models.hybrid_lm``.

The reference has no decoder-only workload; this recipe is the zoo's fifth,
shaped like the other four: data resolution (a ``.npy`` of token rows if
given, seeded synthetic rows otherwise), model, optimizer, ``fit``, eval.
Rows are full: every position is a real token, position ``t`` is scored
against token ``t + 1``, and nothing is padded or packed.

The loss is the mean next-token cross-entropy plus ``router_aux_weight``
times the expert layers' load-balance term; the step metrics carry
``moe_aux``, ``moe_tokens_held_mean``, ``moe_tokens_held_max``,
``moe_assignments_local`` and ``moe_assignments_computed`` (the router's
choices that name a held expert, and the rows the grouped products were
given: equal every step, nothing is dropped).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from machine_learning_apache_spark_tpu.data import ArrayDataset
from machine_learning_apache_spark_tpu.models.hybrid_lm import (
    HybridLM,
    HybridLMConfig,
)
from machine_learning_apache_spark_tpu.recipes._common import (
    checkpointing,
    default_compute_dtype,
    make_loaders,
    resolve_mesh,
    summarize,
    with_overrides,
)
from machine_learning_apache_spark_tpu.train.loop import evaluate, fit
from machine_learning_apache_spark_tpu.train.state import TrainState, make_optimizer


@dataclass
class LMRecipe:
    """Defaults are a small model of the published block shape (three
    linear-attention layers to one full-attention layer, sparse experts in
    every layer); the benchmark's configuration file holds the real widths."""

    vocab_size: int = 512
    seq_len: int = 128
    hidden_size: int = 128
    num_layers: int = 4
    full_attention_interval: int = 4
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_key_heads: int = 2
    linear_value_heads: int = 4
    linear_key_dim: int = 32
    linear_value_dim: int = 32
    linear_conv_kernel: int = 4
    num_experts: int = 16
    experts_per_token: int = 4
    experts_held: tuple[int, int] | None = None
    expert_hidden: int = 64
    shared_expert_hidden: int = 64
    router_aux_weight: float = 0.001
    remat: bool = True
    epochs: int = 1
    learning_rate: float = 3e-4
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    batch_size: int = 8
    seed: int = 0
    data_path: str | None = None  # .npy of int token rows [N, >= seq_len + 1]
    synthetic_n: int = 256
    use_mesh: bool = True
    log_every: int = 0
    dtype: str | None = None  # None -> bfloat16 on TPU, float32 elsewhere
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1
    resume: bool = True
    metrics_path: str | None = None
    prefetch_to_device: int = 2

    def model_config(self) -> HybridLMConfig:
        return HybridLMConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers,
            full_attention_interval=self.full_attention_interval,
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            partial_rotary_factor=self.partial_rotary_factor,
            rope_theta=self.rope_theta,
            linear_key_heads=self.linear_key_heads,
            linear_value_heads=self.linear_value_heads,
            linear_key_dim=self.linear_key_dim,
            linear_value_dim=self.linear_value_dim,
            linear_conv_kernel=self.linear_conv_kernel,
            num_experts=self.num_experts,
            experts_per_token=self.experts_per_token,
            experts_held=self.experts_held, expert_hidden=self.expert_hidden,
            shared_expert_hidden=self.shared_expert_hidden,
            router_aux_weight=self.router_aux_weight, remat=self.remat,
            dtype=default_compute_dtype(self.dtype),
        )


def make_lm_loss(model: HybridLM):
    """``loss_fn(params, batch, rng)`` for ``fit``: ``batch`` is token rows
    ``[B, S + 1]`` (or a 1-tuple of them); the first ``S`` are the inputs,
    the last ``S`` the labels."""
    aux_weight = model.cfg.router_aux_weight

    def loss_fn(params, batch, rng):  # noqa: ARG001  (no dropout: no rng use)
        tokens = batch[0] if isinstance(batch, (tuple, list)) else batch
        (total, count), stats = model.apply(
            {"params": params}, tokens[:, :-1], tokens[:, 1:]
        )
        loss = total / count + aux_weight * stats["aux"]
        return loss, {"moe_" + k: v for k, v in stats.items()}

    return loss_fn


def synthetic_token_rows(n: int, length: int, vocab_size: int, seed: int):
    """``n`` rows of ``length`` token ids with something to learn: each row
    counts up from a random start by a stride drawn from four values."""
    rng = np.random.default_rng(seed)
    start = rng.integers(0, vocab_size, (n, 1))
    stride = rng.choice([1, 2, 3, 5], (n, 1))
    return ((start + stride * np.arange(length)[None, :]) % vocab_size).astype(
        np.int32
    )


def train_lm(
    recipe: LMRecipe | None = None, *, _return_state: bool = False, **overrides
) -> dict:
    """Run the language-model workload end to end; returns the metric dict."""
    r = with_overrides(recipe or LMRecipe(), overrides)
    if r.data_path:
        rows = np.load(r.data_path).astype(np.int32)[:, : r.seq_len + 1]
        if rows.shape[1] < r.seq_len + 1 or rows.max() >= r.vocab_size:
            raise ValueError(
                f"{r.data_path}: need rows of {r.seq_len + 1} ids under "
                f"{r.vocab_size}, got shape {rows.shape}, max {rows.max()}"
            )
    else:
        rows = synthetic_token_rows(
            r.synthetic_n, r.seq_len + 1, r.vocab_size, r.seed
        )
    n_val = max(len(rows) // 8, 1)
    train_ds, val_ds = ArrayDataset(rows[n_val:]), ArrayDataset(rows[:n_val])
    mesh = resolve_mesh(r.use_mesh)
    train_loader, val_loader = make_loaders(
        train_ds, val_ds, batch_size=r.batch_size, mesh=mesh, seed=r.seed
    )
    model = HybridLM(r.model_config())
    params = model.init(jax.random.key(r.seed), rows[:1, :-1])["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params,
        tx=make_optimizer("adam", r.learning_rate, b1=r.adam_b1, b2=r.adam_b2),
    )
    loss_fn = make_lm_loss(model)
    with checkpointing(
        r.checkpoint_dir, state, resume=r.resume
    ) as (ckpt, state, resumed):
        result = fit(
            state, loss_fn, train_loader, epochs=r.epochs,
            rng=jax.random.key(r.seed), mesh=mesh, log_every=r.log_every,
            checkpointer=ckpt, checkpoint_every=r.checkpoint_every,
            metrics_file=r.metrics_path,
            prefetch_to_device=r.prefetch_to_device,
        )
    metrics = evaluate(result.state, loss_fn, val_loader, mesh=mesh)
    extra = {"resumed_from_step": resumed} if resumed is not None else {}
    out = summarize(result, metrics, metrics_path=r.metrics_path, **extra)
    if _return_state:
        out["state"] = result.state
    return out
