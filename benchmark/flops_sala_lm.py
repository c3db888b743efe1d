"""Operations and bytes the MiniCPM-SALA block needs when served, from a
configuration's widths: the same whatever implements them.

Only matrix-unit work is counted (projections, the MLP, the head, the
attention products); norms, gates, softmax, rotary and the top-k are left
out, so every share computed from these counts errs low, never over 100 %.

A token through the stack, whatever its position: ``linear_token_flops``. On
top of that, by position:

- a sparse layer's **decode step** at context ``t`` (positions 0..t-1 cached):
  the selector scores every unit mean (``t / stride`` units, each head of a
  group's 16, ``2 d`` operations) and the attention reads the ``topk`` (or
  fewer) selected blocks' K and V (``2 * 2 * heads * tokens * d``). Bytes: the
  unit means of the context, K and V of the selected blocks (a KV head's
  blocks are read once for its 16 query heads), written K/V of one position;
- a lightning layer's **decode step**: the outer product and the output
  product (``2 * 2 * heads * d * d``). Its state's bytes (``[heads, d, d]``
  float32 read and written once a step) are not counted here: no share of a
  roofline reads them, because the compiled launch moves the state planes in
  copies that overlap other layers' work, so no time is the layer's alone
  (PERF.md, Open questions).
"""

from __future__ import annotations

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _kinds(cfg: dict) -> list[str]:
    return list(cfg["mixer_types"][: cfg["num_layers"]])


def linear_token_flops(cfg: dict) -> float:
    """Projections, MLP and head of one token (2 operations a parameter)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    gd = cfg["num_key_value_heads"] * cfg["head_dim"]
    ld = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    mlp = 3 * d * f
    per = {SPARSE: 3 * d * hd + 2 * d * gd + mlp, LIGHTNING: 5 * d * ld + mlp}
    return 2.0 * (sum(per[k] for k in _kinds(cfg)) + d * cfg["vocab_size"])


def attended_positions(cfg: dict, context: float) -> float:
    """Key positions a sparse query at ``context`` cached positions attends."""
    s = cfg["sparse_config"]
    return min(context, s["topk"] * s["block_size"])


def sparse_step_cost(cfg: dict, context: float, itemsize: int = 2):
    """(FLOPs, bytes) of one row's decode step in ONE sparse layer."""
    s = cfg["sparse_config"]
    h, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    units = context / s["kernel_stride"]
    attended = attended_positions(cfg, context)
    flops = 2.0 * h * units * d + 2 * 2.0 * h * attended * d
    bytes_ = itemsize * g * d * (units + 2 * attended + 2)
    return flops, bytes_


def lightning_step_flops(cfg: dict) -> float:
    """FLOPs of one row's decode step in ONE lightning layer."""
    h, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return 2 * 2.0 * h * d * d


def sparse_launch_cost(cfg: dict, contexts, steps: int):
    """(FLOPs, bytes) the sparse attention of a launch of ``steps`` steps
    over rows at ``contexts`` needs, every sparse layer counted."""
    n_sparse = _kinds(cfg).count(SPARSE)
    flops = bytes_ = 0.0
    for c in contexts:
        for k in range(steps):
            f, b = sparse_step_cost(cfg, c + k)
            flops, bytes_ = flops + f, bytes_ + b
    return n_sparse * flops, n_sparse * bytes_


def request_flops(cfg: dict, prompt: int, resumed: int, new_tokens: int) -> float:
    """Operations serving one request needs: its prompt's positions past the
    resumed prefix and ``new_tokens`` decode steps, attention included (a
    prefill position's attention counted as a decode step's at its context).
    The head is counted for the decode steps only."""
    kinds = _kinds(cfg)
    n_sparse, n_light = kinds.count(SPARSE), kinds.count(LIGHTNING)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    lf = lightning_step_flops(cfg)
    prefilled = max(prompt - 1 - resumed, 0)
    total = prefilled * (linear_token_flops(cfg) - head) + new_tokens * linear_token_flops(cfg)
    mean_context = resumed + prefilled / 2  # of the prefilled positions
    total += prefilled * (
        n_sparse * sparse_step_cost(cfg, mean_context)[0] + n_light * lf
    )
    for k in range(new_tokens):
        total += n_sparse * sparse_step_cost(cfg, prompt + k)[0] + n_light * lf
    return total
