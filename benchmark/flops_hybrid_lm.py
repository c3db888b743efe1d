"""Operations and bytes the hybrid language model needs, from a
configuration's widths and independent of how the program computes them.

As ``flops.py``: only matrix-unit work is counted (projections, the
recurrence, attention products, experts, output head); norms, the depthwise
convolution, softmax, the router's top-k, the sort and the optimizer are left
out, and so is anything recomputed (rematerialised layers, a kernel's
recomputed scores). Causal attention is counted at half its square. Routed
experts are counted by the assignments that were local, not by a buffer's
size. Every share computed from these counts errs low.
"""

from __future__ import annotations

from benchmark import flops


def is_full_attention(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def layer_kinds(cfg: dict) -> tuple[int, int]:
    """(linear-attention layers, full-attention layers) of the depth run."""
    full = sum(is_full_attention(cfg, i) for i in range(cfg["num_layers"]))
    return cfg["num_layers"] - full, full


def gdn_projection_flops(cfg: dict) -> float:
    """One token through a Gated DeltaNet mixer's three projections."""
    d = cfg["hidden_size"]
    key_dim = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value_dim = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return 2.0 * d * (
        2 * key_dim + 2 * value_dim + 2 * cfg["linear_num_value_heads"]
    ) + 2.0 * value_dim * d


def gdn_scan_flops(cfg: dict) -> float:
    """One token through the recurrence of one layer: decayed state times
    key, the rank-one write, state times query: 6 dk dv a value head."""
    return 6.0 * (
        cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
        * cfg["linear_value_head_dim"]
    )


def gdn_scan_bytes(cfg: dict, itemsize: int = 2) -> float:
    """One token, one direction: q and k a key head, v read and o written a
    value head in the compute type, g and beta a value head in float32."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    return (
        itemsize * (2 * hk * cfg["linear_key_head_dim"]
                    + 2 * hv * cfg["linear_value_head_dim"])
        + 4 * 2 * hv
    )


def attention_projection_flops(cfg: dict) -> float:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2.0 * d * (2 * h * dh + 2 * hkv * dh) + 2.0 * h * dh * d


def attention_flops(cfg: dict, s: int) -> float:
    """One token's share of causal attention over ``s`` positions: two
    products over half the square."""
    return 2 * 2.0 * s * cfg["head_dim"] * cfg["num_attention_heads"] / 2


def layer_dense_flops(cfg: dict) -> float:
    """What every layer does for every token outside the routed experts:
    router, shared expert and its gate."""
    d = cfg["hidden_size"]
    return (
        2.0 * d * cfg["router_width"]
        + 3 * 2.0 * d * cfg["shared_expert_intermediate_size"] + 2.0 * d
    )


def expert_assignment_flops(cfg: dict) -> float:
    """One (token, expert) assignment through one routed expert."""
    return 3 * 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    """One read of the held experts' matrices of every layer."""
    return float(
        itemsize * cfg["num_layers"] * cfg["experts_held"][1] * 3
        * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    )


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def forward_flops_per_token(cfg: dict, s: int, local_assignments_per_token: float):
    """One token's forward pass; ``local_assignments_per_token`` is summed
    over the layers (what the step's counter reads, over the tokens)."""
    linear, full = layer_kinds(cfg)
    return (
        linear * (gdn_projection_flops(cfg) + gdn_scan_flops(cfg))
        + full * (attention_projection_flops(cfg) + attention_flops(cfg, s))
        + cfg["num_layers"] * layer_dense_flops(cfg)
        + local_assignments_per_token * expert_assignment_flops(cfg)
        + head_flops(cfg)
    )


def train_step_flops(cfg: dict, rows: int, s: int, local_assignments: float):
    """Forward + backward (2x forward) of one step over ``rows`` rows of
    ``s`` positions; ``local_assignments`` a step, summed over layers."""
    tokens = rows * s
    return 3.0 * tokens * forward_flops_per_token(
        cfg, s, local_assignments / tokens
    )


def scan_cost_per_step(cfg: dict, rows: int, s: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the recurrence of every linear layer, forward and
    backward: the backward needs twice the forward's products and the same
    operands again."""
    linear, _ = layer_kinds(cfg)
    tokens = rows * s
    return (
        3.0 * tokens * linear * gdn_scan_flops(cfg),
        2.0 * tokens * linear * gdn_scan_bytes(cfg),
    )


def experts_cost_per_step(cfg: dict, local_assignments: float):
    """(FLOPs, bytes) of the routed experts, forward and backward: the
    local assignments' products and one read of the held experts' weights a
    direction."""
    return (
        3.0 * local_assignments * expert_assignment_flops(cfg),
        2.0 * expert_weight_bytes(cfg),
    )


def flash_cost_per_step(cfg: dict, rows: int, s: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the full-attention layers' kernels, forward and
    backward: the forward's two products and the backward's four (the
    recomputed scores are not needed work), operands counted once a
    direction."""
    _, full = layer_kinds(cfg)
    f, b = flops.flash_forward_cost(
        rows, cfg["num_attention_heads"], s, s, cfg["head_dim"], causal=True
    )
    return 3.0 * full * f, 2.0 * full * b
