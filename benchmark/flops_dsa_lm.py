"""Operations and bytes the DeepSeek-V3.2-Exp block needs when served, from a
configuration's widths: the same whatever implements them.

Only matrix-unit work is counted (projections, the absorbed attention's
products, the index scores, the experts, the head); norms, softmax, rotary,
the router's sort and the selection are left out, so every share computed
from these counts errs low.

A token through the stack, whatever its position: ``linear_token_flops``
(the routed experts at the share of assignments this chip expects, ``k
held / E`` a token). On top of that, a layer's **decode step** at context
``t`` (positions 0..t, the new one written):

- MLA (``mla_step_cost``): the ``min(index_topk, t + 1)`` selected latent
  rows read once (``kv_rank + rope`` values, 1,152 B), and for each of the
  ``heads`` the scores over ``kv_rank + rope`` and the output over
  ``kv_rank``: ``2 * heads * rows * (2 kv_rank + rope)`` operations;
- the indexer (``index_step_cost``): every position's index key read once
  (256 B) and ``2 * index_heads * index_head_dim`` operations a position;
- the held experts (``experts_cost``): each expert with an assignment read
  once (its three matrices, 88 MB), ``2 * 3 * d * expert_hidden``
  operations an assignment.
"""

from __future__ import annotations


def linear_token_flops(cfg: dict) -> float:
    """Projections, feed-forward and head of one token (2 operations a
    parameter it reads)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    fe = cfg["moe_intermediate_size"]
    mla = d * ql + ql * h * (dn + dr) + d * (kvl + dr) + kvl * h * (dn + dv) + h * dv * d
    index = ql * hi * di + d * di + d * hi
    dense = 3 * d * cfg["intermediate_size"]
    held = cfg["experts_held"][1]
    routed = cfg["num_experts_per_tok"] * held / cfg["router_width"]
    expert = d * cfg["router_width"] + 3 * d * fe * (cfg["n_shared_experts"] + routed)
    layers = cfg["num_layers"]
    first = cfg["first_k_dense_replace"]
    per = layers * (mla + index) + first * dense + (layers - first) * expert
    return 2.0 * (per + d * cfg["vocab_size"])


def mla_step_cost(cfg: dict, context: float, itemsize: int = 2):
    """(FLOPs, bytes) of one row's decode step in ONE layer's attention over
    the latent store."""
    rows = min(context + 1, cfg["index_topk"])
    kvl, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    flops = 2.0 * cfg["num_attention_heads"] * rows * (2 * kvl + dr)
    return flops, float(itemsize * rows * (kvl + dr))


def index_step_cost(cfg: dict, context: float, itemsize: int = 2):
    """(FLOPs, bytes) of one row's decode step in ONE layer's indexer."""
    positions = context + 1
    di = cfg["index_head_dim"]
    return 2.0 * cfg["index_n_heads"] * di * positions, float(itemsize * di * positions)


def experts_cost(cfg: dict, touched: float, assignments: float, itemsize: int = 2):
    """(FLOPs, bytes) of the held experts: ``touched`` expert reads and
    ``assignments`` token-expert products."""
    per = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return 2.0 * per * assignments, float(itemsize * per * touched)


def launch_cost(cfg: dict, step_cost, contexts, steps: int):
    """(FLOPs, bytes) of ``step_cost`` over a launch of ``steps`` steps over
    rows at ``contexts``, every layer counted."""
    flops = bytes_ = 0.0
    for c in contexts:
        for k in range(steps):
            f, b = step_cost(cfg, c + k)
            flops, bytes_ = flops + f, bytes_ + b
    layers = cfg["num_layers"]
    return layers * flops, layers * bytes_


def request_flops(cfg: dict, prompt: int, resumed: int, new_tokens: int) -> float:
    """Operations serving one request needs: its prompt's positions past the
    resumed prefix and ``new_tokens`` decode steps, attention and indexer
    included (a prefill position's counted as a decode step's at its
    context). The head is counted for the decode steps only."""
    layers = cfg["num_layers"]
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]

    def attention(context):
        return layers * (mla_step_cost(cfg, context)[0] + index_step_cost(cfg, context)[0])

    prefilled = max(prompt - 1 - resumed, 0)
    total = prefilled * (linear_token_flops(cfg) - head) + new_tokens * linear_token_flops(cfg)
    total += prefilled * attention(resumed + prefilled / 2)
    for k in range(new_tokens):
        total += attention(prompt - 1 + k)
    return total
