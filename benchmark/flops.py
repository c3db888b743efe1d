"""Operations and bytes the algorithm needs, from a configuration's widths.

Only matrix-unit work is counted (projections, attention products, FFN,
output head); embedding look-ups, softmax, LayerNorm and the optimizer are
left out, and so is anything recomputed in the backward pass. A causal
attention is counted at half of its square (what the mask leaves), so every
share computed from these counts errs low, never over 100 %.

Generalised from ``bench.py:transformer_train_flops_per_step`` (sound
arithmetic, hard-wired to the reference widths, causal counted whole); the
original stays in the program and is listed in PERF.md for deletion.
"""

from __future__ import annotations


def _proj(tokens: float, d_in: int, d_out: int) -> float:
    return 2.0 * tokens * d_in * d_out


def encoder_forward_flops(cfg: dict, s: float) -> float:
    """One sequence of ``s`` source positions through the encoder."""
    d, f = cfg["d_model"], cfg["ffn_hidden"]
    layer = (
        _proj(s, d, 3 * d) + _proj(s, d, d)        # qkv, out
        + 2 * 2.0 * s * s * d                      # scores, weighted values
        + _proj(s, d, f) + _proj(s, f, d)          # ffn
    )
    return cfg["num_layers"] * layer


def decoder_forward_flops(cfg: dict, s: float, t: float) -> float:
    """One sequence of ``t`` target positions, teacher-forced, over ``s``
    memory positions, with the output head."""
    d, f = cfg["d_model"], cfg["ffn_hidden"]
    layer = (
        _proj(t, d, 3 * d) + _proj(t, d, d)
        + 2 * 2.0 * t * t * d / 2                  # causal: half the square
        + _proj(t, d, d) + _proj(s, d, 2 * d) + _proj(t, d, d)
        + 2 * 2.0 * t * s * d
        + _proj(t, d, f) + _proj(t, f, d)
    )
    return cfg["num_layers"] * layer + _proj(t, d, cfg["trg_vocab_size"])


def train_step_flops(cfg: dict, rows: int, s: int, t: int) -> float:
    """Forward + backward (2x forward) of one step over ``rows`` pairs."""
    fwd = encoder_forward_flops(cfg, s) + decoder_forward_flops(cfg, s, t)
    return 3.0 * rows * fwd


def prefill_flops(cfg: dict, s: int) -> float:
    """Serving one prompt of ``s`` positions: the encoder and every decoder
    layer's cross-attention K/V projection of the memory."""
    d = cfg["d_model"]
    return encoder_forward_flops(cfg, s) + cfg["num_layers"] * _proj(s, d, 2 * d)


def decode_token_flops(cfg: dict, s: float, t: float) -> float:
    """One decode step of one row at target position ``t`` (``t`` cached
    positions attended, itself included) over ``s`` memory positions."""
    d, f = cfg["d_model"], cfg["ffn_hidden"]
    layer = (
        _proj(1, d, 3 * d) + _proj(1, d, d) + 2 * 2.0 * t * d
        + _proj(1, d, d) + _proj(1, d, d) + 2 * 2.0 * s * d
        + _proj(1, d, f) + _proj(1, f, d)
    )
    return cfg["num_layers"] * layer + _proj(1, d, cfg["trg_vocab_size"])


def request_flops(cfg: dict, s: int, new_tokens: int) -> float:
    """Prefill plus ``new_tokens`` decode steps of one request."""
    steps = sum(decode_token_flops(cfg, s, t) for t in range(1, new_tokens + 1))
    return prefill_flops(cfg, s) + steps


def flash_forward_cost(
    rows: int, heads: int, sq: int, sk: int, head_dim: int, *,
    causal: bool, itemsize: int = 2,
) -> tuple[float, float]:
    """(FLOPs, bytes) one flash-attention forward needs: the two products
    (halved under a causal mask) and one read of Q, K, V plus one write of
    the output."""
    flops = 2 * 2.0 * rows * heads * sq * sk * head_dim
    if causal:
        flops /= 2
    elems = rows * heads * head_dim * (2 * sq + 2 * sk)
    return flops, float(elems * itemsize)


def train_flash_forward_cost(cfg: dict, rows: int, s: int, t: int):
    """The three flash forwards of one train step's forward pass per layer:
    encoder self, decoder self (causal), decoder cross."""
    h = cfg["num_heads"]
    dh = cfg["d_model"] // h
    sites = [
        flash_forward_cost(rows, h, s, s, dh, causal=False),
        flash_forward_cost(rows, h, t, t, dh, causal=True),
        flash_forward_cost(rows, h, t, s, dh, causal=False),
    ]
    n = cfg["num_layers"]
    return n * sum(f for f, _ in sites), n * sum(b for _, b in sites)
