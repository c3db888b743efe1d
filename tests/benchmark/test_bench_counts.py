"""The benchmark's own arithmetic: FLOP and byte counts against hand-worked
values for both configurations, the parameter count, the peaks table."""

import json
import os

import pytest

from benchmark import flops, manifest, peaks, weights


def _cfg(name):
    with open(os.path.join(manifest.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_reference_train_step_flops_by_hand():
    cfg = _cfg("multi30k_ref_mt")
    # One row, s = t = 200, d = 512, ffn = 1024, vocabulary 10,240.
    enc = 314_572_800 + 104_857_600 + 81_920_000 + 419_430_400
    dec = (314_572_800 + 104_857_600 + 40_960_000        # self (causal: half)
           + 104_857_600 + 209_715_200 + 104_857_600     # cross q, kv, out
           + 81_920_000 + 419_430_400)                   # cross scores, ffn
    head = 2 * 200 * 512 * 10_240
    assert enc == 920_780_800 and dec == 1_381_171_200
    assert flops.encoder_forward_flops(cfg, 200) == enc
    assert flops.decoder_forward_flops(cfg, 200, 200) == dec + head
    assert flops.train_step_flops(cfg, 512, 200, 200) == 3 * 512 * (enc + dec + head)
    per_token = flops.train_step_flops(cfg, 512, 200, 200) / (512 * 200)
    assert per_token == pytest.approx(65_986_560)
    assert head / (enc + dec + head) == pytest.approx(0.4767, abs=1e-3)


def test_big_train_step_flops_by_hand():
    cfg = _cfg("vaswani_big_ende")
    s = t = 256
    d, f, v, n = 1024, 4096, 37_000, 6
    enc = n * (2 * s * d * 3 * d + 2 * s * d * d + 4 * s * s * d + 4 * s * d * f)
    dec = n * (2 * t * d * 3 * d + 2 * t * d * d + 2 * t * t * d
               + 2 * t * d * d + 2 * s * d * 2 * d + 2 * t * d * d
               + 4 * t * s * d + 4 * t * d * f)
    head = 2 * t * d * v
    assert flops.train_step_flops(cfg, 96, s, t) == 3 * 96 * (enc + dec + head)
    assert flops.train_step_flops(cfg, 96, s, t) / (96 * t) == pytest.approx(
        1.3315e9, rel=1e-3
    )


def test_decode_token_and_prefill_flops_by_hand():
    cfg = _cfg("vaswani_big_ende")
    d, f, v, n = 1024, 4096, 37_000, 6
    s, t = 30, 16
    layer = (2 * d * 3 * d + 2 * d * d + 4 * t * d        # self
             + 2 * d * d + 2 * d * d + 4 * s * d          # cross
             + 4 * d * f)
    assert flops.decode_token_flops(cfg, s, t) == n * layer + 2 * d * v
    assert flops.decode_token_flops(cfg, s, t) == pytest.approx(253.07e6, rel=1e-3)
    enc = n * (2 * s * d * 3 * d + 2 * s * d * d + 4 * s * s * d + 4 * s * d * f)
    assert flops.prefill_flops(cfg, s) == enc + n * 2 * s * d * 2 * d
    total = flops.request_flops(cfg, s, 32)
    assert total == flops.prefill_flops(cfg, s) + sum(
        flops.decode_token_flops(cfg, s, k) for k in range(1, 33)
    )


def test_flash_forward_cost_by_hand():
    # 512 rows x 8 heads of 64, 200 x 200, bf16.
    f, b = flops.flash_forward_cost(512, 8, 200, 200, 64, causal=False)
    assert f == 4 * 512 * 8 * 200 * 200 * 64
    assert b == 512 * 8 * 64 * 800 * 2
    fc, _ = flops.flash_forward_cost(512, 8, 200, 200, 64, causal=True)
    assert fc == f / 2
    cfg = _cfg("multi30k_ref_mt")
    total_f, total_b = flops.train_flash_forward_cost(cfg, 512, 200, 200)
    assert total_f == 2.5 * f and total_b == 3 * b
    # Against the chip's peaks on paper: the three forwards are bound by
    # compute (0.53 ms at 197 TFLOP/s against 1.54 ms of HBM traffic: memory).
    table = peaks.peaks_for("TPU v5 lite")
    assert total_f / table["bf16_flops_per_s"] < total_b / table["hbm_bytes_per_s"]


def test_parameter_counts():
    assert weights.parameter_count(_cfg("vaswani_big_ende")) == 290_058_376
    ref = _cfg("multi30k_ref_mt")
    d, f = 512, 1024
    attn = d * 3 * d + 3 * d + d * d + d
    cross = d * d + d + d * 2 * d + 2 * d + d * d + d
    ffn = d * f + f + f * d + d
    expect = (8192 * d + attn + ffn + 4 * d
              + 10240 * d + attn + cross + ffn + 6 * d
              + d * 10240 + 10240)
    assert weights.parameter_count(ref) == expect


def test_unknown_device_has_no_peak():
    with pytest.raises(KeyError, match="no peaks on record"):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
