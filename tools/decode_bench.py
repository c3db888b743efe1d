"""Inference decode throughput on the reference MT model shapes.

The reference ships no inference path at all (SURVEY.md C23: its
``Transformer`` stops at training); this framework adds KV-cache greedy,
sampling, and flat-batched beam decoding. This tool measures them on chip:

- ``greedy_cached`` — O(1) decoder work per token (the product decode path)
- ``beam4`` — beam_size=4 flat-batched beams sharing one cache
- ``greedy_naive`` — the O(L) full re-decode (``greedy_translate``), the
  baseline that quantifies what the cache buys

Metric: NEW tokens/sec/chip (generated tokens only, ``B × max_new`` per
call). Median of TRIALS timed windows, spread alongside. Chip-only, like
bench.py: run it through the chip tool (``python tools/decode_bench.py``);
without a TPU it exits non-zero, and a decoder that raises ends the run.
One JSON line per decoder plus a summary line.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def main() -> None:
    jax = bench.init_chip()

    import jax.numpy as jnp

    from machine_learning_apache_spark_tpu.models import (
        Transformer,
        TransformerConfig,
    )
    from machine_learning_apache_spark_tpu.models.transformer import (
        beam_translate,
        greedy_translate,
        greedy_translate_cached,
    )

    bs = int(os.environ.get("DECODE_BATCH", "64"))
    src_len, max_new = 32, 64
    trials, calls, warmup = 5, 4, 3
    cfg = TransformerConfig(
        src_vocab_size=bench.SRC_VOCAB,
        trg_vocab_size=bench.TRG_VOCAB,
        max_len=bench.SEQ,
        num_layers=bench.LAYERS,
        dropout=0.0,
        dtype=jnp.bfloat16,
    )
    model = Transformer(cfg)
    src = jax.random.randint(
        jax.random.key(0), (bs, src_len), 3, cfg.src_vocab_size,
        dtype=jnp.int32,
    )
    params = model.init(jax.random.key(1), src[:2], src[:2])["params"]

    decoders = {
        "greedy_cached": jax.jit(
            lambda p, s: greedy_translate_cached(
                model, p, s, max_new_tokens=max_new
            )
        ),
        "beam4": jax.jit(
            lambda p, s: beam_translate(
                model, p, s, beam_size=4, max_new_tokens=max_new
            )
        ),
        "greedy_naive": jax.jit(
            lambda p, s: greedy_translate(
                model, p, s, max_new_tokens=max_new
            )
        ),
    }

    results = {}
    for name, fn in decoders.items():
        # Value fetch as the completion barrier (see bench._value_barrier).
        for _ in range(1 + warmup):
            float(fn(params, src)[0, -1])
        times = []
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(calls):
                out = fn(params, src)
            float(out[0, -1])
            times.append(time.perf_counter() - t0)
        rates = sorted(bs * max_new * calls / t for t in times)
        r = {
            "new_tokens_per_sec_chip": round(statistics.median(rates), 1),
            "max": round(rates[-1], 1),
            "spread": round(rates[-1] / rates[0], 2),
            "batch": bs,
            "max_new_tokens": max_new,
        }
        results[name] = r
        print(json.dumps({"decoder": name, **r}), flush=True)
    gc, gn, b4 = (
        results[name]["new_tokens_per_sec_chip"]
        for name in ("greedy_cached", "greedy_naive", "beam4")
    )
    summary = {
        "cache_speedup_vs_naive": round(gc / gn, 2),
        # Raw emitted-tokens slowdown of beam-4 vs greedy. Each beam row
        # also decodes 4 hypotheses internally, so the per-hypothesis
        # cost is this divided by 4 — reported separately.
        "beam4_cost_vs_greedy": round(gc / b4, 2),
        "beam4_cost_per_hypothesis": round(gc / (4 * b4), 2),
    }
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
