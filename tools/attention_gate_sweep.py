"""One attention site on either side of ``ops.attention.FLASH_MIN_SCORES``,
forward and forward + backward under ``jit``: the sweep behind PERF.md's
table (section 6, PR 25). ``tools/longctx_bench.py`` times the whole MT
step at 2,048 positions and more; this times the site alone, from 128.

Sides: ``flash`` (kernel forward, Pallas backward: the gate moved to 0),
``dense`` (the rematerialized fused-XLA path) and ``hybrid`` (kernel
forward, dense backward, under the gate as committed: what auto-dispatch
compiled before PR 25). Rows are cut as the length grows to hold the two
configurations' tokens a step. Chip-only, like bench.py:
``chiprun -- python tools/attention_gate_sweep.py [sides [lengths]]``, both
comma lists; one JSON line a case. The TPU compiler has died on the dense
causal site at ``[50, 8, 2048, 64]``, so cut a sweep into processes.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench

# heads, head_dim, rows x S to hold: multi30k_ref_mt's [512, 8, 200, 64] and
# vaswani_big_ende's [24, 16, 256, 64] a chip.
LAYOUTS = {"ref_8x64": (8, 64, 102_400), "big_16x64": (16, 64, 6_144)}


def main() -> None:
    sides = (sys.argv[1:2] or ["flash,dense,hybrid"])[0].split(",")
    lengths = (sys.argv[2:3] or ["128,200,256,512,1024,2048,4096"])[0]
    jax = bench.init_chip()
    import jax.numpy as jnp

    from machine_learning_apache_spark_tpu.ops import attention

    committed = attention.FLASH_MIN_SCORES

    def timed_ms(fn, *args) -> float:
        """Median of 5 samples of 5 synced calls each, ms a call."""
        jax.block_until_ready(fn(*args))
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(5):
                out = fn(*args)
            jax.block_until_ready(out)
            samples.append((time.perf_counter() - t0) / 5 * 1e3)
        return statistics.median(samples)

    for side in sides:
        # The gate is read while tracing: move it for the all-kernel side,
        # and drop what was traced under the last one.
        attention.FLASH_MIN_SCORES = 0 if side == "flash" else committed
        jax.clear_caches()
        impl = "dense" if side == "dense" else "flash"
        for layout, (heads, head_dim, tokens) in LAYOUTS.items():
            for seq in map(int, lengths.split(",")):
                if side == "hybrid" and seq * seq >= committed:
                    continue
                rows = max(1, round(tokens / seq))
                q, k, v, w = (
                    jax.random.normal(
                        jax.random.key(i), (rows, heads, seq, head_dim)
                    ).astype(jnp.bfloat16) for i in range(4)
                )
                valid = jnp.ones((rows, seq), bool)
                for causal in (False, True):
                    def loss(q, k, v):
                        with attention.attention_impl(impl):
                            out = attention.dot_product_attention(
                                q, k, v, causal=causal, kv_valid=valid
                            )
                        return jnp.sum(out.astype(jnp.float32) * w)

                    row = dict(side=side, layout=layout, seq=seq, rows=rows,
                               causal=causal)
                    try:
                        row["fwd_ms"] = timed_ms(jax.jit(loss), q, k, v)
                        row["fwd_bwd_ms"] = timed_ms(jax.jit(
                            jax.value_and_grad(loss, argnums=(0, 1, 2))
                        ), q, k, v)
                    except jax.errors.JaxRuntimeError as e:
                        # Float32 [rows, H, S, S] scores outgrowing the chip
                        # is the dense side's expected end; nothing else is.
                        if side != "dense" or "RESOURCE_EXHAUSTED" not in str(e):
                            raise
                        row["oom"] = True
                    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
