"""The flash kernels' tiles, swept on the chip: device ms a call of
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` at one site, for each
``block_q x block_k`` given, read from a profiler trace by the kernels'
names. The table behind ``ops.pallas_attention._choose_tiling``'s rule
(PERF.md section 6, PR 27).

    chiprun -- python tools/flash_tile_sweep.py \
        [--shape 4,16,4096,256] [--dtype bfloat16] [--causal 1] \
        [--tiles chosen,128x128,512x512,...]

``chosen`` leaves both sides to the chooser. One JSON line a case; the whole
table also lands in ``chiprun_out/flash_tile_sweep/``. Chip-only, like
``tools/attention_gate_sweep.py``; ``--rehearse 1`` interprets the kernels
on the CPU at whatever (small) shape is given and reports no time.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
REPS = 3  # traced calls a case; the median is reported
DEFAULT_TILES = (
    "chosen,128x128,256x256,256x512,512x256,512x512,512x1024,1024x512,"
    "1024x1024"
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", default="4,16,4096,256")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--tiles", default=DEFAULT_TILES)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)

    if args.rehearse:
        import jax
    else:
        import bench

        jax = bench.init_chip()
    import jax.numpy as jnp

    from benchmark import readers, trace_reduce
    from machine_learning_apache_spark_tpu import telemetry
    from machine_learning_apache_spark_tpu.ops.pallas_attention import (
        flash_attention,
    )

    b, h, s, d = map(int, args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    q, w = (
        jax.random.normal(jax.random.key(i), (b, h, s, d)).astype(dtype)
        for i in (0, 3)
    )
    k, v = (
        jax.random.normal(jax.random.key(i), (b, h, s, d)).astype(dtype)
        for i in (1, 2)
    )

    def case(tile: str):
        bq, bk = (
            (None, None) if tile == "chosen"
            else (int(t) for t in tile.split("x"))
        )

        def loss(q, k, v):
            out = flash_attention(
                q, k, v, causal=bool(args.causal), block_q=bq, block_k=bk,
                interpret=bool(args.rehearse),
            )
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

        # The module's name in the trace tells the cases apart.
        loss.__name__ = f"sweep_{tile}"
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    rows, fns, want = [], {}, None
    for tile in args.tiles.split(","):
        row = dict(
            shape=[b, h, s, d], dtype=dtype.name, causal=bool(args.causal),
            tiles=tile,
        )
        telemetry.get_log().clear()
        try:
            fns[tile] = case(tile)
            got = jax.block_until_ready(fns[tile](q, k, v))
        except Exception as e:  # a tile Mosaic refuses is a row, not the end
            row["error"] = str(e)[:300]
            del fns[tile]
            rows.append(row)
            continue
        row["dispatch"] = [
            e.attrs["reason"] for e in telemetry.get_log().snapshot()
            if e.name == "ops.attention_dispatch"
            and e.attrs.get("site") == "flash_tiles"
        ]
        grads = [g.astype(jnp.float32) for g in got[1]]
        if want is None:
            want = grads  # the first case is the one the others are held to
        row["grad_gap_to_first"] = max(
            float(jnp.max(jnp.abs(g - r))) for g, r in zip(grads, want)
        )
        rows.append(row)

    if not args.rehearse and fns:
        with tempfile.TemporaryDirectory() as trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # device events are all it reads
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            for fn in fns.values():
                for _ in range(REPS):
                    out = fn(q, k, v)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        for row in rows:
            if "error" in row:
                continue
            runs = trace_reduce.module_runs(
                trace, rf"^jit_sweep_{row['tiles']}\b"
            )
            for name in KERNELS:
                calls = trace_reduce.ops_matching(trace, rf"^%?{name}[.\d]* = ")
                durs = [
                    e.dur * 1e3
                    for chip, spans in runs.items()
                    for e in readers._inside(calls.get(chip, []), spans)
                ]
                row[f"{name}_ms"] = statistics.median(durs) if durs else None
                row[f"{name}_calls"] = len(durs)
            row["module_ms"] = statistics.median(
                e.dur * 1e3 for spans in runs.values() for e in spans
            ) if any(runs.values()) else None

    os.makedirs("chiprun_out/flash_tile_sweep", exist_ok=True)
    name = (
        f"{b}x{h}x{s}x{d}_{dtype.name}"
        f"{'_causal' if args.causal else ''}.jsonl"
    )
    with open(os.path.join("chiprun_out/flash_tile_sweep", name), "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
            # the launches' own records stay in the file: stdout is capped
            row.pop("dispatch", None)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
