"""Long-context single-chip proof: MT train step at seq 2048/4096/8192,
bf16, measured with the bench's synced protocol — flash (Pallas blockwise,
the default on TPU) AND the dense-XLA path it replaces (the materialized
``[S,S]`` core of the reference, ``transformer.py:12-25``), per length.

The dense attempt is the point: where it still fits, the ratio quantifies
the kernel's win; where it OOMs (the [B,H,S,S] score tensor at long S),
the recorded failure is direct evidence for the flash kernel's O(S)
memory claim. Batch sizes halve as length doubles (constant token budget
per step).

Chip-only, like bench.py: run it through the chip tool
(`python tools/longctx_bench.py` from the repo root); without a TPU it
exits non-zero, and any failure but the dense path's expected
out-of-memory ends the run. Writes one JSON line per (seq, impl) plus a
summary line.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def main() -> None:
    jax = bench.init_chip()
    from machine_learning_apache_spark_tpu.ops.attention import attention_impl

    def _hbm_gb():
        # HBM note per config: the flash kernel's O(S) claim vs the dense
        # path's [B,H,S,S] score tensor is a MEMORY claim first — record
        # it, not just the throughput. The allocator's peak counter is
        # cumulative over the PROCESS (no reset API), so it is labeled as
        # such: the first config's peak is exact; later configs' peaks
        # are a running max and only meaningful when they RISE. Current
        # bytes_in_use accompanies it.
        stats = jax.local_devices()[0].memory_stats()
        return {
            "peak_hbm_gb_cumulative": round(
                stats["peak_bytes_in_use"] / 2**30, 3
            ),
            "hbm_gb_in_use": round(stats["bytes_in_use"] / 2**30, 3),
        }

    def run(seq, bpc, impl):
        with attention_impl(impl):
            r = bench.bench_transformer(
                jax, batch_per_chip=bpc, trials=3, steps=5, warmup=5,
                seq=seq,
            )
        r.update(_hbm_gb())
        return r

    results = []
    for seq, bpc in ((2048, 16), (4096, 8), (8192, 4)):
        for impl in ("flash", "dense"):
            try:
                r = run(seq, bpc, impl)
                out = {
                    "seq": seq, "batch_per_chip": bpc, "impl": impl,
                    "tokens_per_sec_chip": r["median"], "mfu": r["mfu"],
                    "spread": r["spread"],
                    "paired": r.get("paired_window", {}),
                }
                for k in ("peak_hbm_gb_cumulative", "hbm_gb_in_use"):
                    if k in r:
                        out[k] = r[k]
            except jax.errors.JaxRuntimeError as e:
                # A dense OOM is an expected, *informative* failure (the
                # [B,H,S,S] tensor outgrowing HBM) — recorded as evidence.
                # Anything else, and any flash failure, ends the run.
                if impl != "dense" or "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                out = {
                    "seq": seq, "batch_per_chip": bpc, "impl": impl,
                    "error": repr(e), "oom": True,
                }
                # Peak-at-failure is the most informative memory reading
                # the tool can take: it shows how full HBM was when the
                # [B,H,S,S] materialization broke.
                out.update(_hbm_gb())
            results.append(out)
            print(json.dumps(out), flush=True)
    print(json.dumps({"summary": _summarize(results)}), flush=True)


def _summarize(results: list) -> list:
    """Per-length flash-vs-dense verdicts: the speedup ratio where both
    ran, or what the dense failure proves where it didn't."""
    by_seq: dict = {}
    for r in results:
        by_seq.setdefault(r["seq"], {})[r["impl"]] = r
    rows = []
    for seq, pair in sorted(by_seq.items()):
        fl, de = pair.get("flash", {}), pair.get("dense", {})
        row = {"seq": seq}
        if "tokens_per_sec_chip" in fl:
            row["flash_tokens_per_sec_chip"] = fl["tokens_per_sec_chip"]
        if "tokens_per_sec_chip" in de:
            row["dense_tokens_per_sec_chip"] = de["tokens_per_sec_chip"]
            if "tokens_per_sec_chip" in fl and de["tokens_per_sec_chip"]:
                row["flash_speedup"] = round(
                    fl["tokens_per_sec_chip"] / de["tokens_per_sec_chip"], 2
                )
        elif de.get("oom"):
            row["dense"] = "OOM (materialized [B,H,S,S] outgrew HBM)"
        elif "error" in de:
            row["dense"] = "failed (see per-config line)"
        rows.append(row)
    return rows


if __name__ == "__main__":
    main()
