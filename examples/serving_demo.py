"""Serving demo — 48 concurrent clients through the continuous batcher.

Builds a small translator (trained briefly on the synthetic word→word
task so outputs are meaningful), starts ``Translator.serve()`` on CPU,
and fires concurrent client threads at it in two waves: a warm steady
wave, then a burst beyond queue capacity to show admission control
(``Backpressure`` with a retry-after hint) doing its job. Asserts the
serving invariant the subsystem exists for — ZERO recompiles after
warmup, every live request's batch hit a precompiled bucket program —
then prints the metrics summary.

Usage: JAX_PLATFORMS=cpu python examples/serving_demo.py [n_clients]
"""

import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from machine_learning_apache_spark_tpu.data.datasets import (
    synthetic_translation_pairs,
)
from machine_learning_apache_spark_tpu.recipes import train_translator
from machine_learning_apache_spark_tpu.serving import Backpressure

N_CLIENTS = int(sys.argv[1]) if len(sys.argv) > 1 else 48
assert N_CLIENTS >= 32, "the demo's contract is >= 32 concurrent requests"

out = train_translator(
    epochs=6, synthetic_n=1024, batch_size=16, max_len=12,
    d_model=64, ffn_hidden=128, num_heads=4, dropout=0.0, log_every=0,
    use_mesh=False, seed=0, _return_translator=True,
)
translator = out["translator"]

pairs = synthetic_translation_pairs(N_CLIENTS, min_len=3, max_len=8, seed=42)
texts = [s for s, _ in pairs]

results: dict[int, str] = {}
rejected: list[int] = []
lock = threading.Lock()

engine = translator.serve(
    boundaries=(8, 12), max_batch=8,
    max_queue_depth=max(N_CLIENTS, 64), max_new_tokens=10,
)


def client(i: int) -> None:
    try:
        req = engine.submit(texts[i], deadline_s=60.0)
        with lock:
            results[i] = req.result(timeout=60.0)
    except Backpressure as e:
        with lock:
            rejected.append(i)
        print(f"client {i}: backpressure, retry after {e.retry_after:.3f}s")


with engine:
    # Wave 1: all clients at once — the batcher's steady-state traffic.
    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(N_CLIENTS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    served = len(results)
    assert served >= 32, f"only {served} of {N_CLIENTS} requests served"
    recompiles = engine.recompiles_after_warmup
    assert recompiles == 0, (
        f"{recompiles} recompiles after warmup — a bucket shape leaked past "
        "the precompiled program set"
    )

    # Wave 2: overload a tiny queue to demonstrate admission control.
    small = translator.serve(
        boundaries=(8, 12), max_batch=4, max_queue_depth=2, max_new_tokens=10,
        start=False,
    )
    small.start(warmup=False)  # no warmup: keep its first batches slow
    burst_rejected = 0
    for i in range(16):
        try:
            small.submit(texts[i % len(texts)])
        except Backpressure:
            burst_rejected += 1
    small.stop()
    print(f"burst: {burst_rejected}/16 rejected by a depth-2 queue")

    print(f"served {served}/{N_CLIENTS} concurrent requests, "
          f"{len(rejected)} backpressured, {recompiles} recompiles after warmup")
    print("sample:", texts[0], "->", results[0])
    summary = engine.metrics.log_summary()
    print(f"tokens/sec: {summary['tokens_per_sec']}")
    print(f"total latency p50/p99: {summary['total_latency_s']['p50']:.4f}/"
          f"{summary['total_latency_s']['p99']:.4f} s")
    print(f"batch occupancy p50: {summary['batch_occupancy']['p50']:.2f}")
