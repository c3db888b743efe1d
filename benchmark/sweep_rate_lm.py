"""Builder's tool: find the rate a ``serve_lm`` cell's engine sustains.

    python3 -m benchmark.sweep_rate_lm --workload sala_serve_doc_qa_64k \
        --rates 4,8,12,16,20 --seconds 8 --seed 7

One engine with the mix's documents resident, one level after another (each
after a drain). For each rate it prints requests completed a second, the
queue's depth and the requests in flight at the close (a backlog that grows
is a rate above the knee), and the latency from the due instant, whole and
over the level's second half. The mix's file then takes four fifths of the
highest rate without a growing backlog, as a number; the driver's runs never
search for a rate. Needs the chip like every measurement.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

from benchmark import manifest as manifest_mod, traffic
from benchmark.kinds import serve, serve_lm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep_rate_lm: not a TPU; a rate found elsewhere means nothing",
              file=sys.stderr)
        return 3
    manifest = manifest_mod.load_manifest()
    cell = manifest_mod.find_cell(manifest, args.workload)
    cfg = manifest_mod.load_config(manifest, cell["config"])
    mix = manifest_mod.load_traffic(cell["traffic"])
    note = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    _, _, engine = serve_lm.build_engine(cfg, args.seed)
    docs = serve_lm.make_documents(mix["documents"], cfg["vocab_size"], args.seed)
    serve_lm.serve_documents(engine, docs, note)
    for rate in [float(r) for r in args.rates.split(",")]:
        arrivals = dict(mix["arrivals"], rate_per_s=rate)
        due = traffic.due_times(arrivals, args.seconds, args.seed)
        requests = serve_lm.make_requests(
            mix, docs, cfg["vocab_size"], args.seed + int(rate * 1000), len(due)
        )
        t0 = time.monotonic() + 0.05
        client = serve.OpenLoop(engine, [ids for _, ids in requests], t0 + due)
        done0 = engine.metrics.ledger()["completed"]
        client.start()
        time.sleep(max(t0 + args.seconds - time.monotonic(), 0))
        ledger = engine.metrics.ledger()
        depth, in_flight = engine.queue.depth, ledger["in_flight"]
        completed = ledger["completed"] - done0
        client.stop()
        client.wait_for_answers(180.0)
        lat = [r.done - r.due for r in client.records if r.finished_ok()]
        half = [r.done - r.due for r in client.records
                if r.finished_ok() and r.due - t0 > args.seconds / 2]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(client.records),
            "completed_per_s_in_window": completed / args.seconds,
            "queue_depth_at_close": depth, "in_flight_at_close": in_flight,
            "latency_p50_ms": statistics.median(lat) * 1e3,
            "latency_p95_ms": traffic.nearest_rank(lat, 95) * 1e3,
            "latency_p50_ms_second_half": statistics.median(half) * 1e3 if half else None,
            "failed": sum(1 for r in client.records if not r.finished_ok()),
        }), flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
