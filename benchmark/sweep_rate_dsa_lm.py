"""Builder's tool: find the rate a ``serve_dsa_lm`` cell's engine sustains
(``sweep_rate_lm``'s procedure, with this kind's engine).

    python3 -m benchmark.sweep_rate_dsa_lm --workload dsv32_serve_doc_qa_64k \
        --rates 6,10,14,18,22 --seconds 8 --seed 7

One engine with the mix's documents resident, one level after another (each
after a drain). For each rate it prints requests completed a second, the
queue's depth and the requests in flight at the close (a backlog that grows
is a rate above the knee), and the latency from the due instant, whole and
over the level's second half. The mix's file then takes its share of the
highest rate without a growing backlog, as a number; the driver's runs never
search for a rate. Needs the chip like every measurement.

``--repeat N`` then runs N windows of ``--window`` seconds (after the mix's
warm period) at ``--share`` of the knee, each with other requests, on the
same engine: the median latency of each and their spread, the quartiles'
distance over the median. The knee is taken half way between the last level
that ends with at most 4 requests queued and the first that does not.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

from benchmark import manifest as manifest_mod, traffic
from benchmark.kinds import serve, serve_dsa_lm, serve_lm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--repeat", type=int, default=0)
    ap.add_argument("--share", type=float, default=0.6)
    ap.add_argument("--window", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep_rate_dsa_lm: not a TPU; a rate found elsewhere means nothing",
              file=sys.stderr)
        return 3
    manifest = manifest_mod.load_manifest()
    cell = manifest_mod.find_cell(manifest, args.workload)
    cfg = manifest_mod.load_config(manifest, cell["config"])
    mix = manifest_mod.load_traffic(cell["traffic"])
    note = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    _, _, engine = serve_dsa_lm.build_engine(cfg, args.seed)
    docs = serve_lm.make_documents(mix["documents"], cfg["vocab_size"], args.seed)
    serve_lm.serve_documents(engine, docs, note)
    def level(rate: float, seconds: float, seed: int):
        """An open loop at ``rate`` for ``seconds``: the client (drained) and
        the queue's depth and the requests in flight at the close."""
        arrivals = dict(mix["arrivals"], rate_per_s=rate)
        due = traffic.due_times(arrivals, seconds, seed)
        requests = serve_lm.make_requests(mix, docs, cfg["vocab_size"], seed, len(due))
        t0 = time.monotonic() + 0.05
        client = serve.OpenLoop(engine, [ids for _, ids in requests], t0 + due)
        done0 = engine.metrics.ledger()["completed"]
        client.start()
        time.sleep(max(t0 + seconds - time.monotonic(), 0))
        ledger = engine.metrics.ledger()
        close = dict(queue_depth_at_close=engine.queue.depth,
                     in_flight_at_close=ledger["in_flight"],
                     completed=ledger["completed"] - done0)
        client.stop()
        client.wait_for_answers(180.0)
        return client, t0, close

    sustained, knee = [], None
    for rate in [float(r) for r in args.rates.split(",")]:
        client, t0, close = level(rate, args.seconds, args.seed + int(rate * 1000))
        lat = [r.done - r.due for r in client.records if r.finished_ok()]
        half = [r.done - r.due for r in client.records
                if r.finished_ok() and r.due - t0 > args.seconds / 2]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(client.records),
            "completed_per_s_in_window": close["completed"] / args.seconds,
            "queue_depth_at_close": close["queue_depth_at_close"],
            "in_flight_at_close": close["in_flight_at_close"],
            "latency_p50_ms": statistics.median(lat) * 1e3,
            "latency_p95_ms": traffic.nearest_rank(lat, 95) * 1e3,
            "latency_p50_ms_second_half": statistics.median(half) * 1e3 if half else None,
            "failed": sum(1 for r in client.records if not r.finished_ok()),
        }), flush=True)
        if knee is None and close["queue_depth_at_close"] <= 4:
            sustained.append(rate)
        elif knee is None:
            knee = (max(sustained) + rate) / 2 if sustained else rate
    if args.repeat and sustained:
        knee = knee or max(sustained)
        rate, warm = args.share * knee, float(mix["warm_seconds"])
        p50s = []
        for i in range(args.repeat):
            client, t0, _ = level(rate, warm + args.window, args.seed + 7919 * (i + 1))
            mine = [r for r in client.records if warm <= r.due - t0 < warm + args.window]
            p50s.append(statistics.median(
                (r.done - r.due) if r.finished_ok() else args.window for r in mine
            ) * 1e3)
            print(json.dumps({"window": i, "rate_per_s": rate, "due": len(mine),
                              "latency_p50_ms": p50s[-1]}), flush=True)
        if len(p50s) >= 2:
            q1, _, q3 = statistics.quantiles(p50s, n=4)
            print(json.dumps({"knee_per_s": knee, "rate_per_s": rate,
                              "latency_p50_ms": p50s,
                              "spread": (q3 - q1) / statistics.median(p50s)}),
                  flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
