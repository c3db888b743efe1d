"""Builder's tool: find the rate an open-loop cell's engine sustains.

    python3 -m benchmark.sweep_rate --workload big_serve_steady \
        --rates 200,250,300,350,400 --seconds 10 --seed 7

One engine, one level after another (each after a drain), same prompts.
For each rate it prints requests completed a second, the queue's depth and
the requests in flight at the close (a backlog that grows is a rate above
the knee), and the latency from the due instant. The cell's file then takes
about four fifths of the highest rate without a growing backlog, as a
number; the driver's runs never search for a rate. Not part of the
contract's command; needs the chip like every measurement.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

from benchmark import manifest as manifest_mod, program, traffic, weights
from benchmark.kinds import serve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    from machine_learning_apache_spark_tpu.inference import Translator

    if jax.devices()[0].platform != "tpu":
        print("sweep_rate: not a TPU; a rate found elsewhere means nothing",
              file=sys.stderr)
        return 3
    manifest = manifest_mod.load_manifest()
    cell = manifest_mod.find_cell(manifest, args.workload)
    cfg = manifest_mod.load_config(manifest, cell["config"])
    mix = manifest_mod.load_traffic(cell["traffic"])
    engine_kw = dict(cfg["engine"])
    max_src = max(engine_kw["boundaries"])
    max_new = int(engine_kw["max_new_tokens"])
    params = weights.make_params(args.seed, cfg, suppress_stop=True)
    src_pipe, trg_pipe = serve.make_pipelines(cfg, max_src, max_new)
    prompts = traffic.prompts(mix["lengths"], cfg["src_vocab_size"], args.seed)
    texts = [serve._text(ids) for ids in prompts]
    model = program.make_model(cfg)
    engine = Translator(model, params, src_pipe, trg_pipe).serve(**engine_kw)
    for rate in [float(r) for r in args.rates.split(",")]:
        arrivals = dict(mix["arrivals"], rate_per_s=rate)
        due = traffic.due_times(arrivals, args.seconds, args.seed)
        t0 = time.monotonic() + 0.05
        client = serve.OpenLoop(engine, texts, t0 + due)
        done0 = engine.metrics.ledger()["completed"]
        client.start()
        time.sleep(max(t0 + args.seconds - time.monotonic(), 0))
        ledger = engine.metrics.ledger()
        depth, in_flight = engine.queue.depth, ledger["in_flight"]
        completed = ledger["completed"] - done0
        client.stop()
        client.wait_for_answers(120.0)
        lat = [r.done - r.due for r in client.records if r.finished_ok()]
        half = [r.done - r.due for r in client.records
                if r.finished_ok() and r.due - t0 > args.seconds / 2]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(client.records),
            "completed_per_s_in_window": completed / args.seconds,
            "queue_depth_at_close": depth, "in_flight_at_close": in_flight,
            "latency_p50_ms": statistics.median(lat) * 1e3,
            "latency_p95_ms": traffic.nearest_rank(lat, 95) * 1e3,
            "latency_p50_ms_second_half": statistics.median(half) * 1e3,
            "failed": sum(1 for r in client.records if not r.finished_ok()),
        }), flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
