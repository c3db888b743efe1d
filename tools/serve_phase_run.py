"""A builder's run of a serving cell outside the benchmark's contract: the
cell as ``benchmark.run`` runs it, traced with the profiler's **Python
tracer off**, so the traced seconds stay in the regime the untraced runs
are judged in and the idle gaps are named by the engine's phase
annotations alone.

    chiprun -- python tools/serve_phase_run.py --workload big_serve_steady \
        --seed 9001 --seconds 20 --trace 1

It counts the launches in the window itself (a stamp a launch in
``ServingMetrics.on_batch``), so the count is there with
``MLSPARK_TELEMETRY=0`` too: ``--trace 0`` runs with the variable set and
unset say what the event log costs. The last line of standard output is the
run's result object with ``launches_in_window`` added.

It goes when ``benchmark.run`` can start the profiler with
``ProfileOptions(python_tracer_level=0)`` itself (ROADMAP, the follow-up
``benchmark`` issue): until then nothing under ``benchmark/`` may be
edited by the PR that adds the spans.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="big_serve_steady")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)

    from benchmark import run as bench_run

    import jax

    from machine_learning_apache_spark_tpu.serving.metrics import (
        ServingMetrics,
    )

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    start_trace = jax.profiler.start_trace
    jax.profiler.start_trace = lambda log_dir, **kw: start_trace(
        log_dir, profiler_options=options, **kw
    )
    stamps: list[float] = []
    on_batch = ServingMetrics.on_batch

    def counted(self, **kw):
        stamps.append(time.monotonic())
        return on_batch(self, **kw)

    ServingMetrics.on_batch = counted
    result = bench_run.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        require_chip=not args.rehearse, rehearse=bool(args.rehearse),
    )
    setup_s = result["metrics"].get("setup_s", {}).get("value")
    if setup_s is not None:  # a traced run's line leaves setup_s out
        w0 = bench_run._T0 + setup_s - bench_run._AGE_AT_T0
        result["launches_in_window"] = sum(
            1 for t in stamps if w0 <= t < w0 + args.seconds
        )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
