"""A train step's device time by named scope, and what lies under none.

Runs one traced run of a benchmark cell (``benchmark.run.run_cell``) and,
before the harness deletes the trace, reads every device operation of the
whole runs of the step's program out of the ``.xplane.pb`` with
``benchmark/scope_trace.py``'s reader. Prints, in ms a step (the mean over
the trace's whole runs and chips):

- the step, and the time inside it with no operation running;
- each scope the ``train_lm`` kind collects (``SCOPES``) and each of the
  step's others (``OTHER_SCOPES``), summed as the kind sums its own, and the
  operations under none of them;
- the operations under none of them grouped by where they sit
  (``place``: forward, backward or step, the ``tf_op`` path without its
  transforms and layer numbers), and every one of at least ``--min-ms`` a
  step by HLO name, with its shape and its ``tf_op`` path;
- every operation counted under two scopes or more (the kind's sum then
  counts it twice).

The table and the run's result line land in ``<--out>/<label>.json``
(``chiprun_out/step_scope_table`` by default). On a TPU host, from the
root of a checkout (about 5 minutes):

    python3 tools/step_scope_table.py \\
        --workload q3next_train_full_4k --seed 3600000001 --label change

From another checkout's root (a parent commit's, say) the same script
reads that checkout's program and benchmark: it imports them from the
working directory.

``--rehearse 1`` with ``JAX_PLATFORMS=cpu`` drives the cell at toy widths
on a CPU; a CPU trace has no device plane, so the table is empty.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.getcwd())

from benchmark import scope_trace, trace_reduce  # noqa: E402
from benchmark.readers import TRAIN_STEP_MODULE  # noqa: E402


TRANSFORM = re.compile(r"^(jit|jvp|transpose|vmap)\(.*\)$")
# The train step's scopes that the kind does not collect: the optimizer's
# update (``train.state.UPDATE_SCOPE``), the embedding and the block's norms
# and residual adds (``models.hybrid_lm``). Names, not imports, so that a
# checkout whose program lacks them reads 0 under them.
OTHER_SCOPES = ("train.update", "lm.embed", "lm.residual_norm")


def place(tf_op: str) -> str:
    """Where an operation under no collected scope sits: ``forward``,
    ``backward`` or ``step`` (outside the model's differentiation: the
    update), then its ``tf_op`` path without the transforms' own
    components (``jit(step)``, ``jvp(...)``, ``transpose(...)``), repeats
    and layer numbers, ending in the operation's primitive. An operation
    the compiler left no ``op_name`` is ``(no op_name)``; one whose
    ``op_name`` the compiler wrote itself, not a path, is that name."""
    if not tf_op:
        return "(no op_name)"
    path = tf_op.rstrip(":")
    if "/" not in path:
        return f"({path})"
    direction = (
        "backward" if "transpose(" in path
        else "forward" if "jvp(" in path else "step"
    )
    parts = []
    for part in re.sub(r"layer_\d+", "layer_N", path).split("/"):
        if not TRANSFORM.match(part) and (not parts or parts[-1] != part):
            parts.append(part)
    return f"{direction}: {'/'.join(parts)}"


def step_table(path: str, scopes, module_pattern: str = TRAIN_STEP_MODULE,
               min_ms: float = 1.0) -> dict | None:
    """ms a whole run of the module: the run, its time with no operation
    running, each scope's operations, the operations under none of them
    (by ``place``, and each of at least ``min_ms`` by name) and those under
    two or more.
    None where the trace holds no such run."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = [
        scope_trace._device_plane(v)
        for field, v in scope_trace._fields(data) if field == 1
    ]
    module_rx = re.compile(module_pattern)
    scope_rx = {
        s: re.compile(r"(^|/)" + re.escape(s) + r"(/|$|:)") for s in scopes
    }
    by_scope = dict.fromkeys(scopes, 0.0)
    outside, places, twice = {}, {}, {}
    runs, run_s, idle_s = 0, 0.0, 0.0
    for plane in filter(None, planes):
        _, ops, events = plane
        modules = [
            (start, dur) for start, dur, key in events.get(trace_reduce.MODULES_LINE, [])
            if module_rx.search(ops.get(key, ("", ""))[0])
        ]
        if not modules:
            continue
        typical = statistics.median(d for _, d in modules)
        whole = [(s, s + d) for s, d in modules if d >= 0.9 * typical]
        runs += len(whole)
        run_s += sum(b - a for a, b in whole)
        inside = [[] for _ in whole]
        for start, dur, key in events.get(trace_reduce.OPS_LINE, []):
            hlo, scope = ops.get(key, ("", ""))
            if trace_reduce.CONTAINER.search(hlo):
                continue
            at = next(
                (i for i, (a, b) in enumerate(whole)
                 if a <= start and start + dur <= b + 1e-9), None,
            )
            if at is None:
                continue
            inside[at].append((start, start + dur))
            hit = [s for s, rx in scope_rx.items() if rx.search(scope)]
            for s in hit:
                by_scope[s] += dur
            if not hit:
                name = trace_reduce.op_short_name(hlo)
                seen = outside.setdefault(name, [0.0, scope])
                seen[0] += dur
                where = places.setdefault(place(scope), [0.0, set()])
                where[0] += dur
                where[1].add(name)
            elif len(hit) > 1:
                twice[trace_reduce.op_short_name(hlo)] = "+".join(hit)
        for (a, b), spans in zip(whole, inside):
            busy, end = 0.0, a
            for s, e in sorted(spans):
                if e > end:
                    busy += e - max(s, end)
                    end = e
            idle_s += (b - a) - busy
    if not runs:
        return None
    ms = lambda s: s / runs * 1e3  # noqa: E731
    return {
        "runs": runs,
        "step_ms": ms(run_s),
        "no_operation_ms": ms(idle_s),
        "scopes_ms": {k: ms(v) for k, v in by_scope.items()},
        "none_ms": ms(sum(v for v, _ in outside.values())),
        "none_places": sorted(
            ([p, ms(v), len(names)] for p, (v, names) in places.items()),
            key=lambda row: -row[1],
        ),
        "none_ops": sorted(
            ([n, ms(v), scope] for n, (v, scope) in outside.items()
             if ms(v) >= min_ms),
            key=lambda row: -row[1],
        ),
        "under_two": twice,
    }


def print_table(table: dict) -> None:
    print(f"step {table['step_ms']:.3f} ms over {table['runs']} whole runs; "
          f"no operation running {table['no_operation_ms']:.3f}")
    for name, value in sorted(table["scopes_ms"].items(), key=lambda kv: -kv[1]):
        print(f"  {name:24s} {value:9.3f}")
    print(f"  {'(under none)':24s} {table['none_ms']:9.3f}")
    print(f"  sum {sum(table['scopes_ms'].values()) + table['none_ms'] + table['no_operation_ms']:.3f}")
    for where, value, count in table["none_places"]:
        print(f"    {value:8.3f}  {count:4d} ops  {where}")
    for name, value, scope in table["none_ops"]:
        print(f"    {value:8.3f}  {name}  [{scope or 'no tf_op'}]")
    for name, scopes in table["under_two"].items():
        print(f"  under two scopes: {name} ({scopes})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="q3next_train_full_4k")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--label", default="run")
    ap.add_argument("--min-ms", type=float, default=1.0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default=os.path.join("chiprun_out", "step_scope_table"),
                    help="directory of the JSON file")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run
    from benchmark.kinds import train_lm

    scopes = [*train_lm.SCOPES, *(s for s in OTHER_SCOPES if s not in train_lm.SCOPES)]
    found = {}
    reduce_trace = bench_run._reduce_trace

    def table_then_reduce(run, result):
        path = trace_reduce.find_xplane(run.trace_dir) if run.trace_dir else None
        if path is not None:
            found["table"] = step_table(path, scopes, min_ms=args.min_ms)
        reduce_trace(run, result)

    bench_run._reduce_trace = table_then_reduce
    result = bench_run.run_cell(
        args.workload, args.seed, args.seconds, True,
        require_chip=not args.rehearse, rehearse=bool(args.rehearse),
    )
    table = found.get("table")
    if table:
        print_table(table)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{args.label}.json"), "w") as f:
        json.dump({"seed": args.seed, "scopes": scopes, "table": table,
                   "result": result}, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
