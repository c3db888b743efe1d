"""The token indexer's decode site alone, on the chip: device ms a call of the
scan's scores and of the whole selection (``ops.dsa_index.select``), through
the paged kernel (``dsa_index_scan``) and through the XLA scan, at the
benchmark cell's engine shapes (32 rows, 64 index heads of 128, pages of 64,
passes of 4,096, a table wide enough for 66,560 positions and a prefill
chunk) with 4 / 8 / 16 / 32 of the rows occupied and the rest at ``t = 0``,
as a decode launch leaves them. Occupied rows take contexts from the cell's
ladder of documents (``64 * floor(32768 * 2^(i/9) / 64)``) plus a question of
up to 512 positions (the pages as ``--layout`` says).

    python tools/dsa_index_sweep.py [--occupied 4,8,16,32] \
        [--cases xla,kernel] [--layout document|scattered] [--seed 0]

A case ``kernel`` takes the kernel as the program chooses it, ``kernel_c<n>``
scores ``n`` positions at a time inside a pass, ``kernel_m<n>`` copies up to
``n`` consecutive pages at a time (``kernel_m1`` a page a copy), ``xla`` the
XLA scan; each case is traced on its own. ``--layout document`` lays each
document's pages one after another, as the cell's set-up leaves them,
``scattered`` draws every page at random. Each row says the dispatch taken,
the device ms a call of the scores module and of the selection module
(median of the traced calls), the kernel's own ms, the pages read over the
XLA scan's, the largest gap of the case's finite scores to the first case's
over their largest, and whether the positions selected are the same. One
JSON line a case; the table also lands in ``chiprun_out/dsa_index_sweep/``.
Chip-only, like ``tools/gdn_chunk_sweep.py``; ``--rehearse 1`` interprets
the kernel on the CPU at a small shape and reports no time.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPS = 5  # traced calls a module; the median is reported
LADDER = [64 * int(32768 * 2 ** (i / 9) // 64) for i in range(10)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--occupied", default="4,8,16,32")
    ap.add_argument("--cases", default="xla,kernel")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layout", default="document",
                    choices=("document", "scattered"))
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)

    if args.rehearse:
        import jax
    else:
        import bench

        jax = bench.init_chip()
    import jax.numpy as jnp
    import numpy as np

    from benchmark import readers, trace_reduce
    from machine_learning_apache_spark_tpu import telemetry
    from machine_learning_apache_spark_tpu.ops import dsa_index, pallas_dsa_index

    if args.rehearse:  # the cell's shapes cut to what the interpreter runs
        rows, heads, d, page, block, topk = 8, 16, 128, 16, 256, 64
        ladder, question, num_pages = [304, 416, 528], 64, 400
        width = -(-(max(ladder) + question) // page)
    else:
        rows, heads, d, page, block, topk = 32, 64, 128, 64, 4096, 2048
        ladder, question, num_pages = LADDER, 512, 7800
        width = -(-66560 // page) + 512 // page
    rng = np.random.default_rng(args.seed)
    plane = jnp.asarray(
        rng.standard_normal((num_pages * page, d), np.float32), jnp.bfloat16
    )
    q = jnp.asarray(rng.standard_normal((rows, heads, d), np.float32), jnp.bfloat16)
    w = dsa_index.index_weights(
        jnp.asarray(rng.standard_normal((rows, heads), np.float32)), heads, d
    )

    # The documents lie one after another from page 1, as the set-up's
    # prefill leaves them; a question's pages come from anywhere after them.
    starts = np.cumsum([1] + [n // page for n in ladder])
    loose = np.arange(starts[-1], num_pages)

    def site(occupied: int):
        t = np.zeros(rows, np.int32)
        tables = np.zeros((rows, width), np.int32)
        for r in rng.choice(rows, occupied, replace=False):
            doc = rng.integers(len(ladder))
            t[r] = ladder[doc] + rng.integers(16, question)
            n, kept = t[r] // page + 1, ladder[doc] // page
            if args.layout == "scattered":
                tables[r, :n] = rng.choice(np.arange(1, num_pages), n, replace=False)
            else:
                tables[r, :kept] = np.arange(starts[doc], starts[doc] + kept)
                tables[r, kept:n] = rng.choice(loose, n - kept, replace=False)
        return jnp.asarray(t), jnp.asarray(tables)

    # The sweep steers the dispatch from outside, as a test would: the
    # program has no option for it.
    observed = (dsa_index._kernel_refusal, pallas_dsa_index.CHUNK,
                pallas_dsa_index.MAX_COPY)

    def steer(case: str) -> None:
        """``kernel_c<n>`` scores ``n`` positions at a time, ``kernel_m<n>``
        copies up to ``n`` consecutive pages at a time."""
        (dsa_index._kernel_refusal, pallas_dsa_index.CHUNK,
         pallas_dsa_index.MAX_COPY) = observed
        if case == "xla":
            dsa_index._kernel_refusal = lambda *a, **k: "the sweep's xla case"
        elif args.rehearse:
            dsa_index._kernel_refusal = lambda *a, **k: None
        for prefix, name in (("kernel_c", "CHUNK"), ("kernel_m", "MAX_COPY")):
            if case.startswith(prefix):
                setattr(pallas_dsa_index, name, int(case[len(prefix):]))

    def modules(case: str, occupied: int):
        def scores(q, w, plane, tables, t):
            if case == "xla":
                return dsa_index.paged_scores(q, w, plane, tables, t,
                                              page=page, block=block)
            padded = dsa_index._padded_tables(tables, block // page)
            return pallas_dsa_index.scan_scores(
                q, w, plane, padded, t, page=page, block=block,
                interpret=bool(args.rehearse),
            )

        def select(q, w, plane, tables, t):
            return dsa_index.select(q, w, plane, tables, t, page=page, block=block,
                                    topk=topk, site="dsa_index_decode")

        # The modules' names in the trace tell the cases apart.
        scores.__name__ = f"sweep_{case}_{occupied}_scores"
        select.__name__ = f"sweep_{case}_{occupied}_select"
        return {"scores": jax.jit(scores), "select": jax.jit(select)}

    out_rows, runs = [], []
    for occupied in map(int, args.occupied.split(",")):
        t, tables = site(occupied)
        operands = (q, w, plane, tables, t)
        want = None
        for case in args.cases.split(","):
            row = dict(rows=rows, occupied=occupied, case=case,
                       contexts=sorted(int(x) + 1 for x in np.asarray(t) if x),
                       page=page, positions_a_pass=block)
            telemetry.get_log().clear()
            steer(case)
            try:
                fn = modules(case, occupied)
                s = np.asarray(jax.block_until_ready(fn["scores"](*operands)))
                chosen = jax.block_until_ready(fn["select"](*operands))
                read, padded = dsa_index.pages_read(q, plane, tables, t,
                                                    page=page, block=block)
            except Exception as e:  # a form Mosaic refuses is a row, not the end
                row["error"] = str(e)[:300]
                out_rows.append(row)
                continue
            runs.append((row, fn, operands))
            row["dispatch"] = sorted({
                f"{e.attrs['impl']} ({e.attrs['reason']})"
                for e in telemetry.get_log().snapshot()
                if e.name == "ops.dsa_index_dispatch"
            })
            row["pages_read_share"] = int(read) / int(padded)
            positions = np.asarray(chosen[0])
            if want is None:
                want = (s, positions)  # the first case is the one the others meet
            finite = np.isfinite(want[0])
            row["same_minus_inf"] = bool(np.array_equal(finite, np.isfinite(s)))
            scale = float(np.max(np.abs(want[0][finite]), initial=1e-30))
            row["score_gap"] = float(np.max(
                np.abs(s[finite] - want[0][finite]), initial=0.0
            )) / scale
            row["same_selection"] = bool(np.array_equal(positions, want[1]))
            out_rows.append(row)
    steer("kernel")

    for row, fn, operands in [] if args.rehearse else runs:
        with tempfile.TemporaryDirectory() as trace_dir:  # a trace a case
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # device events are all it reads
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            for module in fn.values():
                for _ in range(REPS):
                    out = module(*operands)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        kernel = trace_reduce.ops_matching(trace, r"^%?dsa_index_scan[.\d]* = ")
        for which in fn:
            spans = trace_reduce.module_runs(
                trace, rf"^jit_sweep_{row['case']}_{row['occupied']}_{which}\b"
            )
            durs = [e.dur * 1e3 for evs in spans.values() for e in evs]
            row[f"{which}_module_ms"] = statistics.median(durs) if durs else None
            inside = [
                e.dur * 1e3 for chip, evs in spans.items()
                for e in readers._inside(kernel.get(chip, []), evs)
            ]
            if inside:
                row[f"{which}_dsa_index_scan_ms"] = statistics.median(inside)

    os.makedirs("chiprun_out/dsa_index_sweep", exist_ok=True)
    name = f"{args.layout}_{args.occupied.replace(',', '-')}_{args.seed}.jsonl"
    with open(os.path.join("chiprun_out/dsa_index_sweep", name), "w") as f:
        for row in out_rows:
            f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
