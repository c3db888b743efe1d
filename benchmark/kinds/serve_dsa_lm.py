"""A serving cell of DeepSeek-V3.2-Exp's block (``models.dsa_lm``):
``LanguageModel.serve()`` -> ``ServingEngine.submit`` under an open loop at a
fixed rate, over resident documents.

The same cell as ``kinds/serve_lm.py`` in every step but the model's own:
the traffic (its documents, requests, client and window counters) and the
two links that decide ``correct`` are that kind's, imported from it
unchanged (``make_documents``, ``make_requests``, ``serve_documents``,
``_replay``, ``_first_difference``, ``_step_gaps``, ``_gap_numbers``); this
kind brings the weights (``weights_dsa_lm``), the operation counts
(``flops_dsa_lm``), the reference (``reference/dsa_lm.py``), the sample (on
one document), the scopes it reads, and a number held at 0:
``moe_launches_unequal``, the launches (all of the run's, set-up included)
in which the expert assignments the router made to held experts and those
the grouped products were given differ.

The reference (``reference/dsa_lm.py``) computes the sampled requests'
document itself, in float32 at ``highest`` (``document_rows``: every layer's
latent rows and index keys, its own indexer and exact top-2,048), then each
request from the document's snapshot on over those rows. The sampled
requests share one document, the shortest that a finished request of the
window names, so that it is computed once (about 35 s at 32k on the chip,
and once more for each stand-in). At a step and
layer where the program's selection is a near-tie of the reference's own
(no position left out of it scores over one in it by more than
``SELECTION_TOLERANCE`` deviations of the step's scores) the reference
attends the program's: rounding settles such ties either way, and a flipped
position carries an average key's share of the attention, so the logits'
gap would otherwise measure the ties. Compared, beside the two links: the
gap of the program's latent rows of the document's first layer from the
reference's (``latent_gap_layer0``, rounding alone: no selection has acted
on them yet), and the share of the program's selected positions that score
under the reference's 2,048th by more than the tolerance
(``selected_outside_share``); printed, the gaps of the rows and index keys
layer by layer, the share of steps and layers whose selection the reference
took, and how deep the others lay.

``--control``: the reference in int8 and in float8 (``control_int8``,
``control_fp8``; document and requests), with the indexer's selection off at
the requests' steps, every earlier position attended
(``fault_select_off``), and with the router's group limit off, the top 8 of
all 256 experts (``fault_group_limit_off``; document and requests), stand in
the program's place over the same sampled requests (``all`` names these
four). ``fault_live_page``, asked for by name, is planted in the program
itself: half way through the window one live row's own newest page of
latent rows is overwritten on the device.
"""

from __future__ import annotations

import gc
import os
import re
import resource
import statistics
import time

import numpy as np

from benchmark import (
    compare,
    flops_dsa_lm,
    phase_readers,
    scope_trace,
    trace_reduce,
    traffic,
    weights_dsa_lm,
)
from benchmark.kinds import serve as serve_kind
from benchmark.kinds import serve_lm

SCOPES = (
    "lm.mla", "lm.mla_proj", "lm.dsa.index", "lm.mlp", "lm.moe.route",
    "lm.moe.experts", "lm.moe.shared", "lm.head",
)
STAND_INS = ("control_int8", "control_fp8", "fault_select_off", "fault_group_limit_off")
LIVE_FAULT = "fault_live_page"
DISPATCH = ("ops.dsa_index_dispatch", "ops.latent_attention_dispatch")
RAGGED_DOT = r"^%?ragged-dot"
# In deviations of a step's index scores: a selection of the program's whose
# worst inversion against the reference's own scores is no deeper is a
# near-tie of it, and the reference attends it (``reference.dsa_lm.adopted``).
# The program's picks deeper than this under the reference's 2,048th score
# are ``selected_outside_share`` (PERF.md, section 2, has the readings).
SELECTION_TOLERANCE = 0.05


def build_engine(cfg: dict, seed: int):
    """Weights, bundle, started engine: what a run and the rate sweep share.
    The program's model is imported first, so that a tree without it stops
    here, before any weight is made."""
    import jax

    from machine_learning_apache_spark_tpu.inference import LanguageModel
    from machine_learning_apache_spark_tpu.models import dsa_lm  # noqa: F401

    params = weights_dsa_lm.make_params(seed, cfg)
    jax.block_until_ready(params)
    lm = LanguageModel(weights_dsa_lm.model_config(cfg), params)
    engine = lm.serve(**dict(cfg["engine"]))
    return params, lm, engine


def run(run) -> None:
    with run.phase("program_imports"):
        from machine_learning_apache_spark_tpu import telemetry
        from machine_learning_apache_spark_tpu.models import dsa_lm  # noqa: F401

    cfg, mix = run.cfg, run.mix
    max_new = int(cfg["engine"]["max_new_tokens"])
    vocab = int(cfg["vocab_size"])

    with run.phase("weights_engine_and_compile"):
        params, lm, engine = build_engine(cfg, run.seed)
    runtime = engine.runtime
    with run.phase("documents_and_requests"):
        docs = serve_lm.make_documents(mix["documents"], vocab, run.seed)
        warm_s = float(mix["warm_seconds"])
        schedule = traffic.due_times(
            mix["arrivals"], warm_s + run.seconds + 1.0, run.seed
        )
        requests = serve_lm.make_requests(mix, docs, vocab, run.seed, len(schedule))
    with run.phase("documents_prefill"):
        serve_lm.serve_documents(engine, docs, run.note)
    run.note(f"after the documents: {runtime.stats()}")

    client = serve_kind.OpenLoop(engine, [ids for _, ids in requests], schedule)
    with run.phase("gc_collect_and_freeze"):
        gc.collect()
        gc.freeze()
    t_warm = time.monotonic()
    client.due = t_warm + 0.05 + client.due
    client.start()
    time.sleep(max(t_warm + 0.05 + warm_s - time.monotonic(), 0))
    run.setup["warm_period_s"] = time.monotonic() - t_warm

    # -- the window ---------------------------------------------------------
    metrics = engine.metrics
    run.setup_events = [
        e for e in telemetry.get_log().snapshot() if e.name.endswith("_dispatch")
    ]
    telemetry.get_log().clear()
    from benchmark.run import GcWatch

    gc_watch = GcWatch()
    gc_watch.start()
    recompiles0 = engine.recompiles_after_warmup or 0
    ledger0, counters0 = metrics.ledger(), dict(runtime.counters)
    usage0 = resource.getrusage(resource.RUSAGE_SELF)
    tokens0, w0 = metrics.tokens_out, time.monotonic()
    run.mark_window_start(w0)
    planted = (
        _plant_live_fault(runtime, w0 + run.seconds / 2)
        if LIVE_FAULT in run.control else None
    )
    trace_dir = None
    if run.trace:
        trace_dir = run.start_trace(
            after=float(mix["trace_after_s"]), seconds=float(mix["trace_seconds"])
        )
    time.sleep(max(w0 + run.seconds - time.monotonic(), 0))
    tokens1, w1 = metrics.tokens_out, time.monotonic()
    usage1 = resource.getrusage(resource.RUSAGE_SELF)
    ledger1, counters1 = metrics.ledger(), dict(runtime.counters)
    run.events = telemetry.get_log().snapshot()
    gc_report = gc_watch.stop()
    dropped = getattr(telemetry.get_log(), "dropped", 0)
    recompiles1 = engine.recompiles_after_warmup or 0
    run.window_s = w1 - w0
    run.trace_dir = trace_dir
    if run.trace:
        run.stop_trace()

    client.stop()
    unanswered = client.wait_for_answers(serve_lm.ANSWER_WAIT_S)
    run.read_memory()
    run.note(f"conservation ledger: "
             f"{metrics.check_conservation(in_flight=unanswered)}")
    _report_dispatch(run)
    if trace_dir:
        _read_scopes(run, trace_dir)

    # -- what the window held -----------------------------------------------
    mine = [r for r in client.records if w0 <= r.due < w1]
    run.attempted = len(mine)
    run.failed = sum(1 for r in mine if not r.finished_ok())
    done_in = [r for r in client.records if r.finished_ok() and w0 <= r.done < w1]
    latencies = [
        (r.done - r.due) if r.finished_ok() else run.window_s for r in mine
    ]
    run.e2e["latency_p50_ms"] = statistics.median(latencies) * 1e3
    run.e2e["latency_p95_ms"] = traffic.nearest_rank(latencies, 95) * 1e3
    late = [r.submit - r.due for r in mine]
    run.note(
        f"generator lateness: median {statistics.median(late) * 1e3:.3f} ms, "
        f"max {max(late) * 1e3:.3f} ms over {len(mine)} requests due"
    )
    answered = [
        r for r in client.records if r.finished_ok() and r.due < w1 and r.done >= w0
    ]
    served = {id(r): np.asarray(r.req.future.result()) for r in answered}
    launches = serve_kind._launch_spans(run.events)
    gaps = [b[0] - a[1] for a, b in zip(launches, launches[1:])]
    rows = [
        e.attrs["rows"] for e in run.events
        if e.kind == "span_start" and e.name == "serving.batch"
    ]

    def needed(r) -> float:
        doc, ids = requests[r.idx]
        admit = r.req.trace.attrs("admit")
        resumed = (
            len(docs[doc]) // runtime.page_size * runtime.page_size
            - runtime.page_size
        ) if admit.get("kind") == "hit" else 0
        return flops_dsa_lm.request_flops(cfg, len(ids), resumed, len(served[id(r)]))

    delta = lambda k: counters1[k] - counters0[k]  # noqa: E731
    run.counters.update(
        completed=ledger1["completed"] - ledger0["completed"],
        tokens_out=tokens1 - tokens0,
        launches=len(launches),
        launch_ms=[(e - s) * 1e3 for s, e in launches],
        launch_gap_ms=[g * 1e3 for g in gaps],
        rows_per_launch=rows,
        queue_wait_ms=[
            r.req.trace.breakdown().get("queue_wait_s", 0.0) * 1e3
            for r in mine if r.finished_ok()
        ],
        recompiles=recompiles1 - recompiles0,
        window_flops=sum(needed(r) for r in done_in),
        prompt_tokens=delta("prompt_tokens"),
        resumed_tokens=delta("resumed_tokens"),
        selected_share_sum=delta("selected_share_sum"),
        selected_share_n=delta("selected_share_n"),
        snapshots_taken=delta("snapshots_taken"),
    )
    _launch_costs(run, cfg, runtime.steps_per_launch)
    run.note(
        f"window: completed {run.counters['completed']}, tokens_out "
        f"{tokens1 - tokens0}, launches {len(launches)}, mean rows a launch "
        f"{(sum(rows) / len(rows)) if rows else float('nan'):.2f}, prompt "
        f"positions admitted {delta('prompt_tokens')} of which resumed from a "
        f"snapshot {delta('resumed_tokens')}, prefill chunks "
        f"{delta('prefill_chunks')}, snapshots taken {delta('snapshots_taken')}, "
        f"elapsed {run.window_s:.4f} s, longest gap between launches "
        f"{(max(gaps) * 1e3) if gaps else float('nan'):.1f} ms, {gc_report}, "
        f"telemetry events dropped by the ring {dropped}, recompiles "
        f"{run.counters['recompiles']}, unanswered after the close {unanswered}"
    )
    run.note(
        f"experts in the window: assignments to held experts "
        f"{delta('moe_assignments_local')} by the router, "
        f"{delta('moe_assignments_computed')} computed, "
        f"{delta('moe_experts_touched')} expert reads over "
        f"{delta('selected_share_n')} row steps; launches in which the two "
        f"differ {delta('launches_unequal')} in the window, "
        f"{counters1['launches_unequal']} in the run"
    )
    run.note(
        f"host in window: process CPU {usage1.ru_utime - usage0.ru_utime:.2f} s"
        f" user + {usage1.ru_stime - usage0.ru_stime:.2f} s system over "
        f"{len(os.sched_getaffinity(0))} cores, load average "
        f"{os.getloadavg()[0]:.2f}"
    )
    run.note(f"after the window: {runtime.stats()}")

    # -- the window's tokens, then the sample's logits, while the engine idles --
    t_replay = time.monotonic()
    window = [dict(ids=requests[r.idx][1], served=served[id(r)]) for r in answered]
    serve_lm._replay(runtime, window)
    diverged = [
        i for i, s in enumerate(window)
        if not np.array_equal(s["served"], s["replayed"])
    ]
    run.note(
        f"replay of the window's {len(window)} answered requests through the "
        f"runtime: {time.monotonic() - t_replay:.1f} s, served tokens not given "
        f"back by {len(diverged)}"
        + "".join(
            f"; request {answered[i].idx} from step "
            f"{serve_lm._first_difference(window[i]['served'], window[i]['replayed'])}"
            for i in diverged[:4]
        )
    )
    picks = _sample(run, done_in, requests, docs)
    sample = [
        dict(doc=requests[r.idx][0], ids=requests[r.idx][1], served=served[id(r)])
        for r in picks
    ]
    t_replay = time.monotonic()
    serve_lm._replay(runtime, sample, logits=True)
    start = min((s["resumed"] for s in sample), default=0)
    program_rows = _cache_rows(runtime, sample[0]["ids"], start) if sample else []
    run.note(f"replay of {len(sample)} sampled requests with their logits, "
             f"and the cache rows of their resumed prefixes: "
             f"{time.monotonic() - t_replay:.1f} s")
    unequal = runtime.counters["launches_unequal"]
    del window
    engine.stop()
    t_max = runtime.max_context
    del engine, lm, runtime, client, served, answered, done_in, mine, requests, docs
    telemetry.reset()
    gc.unfreeze()
    gc.collect()

    t_ref = time.monotonic()
    numbers, printed, control = reference_numbers(
        run, cfg, params, sample, start, program_rows, max_new, t_max,
        len(diverged),
    )
    numbers.append(("moe_launches_unequal", float(unequal)))
    run.note(
        f"reference: {len(sample)} requests of "
        f"{[len(s['ids']) for s in sample]} prompt positions on one document "
        f"of which it computed the first {start} itself, resumed by the "
        f"program at {[s['resumed'] for s in sample]}, "
        f"{time.monotonic() - t_ref:.1f} s after the window"
    )
    # Tokens and assignments agree or they do not: held at 0 whatever limits
    # a rehearsal keeps of the cell's file.
    run.compared, not_compared = compare.with_limits(numbers, {
        "replay_diverged": 0, "moe_launches_unequal": 0,
        **run.cell_file["limits"],
    })
    run.note(f"not compared: { {**not_compared, **printed} }")
    if planted is not None:
        control = dict(control or {})
        control[LIVE_FAULT] = dict(
            compare.verdict(run.compared, not_compared), planted=planted
        )
    run.control_report = control


def _report_dispatch(run) -> None:
    seen = {}
    for e in run.setup_events + run.events:
        if e.name in DISPATCH and e.attrs:
            key = (e.name, e.attrs.get("site"), e.attrs.get("impl"),
                   tuple(sorted((k, v) for k, v in e.attrs.items()
                                if k not in ("site", "impl", "reason"))))
            seen[key] = seen.get(key, 0) + 1
    for (name, site, impl, shape), n in seen.items():
        run.note(f"{name} site {site}: {impl} {dict(shape)} x{n}")


def _read_scopes(run, trace_dir: str) -> None:
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return
    t = time.monotonic()
    for module, what in ((phase_readers.LAUNCH_MODULE, "launch"),
                         (phase_readers.PREFILL_MODULE, "prefill chunk")):
        found = scope_trace.scope_seconds(path, SCOPES, module, note=run.note)
        if found is None:
            continue
        seconds, runs, run_s = found
        # XLA expands the grouped products (``ragged_dot``) into Mosaic calls
        # of its own, named ``%ragged-dot-*``, that carry no scope (read in
        # the launch compiled for a described v5e): they are the experts'.
        seconds["lm.moe.experts"] += _named_op_seconds(path, RAGGED_DOT, module)
        if what == "launch":
            run.counters["scope_ms"] = {k: v * 1e3 for k, v in seconds.items()}
        outside = run_s - sum(seconds.values())
        run.note(
            f"device ms a {what} under the program's scopes, over {runs} whole "
            f"runs of {run_s * 1e3:.2f} ms (read in {time.monotonic() - t:.1f} "
            "s): " + ", ".join(f"{k} {v * 1e3:.3f}" for k, v in seconds.items())
            + f"; under none of them {outside * 1e3:.3f}"
        )


def _named_op_seconds(path: str, pattern: str, module_pattern: str) -> float:
    """Device seconds a whole run of the module spends in operations whose
    HLO text matches ``pattern``, the mean over the whole runs; 0 where the
    trace cannot be read."""
    try:
        with open(path, "rb") as f:
            data = memoryview(f.read())
        planes = [
            scope_trace._device_plane(v)
            for field, v in scope_trace._fields(data) if field == 1
        ]
    except Exception:  # noqa: BLE001  (a reader must not fail the run)
        return 0.0
    module, op = re.compile(module_pattern), re.compile(pattern)
    total, runs = 0.0, 0
    for _, ops, events in filter(None, planes):
        modules = [
            (start, dur) for start, dur, key in events.get(trace_reduce.MODULES_LINE, [])
            if module.search(ops.get(key, ("", ""))[0])
        ]
        if not modules:
            continue
        typical = statistics.median(dur for _, dur in modules)
        whole = [(a, a + dur) for a, dur in modules if dur >= 0.9 * typical]
        runs += len(whole)
        for start, dur, key in events.get(trace_reduce.OPS_LINE, []):
            if op.search(ops.get(key, ("", ""))[0]) and any(
                a <= start and start + dur <= b + 1e-9 for a, b in whole
            ):
                total += dur
    return total / runs if runs else 0.0


def _launch_costs(run, cfg: dict, steps: int) -> None:
    """Needed (FLOPs, bytes) of a launch's latent attention, indexer and held
    experts, the mean over the launches of the traced seconds (all the
    window's where none was traced), from the rows, contexts and expert
    counters each launch's fold span recorded."""
    folds = [
        s for s in phase_readers.run_spans(run)
        if s.name == "serving.launch.fold" and s.attrs.get("rows")
    ]
    _, traced = phase_readers.regimes(run, folds)
    folds = traced or folds
    if not folds:
        return
    costs = {"mla": [0.0, 0.0], "index": [0.0, 0.0], "experts": [0.0, 0.0]}
    for s in folds:
        rows, context = int(s.attrs["rows"]), float(s.attrs.get("context", 0))
        contexts = [context / rows] * rows
        parts = {
            "mla": flops_dsa_lm.launch_cost(
                cfg, flops_dsa_lm.mla_step_cost, contexts, steps),
            "index": flops_dsa_lm.launch_cost(
                cfg, flops_dsa_lm.index_step_cost, contexts, steps),
            "experts": flops_dsa_lm.experts_cost(
                cfg, s.attrs.get("moe_experts_touched", 0),
                s.attrs.get("moe_assignments_computed", 0)),
        }
        for name, (f, b) in parts.items():
            costs[name][0] += f / len(folds)
            costs[name][1] += b / len(folds)
    for name, cost in costs.items():
        run.counters[f"{name}_cost_per_launch"] = tuple(cost)
    run.note(
        f"launches costed: {len(folds)}, mean rows "
        f"{statistics.mean(s.attrs['rows'] for s in folds):.1f}, mean context a "
        f"row {statistics.mean(s.attrs.get('context', 0) / s.attrs['rows'] for s in folds):.0f}, "
        "mean expert reads "
        f"{statistics.mean(s.attrs.get('moe_experts_touched', 0) for s in folds):.1f}"
    )


def _plant_live_fault(runtime, at: float) -> dict:
    """``fault_live_page``: before the first launch from ``at`` on, the latent
    rows of one live row's own newest page (every layer) are overwritten on
    the device, as a neighbour's stray write would leave them. Runs on the
    engine's thread, inside ``runtime.launch``. Returns a dict that is filled
    once the fault is in."""
    planted, launch = {}, runtime.launch
    page_size = runtime.page_size

    def launch_over_a_fault(*args, **kwargs):
        if not planted and time.monotonic() >= at:
            left = {
                row: int(runtime._last_pos[row] - runtime._pos[row])
                for row, _ in runtime.active_rows()
            }
            row = max(left, key=left.get, default=None)
            if row is not None and left[row] > 0:
                pos = int(runtime._pos[row])
                page = int(runtime._tables[row, (pos - 1) // page_size])
                if runtime.mem_pool.refcount(page) != 1:
                    raise RuntimeError(f"page {page} of row {row} is shared")
                planes = runtime.cache["latent"]
                at_rows = page * page_size + np.arange(page_size)
                for layer, plane in enumerate(planes):
                    planes[layer] = plane.at[at_rows].set(30.0)
                planted.update(
                    request=runtime._req_of_row[row].id, row=row, page=page,
                    position=pos, steps_left=left[row],
                )
        return launch(*args, **kwargs)

    runtime.launch = launch_over_a_fault
    return planted


def _cache_rows(runtime, ids, n: int) -> list:
    """The program's own cache rows of the first ``n`` positions of ``ids``
    (a snapshot's), every layer, as the host's float32 arrays: ``[(latents
    [n, kv_rank + rope], keys [n, index_head_dim]), ...]``. Read while the
    engine idles; the reference's own rows of the document are compared
    with them."""
    if not n:
        return []
    cfg, page = runtime.cfg, runtime.page_size
    entry = runtime.prefix_cache.lookup(ids, n, owner="reference")
    pages = np.asarray(entry["pages"][: n // page])
    at = (pages[:, None] * page + np.arange(page)).reshape(-1)
    out = [
        (np.asarray(latent[at][:, :cfg.latent_width], np.float32),
         np.asarray(keys[at], np.float32))
        for latent, keys in zip(runtime.cache["latent"], runtime.cache["index"])
    ]
    runtime.mem_pool.release_owner("reference")
    return out


def _sample(run, done_in, requests, docs) -> list:
    """Up to ``compare_requests`` finished requests on one document, the
    shortest among the documents of the finished requests, its longest
    prompt first, the rest drawn by the seed: the reference computes the
    document once for them all, and on the shortest in about 35 s where the
    longest takes about 70 (a run has to end inside the harness's limit)."""
    if not done_in:
        return []
    rng = np.random.default_rng([int(run.seed), 4])
    doc = min({requests[r.idx][0] for r in done_in}, key=lambda d: (len(docs[d]), d))
    on = [r for r in done_in if requests[r.idx][0] == doc]
    first = max(on, key=lambda r: len(requests[r.idx][1]))
    rest = [on[i] for i in rng.permutation(len(on)) if on[i] is not first]
    return [first] + rest[: int(run.cell_file["compare_requests"]) - 1]


def reference_numbers(run, cfg, params, sample, start: int, program_rows,
                      max_new: int, t_max: int, diverged: int):
    """The compared numbers (``diverged`` among them), the printed ones, and
    the stand-ins' verdicts. The sampled requests share one document; the
    reference computes its first ``start`` positions itself
    (``document_rows``), then each request from there on over those rows,
    taking the program's selection at a step and layer where it is a
    near-tie of its own (``reference.dsa_lm.adopted``)."""
    import jax

    from benchmark.reference import dsa_lm as ref

    if not sample:
        return serve_lm._gap_numbers(np.array([np.nan]), 0, diverged), {}, None
    block = int(run.cell_file["reference_block"])
    t_max = -(-t_max // block) * block + block
    kw = dict(
        block=block, key_block=int(run.cell_file["reference_key_block"]),
        capacity=int(run.cell_file["reference_capacity"]), t_max=t_max,
        query_rows=int(run.cell_file["reference_query_rows"]),
    )
    bias = np.asarray(params.get("logit_bias", 0.0), np.float32)
    document = sample[0]["ids"][:start]

    def document_rows(**variant):
        t = time.monotonic()
        rows = ref.document_rows(params, cfg, document, **kw, **variant) if start else []
        run.note(f"reference {variant or ''} of the document's first {start} "
                 f"positions: {time.monotonic() - t:.1f} s")
        return rows

    def requests(rows, adopt, **variant):
        for s in sample:
            t = time.monotonic()
            ask = (np.concatenate([s["ids"][start:], s["served"][:-1]]),
                   np.arange(len(s["ids"]) - 1, len(s["ids"]) - 1 + len(s["served"])),
                   adopt(s))
            ((got),) = ref.forward(
                params, cfg, rows, start, [ask], **kw, tolerance=SELECTION_TOLERANCE,
                **variant,
            )
            run.note(f"reference {variant or ''} of {len(ask[0])} positions "
                     f"from {start}: {time.monotonic() - t:.1f} s")
            yield s, got

    def mask(selected):
        """The program's selections ``[steps, layers, k]`` (-1 in an empty
        slot) as ``[layers, steps, t_max]`` bool."""
        out = np.zeros((selected.shape[1], selected.shape[0], t_max), bool)
        step, layer, slot = np.nonzero(selected >= 0)
        out[layer, step, selected[step, layer, slot]] = True
        return out

    def row_gaps(rows, theirs) -> list:
        """Layer by layer, the relative gap of two sets of the document's
        latent rows, and of its index keys."""
        gaps = []
        for mine, want in zip(rows, theirs):
            gaps.append(tuple(
                float(np.linalg.norm(np.asarray(mine[part][:start]) - want[part][:start])
                      / np.linalg.norm(want[part][:start]))
                for part in range(2)
            ))
        return gaps

    def judged(logits_of, upto_of, got, rows):
        """The numbers of ``logits_of(s)`` and ``rows`` against the
        reference's ``got`` (a list of ``(s, (logits, taken, stats))``) and
        its own rows: those a limit may hold, and the rest, printed."""
        gaps, inversion, outside, picks = [], [], 0, 0
        for s, (logits, _, (inv, out, n)) in got:
            upto = upto_of(s)
            gaps.append(serve_lm._step_gaps(logits_of(s)[:upto], logits[:upto], bias))
            inversion.append(inv[:, :upto].reshape(-1))
            outside += int(out[:, :upto].sum())
            picks += int(n[:, :upto].sum())
        inversion = np.concatenate(inversion)
        by_layer = row_gaps(rows, f32_rows) or [(0.0, 0.0)]
        numbers = serve_lm._gap_numbers(np.concatenate(gaps), 0, 0) + [
            ("selected_outside_share", outside / max(picks, 1)),
            ("latent_gap_layer0", by_layer[0][0]),
        ]
        return numbers, dict(
            row_gaps_by_layer=[[round(v, 6) for v in g] for g in by_layer],
            inversion_quantiles=[
                round(float(np.quantile(inversion, q)), 5) for q in (0.5, 0.9, 0.99, 1.0)
            ],
            adopted_share=float(np.mean(inversion <= SELECTION_TOLERANCE)),
        )

    with ref.on_device(jax.local_devices()[0]):
        f32_rows = document_rows()
        got = list(requests(f32_rows, lambda s: mask(s["selected"])))

        def upto(s):
            # Compared as far as the replay with logits (another compiled
            # program than the window's) fed the served tokens back.
            return min(serve_lm._first_difference(s["served"], s["replayed"]) + 1,
                       len(s["served"]))

        numbers, printed = judged(lambda s: s["logits"], upto, got, program_rows)
        short = sum(1 for s in sample if len(s["served"]) != max_new)
        numbers = [
            (k, float(short) if k == "served_len_short"
             else float(diverged) if k == "replay_diverged" else v)
            for k, v in numbers
        ]
        printed.update({
            "served_is_reference_first_share": float(np.mean(np.concatenate([
                np.argmax(logits, -1) == s["served"] for s, (logits, _, _) in got
            ]))),
            "steps_compared": int(sum(upto(s) for s in sample)),
            "logits_replay_left_the_served_tokens": sum(
                int(upto(s) < len(s["served"])) for s in sample
            ),
        })
        control = None
        stand_ins = compare.chosen(
            [c for c in run.control if c != LIVE_FAULT], STAND_INS
        )
        if stand_ins:
            variants = {
                "control_int8": dict(matmul="int8"),
                "control_fp8": dict(matmul="fp8"),
                "fault_select_off": dict(select="all"),
                "fault_group_limit_off": dict(group_limit=False),
            }
            control = {}
            for name in stand_ins:
                t = time.monotonic()
                # the selection off is the requests' own: their documents'
                # rows are the reference's
                on_rows = {k: v for k, v in variants[name].items() if k != "select"}
                rows = document_rows(**on_rows) if on_rows else f32_rows
                theirs = {id(s): v for s, v in requests(rows, lambda s: None, **variants[name])}
                against = list(requests(f32_rows, lambda s: theirs[id(s)][1]))
                stand, more = judged(
                    lambda s: theirs[id(s)][0], lambda s: len(s["served"]), against, rows
                )
                control[name] = compare.verdict(*compare.with_limits(
                    stand, run.cell_file["limits"]
                ))
                control[name].update(more)
                control[name]["seconds"] = round(time.monotonic() - t, 1)
                control[name]["requests"] = len(sample)
                del rows
    return numbers, printed, control


def toy(cfg: dict, mix: dict, cell_file: dict) -> None:
    """This kind's sizes for a CPU rehearsal (``benchmark.rehearse``): every
    width the kind reads cut, 1 dense + 2 expert layers, 8 of 16 router
    outputs held in 2 groups, an index top-k smaller than the documents,
    pages of 8 positions. 16 index heads: with few, a position's score is
    often 0 (every head's ReLU 0) and the top-k would fall among ties."""
    cfg.update(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        index_n_heads=16, index_head_dim=16, index_topk=48,
        num_layers=3, first_k_dense_replace=1,
        router_width=16, experts_held=[0, 8], n_routed_experts=8,
        n_group=2, topk_group=1, num_experts_per_tok=4,
        moe_intermediate_size=32,
        program=dict(page=8, index_block=128, query_block=16),
    )
    cfg["engine"] = dict(
        max_context=448, max_active=4, max_new_tokens=8, prefill_chunk=32,
        steps_per_launch=4, num_pages=220, prefix_cache_size=8,
        prefill_budget=128, max_queue_depth=64,
    )
    mix.update(warm_seconds=0.3, trace_after_s=0.2, trace_seconds=0.5)
    mix["documents"] = dict(count=4, shortest=96, ratio_log2_step=0.6, multiple_of=8)
    mix["questions"] = dict(
        dist="lognormal", median=12, sigma=0.5, min=3, max=40, count=32
    )
    mix["arrivals"] = dict(mix["arrivals"], rate_per_s=12.0)
    cell_file.update(
        compare_requests=3, reference_block=128, reference_key_block=64,
        reference_capacity=16, reference_query_rows=16,
    )
