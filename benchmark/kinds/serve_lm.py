"""A serving cell of a decoder-only language model: ``LanguageModel.serve()``
-> ``ServingEngine.submit`` under an open loop at a fixed rate, over resident
documents.

Set-up: weights from the seed, the engine (its four programs compiled), then
every document of the mix served once, one after the other: that prefill
leaves each document's prefix snapshot resident, and it is the larger part of
``setup_s``. Then the open loop starts; its first ``warm_seconds`` are set-up
too. Every request of the loop is one document (uniform) followed by a fresh
question.

The client is the encoder-decoder serving kind's (``kinds/serve.py``: one
submitter thread, a done-callback that only stamps the time), and so are the
window's counters, so the ``.steady`` readers read this kind's runs unchanged.

What decides ``correct`` is a chain of two links, made once the window has
closed and every request has answered, while the engine idles:

1. **The window's tokens.** Every request that was answered and lived in the
   window (due before its close, done after its start) is served again on
   the idle runtime, ``max_active`` at a time, through the
   very programs the window ran (a prefix hit, the question's chunk, 64
   greedy steps). ``replay_diverged`` counts the requests whose served tokens
   the replay does not give back, and its limit is 0: the programs are
   deterministic and a row's tokens do not depend on its neighbours, so a
   token that differs was made wrong under load (a row reading another's
   pages or state, a block table set wrong by ``grow``, a shared page
   overwritten).
2. **The replay's logits.** A seeded sample of those requests (at least
   ``compare_documents`` documents, the longest prompt among them) is served
   once more through ``launch(logits_of=)``, the launch program that also
   hands back the sampled rows' logits and selections. Then the engine goes,
   and the float32 reference (``reference/sala_lm.py``) runs the whole
   sequence document + question + served tokens of each sampled request.
   Compared, a served step: the widest gap over the vocabulary between the
   program's logits and the reference's, in units of the reference logits'
   deviation at that step: ``served_gap_mean`` and ``served_gap_p90`` over
   the sample's steps (``served_gap_max`` is read too), and
   ``served_len_short``, how many sampled answers were not
   ``max_new_tokens`` long. Printed beside them: the share of selected blocks
   that differ between program and reference, how often the served token is
   the reference's first, and how many of the sampled replays left the served
   tokens (a near-tie between two compiled programs: the steps after it are
   not compared).

``--control``: the reference in int8 and in float8 (``control_int8``,
``control_fp8``) and two planted faults (``fault_window_only``: the sparse
layers attend the forced blocks alone, no top-k; ``fault_state_zero``: the
lightning state is zero at the position a prefix hit resumed from) stand in
the program's place over the same sampled requests (``all`` names these
four). ``fault_live_page``, asked for by name, is planted in the program
itself: half way through the window the values in one live row's own newest
page are overwritten on the device, and the run has to end not ``correct``.
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
    flops_sala_lm,
    phase_readers,
    scope_trace,
    trace_reduce,
    traffic,
    weights_sala_lm,
)
from benchmark.kinds import serve as serve_kind

ANSWER_WAIT_S = 120.0
SCOPES = ("lm.sparse_attn", "lm.sparse_attn.select", "lm.lightning", "lm.mlp", "lm.head")
STAND_INS = ("control_int8", "control_fp8", "fault_window_only", "fault_state_zero")
LIVE_FAULT = "fault_live_page"


def document_lengths(spec: dict) -> list[int]:
    m = int(spec["multiple_of"])
    return [
        m * int(spec["shortest"] * 2.0 ** (i * spec["ratio_log2_step"]) // m)
        for i in range(int(spec["count"]))
    ]


def make_documents(spec: dict, vocab: int, seed: int) -> list[np.ndarray]:
    """The mix's documents for this seed: the same lengths every seed, in a
    seeded order, ids uniform over the vocabulary."""
    rng = np.random.default_rng([int(seed), 5])
    lengths = rng.permutation(document_lengths(spec))
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lengths]


def make_requests(mix: dict, docs, vocab: int, seed: int, count: int):
    """``count`` requests ``(document index, ids)``: documents in equal
    shares, question lengths the mix's multiset, both in seeded order."""
    rng = np.random.default_rng([int(seed), 6])
    lengths = traffic.length_multiset(mix["questions"])
    which = rng.permutation(np.arange(count) % len(docs))
    q_len = rng.permutation(np.resize(lengths, count))
    return [
        (int(d), np.concatenate(
            [docs[d], rng.integers(0, vocab, int(n)).astype(np.int32)]
        ))
        for d, n in zip(which, q_len)
    ]


def build_engine(cfg: dict, seed: int):
    """Weights, bundle, started engine: what a run and the rate sweep share."""
    import jax

    from machine_learning_apache_spark_tpu.inference import LanguageModel

    params = weights_sala_lm.make_params(seed, cfg)
    jax.block_until_ready(params)
    lm = LanguageModel(weights_sala_lm.model_config(cfg), params)
    engine = lm.serve(**dict(cfg["engine"]))
    return params, lm, engine


def serve_documents(engine, docs, note) -> None:
    """Every document once, in turn: its prefill leaves the snapshot."""
    for i, doc in enumerate(docs):
        t = time.monotonic()
        engine.submit(doc).future.result(timeout=600)
        note(f"document {i}: {len(doc)} positions served in "
             f"{time.monotonic() - t:.2f} s")


def run(run) -> None:
    import jax

    with run.phase("program_imports"):
        from machine_learning_apache_spark_tpu import telemetry

    cfg, mix = run.cfg, run.mix
    max_new = int(cfg["engine"]["max_new_tokens"])
    vocab = int(cfg["vocab_size"])

    with run.phase("weights_engine_and_compile"):
        params, lm, engine = build_engine(cfg, run.seed)
    runtime = engine.runtime
    with run.phase("documents_and_requests"):
        docs = make_documents(mix["documents"], vocab, run.seed)
        warm_s = float(mix["warm_seconds"])
        schedule = traffic.due_times(
            mix["arrivals"], warm_s + run.seconds + 1.0, run.seed
        )
        requests = make_requests(mix, docs, vocab, run.seed, len(schedule))
    with run.phase("documents_prefill"):
        serve_documents(engine, docs, run.note)
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
        e for e in telemetry.get_log().snapshot()
        if e.name.endswith("_dispatch")
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
    unanswered = client.wait_for_answers(ANSWER_WAIT_S)
    run.read_memory()
    run.note(f"conservation ledger: "
             f"{metrics.check_conservation(in_flight=unanswered)}")
    _report_dispatch(run)
    if trace_dir:
        _read_scopes(run, trace_dir, cfg)

    # -- what the window held -----------------------------------------------
    mine = [r for r in client.records if w0 <= r.due < w1]
    failed = [r for r in mine if not r.finished_ok()]
    run.attempted, run.failed = len(mine), len(failed)
    done_in = [
        r for r in client.records if r.finished_ok() and w0 <= r.done < w1
    ]
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
    # Every answer whose request lived in the window, the ones that came
    # after its close too: what the replay has to give back.
    answered = [
        r for r in client.records
        if r.finished_ok() and r.due < w1 and r.done >= w0
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
        return flops_sala_lm.request_flops(
            cfg, len(ids), resumed, len(served[id(r)])
        )

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
        f"host in window: process CPU {usage1.ru_utime - usage0.ru_utime:.2f} s"
        f" user + {usage1.ru_stime - usage0.ru_stime:.2f} s system over "
        f"{len(os.sched_getaffinity(0))} cores, context switches "
        f"{usage1.ru_nvcsw - usage0.ru_nvcsw} voluntary / "
        f"{usage1.ru_nivcsw - usage0.ru_nivcsw} involuntary, load average "
        f"{os.getloadavg()[0]:.2f}"
    )
    run.note(f"after the window: {runtime.stats()}")

    # -- the window's tokens, then the sample's logits, while the engine idles --
    t_replay = time.monotonic()
    window = [dict(ids=requests[r.idx][1], served=served[id(r)]) for r in answered]
    _replay(runtime, window)
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
            f"{_first_difference(window[i]['served'], window[i]['replayed'])}"
            for i in diverged[:4]
        )
    )
    picks = _sample(run, done_in, requests)
    sample = [
        dict(ids=requests[r.idx][1], served=served[id(r)]) for r in picks
    ]
    t_replay = time.monotonic()
    _replay(runtime, sample, logits=True)
    run.note(f"replay of {len(sample)} sampled requests with their logits: "
             f"{time.monotonic() - t_replay:.1f} s")
    del window
    engine.stop()
    t_max = runtime.max_context
    del engine, lm, runtime, client, served, answered, done_in, mine, requests, docs
    telemetry.reset()
    gc.unfreeze()
    gc.collect()

    t_ref = time.monotonic()
    numbers, printed, control = reference_numbers(
        run, cfg, params, sample, max_new, t_max, len(diverged)
    )
    run.note(
        f"reference: {len(sample)} requests of "
        f"{[len(s['ids']) for s in sample]} prompt positions, "
        f"{time.monotonic() - t_ref:.1f} s after the window"
    )
    # Tokens are the same or they are not: held at 0 whatever limits a
    # rehearsal keeps of the cell's file.
    run.compared, not_compared = compare.with_limits(
        numbers, {"replay_diverged": 0, **run.cell_file["limits"]}
    )
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
        if e.name in ("ops.lightning_dispatch", "ops.sparse_attention_dispatch") and e.attrs:
            key = (e.name, e.attrs.get("site"), e.attrs.get("impl"))
            seen[key] = seen.get(key, 0) + 1
    for (name, site, impl), n in seen.items():
        run.note(f"{name} site {site}: {impl} x{n}")


def _read_scopes(run, trace_dir: str, cfg: dict) -> None:
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return
    t = time.monotonic()
    found = scope_trace.scope_seconds(
        path, SCOPES, phase_readers.LAUNCH_MODULE, note=run.note
    )
    if found is None:
        return
    seconds, launches, launch_s = found
    # The lightning state's way between HBM and fast memory: the compiler
    # moves each layer's plane around the step's fused update in copies of
    # its own (slice-start / -done in, copy-start / -done out), which carry
    # no scope. They are the layer's time all the same, told by their shape.
    copies = _state_copy_seconds(path, cfg, run.note)
    seconds["lm.lightning"] += sum(copies.values())
    run.counters["scope_ms"] = {k: v * 1e3 for k, v in seconds.items()}
    outside = launch_s - sum(
        v for k, v in seconds.items() if k != "lm.sparse_attn.select"
    )
    run.note(
        f"device ms a launch under the program's scopes, over {launches} whole "
        f"launches of {launch_s * 1e3:.2f} ms (read in "
        f"{time.monotonic() - t:.1f} s): "
        + ", ".join(f"{k} {v * 1e3:.3f}" for k, v in seconds.items())
        + f"; under none of them {outside * 1e3:.3f}; in lm.lightning, the "
        "state planes' copies that no scope names: "
        + (", ".join(f"{k} {v * 1e3:.3f}" for k, v in sorted(copies.items()))
           or "none found")
    )
    chunk = scope_trace.scope_seconds(path, SCOPES, phase_readers.PREFILL_MODULE)
    if chunk is not None:
        seconds, chunks, chunk_s = chunk
        run.note(
            f"device ms a prefill chunk under the same scopes, over {chunks} "
            f"whole chunks of {chunk_s * 1e3:.2f} ms: "
            + ", ".join(f"{k} {v * 1e3:.3f}" for k, v in seconds.items())
        )


def _state_copy_seconds(path: str, cfg: dict, note) -> dict:
    """Seconds a whole launch spends in operations outside ``lm.lightning``
    whose result or operand is a lightning state plane or a leading slice of
    one (``f32[n, heads, d, d]``), by the operation's kind; containers (the
    loop itself) left out. {} where the trace cannot be read."""
    h, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    plane = re.compile(rf"f32\[\d+,{h},{d},{d}\]")
    kind = re.compile(r"\s([\w-]+)\(")
    inside = re.compile(r"(^|/)lm\.lightning(/|$|:)")
    try:
        with open(path, "rb") as f:
            data = memoryview(f.read())
        planes = [
            scope_trace._device_plane(v)
            for field, v in scope_trace._fields(data) if field == 1
        ]
    except Exception as e:  # noqa: BLE001  (a reader must not fail the run)
        note(f"state copies: cannot read {path}: {e!r}")
        return {}
    module = re.compile(phase_readers.LAUNCH_MODULE)
    total, runs = {}, 0
    for _, ops, events in filter(None, planes):
        modules = [
            (start, dur)
            for start, dur, key in events.get(trace_reduce.MODULES_LINE, [])
            if module.search(ops.get(key, ("", ""))[0])
        ]
        if not modules:
            continue
        typical = statistics.median(dur for _, dur in modules)
        whole = [(a, a + dur) for a, dur in modules if dur >= 0.9 * typical]
        runs += len(whole)
        for start, dur, key in events.get(trace_reduce.OPS_LINE, []):
            hlo, scope = ops.get(key, ("", ""))
            if (inside.search(scope) or trace_reduce.CONTAINER.search(hlo)
                    or not plane.search(hlo)):
                continue
            if any(a <= start and start + dur <= b + 1e-9 for a, b in whole):
                name = kind.search(hlo.split(" = ", 1)[-1])
                name = name.group(1) if name else "other"
                total[name] = total.get(name, 0.0) + dur
    return {k: v / runs for k, v in total.items()} if runs else {}


def _launch_costs(run, cfg: dict, steps: int) -> None:
    """Needed (FLOPs, bytes) of a launch's sparse attention, the mean over
    the launches of the traced seconds (all the window's where none was
    traced), from the rows and contexts the fold span of each launch
    recorded."""
    folds = [
        s for s in phase_readers.run_spans(run)
        if s.name == "serving.launch.fold" and s.attrs.get("rows")
    ]
    _, traced = phase_readers.regimes(run, folds)
    folds = traced or folds
    if not folds:
        return
    flops = bytes_ = 0.0
    for s in folds:
        rows, context = int(s.attrs["rows"]), float(s.attrs.get("context", 0))
        f, b = flops_sala_lm.sparse_launch_cost(cfg, [context / rows] * rows, steps)
        flops, bytes_ = flops + f / len(folds), bytes_ + b / len(folds)
    run.counters["sparse_attn_cost_per_launch"] = (flops, bytes_)
    run.note(
        f"launches costed: {len(folds)}, mean rows "
        f"{statistics.mean(s.attrs['rows'] for s in folds):.1f}, mean context a "
        f"row {statistics.mean(s.attrs.get('context', 0) / s.attrs['rows'] for s in folds):.0f}"
    )


def _sample(run, done_in, requests) -> list:
    """``compare_requests`` finished requests on at least
    ``compare_documents`` documents, the longest prompt among them."""
    if not done_in:
        return []
    k = min(int(run.cell_file["compare_requests"]), len(done_in))
    want_docs = int(run.cell_file["compare_documents"])
    rng = np.random.default_rng([int(run.seed), 4])
    order = [done_in[i] for i in rng.permutation(len(done_in))]
    picks = [max(done_in, key=lambda r: len(requests[r.idx][1]))]
    seen = {requests[picks[0].idx][0]}
    for r in order:  # new documents first
        if len(picks) < k and len(seen) < want_docs and requests[r.idx][0] not in seen:
            picks.append(r)
            seen.add(requests[r.idx][0])
    for r in order:
        if len(picks) < k and all(r is not p for p in picks):
            picks.append(r)
    return picks


def _replay(runtime, items, logits: bool = False) -> None:
    """Serve ``items`` (dicts with ``ids``) again on the idle runtime, as many
    at a time as it has rows, through its own compiled programs; fills each
    item's ``replayed`` tokens. With ``logits`` the launches also hand back
    the rows' logits and selections (``launch(logits_of=)``): each item gets
    ``logits [steps, V]``, ``selected [steps, sparse layers, G, topk]``,
    the ``resumed`` positions and whether it ran ``dense``."""
    from machine_learning_apache_spark_tpu.serving.queue import ServeRequest

    if runtime.any_active():
        raise RuntimeError("the runtime still holds rows after the drain")
    for at in range(0, len(items), runtime.max_active):
        group = items[at: at + runtime.max_active]
        rows = np.arange(len(group))
        reqs = []
        for row, s in enumerate(group):
            if logits:
                s["resumed"] = runtime.prefix_cache.match_length(
                    s["ids"], len(s["ids"]) - 1
                )
            req = ServeRequest(text="", ids=s["ids"], submit_time=0.0)
            if runtime.admit(req, row) is None:
                raise RuntimeError("no pages for the replay")
            reqs.append(req)
            if logits:
                s["logits"], s["selected"] = [], []
                s["dense"] = bool(runtime._dense[row])
        answers = {}
        while runtime.any_active():
            if runtime.grow():
                raise RuntimeError("no pages for the replay")
            active = dict(runtime.active_rows())
            result = runtime.launch(logits_of=rows if logits else None)
            if logits:
                got, selected = (np.asarray(x) for x in runtime.captured)
                for row in active:
                    group[row]["logits"].append(got[:, row])
                    group[row]["selected"].append(selected[:, :, row])
            for req, ids, row, _ in result.completed:
                runtime.retire(row)
                answers[req.id] = np.asarray(ids, np.int32)
        for req, s in zip(reqs, group):
            s["replayed"] = answers[req.id]
            if logits:
                s["logits"] = np.concatenate(s["logits"])[: len(s["replayed"])]
                s["selected"] = np.concatenate(s["selected"])[: len(s["replayed"])]


def _first_difference(a, b) -> int:
    n = min(len(a), len(b))
    same = np.asarray(a[:n]) == np.asarray(b[:n])
    return n if same.all() else int(np.argmin(same))


def _plant_live_fault(runtime, at: float) -> dict:
    """``fault_live_page``: before the first launch from ``at`` on, the values
    in one live row's own newest page (the page of its latest written
    position, in every sparse layer) are overwritten on the device, as a
    neighbour's stray write would leave them. The row keeps decoding over
    them; nothing else is touched, and its pages go back to the pool when it
    retires. Runs on the engine's thread, inside ``runtime.launch``. Returns a
    dict that is filled once the fault is in."""
    from machine_learning_apache_spark_tpu.ops.sparse_block_attention import (
        page_rows,
    )

    planted, launch = {}, runtime.launch
    spec, g = runtime.cfg.sparse, runtime.cfg.num_kv_heads

    def launch_over_a_fault(*args, **kwargs):
        if not planted and time.monotonic() >= at:
            left = {
                row: int(runtime._last_pos[row] - runtime._pos[row])
                for row, _ in runtime.active_rows()
            }
            row = max(left, key=left.get, default=None)
            if row is not None and left[row] > 0:
                pos = int(runtime._pos[row])
                page = int(runtime._tables[row, (pos - 1) // spec.block])
                if runtime.mem_pool.refcount(page) != 1:
                    raise RuntimeError(f"page {page} of row {row} is shared")
                values = runtime.cache["v"]
                for layer, plane in enumerate(values):
                    at_rows = page_rows(
                        plane, g, spec.block, page, np.arange(spec.block)
                    ).reshape(-1)
                    values[layer] = plane.at[at_rows].set(1000.0)
                planted.update(
                    request=runtime._req_of_row[row].id, row=row, page=page,
                    position=pos, steps_left=left[row],
                )
        return launch(*args, **kwargs)

    runtime.launch = launch_over_a_fault
    return planted


def _step_gaps(logits, ref_logits, bias) -> np.ndarray:
    """A step's widest gap over the vocabulary between two sets of logits,
    in units of the reference logits' deviation there."""
    scale = np.std(ref_logits - bias, axis=-1)
    return np.max(np.abs(logits - ref_logits), axis=-1) / scale


def _gap_numbers(gaps, short: int, diverged: int) -> list:
    """The numbers a cell's file may limit, of the sample's step gaps."""
    return [
        ("served_gap_mean", float(gaps.mean())),
        ("served_gap_p90", float(np.quantile(gaps, 0.9))),
        ("served_gap_max", float(gaps.max())),
        ("served_len_short", float(short)),
        ("replay_diverged", float(diverged)),
    ]


def reference_numbers(run, cfg, params, sample, max_new: int, t_max: int,
                      diverged: int):
    """The compared numbers (``diverged``, the window's requests whose
    tokens the replay did not give back, among them), the printed ones, and
    the stand-ins' verdicts."""
    import jax

    from benchmark.reference import sala_lm as ref

    if not sample:
        return _gap_numbers(np.array([np.nan]), 0, diverged), {}, None
    block = int(run.cell_file["reference_block"])
    query_rows = int(run.cell_file["reference_query_rows"])
    t_max = -(-t_max // block) * block
    dense_len = cfg["sparse_config"]["dense_len"]
    bias = np.asarray(params.get("logit_bias", 0.0), np.float32)

    def reference(s, **variant):
        n, served = len(s["ids"]), s["served"]
        tokens = np.concatenate([s["ids"], served[:-1]])
        steps = np.arange(n - 1, n - 1 + len(served))
        return ref.forward(
            params, cfg, tokens, steps, t_max=t_max, block=block,
            dense=bool(n + max_new < dense_len), query_rows=query_rows,
            **variant,
        )

    gaps, agree, differ, blocks, left = [], [], 0, 0, 0
    with ref.on_device(jax.local_devices()[0]):
        for s in sample:
            t = time.monotonic()
            s["ref_logits"], taken = reference(s)
            served = s["served"]
            # Compared as far as the replay with logits (another compiled
            # program than the window's) fed the served tokens back.
            upto = min(_first_difference(served, s["replayed"]) + 1, len(served))
            left += int(upto < len(served))
            gap = _step_gaps(s["logits"][:upto], s["ref_logits"][:upto], bias)
            gaps.append(gap)
            agree.append(np.argmax(s["ref_logits"], -1) == served)
            if not s["dense"]:
                for step in range(upto):
                    for layer in range(taken.shape[0]):
                        for g in range(taken.shape[2]):
                            mine = set(int(i) for i in s["selected"][step, layer, g] if i >= 0)
                            theirs = set(np.nonzero(taken[layer, step, g])[0].tolist())
                            differ += len(theirs - mine)
                            blocks += len(theirs)
            run.note(
                f"reference of {len(s['ids'])} + {len(served)} positions in "
                f"{time.monotonic() - t:.1f} s: resumed {s['resumed']}, widest "
                f"gap {gap.max():.4f} at step {int(gap.argmax())}, mean "
                f"{gap.mean():.4f}, replay follows the served tokens for {upto}"
            )
        short = sum(1 for s in sample if len(s["served"]) != max_new)
        numbers = _gap_numbers(np.concatenate(gaps), short, diverged)
        printed = {
            "selected_blocks_differ_share": differ / blocks if blocks else 0.0,
            "selected_blocks_compared": blocks,
            "served_is_reference_first_share": float(np.mean(np.concatenate(agree))),
            "steps_compared": int(sum(len(g) for g in gaps)),
            "logits_replay_left_the_served_tokens": left,
        }
        control = None
        stand_ins = compare.chosen(
            [c for c in run.control if c != LIVE_FAULT], STAND_INS
        )
        if stand_ins:
            variants = {
                "control_int8": lambda s: dict(matmul="int8"),
                "control_fp8": lambda s: dict(matmul="fp8"),
                "fault_window_only": lambda s: dict(select="window_only"),
                "fault_state_zero": lambda s: dict(zero_state_at=s["resumed"]),
            }
            control = {}
            for name in stand_ins:
                t = time.monotonic()
                stand = np.concatenate([
                    _step_gaps(reference(s, **variants[name](s))[0],
                               s["ref_logits"], bias)
                    for s in sample
                ])
                control[name] = compare.verdict(*compare.with_limits(
                    _gap_numbers(stand, 0, 0), run.cell_file["limits"]
                ))
                control[name]["seconds"] = round(time.monotonic() - t, 1)
    return numbers, printed, control


def toy(cfg: dict, mix: dict, cell_file: dict) -> None:
    """This kind's sizes for a CPU rehearsal (``benchmark.rehearse``): every
    width the kind reads, one period of the layer pattern, pages of 8
    positions, documents of a few hundred positions on both sides of
    ``dense_len``."""
    cfg.update(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
        dim_model_base=16, num_layers=4,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"],
        sparse_config=dict(
            kernel_size=4, kernel_stride=2, block_size=8, topk=4,
            window_size=16, init_blocks=1, dense_len=128,
        ),
    )
    cfg["engine"] = dict(
        max_context=448, max_active=4, max_new_tokens=8, prefill_chunk=32,
        steps_per_launch=4, num_pages=220, prefix_cache_size=8,
        prefill_budget=128, max_queue_depth=64,
    )
    mix.update(warm_seconds=0.3, trace_after_s=0.2, trace_seconds=0.5)
    mix["documents"] = dict(
        count=4, shortest=96, ratio_log2_step=0.6, multiple_of=8
    )
    mix["questions"] = dict(
        dist="lognormal", median=12, sigma=0.5, min=3, max=40, count=32
    )
    mix["arrivals"] = dict(mix["arrivals"], rate_per_s=12.0)
    cell_file.update(
        compare_requests=3, compare_documents=3,
        reference_block=64, reference_query_rows=16,
    )
