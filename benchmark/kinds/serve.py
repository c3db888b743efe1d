"""A serving cell: ``Translator.serve()`` -> ``ServingEngine.submit`` under
a closed loop of callers or an open loop at a fixed rate.

The client is quiet by construction: one submitter thread, sentences
prepared in set-up, and a done-callback (run by the engine's thread when it
resolves a future) that only stamps the time and hands the caller back to
the submitter. The warm period before the window is set-up: it takes the
engine past its first generations of requests, so that completions are out
of step when the window opens.

Once the window has closed, every request has answered (or a minute has
passed), the peak memory has been read and the engine is gone, the plain
reference is run once over a seeded sample of the finished requests, the
longest prompt among them, with their served tokens.
"""

from __future__ import annotations

import functools
import gc
import json
import os
import queue
import resource
import statistics
import threading
import time

import numpy as np

from benchmark import compare, flops, program, traffic, weights

ANSWER_WAIT_S = 60.0


class Record:
    __slots__ = ("idx", "due", "submit", "done", "req", "error")

    def __init__(self, idx: int, due: float):
        self.idx, self.due = idx, due
        self.submit = self.done = None
        self.req = self.error = None

    def finished_ok(self) -> bool:
        return (
            self.req is not None and self.done is not None
            and self.req.future.exception() is None
        )


class Client:
    """What both loops share: the records, the one submitter thread."""

    def __init__(self, engine, texts):
        self.engine, self.texts = engine, texts
        self.records: list[Record] = []
        self.stop_flag = threading.Event()
        self.thread = threading.Thread(
            target=self._run, name="bench-submitter", daemon=True
        )

    def start(self):
        self.thread.start()

    def stop(self):
        self.stop_flag.set()
        self.thread.join(timeout=10)

    def _submit(self, rec: Record, on_done) -> bool:
        rec.submit = time.monotonic()
        try:
            rec.req = self.engine.submit(self.texts[rec.idx])
        except Exception as e:  # refused: counts as failed, never as wrong
            rec.error, rec.done = e, time.monotonic()
            self.records.append(rec)
            return False
        self.records.append(rec)
        rec.req.future.add_done_callback(functools.partial(on_done, rec))
        return True

    def wait_for_answers(self, timeout: float) -> int:
        """Wait until every sent request has answered; returns how many
        never did."""
        deadline = time.monotonic() + timeout
        missing = 0
        for rec in self.records:
            if rec.req is None or rec.done is not None:
                continue
            try:
                rec.req.future.exception(
                    timeout=max(deadline - time.monotonic(), 0.0)
                )
            except Exception:  # concurrent.futures.TimeoutError
                missing += 1
        return missing


class ClosedLoop(Client):
    """``callers`` callers, each sending its next sentence only after the
    reply to its last. Caller c sends sentences c, c + callers, ..."""

    def __init__(self, engine, texts, callers: int):
        super().__init__(engine, texts)
        self.callers = callers
        self.sent = [0] * callers
        self.ready: queue.SimpleQueue = queue.SimpleQueue()
        for c in range(callers):
            self.ready.put(c)

    def _done(self, caller: int, rec: Record, _future) -> None:
        rec.done = time.monotonic()
        self.ready.put(caller)

    def _run(self):
        while not self.stop_flag.is_set():
            try:
                caller = self.ready.get(timeout=0.05)
            except queue.Empty:
                continue
            idx = (caller + self.sent[caller] * self.callers) % len(self.texts)
            self.sent[caller] += 1
            rec = Record(idx, time.monotonic())
            if not self._submit(rec, functools.partial(self._done, caller)):
                time.sleep(0.001)
                self.ready.put(caller)


class OpenLoop(Client):
    """Requests at the due instants of a schedule, whatever the engine
    does; a request is timed from when it was due."""

    def __init__(self, engine, texts, due: np.ndarray):
        super().__init__(engine, texts)
        self.due = due  # absolute, on time.monotonic()

    @staticmethod
    def _done(rec: Record, _future) -> None:
        rec.done = time.monotonic()

    def _run(self):
        for k, due in enumerate(self.due):
            wait = due - time.monotonic()
            if wait > 0 and self.stop_flag.wait(wait):
                return
            if self.stop_flag.is_set():
                return
            self._submit(Record(k % len(self.texts), float(due)), self._done)


def make_pipelines(cfg: dict, max_src: int, max_new: int):
    """Vocabularies of the configuration's sizes without a download: word
    ``w<k>`` has id ``k + 4`` behind the four specials (after
    ``chip_smoke.py:write_corpus``, which reaches a stated vocabulary the
    same way, through a corpus on disk)."""
    from machine_learning_apache_spark_tpu.data.text import (
        SPECIALS,
        TextPipeline,
        Vocab,
    )

    def pipe(vocab_size: int, length: int):
        words = [f"w{i}" for i in range(vocab_size - len(SPECIALS))]
        return TextPipeline(Vocab(words), str.split, max_seq_len=length - 1)

    return (
        pipe(cfg["src_vocab_size"], max_src),
        pipe(cfg["trg_vocab_size"], max_new + 2),
    )


def _text(ids: np.ndarray) -> str:
    return " ".join(f"w{int(i) - 4}" for i in ids)


def run(run) -> None:
    import jax

    with run.phase("program_imports"):
        from machine_learning_apache_spark_tpu import telemetry
        from machine_learning_apache_spark_tpu.inference import Translator

    cfg, mix = run.cfg, run.mix
    engine_kw = dict(cfg["engine"])
    max_src = max(engine_kw["boundaries"])
    max_new = int(engine_kw["max_new_tokens"])

    with run.phase("weights"):
        params = weights.make_params(run.seed, cfg, suppress_stop=True)
        jax.block_until_ready(params)
    with run.phase("vocabulary_and_prompts"):
        src_pipe, trg_pipe = make_pipelines(cfg, max_src, max_new)
        prompt_ids = traffic.prompts(
            mix["lengths"], cfg["src_vocab_size"], run.seed
        )
        texts = [_text(ids) for ids in prompt_ids]
    model = program.make_model(cfg)
    with run.phase("translator"):
        translator = Translator(model, params, src_pipe, trg_pipe)
        del params
    with run.phase("engine_start_and_compile"):
        engine = translator.serve(**engine_kw)

    warm_s = float(mix["warm_seconds"])
    if mix["loop"] == "closed":
        client = ClosedLoop(engine, texts, int(mix["callers"]))
    else:
        schedule = traffic.due_times(
            mix["arrivals"], warm_s + run.seconds + 1.0, run.seed
        )
        client = OpenLoop(engine, texts, schedule)
    with run.phase("gc_collect_and_freeze"):
        gc.collect()
        gc.freeze()
    t_warm = time.monotonic()
    if mix["loop"] == "open":
        client.due = t_warm + 0.05 + client.due
    client.start()
    if mix["loop"] == "closed":
        # Past the first generations of requests, and no shorter than the
        # mix's warm seconds.
        want = int(mix["warm_generations"]) * int(mix["callers"])
        while (
            engine.metrics.ledger()["completed"] < want
            or time.monotonic() - t_warm < warm_s
        ) and time.monotonic() - t_warm < 10 * max(warm_s, 1.0):
            time.sleep(0.01)
    else:
        time.sleep(max(t_warm + 0.05 + warm_s - time.monotonic(), 0))
    run.setup["warm_period_s"] = time.monotonic() - t_warm

    # -- the window ---------------------------------------------------------
    metrics = engine.metrics
    run.setup_events = [
        e for e in telemetry.get_log().snapshot()
        if e.name == "ops.attention_dispatch"
    ]
    telemetry.get_log().clear()
    from benchmark.run import GcWatch

    gc_watch = GcWatch()
    gc_watch.start()
    recompiles0 = engine.recompiles_after_warmup or 0
    ledger0 = metrics.ledger()
    usage0 = resource.getrusage(resource.RUSAGE_SELF)
    tokens0, w0 = metrics.tokens_out, time.monotonic()
    run.mark_window_start(w0)
    trace_dir = None
    if run.trace:
        trace_dir = run.start_trace(
            after=float(mix["trace_after_s"]), seconds=float(mix["trace_seconds"])
        )
    time.sleep(max(w0 + run.seconds - time.monotonic(), 0))
    tokens1, w1 = metrics.tokens_out, time.monotonic()
    usage1 = resource.getrusage(resource.RUSAGE_SELF)
    ledger1 = metrics.ledger()
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
    final = metrics.check_conservation(in_flight=unanswered)
    run.note(f"conservation ledger: {final}")
    engine.stop()

    # -- what the window held -----------------------------------------------
    if mix["loop"] == "closed":
        mine = [r for r in client.records if w0 <= r.submit < w1]
    else:
        mine = [r for r in client.records if w0 <= r.due < w1]
    failed = [r for r in mine if not r.finished_ok()]
    run.attempted, run.failed = len(mine), len(failed)
    done_in = [
        r for r in client.records if r.finished_ok() and w0 <= r.done < w1
    ]
    if mix["loop"] == "closed":
        run.e2e["serve_tokens_per_s"] = (tokens1 - tokens0) / run.window_s
    else:
        latencies = [
            (r.done - r.due) if r.finished_ok() else run.window_s
            for r in mine
        ]
        run.e2e["latency_p50_ms"] = statistics.median(latencies) * 1e3
        run.e2e["latency_p95_ms"] = traffic.nearest_rank(latencies, 95) * 1e3
        late = [r.submit - r.due for r in mine]
        run.note(
            f"generator lateness: median {statistics.median(late) * 1e3:.3f}"
            f" ms, max {max(late) * 1e3:.3f} ms over {len(mine)} requests due"
        )

    vocab = trg_pipe.vocab
    served = {id(r): vocab.lookup_indices(r.req.future.result().split())
              for r in done_in}
    early = sum(1 for ids in served.values() if len(ids) != max_new)
    launches = _launch_spans(run.events)
    gaps = [b[0] - a[1] for a, b in zip(launches, launches[1:])]
    rows = [
        e.attrs["rows"] for e in run.events
        if e.kind == "span_start" and e.name == "serving.batch"
    ]
    src_len = lambda r: len(prompt_ids[r.idx]) + 2  # noqa: E731  sos + eos
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
        window_flops=sum(
            flops.request_flops(cfg, src_len(r), len(served[id(r)]))
            for r in done_in
        ),
    )
    run.note(
        f"window: completed {run.counters['completed']}, tokens_out "
        f"{tokens1 - tokens0}, launches {len(launches)}, mean rows a launch "
        f"{(sum(rows) / len(rows)) if rows else float('nan'):.2f}, ended "
        f"early (EOS or pad) {early}/{len(done_in)}, elapsed "
        f"{run.window_s:.4f} s, longest gap between launches "
        f"{(max(gaps) * 1e3) if gaps else float('nan'):.1f} ms, {gc_report}, "
        f"telemetry events dropped by the ring {dropped}, recompiles "
        f"{run.counters['recompiles']}, unanswered after the close {unanswered}"
    )
    # What the host gave the process: where runs of one seed differ in their
    # launch cycle, this says whether the process computed more or waited.
    run.note(
        f"host in window: process CPU {usage1.ru_utime - usage0.ru_utime:.2f} s"
        f" user + {usage1.ru_stime - usage0.ru_stime:.2f} s system over "
        f"{len(os.sched_getaffinity(0))} cores, context switches "
        f"{usage1.ru_nvcsw - usage0.ru_nvcsw} voluntary / "
        f"{usage1.ru_nivcsw - usage0.ru_nivcsw} involuntary, load average "
        f"{os.getloadavg()[0]:.2f}"
    )

    # -- the sample for the comparison, then the engine goes ------------------
    rng = np.random.default_rng([int(run.seed), 4])
    k = min(int(run.cell_file["compare_requests"]), len(done_in))
    picks = [done_in[i] for i in rng.choice(len(done_in), k, replace=False)] if k else []
    if done_in:
        longest = max(done_in, key=src_len)
        if all(longest is not p for p in picks):
            picks[-1] = longest
    sample = [
        (src_pipe.ragged([texts[r.idx]])[0], served[id(r)]) for r in picks
    ]
    del engine, translator, client, served, done_in, mine
    telemetry.reset()
    gc.unfreeze()
    gc.collect()

    t_ref = time.monotonic()
    gap_max, gap_mean, short, control_report = reference_gaps(
        run, cfg, sample, int(run.cell_file["reference_block_rows"]),
        max_src, max_new,
    )
    run.note(
        f"reference: {len(sample)} requests, "
        f"{sum(len(t) for _, t in sample)} served tokens, "
        f"{time.monotonic() - t_ref:.1f} s after the window"
    )
    run.compared, printed = compare.serve_numbers(
        gap_max, gap_mean, short, run.cell_file["limits"]
    )
    if printed:
        run.note(f"not compared: {printed}")
    run.control_report = control_report


def _launch_spans(events) -> list[tuple[float, float]]:
    """(start, end) of every ``serve_decode_paged`` span that ended in the
    window, on the monotonic clock."""
    out = [
        (e.ts - e.value, e.ts) for e in events
        if e.kind == "span_end" and e.name == "serve_decode_paged"
    ]
    out.sort()
    return out


def reference_gaps(run, cfg, sample, block, max_src, max_new):
    """Over the sample: the widest and the mean gap by which a served
    token's reference logit lies below the reference's best at its
    position, and how many sampled answers are not ``max_new`` tokens long.
    For the builder's ``--control`` the same gaps, with ``decide``'s
    verdict, for the tokens that the reference in int8 and in float8 puts
    first. The reference runs in blocks of ``block`` requests (the last one
    padded) that it is handed as arguments, so that every block, run and
    seed finds one compiled program in the cache."""
    import jax

    from benchmark.reference import transformer as ref

    if not sample:
        return float("nan"), float("nan"), 0, None
    rows = -(-len(sample) // block) * block
    pad, sos = cfg["pad_id"], cfg["sos_id"]
    src = np.full((rows, max_src), pad, np.int32)
    trg_in = np.full((rows, max_new), pad, np.int32)
    labels = np.zeros((rows, max_new), np.int32)
    valid = np.zeros((rows, max_new), bool)
    src[:, 0], trg_in[:, 0] = sos, sos  # a padding row attends to something
    for i, (prompt, tokens) in enumerate(sample):
        tokens = tokens[:max_new]
        src[i, : len(prompt)] = prompt
        trg_in[i, 1: len(tokens)] = tokens[:-1]
        labels[i, : len(tokens)] = tokens
        valid[i, : len(tokens)] = True
    short = sum(1 for _, tokens in sample if len(tokens) != max_new)

    def over_blocks(fn, params):
        widest, total = 0.0, 0.0
        for b in range(0, rows, block):
            cut = slice(b, b + block)
            gap = np.where(valid[cut], np.asarray(fn(
                params, src[cut], trg_in[cut], labels[cut])), 0.0)
            widest, total = max(widest, float(gap.max())), total + float(gap.sum())
        return widest, total / float(valid.sum())

    programs = _gap_programs(json.dumps(cfg, sort_keys=True))
    with ref.on_device(jax.local_devices()[0]):
        params = weights.make_params(run.seed, cfg, suppress_stop=True)
        gap_max, gap_mean = over_blocks(programs["served"], params)
        report = None
        if run.control:
            report = {
                name: compare.verdict(*compare.serve_numbers(
                    *over_blocks(programs[name], params), 0,
                    run.cell_file["limits"],
                ))
                for name in compare.chosen(run.control, STAND_INS)
            }
    return gap_max, gap_mean, short, report


STAND_INS = ("control_int8", "control_fp8")


@functools.lru_cache(maxsize=4)
def _gap_programs(cfg_json: str) -> dict:
    """The jitted reference programs for one configuration: ``served`` gives
    the gap of the served tokens, each stand-in the gap of the tokens that
    the reference in its precision puts first. One set a process, so the
    seeds of a ``--seeds`` call share them."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import transformer as ref

    cfg = json.loads(cfg_json)

    def below_best(logits, tokens):
        at = jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
        return jnp.max(logits, axis=-1) - at

    def stand_in(matmul):
        def gaps(p, s, t, _served):
            first = jnp.argmax(ref.forward(p, cfg, s, t, matmul=matmul), -1)
            return below_best(ref.forward(p, cfg, s, t), first)
        return jax.jit(gaps)

    return {
        "served": jax.jit(
            lambda p, s, t, served: below_best(ref.forward(p, cfg, s, t), served)
        ),
        "control_int8": stand_in(ref.lowp_matmul),
        "control_fp8": stand_in(ref.fp8_matmul),
    }


def toy(cfg: dict, mix: dict, cell_file: dict) -> None:
    """This kind's sizes for a CPU rehearsal (``benchmark.rehearse``)."""
    cfg["engine"].update(
        boundaries=[8, 16], max_active=8, max_batch=8, max_new_tokens=6,
        page_size=4, prefill_chunk=8, steps_per_launch=2, num_pages=None,
        prefix_cache_size=4, prefill_budget=4096, max_queue_depth=64,
    )
    mix.update(warm_seconds=0.3, trace_after_s=0.2, trace_seconds=0.5)
    mix["lengths"] = dict(
        dist="lognormal", median=6, sigma=0.5, min=2, max=14, count=64
    )
    if mix["loop"] == "closed":
        mix.update(callers=8, warm_generations=1)
    else:
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=20.0)
    cell_file.update(compare_requests=4, reference_block_rows=4)
