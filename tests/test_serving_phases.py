"""The phase spans inside the paged serving loop (``serving.cycle`` and its
children), the counts they carry, what the log costs, and the loader-wait
span in ``fit``: CPU, toy engine, each property a test of its own."""

import os
import time

import numpy as np
import pytest

from machine_learning_apache_spark_tpu import telemetry
from machine_learning_apache_spark_tpu.telemetry import events as events_mod

CHILDREN = {
    "serving.expire", "serving.admit", "serving.grow", "serving.batch",
    "serving.retire",
}
LAUNCH_PARTS = (
    "serving.launch.dispatch", "serving.launch.wait", "serving.launch.fold",
)


@pytest.fixture(scope="module")
def tiny_translator(make_tiny_translator):
    """Untrained tiny MT bundle over 48 sentence pairs."""
    return make_tiny_translator(48)


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv("MLSPARK_TELEMETRY", raising=False)
    monkeypatch.delenv("MLSPARK_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("MLSPARK_PROCESS_ID", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


def _serve(translator, texts, **kw):
    """Serve ``texts`` through a paged toy engine; (answers, events,
    metrics' ledger, metrics)."""
    t = translator
    with t.serve(
        boundaries=(8, 16), max_batch=4, max_new_tokens=8,
        **kw,
    ) as eng:
        futs = [eng.submit(s) for s in texts]
        answers = [f.result(timeout=120) for f in futs]
        deadline = time.monotonic() + 10
        while eng.runtime.active_count() and time.monotonic() < deadline:
            time.sleep(0.01)
        metrics = eng.metrics
        ledger = metrics.check_conservation()
    return answers, telemetry.get_log().snapshot(), ledger, metrics, futs


@pytest.fixture
def served(tiny_translator):
    t, texts = tiny_translator
    return _serve(t, texts[:24])


def _closed(events):
    """name -> [(start, end, span id, parent id, attrs)] of closed spans."""
    started = {e.span: e.ts for e in events if e.kind == "span_start"}
    out = {}
    for e in events:
        if e.kind == "span_end":
            out.setdefault(e.name, []).append(
                (started[e.span], e.ts, e.span, e.parent, e.attrs or {})
            )
    return out


def test_every_batch_has_one_enclosing_cycle_and_children_name_it(served):
    spans = _closed(served[1])
    cycles = {c[2]: c for c in spans["serving.cycle"]}
    batches = spans["serving.batch"]
    assert batches and len(batches) == sum(
        c[4]["launched"] for c in cycles.values()
    )
    for start, end, _, parent, attrs in batches:
        enclosing = [
            c for c in cycles.values() if c[0] <= start and end <= c[1]
        ]
        assert len(enclosing) == 1
        assert parent == enclosing[0][2]
        assert attrs["seq"] == enclosing[0][4]["seq"]
        assert attrs["mode"] == "paged" and "requests" not in attrs
    for name in CHILDREN:
        assert spans[name], name
        assert all(s[3] in cycles for s in spans[name]), name
    # a cycle that launched has each child exactly once
    for cid, cycle in cycles.items():
        if cycle[4]["launched"]:
            for name in CHILDREN:
                assert sum(1 for s in spans[name] if s[3] == cid) == 1


def test_cycle_children_never_overlap_and_lie_inside_the_cycle(served):
    spans = _closed(served[1])
    for c_start, c_end, cid, parent, _ in spans["serving.cycle"]:
        assert parent is None, "the cycle is a root"
        kids = sorted(
            s for name in CHILDREN for s in spans[name] if s[3] == cid
        )
        assert kids
        assert c_start <= kids[0][0] and kids[-1][1] <= c_end
        for a, b in zip(kids, kids[1:]):
            assert a[1] <= b[0], "children of one thread do not overlap"


def test_launch_spans_lie_inside_serve_decode_paged(served):
    spans = _closed(served[1])
    launches = {s[2]: s for s in spans["serve_decode_paged"]}
    batches = {s[2] for s in spans["serving.batch"]}
    assert launches and all(s[3] in batches for s in launches.values())
    for name in LAUNCH_PARTS:
        assert len(spans[name]) == len(launches), name
        for start, end, _, parent, _ in spans[name]:
            outer = launches[parent]
            assert outer[0] <= start and end <= outer[1]
    for lid in launches:
        d, w, f = (
            next(s for s in spans[name] if s[3] == lid)
            for name in LAUNCH_PARTS
        )
        assert d[1] <= w[0] and w[1] <= f[0], "dispatch, wait, fold in order"


def test_admit_and_retire_counts_sum_to_the_ledger(served):
    _, events, ledger, metrics, _ = served
    spans = _closed(events)
    admits = [s[4] for s in spans["serving.admit"]]
    placed = sum(a.get("hits", 0) + a.get("misses", 0) for a in admits)
    assert placed == ledger["submitted"] == 24
    assert sum(a["taken"] for a in admits) - sum(
        a.get("requeued", 0) for a in admits
    ) == placed
    retires = [s[4] for s in spans["serving.retire"]]
    assert sum(r["completed"] for r in retires) == ledger["completed"] == 24
    assert sum(r["tokens"] for r in retires) == metrics.tokens_out
    folds = [s[4] for s in spans["serving.launch.fold"]]
    assert sum(f["completed"] for f in folds) == ledger["completed"]
    assert sum(f["real_tokens"] for f in folds) <= metrics.tokens_out


def test_token_counters_keep_their_totals_after_the_coalescing(served):
    _, events, _, metrics, _ = served
    total = lambda name: sum(  # noqa: E731
        e.value for e in events if e.kind == "counter" and e.name == name
    )
    assert total("serving.tokens_real") == metrics.real_tokens > 0
    assert total("serving.tokens_padded") == metrics.padded_tokens > 0
    # one pair of counter events an admit round that placed something and
    # one a launch: never one a request
    spans = _closed(events)
    rounds = sum(
        1 for s in spans["serving.admit"]
        if s[4].get("hits", 0) + s[4].get("misses", 0)
    )
    pairs = sum(
        1 for e in events
        if e.kind == "counter" and e.name == "serving.tokens_real"
    )
    assert pairs == rounds + len(spans["serving.batch"])


def test_requests_name_the_cycles_that_served_them(served):
    _, events, _, _, futs = served
    spans = _closed(events)
    seqs = {s[4]["seq"] for s in spans["serving.cycle"] if s[4]["launched"]}
    notes = {
        e.attrs["trace_id"]: e.attrs for e in events
        if e.kind == "annotation" and e.name == "serving.request"
    }
    assert len(notes) == 24
    for f in futs:
        a = notes[f.trace.trace_id]
        assert a["first_batch"] == f.trace.first_batch
        assert a["last_batch"] == f.trace.last_batch
        assert {a["first_batch"], a["last_batch"]} <= seqs
        # a request rides every launch from its first to its last
        assert a["last_batch"] - a["first_batch"] + 1 == a["launches"]


def test_telemetry_off_serves_the_same_answers_and_logs_nothing(
    tiny_translator, monkeypatch
):
    t, texts = tiny_translator
    on = _serve(t, texts[:12])
    assert len(on[1]) > 0
    monkeypatch.setenv("MLSPARK_TELEMETRY", "0")
    telemetry.reset()
    off = _serve(t, texts[:12])
    assert off[0] == on[0]
    assert off[1] == [] and len(telemetry.get_log()) == 0
    assert off[2]["completed"] == 12


def test_an_idle_engine_writes_one_open_span_and_nothing_more(
    tiny_translator
):
    """Empty polls write no event: a quiet replica keeps its history in
    the flight recorder's tail."""
    t, texts = tiny_translator
    with t.serve(
        boundaries=(8, 16), max_batch=4, max_new_tokens=8,
    ) as eng:
        eng.submit(texts[0]).result(timeout=120)
        def idling():
            last = telemetry.get_log().tail(1)
            return bool(last) and (last[0].kind, last[0].name) == (
                "span_start", "serving.idle_wait")

        deadline = time.monotonic() + 10
        while not idling() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert idling()
        n0 = len(telemetry.get_log())
        time.sleep(0.4)  # eight polls of 0.05 s
        assert len(telemetry.get_log()) == n0
        events = telemetry.get_log().snapshot()
        opened = [e for e in events if e.kind == "span_start"
                  and e.name == "serving.idle_wait"]
        closed = [e for e in events if e.kind == "span_end"
                  and e.name == "serving.idle_wait"]
        assert len(opened) == len(closed) + 1
    closed = [e for e in telemetry.get_log().snapshot()
              if e.kind == "span_end" and e.name == "serving.idle_wait"]
    assert len(closed) == len(opened), "stopping the engine closes it"
    assert closed[-1].value >= 0.4 and closed[-1].parent is None


def test_span_set_puts_counts_on_the_end_event_only():
    from machine_learning_apache_spark_tpu.telemetry import spans
    from machine_learning_apache_spark_tpu.utils.profiling import annotate

    with annotate("phase.test") as phase:
        phase.set(taken=3)
        phase.set(hits=1)
    with telemetry.span("span.test", mode="x") as sp:
        sp.set(rows=2)
    ev = {(e.kind, e.name): e for e in telemetry.get_log().snapshot()}
    assert ev[("span_start", "phase.test")].attrs is None
    assert ev[("span_end", "phase.test")].attrs == {"taken": 3, "hits": 1}
    assert ev[("span_start", "span.test")].attrs == {"mode": "x"}
    assert ev[("span_end", "span.test")].attrs == {"mode": "x", "rows": 2}
    telemetry.set_enabled(False)
    with annotate("phase.off") as phase:
        assert phase.set(taken=1) is None
    assert spans.NOOP_SPAN.attrs is None


def test_event_log_reads_pid_and_rank_once_and_again_after_reset(monkeypatch):
    calls = []
    real = os.getpid
    monkeypatch.setattr(events_mod.os, "getpid", lambda: calls.append(1) or real())
    log = events_mod.get_log()
    n0 = len(calls)
    for _ in range(5):
        ev = log.emit("counter", "c", value=1.0)
    assert len(calls) == n0, "no getpid a event"
    assert ev.pid == real() and ev.rank is None
    monkeypatch.setattr(events_mod.os, "getpid", lambda: 4242)
    monkeypatch.setenv("MLSPARK_PROCESS_ID", "3")
    assert log.emit("counter", "c", value=1.0).pid == real(), "same log"
    events_mod.reset()  # the fork/spawn re-arm
    ev = events_mod.get_log().emit("counter", "c", value=1.0)
    assert ev.pid == 4242 and ev.rank == 3


@pytest.mark.parametrize("steps_per_call", [1, 3])
def test_data_wait_appears_once_a_step_in_both_fit_paths(steps_per_call):
    import jax

    from machine_learning_apache_spark_tpu.models import MLP
    from machine_learning_apache_spark_tpu.train import (
        TrainState,
        classification_loss,
        fit,
        make_optimizer,
    )

    rng = np.random.default_rng(3)
    feats = rng.normal(size=(60, 4)).astype(np.float32)
    labels = rng.integers(0, 3, size=60).astype(np.int32)
    batches = [
        (feats[i:i + 10], labels[i:i + 10]) for i in range(0, 60, 10)
    ]
    model = MLP(layers=(4, 5, 4, 3))
    params = model.init(jax.random.key(0), feats[:1])["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer("sgd", 0.03)
    )
    result = fit(
        state, classification_loss(model.apply), batches, epochs=2,
        log_every=0, steps_per_call=steps_per_call,
    )
    assert int(result.state.step) == 12
    ends = [e for e in telemetry.get_log().snapshot()
            if e.kind == "span_end" and e.name == "train.data_wait"]
    waits = [e for e in ends if not (e.attrs or {}).get("exhausted")]
    assert len(waits) == 12, "one a step"
    assert len(ends) - len(waits) == 2, "and the pull that ends each epoch"
    names = {e.name for e in telemetry.get_log().snapshot()}
    assert ("train.step_group" if steps_per_call > 1 else "train.step") in names
