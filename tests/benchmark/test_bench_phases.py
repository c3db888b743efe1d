"""The readers of the serving loop's phase spans (``benchmark.phase_readers``
and the ``layer_metrics`` files that call it): on a hand-built event list
and a hand-built trace whose answers are worked out by hand, on nothing,
and in a traced CPU rehearsal of ``big_serve_steady``."""

import types

import pytest

from benchmark import manifest, phase_readers as pr, run as bench_run
from benchmark.trace_reduce import Event, Trace

W0 = 100.0  # the window's start on the monotonic clock
OFFSET = 102.0  # event-log time less trace time in the hand-built run
SPAN_METRICS = {
    "cycle_ms.steady", "admit_ms.steady", "launch_wait_ms.steady",
    "fold_ms.steady", "retire_ms.steady", "cycle_uncovered_ms.steady",
    "submit_ms_p50.steady", "events_per_request.steady",
}
TRACE_METRICS = {
    "launch_device_ms.steady", "prefill_device_ms.steady",
    "idle_unattributed_share.steady",
}


class Log:
    """Builds span events the way ``telemetry.spans`` writes them."""

    def __init__(self):
        self.events, self.ids = [], iter(range(1, 10**6))

    def span(self, name, start, end, parent=None, **attrs):
        sid = next(self.ids)
        for kind, ts, value in (("span_start", start, None),
                                ("span_end", end, end - start)):
            self.events.append(types.SimpleNamespace(
                kind=kind, name=name, ts=ts, span=sid, parent=parent,
                value=value, attrs=attrs or None,
            ))
        return sid

    def cycle(self, s, k=1.0, admit=0.020, launched=1):
        """One cycle at ``s``: expire 1 ms, admit, grow 5 ms, a launch of
        80 ms (dispatch 2, wait 50, fold 26, 2 of its own), retire 35 ms,
        9 ms of the loop's own; every length times ``k``."""
        e = lambda t: s + k * t  # noqa: E731
        shift = k * (admit - 0.020)
        c = self.span("serving.cycle", s, e(0.150), seq=0, launched=launched)
        self.span("serving.expire", s, e(0.001), c, expired=0)
        self.span("serving.admit", e(0.001), e(0.021) + shift, c, taken=2)
        self.span("serving.grow", e(0.021) + shift, e(0.026) + shift, c)
        b = self.span("serving.batch", e(0.030) + shift, e(0.110) + shift, c,
                      mode="paged", rows=300, steps=4)
        d = self.span("serve_decode_paged", e(0.030) + shift,
                      e(0.110) + shift, b)
        self.span("serving.launch.dispatch", e(0.030) + shift,
                  e(0.032) + shift, d)
        self.span("serving.launch.wait", e(0.032) + shift, e(0.082) + shift, d)
        self.span("serving.launch.fold", e(0.082) + shift, e(0.108) + shift, d)
        self.span("serving.retire", e(0.110) + shift, e(0.145) + shift, c)


def _run(events, trace=None, completed=100):
    notes = []
    run = types.SimpleNamespace(
        events=sorted(events, key=lambda e: e.ts), trace_data=trace,
        t0=0.0, setup_s=W0, age_at_t0=0.0, counters={"completed": completed},
        mix={"trace_after_s": 2.0, "trace_seconds": 2.0}, notes=notes,
    )
    run.note = notes.append
    return run


def _hand_built_log(before=7):
    log = Log()
    for k in range(before):  # before the profiler: admit 20, 20.5, ... ms
        log.cycle(W0 + 0.2 * k, admit=0.020 + 0.0005 * k)
    log.cycle(W0 + 1.8, launched=0)  # launched nothing: no cycle to read
    for j in range(6):  # the traced seconds: everything takes twice as long
        log.cycle(OFFSET + 0.3 * j + 1e-6 * j, k=2.0)
    log.cycle(W0 + 4.2, k=3.0)  # the backlog after the trace
    for i in range(9):  # callers' submits: 1 ms before, 4 ms under the tracer
        log.span("serving.submit", W0 + 0.1 * i, W0 + 0.1 * i + 0.001)
        log.span("serving.submit", OFFSET + 0.1 * i, OFFSET + 0.1 * i + 0.004)
    return log.events


def _read(metric, run):
    return manifest.load_reader(metric)(run)


def test_span_readers_give_the_hand_worked_medians_before_the_profiler():
    run = _run(_hand_built_log())
    assert _read("cycle_ms.steady", run) == pytest.approx(150.0)
    # admit 20..23 ms over 7 cycles: the median is the fourth
    assert _read("admit_ms.steady", run) == pytest.approx(21.5)
    assert _read("launch_wait_ms.steady", run) == pytest.approx(50.0)
    assert _read("fold_ms.steady", run) == pytest.approx(26.0)
    assert _read("retire_ms.steady", run) == pytest.approx(35.0)
    # 150 - (1 + admit + 5 + 80 + 35): 9 ms less the admit's extra 0..3
    assert _read("cycle_uncovered_ms.steady", run) == pytest.approx(7.5)
    assert _read("submit_ms_p50.steady", run) == pytest.approx(1.0)
    assert _read("events_per_request.steady", run) == pytest.approx(
        len(run.events) / 100
    )
    said = "\n".join(run.notes)
    # the traced seconds' medians go to earlier lines, not into the metric
    assert "cycle: 150.000 ms over 7 before the profiler, 300.000 ms over " \
        "6 in the traced seconds" in said
    assert "serving.submit spans: 1.000 ms over 9 before the profiler, " \
        "4.000 ms over 9 in the traced seconds" in said
    assert "cycle check: launch 80.000 ms + gap 120.500 ms" in said


def test_span_readers_return_none_on_too_few_cycles_and_on_nothing():
    few = _run(_hand_built_log(before=4))
    empty = _run([])
    parent = _run([e for e in _hand_built_log()
                   if e.name in ("serving.batch", "serve_decode_paged",
                                 "serving.submit")])
    for metric in SPAN_METRICS - {"submit_ms_p50.steady",
                                  "events_per_request.steady"}:
        assert _read(metric, few) is None, metric
    for metric in SPAN_METRICS | TRACE_METRICS | {"loader_wait_ms_p50"}:
        assert _read(metric, empty) is None, metric
    # the program before PR 24: its spans are there, the cycle is not
    for metric in SPAN_METRICS - {"submit_ms_p50.steady"}:
        assert _read(metric, parent) is None, metric


def _hand_built_trace():
    """Six traced cycles of 0.3 s on the engine's line, a caller's frame on
    another, and on the chip two prefills and one launch a cycle."""
    engine, ops, modules = [], [], []
    for j in range(6):
        t = 0.3 * j
        engine += [
            Event("serving.cycle", t, 0.29),  # 10 ms between cycles
            Event("serving.admit", t + 0.01, 0.04),
            Event("serve_decode_paged", t + 0.06, 0.16),
            Event("serving.launch.wait", t + 0.07, 0.10),
            Event("serving.launch.fold", t + 0.17, 0.04),
            Event("serving.retire", t + 0.22, 0.07),
            Event("$engine.py:700 _paged_step", t + 0.055, 0.24),
        ]
        for start, dur, module in (
            (t + 0.02, 0.01, "jit_paged_prefill_c1(11)"),
            (t + 0.035, 0.01, "jit_paged_prefill_c2(12)"),
            (t + 0.07, 0.09, "jit_paged_launch(13)"),
        ):
            ops.append(Event("%fusion.1 = f32[8]{0} fusion()", start, dur))
            modules.append(Event(module, start, dur))
    ops.append(Event("%fusion.2 = f32[8]{0} fusion()", 2.0, 0.01))
    caller = [Event("$queue.py:230 submit", 0.0, 3.0)]
    return Trace(ops={0: ops}, async_ops={}, modules={0: modules},
                 host={"python#1": caller, "python#2": engine})


def test_trace_readers_give_the_hand_worked_numbers():
    run = _run(_hand_built_log(), _hand_built_trace())
    assert _read("launch_device_ms.steady", run) == pytest.approx(90.0)
    # from one launch's start to the next's: the next cycle's two prefills
    assert _read("prefill_device_ms.steady", run) == pytest.approx(20.0)
    # The chip's gaps a cycle at t: [t+.03, t+.035] under admit; [t+.045,
    # t+.07] under admit 5 ms, the cycle itself 10, the launch itself 10;
    # [t+.16, t+.32] under wait 10, fold 40, the launch 10, retire 70,
    # nothing 10 (between the cycles), the next cycle 10 and its admit 10.
    # The last gap ends with the last cycle (1.79): 130 ms, none under nothing.
    table = pr.idle_by_phase(run.trace_data)
    expected = {
        "serving.admit": 6 * 0.010 + 5 * 0.010,
        "serving.cycle": 6 * 0.010 + 5 * 0.010,
        "serve_decode_paged": 6 * 0.020,
        "serving.launch.wait": 6 * 0.010,
        "serving.launch.fold": 6 * 0.040,
        "serving.retire": 6 * 0.070,
        "unattributed": 5 * 0.010,
    }
    assert set(table) == set(expected)
    for name, seconds in expected.items():
        assert table[name] == pytest.approx(seconds), name
    assert sum(table.values()) == pytest.approx(
        6 * 0.005 + 6 * 0.025 + 5 * 0.16 + 0.13
    )
    assert _read("idle_unattributed_share.steady", run) == pytest.approx(
        100 * 0.05 / 1.11
    )
    assert any(n.startswith("idle seconds by phase: serving.retire 0.4200")
               for n in run.notes)


def test_trace_readers_take_what_a_stalled_trace_holds():
    """Under the Python tracer a stall can leave the traced seconds four
    launches: the device's readers report from three, and from one run of
    the launch alone they report nothing."""
    trace = _hand_built_trace()
    trace.modules = {0: trace.modules[0][:9]}  # three cycles' programs
    run = _run([], trace)
    assert _read("launch_device_ms.steady", run) == pytest.approx(90.0)
    assert _read("prefill_device_ms.steady", run) == pytest.approx(20.0)
    trace.modules = {0: trace.modules[0][:3]}
    assert _read("launch_device_ms.steady", run) is None
    assert _read("prefill_device_ms.steady", run) is None


def test_clock_offset_lays_the_log_on_the_trace():
    run = _run(_hand_built_log(), _hand_built_trace())
    median, spread, pairs = pr.clock_offset(run)
    # the trace's six cycles are the log's ninth to fourteenth
    assert pairs == 6
    assert median == pytest.approx(OFFSET + 2.5e-6, abs=1e-9)
    assert spread == pytest.approx(3.5e-6, abs=1e-7)
    assert pr.clock_offset(_run(_hand_built_log())) is None


def test_trace_readers_find_nothing_in_a_trace_of_the_program_before():
    """``jit_fn`` modules and no phase annotation: every reader returns
    None and none raises."""
    old = _hand_built_trace()
    old.modules = {0: [Event("jit_fn(9)", e.start, e.dur)
                       for e in old.modules[0]]}
    old.host = {"python#2": [Event("serve_decode_paged", 0.06, 0.16)]}
    run = _run([], old)
    for metric in TRACE_METRICS:
        assert _read(metric, run) is None, metric
    assert pr.clock_offset(run) is None


def test_loader_wait_reads_the_windows_steps():
    log = Log()
    log.span("train.data_wait", W0 - 0.5, W0 + 0.2)  # opened before the window
    for k in range(7):
        log.span("train.data_wait", W0 + 1 + k, W0 + 1 + k + 0.001 * (k + 1))
    log.span("train.data_wait", W0 + 9, W0 + 9.5, exhausted=True)
    run = _run(log.events)
    run.mix = {}
    assert _read("loader_wait_ms_p50", run) == pytest.approx(4.0)
    assert _read("loader_wait_ms_p50", _run(log.events[:8])) is None


def test_traced_rehearsal_reports_every_span_metric_and_no_trace_metric(
    tmp_path, capfd
):
    result = bench_run.run_cell(
        "big_serve_steady", seed=2**31 + 7, seconds=3.0, trace=True,
        require_chip=False, rehearse=True, out_dir=str(tmp_path),
        mix_overrides={"trace_after_s": 1.5, "trace_seconds": 0.8,
                       "arrivals": {"rate_per_s": 60.0}},
    )
    err = capfd.readouterr().err
    assert result["correct"] is True, result["compared"]
    reported = set(result["metrics"])
    assert SPAN_METRICS <= reported
    assert not TRACE_METRICS & reported, "no chip, no device trace"
    values = {n: result["metrics"][n]["value"] for n in SPAN_METRICS}
    assert all(v > 0 for v in values.values())
    parts = sum(values[n] for n in (
        "admit_ms.steady", "launch_wait_ms.steady", "fold_ms.steady",
        "retire_ms.steady", "cycle_uncovered_ms.steady"))
    assert parts < 1.5 * values["cycle_ms.steady"]
    assert "cycle check: launch" in err
    assert "telemetry events dropped by the ring 0" in err


def test_rehearsed_train_cell_reports_the_loader_wait(tmp_path):
    result = bench_run.run_cell(
        # long enough for five steps of the toy even beside other workers
        "ref_train_1chip", seed=45, seconds=4.0, trace=True,
        require_chip=False, rehearse=True, out_dir=str(tmp_path),
    )
    assert result["correct"] is True
    assert result["metrics"]["loader_wait_ms_p50"]["value"] >= 0
