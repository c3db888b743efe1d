"""Readers of the phase spans inside the paged serving loop, and of the
loader wait in ``fit`` (what the program gained in PR 24).

The engine's thread writes, every iteration that has work, one
``serving.cycle`` span whose children say where the cycle went:
``serving.expire``, ``serving.admit``, ``serving.grow``, ``serving.batch``
(holding ``serve_decode_paged`` and in it ``serving.launch.dispatch`` /
``.wait`` / ``.fold``) and ``serving.retire``. Each is in the event log
(monotonic clock, every run) and, but for ``serving.batch``, an annotation
of the same bare name on the engine thread's line of the profiler's trace.

A traced serving run is three regimes in one window: the engine as the
untraced runs see it until the profiler starts (``trace_after_s`` into the
window), the traced seconds, and the backlog after them. A program_span
metric is taken over the first; the same median over the traced seconds is
noted beside it on an earlier line. A reader that finds fewer than
``MIN_SAMPLES`` to take a median of (``MIN_DEVICE_SAMPLES`` in the trace)
returns None, never 0: on a program without these spans every reader here
returns None and none raises.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import statistics

from benchmark import trace_reduce

MIN_SAMPLES = 5
# The device's readers take what the traced seconds hold: under the
# profiler's Python tracer a stall can leave them four launches.
MIN_DEVICE_SAMPLES = 2
CYCLE = "serving.cycle"
LAUNCH = "serve_decode_paged"
# The engine thread's annotations, outermost first.
PHASES = (
    CYCLE, "serving.idle_wait", "serving.expire", "serving.admit",
    "serving.grow", LAUNCH, "serving.launch.dispatch", "serving.launch.wait",
    "serving.launch.fold", "serving.retire",
)
LAUNCH_MODULE = r"^jit_paged_launch\("
PREFILL_MODULE = r"^jit_paged_prefill"


@dataclasses.dataclass
class Span:
    name: str
    start: float  # seconds, on time.monotonic()
    end: float
    attrs: dict
    id: int | None = None
    parent: int | None = None

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Cycle:
    span: Span
    children: list[Span]  # the cycle's direct children
    inside: list[Span]  # every span below it, children included

    @property
    def start(self) -> float:
        return self.span.start

    @property
    def end(self) -> float:
        return self.span.end

    @property
    def dur(self) -> float:
        return self.span.dur

    def total(self, name: str) -> float | None:
        found = [s.dur for s in self.inside if s.name == name]
        return sum(found) if found else None

    def uncovered(self) -> float:
        covered = trace_reduce._union(
            [(max(s.start, self.start), min(s.end, self.end))
             for s in self.children]
        )
        return self.dur - sum(e - s for s, e in covered)


def spans_of(events) -> list[Span]:
    """Every closed span of the log, by its end. A span starts at its
    ``span_start`` event's stamp; where the ring has lost that, at its end
    less its duration."""
    started = {e.span: e.ts for e in events if e.kind == "span_start"}
    return [
        Span(e.name, started.get(e.span, e.ts - (e.value or 0.0)), e.ts,
             e.attrs or {}, e.span, e.parent)
        for e in events if e.kind == "span_end"
    ]


def cycles_of(spans: list[Span]) -> list[Cycle]:
    """The ``serving.cycle`` spans that launched, each with what lies
    below it, in order."""
    by_id = {s.id: s for s in spans}
    cycles = {
        s.id: Cycle(s, [], []) for s in spans
        if s.name == CYCLE and s.attrs.get("launched")
    }
    for s in spans:
        root = s
        while root.parent in by_id:
            root = by_id[root.parent]
        if root is not s and root.id in cycles:
            cycles[root.id].inside.append(s)
            if s.parent == root.id:
                cycles[root.id].children.append(s)
    return sorted(cycles.values(), key=lambda c: c.start)


def run_spans(run) -> list[Span]:
    """``spans_of`` the run's events, worked out once a run."""
    if getattr(run, "_phase_spans", None) is None:
        run._phase_spans = spans_of(run.events)
    return run._phase_spans


def run_cycles(run) -> list[Cycle]:
    """``cycles_of`` the run's spans, worked out once a run."""
    if getattr(run, "_phase_cycles", None) is None:
        run._phase_cycles = cycles_of(run_spans(run))
    return run._phase_cycles


def window_start(run) -> float | None:
    """The window's start on ``time.monotonic()``."""
    if run.setup_s is None:
        return None
    return run.t0 + run.setup_s - run.age_at_t0


def traced_seconds(run) -> tuple[float, float] | None:
    """(start, end) of the seconds the harness traces, on the monotonic
    clock; None for a cell whose mix names none."""
    w0 = window_start(run)
    after = run.mix.get("trace_after_s")
    if w0 is None or after is None:
        return None
    start = w0 + float(after)
    return start, start + float(run.mix.get("trace_seconds", 0.0))


def regimes(run, items):
    """``items`` (each with a ``start`` and an ``end``) split into those
    that ended before the profiler started and those that lie inside the
    traced seconds."""
    traced = traced_seconds(run)
    if traced is None:
        return list(items), []
    t0, t1 = traced
    return (
        [x for x in items if x.end < t0],
        [x for x in items if x.start >= t0 and x.end <= t1],
    )


def _median_ms(values, least: int = MIN_SAMPLES) -> float | None:
    values = [v for v in values if v is not None]
    if len(values) < least:
        return None
    return statistics.median(values) * 1e3


def _fmt(ms: float | None) -> str:
    return "nothing" if ms is None else f"{ms:.3f} ms"


def regime_median_ms(run, label: str, items, value_of):
    """Median of ``value_of(item)`` (seconds) over the items that ended
    before the profiler started, in ms; the same over the traced seconds
    goes to an earlier line. None where there is no item at all."""
    if not items:
        return None
    before, during = regimes(run, items)
    value = _median_ms(value_of(x) for x in before)
    run.note(
        f"{label}: {_fmt(value)} over {len(before)} before the profiler, "
        f"{_fmt(_median_ms(value_of(x) for x in during))} over "
        f"{len(during)} in the traced seconds"
    )
    return value


def cycle_ms(run):
    """``serving.cycle``'s duration; beside it, the launch and the gap to
    the next launch over the same cycles, which should add up to it."""
    cycles = run_cycles(run)
    value = regime_median_ms(run, "cycle", cycles, lambda c: c.dur)
    if value is None:
        return None
    before, _ = regimes(run, cycles)
    launches = [s for c in before for s in c.inside if s.name == LAUNCH]
    launch = _median_ms(s.dur for s in launches)
    gap = _median_ms(b.start - a.end for a, b in zip(launches, launches[1:]))
    if launch is not None and gap is not None:
        run.note(
            f"cycle check: launch {launch:.3f} ms + gap {gap:.3f} ms = "
            f"{launch + gap:.3f} ms over the same cycles, "
            f"{100.0 * (value / (launch + gap) - 1.0):+.2f} % from the cycle"
        )
    run.note("phases with no metric of their own, over the same cycles: "
             + ", ".join(
                 f"{name} {_fmt(_median_ms(c.total(name) for c in before))}"
                 for name in ("serving.expire", "serving.grow",
                              "serving.launch.dispatch")
             ))
    return value


def phase_ms(run, name: str):
    """A cycle's time under the spans called ``name``."""
    return regime_median_ms(
        run, f"{name} a cycle", run_cycles(run), lambda c: c.total(name)
    )


def cycle_uncovered_ms(run):
    """A cycle less the union of its direct children: the loop's own
    time."""
    return regime_median_ms(
        run, "cycle uncovered", run_cycles(run), Cycle.uncovered
    )


def span_median_ms(run, name: str):
    """Median of every span called ``name`` (any thread), by regime as the
    cycles' metrics."""
    spans = [s for s in run_spans(run) if s.name == name]
    return regime_median_ms(run, f"{name} spans", spans, lambda s: s.dur)


def events_per_request(run):
    """Events the window's log holds over requests completed in it."""
    completed = run.counters.get("completed")
    if not completed or not any(e.name == CYCLE for e in run.events):
        return None
    top = collections.Counter(e.name for e in run.events).most_common(6)
    run.note(f"events in the window's log: {len(run.events)} over "
             f"{completed} requests completed; most from {top}")
    return len(run.events) / completed


def loader_wait_ms(run):
    """Median ``train.data_wait`` of the window's steps, in ms."""
    w0 = window_start(run)
    waits = [
        s.dur for s in run_spans(run)
        if s.name == "train.data_wait" and not s.attrs.get("exhausted")
        and (w0 is None or s.start >= w0)
    ]
    return _median_ms(waits)


# -- the device trace ---------------------------------------------------------

def engine_line(trace) -> list | None:
    """The phase annotations of the engine's thread (the host line that
    holds ``serving.cycle``), sorted by start."""
    if trace is None:
        return None
    for events in trace.host.values():
        if any(e.name == CYCLE for e in events):
            return [e for e in events if e.name in PHASES]
    return None


def clock_offset(run) -> tuple[float, float, int] | None:
    """(median, spread, pairs) of event-log time less trace time, from the
    ``serving.cycle`` spans of the log laid on the annotations of that name
    in the trace: the k-th annotation is the (j + k)-th span for the j at
    which the differences agree best. The spread is the distance between
    the quartiles of the differences. With the median, a ``RequestTrace``
    mark (monotonic) lies at ``mark - median`` on the trace's clock."""
    line = engine_line(run.trace_data)
    if not line:
        return None
    marks = [e.start for e in line if e.name == CYCLE]
    spans = sorted(s.start for s in run_spans(run) if s.name == CYCLE)
    if len(marks) < MIN_SAMPLES or len(spans) < len(marks):
        return None
    best = None
    for j in range(len(spans) - len(marks) + 1):
        diffs = [spans[j + k] - m for k, m in enumerate(marks)]
        q = statistics.quantiles(diffs, n=4)
        if best is None or q[2] - q[0] < best[1]:
            best = (statistics.median(diffs), q[2] - q[0], len(diffs))
    run.note(
        f"clock: event log less trace {best[0]:.6f} s, spread "
        f"{best[1] * 1e3:.4f} ms over {best[2]} cycles"
    )
    return best


def _lowest_chip(per_chip: dict) -> list:
    chips = sorted(c for c, evs in per_chip.items() if evs)
    return per_chip[chips[0]] if chips else []


def launch_device_ms(run):
    """Median run of the decode launch's program on the chip, in ms."""
    if run.trace_data is None:
        return None
    runs = _lowest_chip(
        trace_reduce.module_runs(run.trace_data, LAUNCH_MODULE)
    )
    return _median_ms((r.dur for r in runs), MIN_DEVICE_SAMPLES)


def prefill_device_ms(run):
    """Device time of the prefill programs from one launch's start to the
    next's, median over those stretches, in ms."""
    if run.trace_data is None:
        return None
    launches = _lowest_chip(
        trace_reduce.module_runs(run.trace_data, LAUNCH_MODULE)
    )
    prefills = _lowest_chip(
        trace_reduce.module_runs(run.trace_data, PREFILL_MODULE)
    )
    if len(launches) <= MIN_DEVICE_SAMPLES or not prefills:
        return None
    starts = [p.start for p in prefills]
    stretches = []
    for a, b in zip(launches, launches[1:]):
        lo, hi = bisect.bisect_left(starts, a.start), bisect.bisect_left(starts, b.start)
        stretches.append(sum(p.dur for p in prefills[lo:hi]))
    run.note(
        f"prefill on the chip: {len(prefills)} runs, median "
        f"{statistics.median(p.dur for p in prefills) * 1e3:.3f} ms each, "
        f"{len(prefills) / len(stretches):.1f} a launch"
    )
    return _median_ms(stretches, MIN_DEVICE_SAMPLES)


def _phase_at(line, starts, t: float) -> str | None:
    """The innermost phase annotation covering instant ``t``: annotations
    of one thread nest, so it is the last one started that still covers
    (a cycle opens a dozen, so it is never far back)."""
    i = bisect.bisect_right(starts, t) - 1
    stop = max(i - 64, -1)
    while i > stop:
        if line[i].end >= t:
            return line[i].name
        i -= 1
    return None


def _innermost_stretches(line) -> list[tuple[float, float, str]]:
    """The engine thread's line cut at every annotation's start and end:
    (start, end, innermost phase) of each stretch under any of them."""
    starts = [e.start for e in line]
    cuts = sorted({t for e in line for t in (e.start, e.end)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        name = _phase_at(line, starts, (a + b) / 2)
        if name is not None:
            out.append((a, b, name))
    return out


def idle_by_phase(trace) -> dict[str, float] | None:
    """Idle seconds of the lowest chip that ran ops, by the engine
    thread's innermost phase annotation over each part of each gap
    (``serving.cycle`` and ``serve_decode_paged`` then stand for their own
    time, outside their children); ``unattributed`` is under none. Taken
    from the first traced cycle's start to the last one's end: an
    annotation that was open when the profiler started or stopped is not
    in the trace, so the trace's edges would read as unattributed."""
    line = engine_line(trace)
    ops = _lowest_chip(trace.ops) if trace is not None else []
    cycles = [e for e in line or [] if e.name == CYCLE]
    if not cycles or not ops:
        return None
    lo, hi = cycles[0].start, max(c.end for c in cycles)
    stretches = _innermost_stretches(line)
    stretch_starts = [s[0] for s in stretches]
    busy = trace_reduce._union([(e.start, e.end) for e in ops])
    table: dict[str, float] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        a, b = max(e0, lo), min(s1, hi)
        if s1 - e0 < 20e-6 or b <= a:
            continue
        left = b - a
        i = max(bisect.bisect_right(stretch_starts, a) - 1, 0)
        while i < len(stretches) and stretches[i][0] < b:
            s, e, name = stretches[i]
            under = min(e, b) - max(s, a)
            if under > 0:
                table[name] = table.get(name, 0.0) + under
                left -= under
            i += 1
        if left > 1e-12:
            table["unattributed"] = table.get("unattributed", 0.0) + left
    return table


def idle_unattributed_percent(run):
    """Of the chip's idle seconds over the whole cycles of the trace, the
    share under none of the engine thread's phase annotations, in percent;
    the table by phase goes to an earlier line."""
    table = idle_by_phase(run.trace_data)
    if not table:
        return None
    idle = sum(table.values())
    clock_offset(run)
    run.note("idle seconds by phase: " + ", ".join(
        f"{name} {seconds:.4f} ({100.0 * seconds / idle:.1f} %)"
        for name, seconds in sorted(table.items(), key=lambda kv: -kv[1])
    ) + f"; idle {idle:.4f} s over the trace's whole cycles")
    return 100.0 * table.get("unattributed", 0.0) / idle
