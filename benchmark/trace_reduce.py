"""From a profiler trace (``.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. What a
TPU trace looks like (looked at by hand, PR 23): one plane per chip named
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per executed
program), ``XLA Ops`` (one event per HLO operation, the event's name is the
operation's HLO text, ``%name = type op(...)``) and ``Async XLA Ops``
(copies and collectives in flight, overlapping the ops); one plane
``/host:CPU`` with a line per thread, where ``TraceAnnotation`` names
(``serve_decode_paged``) and the runtime's own marks sit beside the Python
tracer's frames (names that start with ``$``). All start times are
nanoseconds on one clock.

Busy time is the union of the ``XLA Ops`` intervals of a chip; the window
is from the first op's start to the last op's end over all chips, so the
drain at the end of a traced region is not counted as idle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINER = re.compile(r"\] (while|conditional|call)\(|\) (while|conditional|call)\(")
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds
    dur: float  # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """``ops``/``async_ops``/``modules``: chip index -> events sorted by
    start. ``host``: thread line name -> events sorted by start."""

    ops: dict[int, list[Event]]
    async_ops: dict[int, list[Event]]
    modules: dict[int, list[Event]]
    host: dict[str, list[Event]]


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    return found[-1] if found else None


def _events(line) -> list[Event]:
    out = [
        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
        for e in line.events
    ]
    out.sort(key=lambda ev: ev.start)
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace({}, {}, {}, {})
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.ops[chip] = _events(line)
                elif line.name == ASYNC_LINE:
                    trace.async_ops[chip] = _events(line)
                elif line.name == MODULES_LINE:
                    trace.modules[chip] = _events(line)
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                evs = _events(line)
                if evs:
                    trace.host[f"{line.name or 'thread'}#{i}"] = evs
    return trace


def op_short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,128]{...} fusion(...)`` -> ``fusion.12 bf16[8,128]``:
    enough to recognise an operation, short enough for a result line."""
    m = re.match(r"%?([\w.\-]+) = (\(?[\w]+\[[\d,]*\])?", hlo)
    if not m:
        return hlo[:64]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:64]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def window(trace: Trace) -> tuple[float, float] | None:
    starts = [evs[0].start for evs in trace.ops.values() if evs]
    ends = [max(e.end for e in evs) for evs in trace.ops.values() if evs]
    if not starts:
        return None
    return min(starts), max(ends)


def busy_and_window(trace: Trace) -> tuple[float, float] | None:
    """(busy seconds averaged over the chips that ran an op, window
    seconds); None where no operation ran on a device."""
    win = window(trace)
    if win is None:
        return None
    busy = []
    for evs in trace.ops.values():
        if evs:
            merged = _union([(e.start, e.end) for e in evs])
            busy.append(sum(e - s for s, e in merged))
    return sum(busy) / len(busy), win[1] - win[0]


def top_device_ops(trace: Trace, n: int = 10) -> list[list]:
    """The operations that took most device time: [short name, seconds],
    summed over the window and averaged over chips."""
    total: dict[str, float] = {}
    chips = max(len([c for c, e in trace.ops.items() if e]), 1)
    for evs in trace.ops.values():
        for e in evs:
            if CONTAINER.search(e.name):
                continue  # a loop's event spans its body's, listed beside it
            key = op_short_name(e.name)
            total[key] = total.get(key, 0.0) + e.dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / chips] for k, v in ranked]


def _candidates(host, names):
    """Per thread, the events that may name a gap (annotations, and frames
    of the files in ``names``), sorted by start, with their start times."""
    out = []
    for evs in host.values():
        keep = []
        for e in evs:
            if e.name.startswith("$"):
                file = e.name[1:].split(":", 1)[0]
                if names is None or file not in names:
                    continue
            keep.append(e)
        if keep:
            out.append(([e.start for e in keep], keep))
    return out


def _innermost(candidates, t: float, reach: int = 4000) -> str | None:
    """Name of the innermost candidate event covering instant ``t``. On one
    thread events nest, so walking back from the last event that started
    before ``t`` the first one that still covers ``t`` is the innermost."""
    best: tuple[float, str] | None = None
    for starts, evs in candidates:
        i = bisect.bisect_right(starts, t) - 1
        stop = max(i - reach, -1)
        while i > stop:
            e = evs[i]
            if e.end >= t:
                if best is None or e.dur < best[0]:
                    best = (e.dur, e.name)
                break
            i -= 1
    return None if best is None else best[1]


def idle_gaps(
    trace: Trace, *, program_files: set[str] | None = None, n: int = 10,
    min_gap: float = 20e-6,
) -> list[list]:
    """Idle time of chip 0 (the lowest chip that ran ops) by what the host
    was doing at the middle of each gap: [name, seconds], largest first.
    A gap under no annotation and no program frame is
    ``outside_any_annotation``."""
    chips = sorted(c for c, e in trace.ops.items() if e)
    if not chips:
        return []
    merged = _union([(e.start, e.end) for e in trace.ops[chips[0]]])
    total: dict[str, float] = {}
    candidates = _candidates(trace.host, program_files)
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        gap = s1 - e0
        if gap < min_gap:
            continue
        name = _innermost(candidates, (e0 + s1) / 2)
        name = (name or "outside_any_annotation").lstrip("$")[:64]
        total[name] = total.get(name, 0.0) + gap
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def annotation_events(trace: Trace, name: str) -> list[Event]:
    out = [e for evs in trace.host.values() for e in evs if e.name == name]
    out.sort(key=lambda e: e.start)
    return out


def ops_matching(trace: Trace, pattern: str) -> dict[int, list[Event]]:
    rx = re.compile(pattern)
    return {
        chip: [e for e in evs if rx.search(e.name)]
        for chip, evs in trace.ops.items()
    }


def module_runs(trace: Trace, pattern: str) -> dict[int, list[Event]]:
    rx = re.compile(pattern)
    return {
        chip: [e for e in evs if rx.search(e.name)]
        for chip, evs in trace.modules.items()
    }


def exposed_collective_seconds(trace: Trace) -> float | None:
    """Seconds, averaged over chips, in which a collective was in flight
    and no compute operation ran on that chip. Collectives are the events
    of either device line whose HLO is an all-reduce, all-gather,
    reduce-scatter, all-to-all or collective-permute; compute is every
    other ``XLA Ops`` event. None where the trace holds no collective."""
    per_chip = []
    for chip, evs in trace.ops.items():
        coll = [
            (e.start, e.end)
            for e in evs + trace.async_ops.get(chip, [])
            if COLLECTIVE.search(e.name)
        ]
        if not coll:
            continue
        compute = _union(
            [(e.start, e.end) for e in evs if not COLLECTIVE.search(e.name)]
        )
        exposed = 0.0
        for s, e in _union(coll):
            covered = 0.0
            for cs, ce in compute:
                if ce <= s:
                    continue
                if cs >= e:
                    break
                covered += min(e, ce) - max(s, cs)
            exposed += (e - s) - covered
        per_chip.append(exposed)
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip)
