"""Device time under the program's named scopes, out of a ``.xplane.pb``.

``trace_reduce`` keeps an operation's HLO text and ``jax.profiler.ProfileData``
gives no more; the scope an operation was traced under (``jax.named_scope``,
a Flax module's path) is in the trace all the same, as the ``tf_op`` stat of
the event's *metadata* (``jit(step)/jvp(HybridLM)/layer_0/mixer/lm.gdn_scan/
dot_general``; looked at by hand in ``tests/benchmark/recorded_v5e.xplane.pb``).
This module reads the protobuf's wire format directly, the few fields it
needs (XSpace.planes=1; XPlane name=2 lines=3 event_metadata=4
stat_metadata=5; XLine name=2 timestamp_ns=3 events=4; XEvent metadata_id=1
offset_ps=2 duration_ps=3; XEventMetadata name=2 stats=5; XStat metadata_id=1
str_value=5 ref_value=7; XStatMetadata name=2), so that it needs no generated
protobuf module.

``scope_seconds`` never raises on a trace it cannot read: it returns ``None``
and the readers leave their metrics out.
"""

from __future__ import annotations

import re
import statistics

from benchmark import trace_reduce


def _varint(buf, i):
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited and fixed fields."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield tag >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _device_plane(plane):
    """(name, event metadata id -> (HLO text, tf_op), line name -> [(start s,
    dur s, metadata id)]) of one XPlane."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for field, v in _fields(plane):
        if field == 2:
            name = _text(v)
        elif field == 3:
            lines.append(v)
        elif field == 4:
            event_meta.update([_map_entry(v)])
        elif field == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for f, x in _fields(meta) if f == 2), ""
            )
    if not trace_reduce.DEVICE_PLANE.match(name):
        return None
    tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
    ops = {}
    for key, meta in event_meta.items():
        hlo, scope = "", ""
        for field, v in _fields(meta):
            if field == 2:
                hlo = _text(v)
            elif field == 5:
                stat = dict(_fields(v))
                if stat.get(1) in tf_op_ids:
                    if 5 in stat:
                        scope = _text(stat[5])
                    elif 7 in stat:  # a reference into the stat names
                        scope = stat_names.get(stat[7], "")
        ops[key] = (hlo, scope)
    events = {}
    for line in lines:
        line_name, t0_ns, raw = "", 0, []
        for field, v in _fields(line):
            if field == 2:
                line_name = _text(v)
            elif field == 3:
                t0_ns = v
            elif field == 4:
                raw.append(v)
        if line_name not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        out = []
        for ev in raw:
            e = dict(_fields(ev))
            out.append((
                t0_ns * 1e-9 + e.get(2, 0) * 1e-12, e.get(3, 0) * 1e-12,
                e.get(1),
            ))
        events[line_name] = out
    return name, ops, events


def scope_seconds(path: str, scopes, module_pattern: str, note=None):
    """Seconds of device time a whole run of the module matching
    ``module_pattern`` spends in operations traced under each of ``scopes``
    (a name counts where it is a component of the operation's ``tf_op``
    path; a loop's own event is left out, its body's operations are beside
    it), averaged over the whole runs in the trace and over chips; the
    number of whole runs; and a whole run's mean seconds. ``None`` where the
    trace cannot be read or holds no such run or no scope at all."""
    try:
        with open(path, "rb") as f:
            data = memoryview(f.read())
        planes = [
            _device_plane(v) for field, v in _fields(data) if field == 1
        ]
    except Exception as e:  # noqa: BLE001  (a reader must not fail the run)
        if note:
            note(f"scope trace: cannot read {path}: {e!r}")
        return None
    module_rx = re.compile(module_pattern)
    scope_rx = {
        s: re.compile(r"(^|/)" + re.escape(s) + r"(/|$|:)") for s in scopes
    }
    total = dict.fromkeys(scopes, 0.0)
    runs, run_seconds = 0, 0.0
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
        run_seconds += sum(b - a for a, b in whole)
        for start, dur, key in events.get(trace_reduce.OPS_LINE, []):
            hlo, scope = ops.get(key, ("", ""))
            if not scope or trace_reduce.CONTAINER.search(hlo):
                continue
            if not any(a <= start and start + dur <= b + 1e-9 for a, b in whole):
                continue
            for name, rx in scope_rx.items():
                if rx.search(scope):
                    total[name] += dur
    if not runs or not any(total.values()):
        if note:
            note(f"scope trace: {runs} whole runs, no operation under {list(scopes)}")
        return None
    per_run = {name: seconds / runs for name, seconds in total.items()}
    return per_run, runs, run_seconds / runs
