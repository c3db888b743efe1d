"""The reduction from a profiler trace to numbers: on hand-built traces
whose answers are known, and on one small trace recorded on a TPU v5e
(``recorded_v5e.xplane.pb``, two train steps of the one-layer toy model, PR 23)."""

import os

import pytest

from benchmark import readers, trace_reduce as tr
from benchmark.trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")


def _ops(*spans, name="%fusion.1 = f32[8]{0} fusion()"):
    return [Event(name, s, e - s) for s, e in spans]


def test_busy_is_the_union_and_the_window_runs_first_to_last_op():
    trace = Trace(ops={0: _ops((0.0, 1.0), (0.5, 2.0), (3.0, 4.0))},
                  async_ops={}, modules={}, host={})
    busy, window = tr.busy_and_window(trace)
    assert busy == pytest.approx(3.0) and window == pytest.approx(4.0)
    two = Trace(ops={0: _ops((0.0, 1.0)), 1: _ops((1.0, 4.0))},
                async_ops={}, modules={}, host={})
    busy, window = tr.busy_and_window(two)
    assert busy == pytest.approx(2.0), "averaged over the chips used"
    assert window == pytest.approx(4.0)
    assert tr.busy_and_window(Trace({}, {}, {}, {})) is None


def test_idle_gaps_are_named_by_the_innermost_host_event():
    host = {"python#0": [
        Event("$engine.py:628 _paged_step", 0.9, 1.3),
        Event("serve_decode_paged", 1.0, 0.2),
        Event("$builtins len", 1.05, 0.01),
        Event("$threading.py:1 wait", 2.9, 0.3),
    ]}
    trace = Trace(ops={0: _ops((0.0, 1.0), (1.2, 2.0), (2.1, 3.0), (3.1, 4.0))},
                  async_ops={}, modules={}, host=host)
    gaps = dict(tr.idle_gaps(trace, program_files={"engine.py"}, min_gap=0.01))
    assert gaps["serve_decode_paged"] == pytest.approx(0.2)
    assert gaps["engine.py:628 _paged_step"] == pytest.approx(0.1)
    assert gaps["outside_any_annotation"] == pytest.approx(0.1)
    assert "builtins len" not in gaps and len(gaps) == 3


def test_top_ops_skip_a_loops_own_event_and_shorten_names():
    body = "%fusion.7 = bf16[512,16]{1,0:T(8,128)} fusion(bf16[4] %x), kind=kLoop"
    loop = "%while.5 = (s32[], bf16[4]) while((s32[], bf16[4]) %tuple), body=%b"
    trace = Trace(ops={0: [Event(loop, 0.0, 2.0), Event(body, 0.0, 0.5),
                           Event(body, 1.0, 0.5)]},
                  async_ops={}, modules={}, host={})
    assert tr.top_device_ops(trace) == [["fusion.7 bf16[512,16]", pytest.approx(1.0)]]
    assert tr.busy_and_window(trace)[0] == pytest.approx(2.0)


def test_exposed_collective_time():
    reduce = "%all-reduce.3 = f32[1024]{0} all-reduce(f32[1024] %g), replica_groups={}"
    compute = "%fusion.2 = f32[8]{0} fusion()"
    trace = Trace(
        ops={0: [Event(compute, 0.0, 1.0), Event(reduce, 0.8, 0.7),
                 Event(compute, 1.2, 0.1)]},
        async_ops={}, modules={}, host={},
    )
    # in flight 0.8-1.5; compute covers 0.8-1.0 and 1.2-1.3 of it
    assert tr.exposed_collective_seconds(trace) == pytest.approx(0.4)
    none = Trace(ops={0: [Event(compute, 0.0, 1.0)]}, async_ops={},
                 modules={}, host={})
    assert tr.exposed_collective_seconds(none) is None


def test_op_short_name():
    assert tr.op_short_name(
        "%flash_attention.2 = bf16[512,256,128]{2,1,0:T(8,128)(2,1)} custom-call(...)"
    ) == "flash_attention.2 bf16[512,256,128]"
    assert tr.op_short_name("%copy.1 = (f32[2]{0}, u32[]) copy-start(...)") == "copy.1 (f32[2]"


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_v5e_trace():
    trace = tr.load(RECORDED)
    assert list(trace.ops) == [0] and trace.ops[0]
    busy, window = tr.busy_and_window(trace)
    assert 0 < busy <= window
    steps = tr.module_runs(trace, r"^jit_step\(")[0]
    assert len(steps) == 2
    flash = tr.ops_matching(trace, readers.MOSAIC_CALL)[0]
    assert len(flash) == 2 * 3, "3 forwards a layer, 1 layer, 2 steps"
    top = tr.top_device_ops(trace, n=5)
    assert len(top) == 5 and top[0][1] >= top[-1][1] > 0
    assert tr.exposed_collective_seconds(trace) is None, "one chip: no collective"
    names = {e.name for evs in trace.host.values() for e in evs}
    assert any(n.startswith("PjitFunction(") for n in names)


# The Mosaic call as the TPU's compiler names it in big_train_dp4's step
# (compiled here for a described 2x2 v5e, PR 23): under fit(mesh=) the flash
# launcher runs per shard and the call takes the shard_map's name.
SHARDED_FLASH = (
    '%shard_map.49 = bf16[384,256,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call('
    'bf16[384,256,128]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.338, s32[24,1,256]'
    '{2,1,0:T(1,128)S(1)} %copy.131), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={bf16[384,256,128]{2,1,0}}'
)


class _TracedTrainRun:
    """What the flash reader takes from a run: two chips, two whole steps
    each of ``calls`` Mosaic calls of 1 ms."""

    chips = 2

    def __init__(self, calls, name=SHARDED_FLASH):
        step = "jit_step(123)"
        ops = [Event(name, t + 0.01 * k, 0.001)
               for t in (0.0, 1.0) for k in range(calls)]
        ops.append(Event("%fusion.1 = f32[8]{0} fusion()", 0.5, 0.2))
        self.trace_data = Trace(
            ops={0: list(ops), 1: list(ops)}, async_ops={},
            modules={c: [Event(step, 0.0, 0.9), Event(step, 1.0, 0.9)]
                     for c in (0, 1)},
            host={},
        )
        # least time: 4e9 FLOP / 197e12 = 20.3 us against 8e6 B / 819e9 =
        # 9.8 us a step over both chips, so 10.15 us a chip a step
        self.counters = {"flash_cost_per_step": (4e9, 8e6),
                         "flash_calls_per_step": 3}
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)


@pytest.mark.parametrize("name", [
    SHARDED_FLASH,
    SHARDED_FLASH.replace("%shard_map.49", "%flash_attention.2"),
])
def test_flash_roofline_finds_the_kernel_under_either_name(monkeypatch, name):
    from benchmark import peaks

    monkeypatch.setattr(
        readers, "chip_peaks", lambda run: peaks.peaks_for("TPU v5 lite"))
    run = _TracedTrainRun(calls=3, name=name)
    share = readers.flash_forward_roofline_percent(run)
    assert share == pytest.approx(100 * (4e9 / 197e12 / 2) / 0.003)
    assert "bound by compute" in run.notes[-1]


def test_flash_roofline_says_what_it_counted_when_the_count_is_off(monkeypatch):
    from benchmark import peaks

    monkeypatch.setattr(
        readers, "chip_peaks", lambda run: peaks.peaks_for("TPU v5 lite"))
    run = _TracedTrainRun(calls=4)
    assert readers.flash_forward_roofline_percent(run) is None
    assert "8 Mosaic calls in 2 whole steps, not 3" in run.notes[-1]
