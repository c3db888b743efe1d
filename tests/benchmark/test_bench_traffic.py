"""The traffic generator: the work does not depend on the seed."""

import collections
import time

import numpy as np
import pytest

from benchmark import manifest, traffic
from benchmark.kinds import serve


@pytest.mark.parametrize("mix", ["closed_loop_news", "open_loop_news"])
def test_same_multiset_of_lengths_at_every_seed(mix):
    spec = manifest.load_traffic(mix)["lengths"]
    a = traffic.prompts(spec, 37000, seed=1)
    b = traffic.prompts(spec, 37000, seed=2**31 + 17)
    lens = lambda ps: collections.Counter(len(p) for p in ps)  # noqa: E731
    assert lens(a) == lens(b)
    assert [len(p) for p in a] != [len(p) for p in b]
    assert any((x[: 3] != y[: 3]).any() for x, y in zip(a, b) if len(x) == len(y))
    mean = np.mean([len(p) for p in a])
    assert 26 <= mean <= 30 and max(len(p) for p in a) <= 126
    assert min(int(p.min()) for p in a) >= 4


def test_same_seed_same_prompts():
    spec = manifest.load_traffic("closed_loop_news")["lengths"]
    a = traffic.prompts(spec, 37000, seed=5)
    b = traffic.prompts(spec, 37000, seed=5)
    assert all((x == y).all() for x, y in zip(a, b))


@pytest.mark.parametrize("process", [
    {"process": "poisson", "rate_per_s": 200.0},
    {"process": "poisson", "rate_per_s": 360.0},
])
def test_open_loop_due_times(process):
    a = traffic.due_times(process, 10.0, seed=3)
    b = traffic.due_times(process, 10.0, seed=4)
    assert len(a) == len(b) == round(10 * process["rate_per_s"])
    assert np.all(np.diff(a) >= 0) and a[-1] == pytest.approx(10.0, rel=1e-6)
    gaps = lambda t: np.sort(np.diff(np.concatenate([[0.0], t])))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-6, atol=1e-9)
    assert not np.allclose(a, b)
    cv = np.std(gaps(a)) / np.mean(gaps(a))
    assert cv == pytest.approx(1.0, rel=0.15)


def test_a_mix_the_generator_does_not_know_is_an_error():
    with pytest.raises(ValueError, match="arrival process"):
        traffic.due_times({"process": "gamma", "rate_per_s": 9.0, "cv": 2}, 1.0, 1)
    with pytest.raises(ValueError, match="length distribution"):
        traffic.length_multiset({"dist": "uniform", "min": 1, "max": 9, "count": 4})


def test_train_batches_are_full_and_rows_differ():
    cfg = dict(src_vocab_size=64, trg_vocab_size=80, sos_id=1, eos_id=2)
    spec = dict(src_len=12, trg_len=12)
    (src, trg), (src2, _) = traffic.train_batches(spec, cfg, 8, seed=9, count=2)
    assert src.shape == (8, 12) and trg.shape == (8, 13)
    assert (src != 0).all() and (trg != 0).all()
    assert len({tuple(r) for r in src}) == 8 and not (src == src2).all()


def test_nearest_rank():
    assert traffic.nearest_rank(range(1, 101), 95) == 95
    assert traffic.nearest_rank([5.0], 95) == 5.0


class _FakeFuture:
    def __init__(self):
        self.callbacks = []

    def add_done_callback(self, cb):
        self.callbacks.append(cb)

    def exception(self, timeout=None):
        return None


class _FakeEngine:
    """Answers nothing by itself; the test resolves the futures."""

    def __init__(self, stall_at=None):
        self.requests, self.stall_at = [], stall_at

    def submit(self, text):
        if self.stall_at is not None and len(self.requests) == self.stall_at:
            time.sleep(0.05)  # a stalled submit makes the generator late
        req = type("Req", (), {})()
        req.future = _FakeFuture()
        self.requests.append(req)
        return req


def test_open_loop_times_from_the_due_instant_and_reports_lateness():
    engine = _FakeEngine(stall_at=2)
    due = time.monotonic() + 0.01 + np.arange(6) * 0.01
    client = serve.OpenLoop(engine, ["a b"] * 3, due)
    client.start()
    client.thread.join(timeout=5)
    assert not client.thread.is_alive() and len(client.records) == 6
    late = [r.submit - r.due for r in client.records]
    assert all(x >= 0 for x in late)
    assert late[3] > 0.03, "the stall's wait falls on the next request"
    assert [r.due for r in client.records] == pytest.approx(list(due))


def test_closed_loop_sends_a_callers_next_only_after_its_reply():
    engine = _FakeEngine()
    client = serve.ClosedLoop(engine, [f"t{i}" for i in range(8)], callers=2)
    client.start()
    deadline = time.monotonic() + 5
    while len(engine.requests) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    time.sleep(0.1)
    assert len(engine.requests) == 2, "two callers, two requests in flight"
    first = engine.requests[0]
    for cb in first.future.callbacks:
        cb(first.future)
    while len(engine.requests) < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    client.stop()
    assert len(engine.requests) == 3
    assert not client.thread.is_alive()
    assert [r.idx for r in client.records] == [0, 1, 2]
