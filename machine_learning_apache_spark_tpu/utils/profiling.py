"""Profiling / tracing hooks — the observability the reference lacks.

The reference's only instrumentation is manual ``time.time()`` pairs
(SURVEY.md §5 tracing: 19 sites, plus one unused ``timeit`` import at
``pytorch_cnn.py:6``). The framework keeps that span vocabulary
(``utils.timing``) and adds the real thing: ``jax.profiler`` device traces
viewable in TensorBoard/XProf (compiled-step timelines, HBM usage, ICI
collectives), plus named trace annotations that label host-side regions
inside the trace.

Usage:
    with device_trace("/tmp/trace"):          # whole-region trace
        run_steps()

    fit(..., profile_dir="/tmp/trace")        # trace a step window mid-run

    with annotate("tokenize"):                # label host work in the trace
        pipe(texts)
"""

from __future__ import annotations

import contextlib

import jax

from machine_learning_apache_spark_tpu.telemetry import spans as _spans
from machine_learning_apache_spark_tpu.utils.logging import get_logger

log = get_logger(__name__)


def _sync_local_devices() -> None:
    """Fence: a trivial computation per local device executes only after all
    previously-dispatched work on that device — required before stop_trace
    or the traced steps' device timeline is still in flight and missing."""
    import jax.numpy as jnp

    probes = [
        jax.device_put(jnp.zeros(()), d) + 0 for d in jax.local_devices()
    ]
    jax.block_until_ready(probes)


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Capture a jax.profiler trace for the enclosed region into
    ``log_dir`` (TensorBoard: ``tensorboard --logdir <log_dir>``)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        _sync_local_devices()
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", log_dir)


class _AnnotatedRegion:
    """Context manager pairing a jax.profiler.TraceAnnotation (device
    timeline) with a telemetry span (host event log): one entry point, the
    region shows up in both worlds. The telemetry half is the shared no-op
    when disabled, so the hot serving decode path pays only the
    TraceAnnotation it already paid."""

    __slots__ = ("_trace", "_span")

    def __init__(self, name: str, **kwargs):
        self._trace = jax.profiler.TraceAnnotation(name, **kwargs)
        self._span = _spans.span(name, **kwargs)

    def __enter__(self):
        self._span.__enter__()
        self._trace.__enter__()
        return self

    def __exit__(self, *exc):
        self._trace.__exit__(*exc)
        self._span.__exit__(*exc)

    def set(self, **attrs) -> None:
        """Counts known when the region ends, onto the span's
        ``span_end``. The trace annotation keeps its bare name: a reader
        of the trace finds a region by that name."""
        self._span.set(**attrs)


def annotate(name: str, **kwargs):
    """Named region annotation appearing on the trace timeline (and, when
    telemetry is enabled, as a span on the event log)."""
    return _AnnotatedRegion(name, **kwargs)


class StepWindowTracer:
    """Trace a ``[start, stop)`` window of steps inside a long run — the
    usual profiling pattern: skip compile/warmup steps, capture a few steady
    -state ones, stop before the trace gets huge.
    """

    def __init__(self, log_dir: str | None, *, start: int = 2, stop: int = 5):
        if stop <= start:
            raise ValueError(f"empty trace window [{start}, {stop})")
        self.log_dir = log_dir
        self.start, self.stop = start, stop
        self._active = False
        self._done = False

    def on_step(self, step: int) -> None:
        # Boundary-crossing (>=), not equality: callers may advance the step
        # counter in strides > 1 (fit's steps_per_call dispatches K steps
        # per on_step call) and must still enter/leave the window. Order
        # matters: the stop check applies only while active, so a single
        # stride crossing BOTH boundaries still starts a trace (covering at
        # least its own dispatch; the next call closes it).
        if self.log_dir is None:
            return
        if self._active and step >= self.stop:
            self.close()
            return
        if not self._active and not self._done and step >= self.start:
            jax.profiler.start_trace(self.log_dir)
            self._active = True

    def close(self) -> None:
        if self._active:
            _sync_local_devices()
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            log.info(
                "profiler trace (steps %d-%d) written to %s",
                self.start, self.stop, self.log_dir,
            )
