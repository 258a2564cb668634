"""Profiler capture: the device-tracing half of the observability story.

The reference's tracing is flamegraph-style host tracing of its C++ threads
(reference: src/moolib.cc trace hooks / py/moolib docs). On TPU the
actionable trace is XLA's: ``jax.profiler`` captures device timelines
(MXU occupancy, HBM traffic, collective overlap) viewable in TensorBoard
or Perfetto. This wraps it with a zero-dependency context manager and a
step-window helper so experiments can capture exactly N steps without
instrumenting their loops twice. A capture also holds the program's own
spans (``moolib.<loop>.<phase>``, ``moolib.acc.*``) on the host plane,
on the device planes' clock: every ``StepScope`` phase opens one while a
session is live (``telemetry/trace.py:ProgramSpan``).

Timeline merge: every capture window is also recorded as a span on the
:mod:`moolib_tpu.telemetry` trace buffer (category ``profiler``, args
pointing at the logdir), so a cohort dump from
``tools/telemetry_dump.py`` shows *where* the XLA capture sat relative to
RPC call/handle spans and chaosnet injections — open the logdir's own
Perfetto trace beside it for the device-level zoom of that window.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator, Optional

__all__ = ["profile_trace", "StepWindowProfiler"]


def _record_window(logdir: str, wall0: float, args: Optional[dict] = None):
    """Mark a finished capture window on the shared telemetry timeline.
    Unconditional (capture is rare and deliberate — no hot-path gate)."""
    from ..telemetry import global_telemetry

    span_args = {"logdir": logdir}
    if args:
        span_args.update(args)
    global_telemetry().traces.add_span(
        "jax_profiler_capture", "profiler", pid="profiler",
        ts_us=int(wall0 * 1e6), dur_us=int((time.time() - wall0) * 1e6),
        args=span_args,
    )


@contextlib.contextmanager
def profile_trace(logdir: str) -> Iterator[None]:
    """Capture a jax profiler trace into ``logdir`` for the duration of the
    with-block (view with TensorBoard's profile plugin or Perfetto)."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    wall0 = time.time()
    try:
        with jax.profiler.trace(logdir):
            yield
    finally:
        _record_window(logdir, wall0)


class StepWindowProfiler:
    """Capture steps [start, stop) of a training loop.

    >>> prof = StepWindowProfiler(logdir, start=10, stop=13)
    >>> for step in range(n):
    ...     prof.step(step)   # starts/stops the capture at the window edges
    ...     train_step(...)
    >>> prof.close()          # safety: stop if the loop exited early

    Skipping the first steps avoids tracing compilation, which would dwarf
    the steady-state timeline.
    """

    def __init__(self, logdir: Optional[str], start: int = 10, stop: int = 13):
        self.logdir = logdir
        self.start = start
        self.stop = stop
        self._active = False
        self._wall0 = 0.0

    def step(self, step_index: int) -> None:
        if self.logdir is None:
            return
        import jax

        if not self._active and self.start <= step_index < self.stop:
            os.makedirs(self.logdir, exist_ok=True)
            self._wall0 = time.time()
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif self._active and step_index >= self.stop:
            jax.profiler.stop_trace()
            self._active = False
            _record_window(self.logdir, self._wall0,
                           {"start_step": self.start, "stop_step": self.stop})

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            _record_window(self.logdir, self._wall0,
                           {"start_step": self.start, "closed_early": True})
