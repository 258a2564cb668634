"""Trace spans: a bounded buffer exportable as Chrome-trace/Perfetto JSON.

The reference's observability is flamegraph-style *host* tracing of its
C++ worker threads; here the actionable cross-peer picture is a timeline
of RPC call/handle spans — caller and handler sides of one call share a
**trace id** propagated through the wire payload (see
``moolib_tpu/rpc/rpc.py``), so a merged dump from several peers
(``tools/telemetry_dump.py``) reconstructs causality across the cohort.
chaosnet injected-fault events and ``utils/profiling.py`` jax-profiler
capture windows land on the same timeline, which is what makes a seeded
chaos replay *readable*: the drop/delay instants sit right next to the
latency they caused.

Span timestamps are wall-clock microseconds (``time.time()``), the one
clock different hosts share well enough to merge; durations are measured
with the monotonic clock, so a span's extent is immune to wall-clock
steps even though its placement is not.

The program's own spans (a loop's step and its phases, the Accumulator's
off-thread blocks) go through one seam, :class:`ProgramSpan`, which has
two sinks: a ``jax.profiler.TraceAnnotation`` on the host plane of any
live profiler session, on the device trace's own clock, and this buffer
on the wall clock (``docs/observability.md``, "Trace spans").
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["Span", "TraceBuffer", "ProgramSpan", "now_us"]


def now_us() -> int:
    """Wall-clock microseconds — the shared axis of the merged timeline."""
    return int(time.time() * 1e6)


class Span:
    """One trace event (Chrome-trace ``X`` complete or ``i`` instant)."""

    __slots__ = ("name", "cat", "ph", "ts", "dur", "pid", "tid",
                 "trace_id", "args")

    def __init__(self, name: str, cat: str, ph: str, ts: int, dur: int,
                 pid: str, tid: int, trace_id: Optional[str],
                 args: Optional[Dict[str, Any]]):
        self.name = name
        self.cat = cat
        self.ph = ph
        self.ts = ts
        self.dur = dur
        self.pid = pid
        self.tid = tid
        self.trace_id = trace_id
        self.args = args

    def to_event(self, pid_map: Dict[str, int]) -> Dict[str, Any]:
        args = dict(self.args) if self.args else {}
        if self.trace_id is not None:
            args["trace_id"] = self.trace_id
        ev: Dict[str, Any] = {
            "name": self.name,
            "cat": self.cat,
            "ph": self.ph,
            "ts": self.ts,
            "pid": pid_map[self.pid],
            "tid": self.tid,
            "args": args,
        }
        if self.ph == "X":
            ev["dur"] = self.dur
        else:
            ev["s"] = "p"  # instant scope: process
        return ev


class ProgramSpan:
    """One named region of the program, written to two sinks.

    On entry it opens a ``jax.profiler.TraceAnnotation(name)`` if a
    profiler session is live (the benchmark's ``--trace 1``, an
    operator's ``profile_dir``): the span lands on this thread's line of
    the xplane's host plane, on the clock the device's operations are
    on. With no session that is one static check. When the owner's
    ``Telemetry.tracing`` is on, the exit also records the span into the
    ``TraceBuffer`` at its real start (wall clock) and duration.

    Created by :meth:`Telemetry.span` and reused: an entry allocates
    nothing but the annotation itself. The open span's state is on the
    object, so an object serves one thread at a time and does not nest
    in itself. As a context manager it is gated on ``Telemetry.on`` and
    times itself; an owner with a gate and a clock of its own
    (``StepScope``) calls :meth:`begin` and :meth:`end` with the
    duration it measured, so its counter and its span come from the same
    two readings.
    """

    __slots__ = ("name", "_tel", "_cat", "_args", "_annotate", "_ann",
                 "_ts_us", "_t0")

    def __init__(self, telemetry, name: str, cat: str,
                 args: Optional[Dict[str, Any]]):
        # The one place the program meets the profiler. Imported here
        # and not at module level: env workers import this package and
        # must not pay for (or touch) jax.
        from jax.profiler import TraceAnnotation

        self.name = name
        self._tel = telemetry
        self._cat = cat
        self._args = args
        self._annotate = TraceAnnotation
        self._ann = None
        self._ts_us = 0
        self._t0 = -1.0

    def begin(self) -> None:
        if self._annotate.is_enabled():
            # The annotation starts at construction; its __enter__ is a
            # no-op.
            self._ann = self._annotate(self.name)
        if self._tel.tracing:
            self._ts_us = now_us()

    def end(self, seconds: float) -> None:
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        ts_us = self._ts_us
        if ts_us:
            self._ts_us = 0
            tel = self._tel
            tel.traces.add_span(
                self.name, self._cat, pid=tel.name or "program",
                ts_us=ts_us, dur_us=int(seconds * 1e6), args=self._args,
            )

    def __enter__(self) -> "ProgramSpan":
        if self._tel.on:
            self._t0 = time.monotonic()
            self.begin()
        else:
            self._t0 = -1.0
        return self

    def __exit__(self, *exc: Any) -> bool:
        if self._t0 >= 0.0:
            self.end(time.monotonic() - self._t0)
        return False


class TraceBuffer:
    """Bounded span ring (oldest spans evicted first).

    Recording is append-under-lock; owners gate recording on their
    ``Telemetry.tracing`` flag, so an idle buffer costs nothing.
    Evictions are **counted**: :attr:`dropped` and the
    ``trace_spans_dropped_total`` counter (``drop_counter``, wired by
    :class:`~moolib_tpu.telemetry.Telemetry`) record how many spans a
    full ring discarded, and the count rides the Chrome-trace export
    metadata — a truncated timeline is labeled, never misleading.
    """

    def __init__(self, capacity: int = 65536, drop_counter=None):
        self._lock = threading.Lock()
        self._capacity = int(capacity)
        self._spans: deque = deque(maxlen=self._capacity)
        self._dropped = 0
        self._drop_counter = drop_counter  # anything with .inc(), or None

    def _append(self, span: Span) -> None:
        dc = None
        with self._lock:
            if len(self._spans) == self._capacity:
                self._dropped += 1
                dc = self._drop_counter
            self._spans.append(span)
        if dc is not None:
            dc.inc()  # the counter has its own lock; keep ours a leaf

    def add_span(self, name: str, cat: str, pid: str, ts_us: int,
                 dur_us: int, trace_id: Optional[str] = None,
                 tid: int = 0, args: Optional[Dict[str, Any]] = None) -> None:
        """Record a complete (``ph=X``) span."""
        self._append(Span(name, cat, "X", int(ts_us), max(0, int(dur_us)),
                          pid, tid, trace_id, args))

    def add_instant(self, name: str, cat: str, pid: str,
                    ts_us: Optional[int] = None,
                    trace_id: Optional[str] = None,
                    args: Optional[Dict[str, Any]] = None) -> None:
        """Record an instant (``ph=i``) event — chaos injections etc."""
        self._append(
            Span(name, cat, "i", now_us() if ts_us is None else int(ts_us),
                 0, pid, 0, trace_id, args)
        )

    @property
    def dropped(self) -> int:
        """Spans evicted by ring overflow since construction/clear."""
        with self._lock:
            return self._dropped

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._dropped = 0

    def __len__(self) -> int:
        return len(self._spans)

    def chrome_trace(self) -> Dict[str, Any]:
        """Export as a Chrome-trace JSON object (load in Perfetto /
        chrome://tracing). ``pid`` strings (peer names) are mapped to
        stable small ints with ``process_name`` metadata events so every
        peer renders as its own named process track. Eviction counts ride
        in ``otherData`` so a truncated export is labeled."""
        spans = sorted(self.spans(), key=lambda s: (s.ts, s.pid, s.name))
        return spans_to_chrome(spans, dropped=self.dropped)


def spans_to_chrome(spans: List[Span],
                    dropped: Optional[int] = None) -> Dict[str, Any]:
    """Shared Chrome-trace assembly for one buffer or a cross-peer merge
    (``tools/telemetry_dump.py`` concatenates peers' span lists first).
    ``dropped`` (when given) labels the export with the span-ring
    eviction count in ``otherData`` — a truncated timeline must say so."""
    pid_map: Dict[str, int] = {}
    for s in spans:
        if s.pid not in pid_map:
            pid_map[s.pid] = len(pid_map) + 1
    events: List[Dict[str, Any]] = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "tid": 0,
            "args": {"name": name},
        }
        for name, pid in sorted(pid_map.items(), key=lambda kv: kv[1])
    ]
    events.extend(s.to_event(pid_map) for s in spans)
    trace = {"traceEvents": events, "displayTimeUnit": "ms"}
    if dropped is not None:
        trace["otherData"] = {"spans_dropped": int(dropped)}
    return trace
