"""What every driver shares: the compile log, device facts, the result
line, percentiles."""

from __future__ import annotations

import json
import math
import time

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Every XLA program this process builds or fetches from the persistent
    cache, with the host time it finished at (jax's own monitoring events).
    A window in which one falls has compiled inside the measurement."""

    def __init__(self):
        import jax.monitoring

        self.events = []  # (monotonic time, program name, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **kw):
        if event == _BACKEND_COMPILE:
            self.events.append(
                (time.monotonic(), kw.get("fun_name", "?"), seconds)
            )

    def between(self, start: float, end: float) -> list:
        return [e for e in self.events if start <= e[0] <= end]


def device_facts(devices) -> dict:
    return {
        "platform": str(devices[0].platform),
        "kind": str(devices[0].device_kind),
        "count": len(devices),
    }


def memory_stats(devices) -> list:
    return [dict(d.memory_stats() or {}) for d in devices]


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip; 0 where the backend reports none. On a
    TPU the allocator counts live arrays under ``peak_bytes_in_use`` and
    the temporaries of the programs it runs under ``peak_bytes_reserved``
    (atari step: 0.15 GB and 4.8 GB, my chip run, PR 23): both hold HBM."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def result_line(correct, attempted, failed, metrics, device, breakdown=None):
    """The last line of a run's standard output. ``metrics`` maps a name
    to ``(value, unit)``; values go out as measured, unrounded."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {
            name: {"value": float(value), "unit": unit}
            for name, (value, unit) in metrics.items()
        },
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


class PhaseClock:
    """Seconds by phase of a driver's set-up, for the log: what a later PR
    would shorten."""

    def __init__(self):
        self.last = time.monotonic()
        self.phases = []

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.phases.append((name, now - self.last))
        self.last = now

    def __str__(self) -> str:
        return " ".join(f"{name}={s:.2f}s" for name, s in self.phases)
