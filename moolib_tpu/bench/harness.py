"""perfwatch harness: one timing protocol, one result schema.

Every number this repo quotes — device headline steps/s, CPU-proxy echo
latency, loopback allreduce GB/s — goes through this module's protocol
and leaves as one machine-readable row:

- **protocol**: ``warmup`` untimed reps, then ``repeats`` timed reps on
  ``time.perf_counter`` (the monotonic high-resolution clock; the
  ``bench-wallclock`` lint rule keeps ``time.time()`` out of duration
  math in bench/tools code), summarized by :func:`trimmed_stats` so one
  GC pause or scheduler hiccup cannot move the headline value;
- **schema**: :class:`BenchResult` — metric/value/unit/direction plus the
  per-rep stats, an :func:`env_fingerprint`, the reproduce command, and
  an optional telemetry-registry snapshot, so every benchmark row doubles
  as a scrape fixture (docs/perf.md documents the schema);
- **trend plumbing**: :func:`maybe_append_trend` appends rows to the
  append-only JSONL store (``bench/trends.jsonl`` by convention) when
  ``MOOLIB_TRENDS`` (or an explicit path) names one, which is how the
  legacy ``bench*.py`` wrappers feed the same trend schema the CPU-proxy
  CI suite uses.

The *device-side* timing primitives (chained in-jit steps + D2H
fingerprint readback) stay in ``moolib_tpu/utils/benchmark.py`` — they
are re-exported here so harness users need one import.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import platform
import statistics
import time
from typing import Any, Callable, Dict, List, Optional

# Device-side protocol (chained in-jit steps) — one import surface for
# benchmark authors.
from ..utils.benchmark import time_chained, time_train_step  # noqa: F401

__all__ = [
    "SCHEMA_VERSION",
    "BenchResult",
    "append_device_trend",
    "clock",
    "env_fingerprint",
    "maybe_append_trend",
    "measure",
    "parse_result",
    "time_chained",
    "time_train_step",
    "trimmed_stats",
]

SCHEMA_VERSION = 1

#: THE harness timer. Benchmarks measure durations with this (or the
#: device-side helpers above), never ``time.time()`` — wall clock steps
#: (NTP slew, manual set) corrupt short intervals silently.
clock: Callable[[], float] = time.perf_counter


def trimmed_stats(samples: List[float], trim: float = 0.2) -> Dict[str, Any]:
    """Order statistics over per-rep samples, with a symmetric trimmed
    mean (``trim`` total fraction dropped, split between both tails) so a
    single outlier rep cannot move the headline value. Median is the
    recommended ``value`` source; everything else is for the record."""
    if not samples:
        raise ValueError("no samples")
    if not 0.0 <= trim < 1.0:
        raise ValueError(f"trim must be in [0, 1), got {trim}")
    s = sorted(float(x) for x in samples)
    k = int(len(s) * trim / 2)
    core = s[k:len(s) - k] if k else s
    return {
        "n": len(s),
        "trim": trim,
        "mean": statistics.fmean(s),
        "trimmed_mean": statistics.fmean(core),
        "median": statistics.median(s),
        "min": s[0],
        "max": s[-1],
        "stdev": statistics.stdev(s) if len(s) > 1 else 0.0,
        "samples": [round(x, 9) for x in s],
    }


def measure(
    fn: Callable[[], Any], *, warmup: int = 1, repeats: int = 5
) -> List[float]:
    """The shared rep loop: ``warmup`` untimed calls, then ``repeats``
    calls each timed with :data:`clock`. Returns per-rep seconds (feed to
    :func:`trimmed_stats`)."""
    if repeats < 1:
        raise ValueError("repeats must be >= 1")
    for _ in range(warmup):
        fn()
    out = []
    for _ in range(repeats):
        t0 = clock()
        fn()
        out.append(clock() - t0)
    return out


def env_fingerprint() -> Dict[str, Any]:
    """Where a row came from: enough to tell two hosts/configs apart when
    reading a trend file, cheap enough to stamp on every row. Never
    initializes a JAX backend (a fingerprint must not claim a device)."""
    fp: Dict[str, Any] = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "machine": platform.machine(),
        "host": platform.node(),
        "cpus": os.cpu_count(),
        "jax_platforms": os.environ.get("JAX_PLATFORMS"),
    }
    try:  # version metadata only — no import, no backend init
        from importlib.metadata import version

        fp["jax"] = version("jax")
    except Exception:
        fp["jax"] = None
    return fp


@dataclasses.dataclass
class BenchResult:
    """One benchmark outcome in the unified schema.

    ``direction`` tells the regression detector which way is bad:
    ``"higher"`` for throughputs (a drop regresses), ``"lower"`` for
    latencies (a rise regresses). ``cmd`` is the reproduce command a CI
    failure prints. ``telemetry`` is a registry snapshot taken right
    after the timed reps (histogram series carry p50/p95/p99 — the
    budget layer reads those). ``value`` is ``None`` with ``error`` set
    when the benchmark could not run (the null-artifact convention,
    kept machine-readable)."""

    metric: str
    value: Optional[float]
    unit: str
    direction: str = "higher"
    suite: str = ""
    smoke: bool = False
    cmd: str = ""
    #: Per-metric relative trend tolerance override (None -> the
    #: detector's default). Benchmarks that are inherently noisy on
    #: shared CI hosts (ms-scale CPU-bound throughputs) declare their
    #: OBSERVED run-to-run variance here, so the trend gate catches
    #: structural slowdowns without crying wolf — a gate that flakes
    #: gets deleted. Quiet metrics leave it unset and keep the tight
    #: default band.
    tol: Optional[float] = None
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    env: Dict[str, Any] = dataclasses.field(default_factory=env_fingerprint)
    telemetry: Optional[Dict[str, Any]] = None
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)
    error: Optional[str] = None
    t: float = dataclasses.field(default_factory=time.time)  # wall stamp
    schema: int = SCHEMA_VERSION

    def __post_init__(self):
        if self.direction not in ("higher", "lower"):
            raise ValueError(f"bad direction {self.direction!r}")
        if self.value is not None and not math.isfinite(float(self.value)):
            raise ValueError(f"{self.metric}: non-finite value {self.value}")
        if self.tol is not None and not 0.0 < self.tol < 1.0:
            raise ValueError(f"{self.metric}: tol must be in (0, 1)")

    def to_row(self) -> Dict[str, Any]:
        """Plain-JSON dict — the JSONL trend-store line."""
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        """One line, strict JSON (``allow_nan=False``: a NaN that cannot
        round-trip must fail at write time, not at the reader)."""
        return json.dumps(self.to_row(), allow_nan=False)


def parse_result(row: Any) -> BenchResult:
    """Inverse of :meth:`BenchResult.to_row`/``to_json`` — the schema
    round-trip is pinned by tests (result -> JSONL -> parse -> identical)."""
    if isinstance(row, str):
        row = json.loads(row)
    if not isinstance(row, dict):
        raise ValueError(f"not a result row: {type(row).__name__}")
    if row.get("schema") != SCHEMA_VERSION:
        raise ValueError(
            f"unsupported result schema {row.get('schema')!r} "
            f"(expected {SCHEMA_VERSION})"
        )
    known = {f.name for f in dataclasses.fields(BenchResult)}
    unknown = set(row) - known
    if unknown:
        raise ValueError(f"unknown result fields: {sorted(unknown)}")
    missing = {"metric", "value", "unit"} - set(row)
    if missing:
        raise ValueError(f"result row missing fields: {sorted(missing)}")
    return BenchResult(**row)


def maybe_append_trend(
    results, path: Optional[str] = None, env_var: str = "MOOLIB_TRENDS"
) -> Optional[str]:
    """Append result rows to the JSONL trend store named by ``path`` or
    ``$MOOLIB_TRENDS``; silently a no-op when neither is set (so the
    legacy one-line-JSON scripts cost nothing outside a perfwatch run).
    Returns the path written, if any."""
    path = path or os.environ.get(env_var)
    if not path:
        return None
    from .trends import append_trend

    for r in results:
        append_trend(path, r)
    return path


def append_device_trend(
    metric: str, value: float, unit: str, cmd: str, *,
    direction: str = "higher",
    stats: Optional[Dict[str, Any]] = None,
    extra: Optional[Dict[str, Any]] = None,
    tol: Optional[float] = None,
) -> Optional[str]:
    """One-call trend append for the legacy device-suite wrappers
    (``bench*.py``, ``tools/*_bench*``): builds the harness row and hands
    it to :func:`maybe_append_trend` — still a no-op unless
    ``$MOOLIB_TRENDS`` names a store."""
    return maybe_append_trend([BenchResult(
        metric=metric, value=value, unit=unit, direction=direction,
        suite="device", cmd=cmd, stats=stats or {}, extra=extra or {},
        tol=tol,
    )])
