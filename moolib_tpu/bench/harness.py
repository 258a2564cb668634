"""perfwatch harness: one timing protocol, one result schema.

Every host-plane number perfwatch gates — echo latency, serializer GB/s,
loopback allreduce GB/s — goes through this module's protocol and leaves
as one machine-readable row (device speed is measured by
``benchmark/run.py`` and nowhere else):

- **protocol**: ``warmup`` untimed reps, then ``repeats`` timed reps on
  ``time.perf_counter`` (the monotonic high-resolution clock; the
  ``bench-wallclock`` lint rule keeps ``time.time()`` out of duration
  math in bench/tools code), summarized by :func:`trimmed_stats` so one
  GC pause or scheduler hiccup cannot move the headline value;
- **schema**: :class:`BenchResult` — metric/value/unit/direction plus the
  per-rep stats, an :func:`env_fingerprint`, the reproduce command, and
  an optional telemetry-registry snapshot, so every benchmark row doubles
  as a scrape fixture (docs/perf.md documents the schema).
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

__all__ = [
    "SCHEMA_VERSION",
    "STEPSCOPE_TREND_TOLERANCE",
    "BenchResult",
    "clock",
    "env_fingerprint",
    "measure",
    "parse_result",
    "stepscope_trend_rows",
    "trimmed_stats",
]

SCHEMA_VERSION = 1

#: Default trend tolerance for the stepscope fraction rows. Fractions are
#: noisy at smoke scale (tens of steps on a shared CPU runner), so the
#: band is wide — the detector's MAD floor tightens it automatically once
#: the trend store accumulates stable history.
STEPSCOPE_TREND_TOLERANCE = 0.5

#: THE harness timer. Benchmarks measure durations with this, never
#: ``time.time()`` — wall clock steps (NTP slew, manual set) corrupt
#: short intervals silently.
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


def stepscope_trend_rows(summary: Dict[str, Any], *, smoke: bool, cmd: str,
                         suite: str = "stepscope",
                         tol: float = STEPSCOPE_TREND_TOLERANCE,
                         extra: Optional[Dict[str, Any]] = None
                         ) -> List[BenchResult]:
    """Build schema-valid :class:`BenchResult` rows from one stepscope
    loop summary (:func:`moolib_tpu.telemetry.summarize_stepscope`) — one
    per derived fraction, unit ``fraction``, direction ``lower`` (a
    growing exposed-comms or host-blocked share is a step-composition
    regression even when headline throughput holds). The loop name is
    part of the metric (``stepscope_<loop>_<class>_fraction``): the
    detector baselines each metric as one series, and an envpool's
    env-wait share must never share a baseline with a learner's. Append
    to the CI trends artifact via
    :func:`~moolib_tpu.bench.trends.append_trend`."""
    base_extra = {"loop": summary["loop"], "steps": summary["steps"]}
    if extra:
        base_extra.update(extra)
    return [
        BenchResult(
            metric=f"stepscope_{summary['loop']}_{key}_fraction",
            value=float(value),
            unit="fraction",
            direction="lower",
            suite=suite,
            smoke=bool(smoke),
            cmd=cmd,
            tol=tol,
            extra=dict(base_extra),
        )
        for key, value in summary["fractions"].items()
    ]
