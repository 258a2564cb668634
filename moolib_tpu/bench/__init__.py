"""perfwatch: the unified benchmark harness, CPU-proxy suite, telemetry-
derived budgets, and the append-only trend store + regression detector.

One CLI fronts all of it: ``python tools/perf.py`` (see docs/perf.md).
It times the host plane only (RPC, serialization, the tree all-reduce,
batcher, envpool); device speed is ``benchmark/run.py``'s to measure.
"""

from .harness import (
    SCHEMA_VERSION,
    BenchResult,
    clock,
    env_fingerprint,
    measure,
    parse_result,
    trimmed_stats,
)
from .budgets import CPU_PROXY_BUDGETS, Budget, BudgetBreach, evaluate_budgets
from .suite import CPU_PROXY_SUITE, run_suite
from .trends import Regression, append_trend, detect_regressions, load_trends

__all__ = [
    "SCHEMA_VERSION",
    "BenchResult",
    "Budget",
    "BudgetBreach",
    "CPU_PROXY_BUDGETS",
    "CPU_PROXY_SUITE",
    "Regression",
    "append_trend",
    "clock",
    "detect_regressions",
    "env_fingerprint",
    "evaluate_budgets",
    "load_trends",
    "measure",
    "parse_result",
    "run_suite",
    "trimmed_stats",
]
