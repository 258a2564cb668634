"""perfwatch: harness schema, trend store, regression detector, budgets,
and the tools/perf.py gate (ISSUE 7).

The detector tests are the load-bearing ones: a perf gate that misses a
planted 20% regression is not a gate, and one that fires on tolerance-band
noise gets deleted by the first annoyed maintainer. Both behaviours are
pinned on seeded fixture trends, and the CLI-level acceptance (planted
regression -> exit 1 with a reproduce command; clean trend -> exit 0) runs
the real ``tools/perf.py`` entrypoint.
"""

import json
import random
import subprocess
import sys
from pathlib import Path

import pytest

from moolib_tpu.bench import (
    BenchResult,
    append_trend,
    detect_regressions,
    evaluate_budgets,
    load_trends,
    parse_result,
    trimmed_stats,
)
from moolib_tpu.bench.budgets import Budget
from moolib_tpu.bench.suite import CPU_PROXY_SUITE

REPO = Path(__file__).resolve().parent.parent
PERF = REPO / "tools" / "perf.py"


# -- harness schema -----------------------------------------------------------


def test_trimmed_stats_drops_outlier_tails():
    s = trimmed_stats([1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 100.0],
                      trim=0.2)
    assert s["n"] == 10
    assert s["median"] == 1.0
    assert s["trimmed_mean"] == 1.0      # the 100.0 tail is out
    assert s["mean"] == pytest.approx(10.9)
    assert s["max"] == 100.0             # but stays on the record


def test_result_roundtrip_jsonl_identical(tmp_path):
    """The satellite contract: result -> JSONL -> parse -> identical."""
    r = BenchResult(
        metric="rpc_echo_latency_s", value=0.0011, unit="s/call",
        direction="lower", suite="cpu-proxy", smoke=True, tol=0.5,
        cmd="python tools/perf.py --suite cpu-proxy --only rpc_echo_latency_s",
        stats={"n": 5, "median": 0.0011},
        telemetry={"x_seconds": {"type": "histogram", "edges": [1.0],
                                 "buckets": [2, 2], "sum": 0.4, "count": 2,
                                 "p50": 0.5, "p95": 0.9, "p99": 0.99}},
        extra={"note": "fixture"},
    )
    assert parse_result(r.to_json()) == r
    p = tmp_path / "trends.jsonl"
    append_trend(str(p), r)
    append_trend(str(p), r.to_row())  # dict form validates + appends too
    rows = load_trends(str(p))
    assert rows == [r, r]


def test_parse_result_rejects_bad_rows():
    with pytest.raises(ValueError, match="schema"):
        parse_result({"schema": 99, "metric": "m", "value": 1, "unit": "x"})
    with pytest.raises(ValueError, match="unknown result fields"):
        parse_result({"schema": 1, "metric": "m", "value": 1, "unit": "x",
                      "bogus": True})
    with pytest.raises(ValueError, match="missing"):
        parse_result({"schema": 1, "metric": "m"})
    with pytest.raises(ValueError, match="direction"):
        BenchResult(metric="m", value=1.0, unit="x", direction="sideways")


def test_load_trends_raises_on_corrupt_line(tmp_path):
    p = tmp_path / "trends.jsonl"
    append_trend(str(p), BenchResult(metric="m", value=1.0, unit="x"))
    with open(p, "a") as f:
        f.write("not json\n")
    with pytest.raises(ValueError, match="bad trend row"):
        load_trends(str(p))


def test_suite_catalogue_covers_the_cpu_proxies():
    # The ISSUE 7 catalogue plus ISSUE 8's serving rows, ISSUE 12's
    # env-tier recovery row, ISSUE 14's shm transport-lane row, ISSUE
    # 15's durable-state replication row and ISSUE 19's fleet rollout
    # row: every named host-plane proxy present, every entry carrying a
    # reproduce-command-compatible name.
    assert set(CPU_PROXY_SUITE) == {
        "rpc_echo_latency_s", "rpc_payload_gbps", "rpc_shm_payload_gbps",
        "allreduce_tree_gbps",
        "batcher_fill_s", "envpool_steps_per_s", "envpool_recovery_s",
        "serial_encode_gbps", "serial_decode_gbps",
        "statestore_replicate_gbps", "serving_qps",
        "serving_p99_latency_s", "fleet_rollout_s",
    }


# -- regression detector ------------------------------------------------------


def _trend_rows(values, metric="proxy_gbps", direction="higher"):
    return [
        BenchResult(metric=metric, value=v, unit="GB/s",
                    direction=direction, suite="cpu-proxy", smoke=True,
                    cmd=f"python tools/perf.py --suite cpu-proxy "
                        f"--only {metric} --smoke")
        for v in values
    ]


def test_detector_flags_planted_20pct_regression():
    rng = random.Random(7)
    history = [100.0 * (1 + rng.gauss(0, 0.01)) for _ in range(8)]
    rows = _trend_rows(history + [80.0])  # planted -20%
    regs = detect_regressions(rows)
    assert len(regs) == 1
    r = regs[0]
    assert r.metric == "proxy_gbps"
    assert r.ratio == pytest.approx(0.8, abs=0.02)
    assert "--only proxy_gbps" in r.cmd
    assert "reproduce:" in r.message()


def test_detector_ignores_noise_at_the_tolerance_band():
    """Values jittering up to the 15% tolerance band must not flag —
    including a final sample sitting right at the band edge."""
    rng = random.Random(3)
    history = [100.0 * (1 + rng.gauss(0, 0.03)) for _ in range(8)]
    rows = _trend_rows(history + [86.0])  # ~-14%: inside the band
    assert detect_regressions(rows) == []


def test_detector_latency_direction_flags_rises_not_drops():
    lat = _trend_rows([1.0, 1.01, 0.99, 1.0], metric="echo_s",
                      direction="lower")
    assert detect_regressions(lat + _trend_rows([1.4], "echo_s", "lower"))
    # A latency IMPROVEMENT never flags.
    assert not detect_regressions(
        lat + _trend_rows([0.5], "echo_s", "lower"))


def test_detector_needs_history_and_skips_null_rows():
    assert detect_regressions(_trend_rows([100.0, 50.0])) == []  # too little
    rows = _trend_rows([100.0, 101.0, 99.0, 100.0])
    rows.append(BenchResult(metric="proxy_gbps", value=None, unit="GB/s",
                            suite="cpu-proxy", smoke=True,
                            error="no device"))
    # The null artifact stays on record but is not a regression verdict.
    assert detect_regressions(rows) == []


def test_detector_widens_band_for_noisy_history():
    """A metric whose own history jitters +-20% needs a bigger step to
    flag than the 15% relative tolerance."""
    noisy = [100, 120, 80, 115, 85, 110, 90, 100]
    rows = _trend_rows([float(v) for v in noisy] + [78.0])
    assert detect_regressions(rows) == []  # inside the MAD-derived band


def test_detector_honors_row_declared_tolerance():
    """A benchmark that declares its observed CI noise as a per-row
    ``tol`` widens its own band (a -20% step stays quiet at tol=0.5)
    without loosening the default band for other metrics."""
    rng = random.Random(9)
    history = [100.0 * (1 + rng.gauss(0, 0.01)) for _ in range(8)]
    rows = _trend_rows(history + [80.0])
    for r in rows:
        r.tol = 0.5
    assert detect_regressions(rows) == []
    rows[-1].value = 45.0  # but a structural 2x-class step still flags
    regs = detect_regressions(rows)
    assert len(regs) == 1 and regs[0].band == pytest.approx(
        0.5 * regs[0].baseline)
    with pytest.raises(ValueError, match="tol"):
        BenchResult(metric="m", value=1.0, unit="x", tol=1.5)


# -- stepscope fraction rows (ISSUE 20) ---------------------------------------
#
# The critical-path fractions ride the SAME store and detector as the
# throughput rows: unit "fraction", direction "lower" (a growing
# exposed-comms share is a step-composition regression even when
# headline throughput holds), loop-qualified metric names so an
# envpool's env-wait series never shares a baseline with a learner's.

STEPSCOPE_SMOKE_CMD = "python tools/stepscope_report.py --smoke"


def _fraction_summary(exposed, loop="a2c_learner"):
    return {
        "loop": loop, "steps": 50, "wall_s": 1.0,
        "phases": {"grad_allreduce": exposed, "other": 1.0 - exposed},
        "fractions": {"exposed_comms": exposed, "host_blocked": 0.0,
                      "env_wait": 0.0},
    }


def _fraction_rows(exposed_values, loop="a2c_learner"):
    from moolib_tpu.bench.harness import stepscope_trend_rows

    rows = []
    for v in exposed_values:
        rows.extend(stepscope_trend_rows(_fraction_summary(v, loop),
                                         smoke=True,
                                         cmd=STEPSCOPE_SMOKE_CMD))
    return rows


def test_stepscope_trend_rows_are_schema_valid_fraction_rows(tmp_path):
    from moolib_tpu.bench.harness import (STEPSCOPE_TREND_TOLERANCE,
                                          stepscope_trend_rows)

    rows = stepscope_trend_rows(_fraction_summary(0.2), smoke=True,
                                cmd=STEPSCOPE_SMOKE_CMD)
    assert [r.metric for r in rows] == [
        "stepscope_a2c_learner_exposed_comms_fraction",
        "stepscope_a2c_learner_host_blocked_fraction",
        "stepscope_a2c_learner_env_wait_fraction",
    ]
    store = tmp_path / "trends.jsonl"
    for r in rows:
        # Every row rides the unified schema: unit "fraction", the bad
        # direction is UP so the schema direction is "lower", the wide
        # smoke-scale tolerance is declared per row, and the round-trip
        # through the store is exact.
        assert r.unit == "fraction"
        assert r.direction == "lower"
        assert r.suite == "stepscope"
        assert r.tol == STEPSCOPE_TREND_TOLERANCE
        assert 0.0 <= r.value <= 1.0
        assert r.extra == {"loop": "a2c_learner", "steps": 50}
        assert parse_result(r.to_json()) == r
        append_trend(store, r)
    assert [r.metric for r in load_trends(store)] == [r.metric for r in rows]


def test_stepscope_direction_vocabulary_is_lower_not_down():
    # The phase fractions trend "down is good"; the schema's vocabulary
    # for that is direction="lower" — "down" itself must be rejected at
    # construction, not silently stored and skipped by the detector.
    with pytest.raises(ValueError, match="direction"):
        BenchResult(metric="stepscope_x_exposed_comms_fraction", value=0.1,
                    unit="fraction", direction="down")


def test_detector_flags_planted_exposed_comms_regression():
    """An exposed-comms share stepping 0.04 -> 0.5 (overlap silently
    disabled) must flag despite the wide tol=0.5 band, with the smoke's
    reproduce command on the verdict."""
    rng = random.Random(20)
    history = [0.04 * (1 + rng.gauss(0, 0.05)) for _ in range(8)]
    regs = detect_regressions(_fraction_rows(history + [0.5]))
    assert len(regs) == 1
    r = regs[0]
    assert r.metric == "stepscope_a2c_learner_exposed_comms_fraction"
    assert r.current == pytest.approx(0.5)
    assert r.cmd == STEPSCOPE_SMOKE_CMD
    assert "rose" in r.message() and "reproduce:" in r.message()


def test_detector_fraction_tolerance_and_direction_semantics():
    rng = random.Random(21)
    history = [0.04 * (1 + rng.gauss(0, 0.05)) for _ in range(8)]
    # tol=0.5 semantics: a +40% drift stays inside the declared band
    # (fractions are noisy at smoke scale) ...
    assert detect_regressions(_fraction_rows(history + [0.055])) == []
    # ... and an IMPROVEMENT (comms fully overlapped) never flags.
    assert detect_regressions(_fraction_rows(history + [0.0])) == []


def test_fraction_rows_per_loop_series_never_share_a_baseline():
    """A learner sitting at 0.05 exposed comms and an accumulator whose
    wire-wait share is legitimately ~0.9 coexist in one store: the
    loop-qualified metric names keep their baselines apart, so neither
    flags the other."""
    rows = _fraction_rows([0.05, 0.05, 0.05, 0.05, 0.05], "a2c_learner")
    rows += _fraction_rows([0.9, 0.9, 0.9, 0.9, 0.9], "acc_grad_round")
    assert detect_regressions(rows) == []


# -- budgets ------------------------------------------------------------------


def _hist_series(p99):
    return {"type": "histogram", "edges": [1.0], "buckets": [10, 10],
            "sum": 1.0, "count": 10, "p50": p99 / 2, "p95": p99 * 0.9,
            "p99": p99}


def test_budget_reads_quantiles_from_attached_snapshot():
    budgets = {"m": Budget(quantiles=[
        ("rpc_server_handle_seconds", 'endpoint="echo"', {"p99": 0.5}),
    ])}
    ok = BenchResult(
        metric="m", value=1.0, unit="x", cmd="repro",
        telemetry={'rpc_server_handle_seconds{endpoint="echo"}':
                   _hist_series(p99=0.2)})
    assert evaluate_budgets(ok, budgets) == []
    bad = BenchResult(
        metric="m", value=1.0, unit="x", cmd="repro",
        telemetry={'rpc_server_handle_seconds{endpoint="echo"}':
                   _hist_series(p99=0.9)})
    breaches = evaluate_budgets(bad, budgets)
    assert len(breaches) == 1
    assert breaches[0].what.endswith(".p99")
    assert "repro" in breaches[0].message()
    # Series-name prefix must not cross metrics: a different endpoint
    # label or metric name stays unmatched (value bounds still apply).
    other = BenchResult(
        metric="m", value=1.0, unit="x",
        telemetry={'rpc_server_handle_seconds_extra{endpoint="echo"}':
                   _hist_series(p99=9.9)})
    assert evaluate_budgets(other, budgets) == []


def test_budget_value_floor_and_ceiling():
    budgets = {"thr": Budget(value_min=1.0), "lat": Budget(value_max=0.1)}
    assert evaluate_budgets(
        BenchResult(metric="thr", value=0.5, unit="GB/s"), budgets
    )[0].kind == "floor"
    assert evaluate_budgets(
        BenchResult(metric="lat", value=0.5, unit="s"), budgets
    )[0].kind == "ceiling"
    # Null rows are the trend layer's business, never a budget breach.
    assert evaluate_budgets(
        BenchResult(metric="thr", value=None, unit="", error="x"), budgets
    ) == []


# -- tools/perf.py gate (the CLI acceptance) ---------------------------------


def _run_perf(args, timeout=120):
    return subprocess.run(
        [sys.executable, str(PERF)] + args,
        capture_output=True, text=True, cwd=str(REPO), timeout=timeout,
    )


def test_perf_cli_list():
    proc = _run_perf(["--list"])
    assert proc.returncode == 0, proc.stderr
    for name in CPU_PROXY_SUITE:
        assert name in proc.stdout


def test_perf_cli_gate_planted_regression_fails_clean_passes(tmp_path):
    """ISSUE 7 acceptance: a planted regression in a fixture trend fails
    the gate printing the reproduce command; the clean trend passes."""
    clean = tmp_path / "clean.jsonl"
    rng = random.Random(11)
    history = [100.0 * (1 + rng.gauss(0, 0.01)) for _ in range(6)]
    for r in _trend_rows(history + [99.5]):
        append_trend(str(clean), r)
    proc = _run_perf(["--check-trends-only", "--trends", str(clean)])
    assert proc.returncode == 0, proc.stdout + proc.stderr

    planted = tmp_path / "planted.jsonl"
    for r in _trend_rows(history + [80.0]):
        append_trend(str(planted), r)
    proc = _run_perf(["--check-trends-only", "--trends", str(planted)])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "REGRESSION proxy_gbps" in proc.stdout
    assert "reproduce: python tools/perf.py" in proc.stdout
    # GHA format turns the same failure into a workflow annotation.
    proc = _run_perf(["--check-trends-only", "--trends", str(planted),
                      "--format", "gha"])
    assert proc.returncode == 1
    assert "::error title=perfwatch::" in proc.stdout


def test_perf_cli_runs_fast_benches_and_appends_schema_valid_rows(tmp_path):
    """End-to-end through the real CLI on the cheap serial benchmarks:
    exit 0, schema-valid rows appended, summary line parseable."""
    trends = tmp_path / "trends.jsonl"
    proc = _run_perf([
        "--suite", "cpu-proxy", "--smoke",
        "--only", "serial_encode_gbps,serial_decode_gbps",
        "--trends", str(trends),
    ], timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = load_trends(str(trends))
    assert [r.metric for r in rows] == ["serial_encode_gbps",
                                       "serial_decode_gbps"]
    assert all(r.value is not None and r.smoke for r in rows)
    assert all(r.cmd.startswith("python tools/perf.py") for r in rows)
    summary = [json.loads(l) for l in proc.stdout.splitlines()
               if l.startswith("{")][-1]
    assert summary["results"] == 2
    assert summary["nulls"] == 0


def test_perf_cli_post_run_gate_ignores_stale_foreign_series(tmp_path):
    """The post-run gate only fails on metrics THIS run produced: a
    stale regressive series from another suite sharing the store (e.g.
    device rows) must not red an unrelated cpu-proxy run — whole-store
    semantics belong to --check-trends-only, which must still flag it."""
    trends = tmp_path / "trends.jsonl"
    rng = random.Random(13)
    history = [100.0 * (1 + rng.gauss(0, 0.01)) for _ in range(6)]
    for r in _trend_rows(history + [60.0], metric="device_gbps"):
        append_trend(str(trends), r)
    proc = _run_perf([
        "--suite", "cpu-proxy", "--smoke", "--only", "serial_encode_gbps",
        "--trends", str(trends),
    ], timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = _run_perf(["--check-trends-only", "--trends", str(trends)])
    assert proc.returncode == 1
    assert "REGRESSION device_gbps" in proc.stdout


def test_perf_cli_check_trends_flags_trailing_nulls(tmp_path):
    """A store whose latest row per series is a null artifact (every
    stage of a device session errored) must NOT read as a green gate."""
    trends = tmp_path / "trends.jsonl"
    append_trend(str(trends), BenchResult(
        metric="impala_train_env_steps_per_sec_per_chip", value=None,
        unit="", suite="device", cmd="python bench.py",
        error="no TPU found"))
    proc = _run_perf(["--check-trends-only", "--trends", str(trends)])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "NULL impala_train_env_steps_per_sec_per_chip" in proc.stdout
    assert "reproduce: python bench.py" in proc.stdout
    # A later good row for the same series clears the trailing null.
    append_trend(str(trends), BenchResult(
        metric="impala_train_env_steps_per_sec_per_chip", value=77000.0,
        unit="env-steps/s/chip", suite="device", cmd="python bench.py"))
    proc = _run_perf(["--check-trends-only", "--trends", str(trends)])
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_perf_cli_unknown_bench_is_usage_error():
    proc = _run_perf(["--suite", "cpu-proxy", "--only", "nope",
                      "--no-trends"])
    assert proc.returncode == 2
    assert "unknown benchmark" in proc.stderr
