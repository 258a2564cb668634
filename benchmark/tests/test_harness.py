"""The harness's own arithmetic and the manifest's shape."""

import json
import os
import re
import sys

import pytest

from benchmark.lib import compare, harness, readers

from helpers import BENCH, REHEARSAL, REPO

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest(path=os.path.join(REPO, "BENCHMARK.json")):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", [
    os.path.join(REPO, "BENCHMARK.json"), REHEARSAL,
])
def test_manifest_keeps_to_the_contract(path):
    m = manifest(path)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    base = os.path.dirname(path)
    cells = {w["name"] for w in m["workloads"]}
    configs = {c["name"] for c in m["configs"]}
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(
        m["workloads"])
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(base, c["file"]))
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
        assert os.path.exists(os.path.join(
            base, m["paths"][0], "workloads", w["name"] + ".json"))
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= e["bound"] <= 0.1 and UNIT.match(e["unit"])
        assert e["source"] in ("host_clock", "device_trace")
        assert set(e.get("workloads", [])) <= cells
    for p in m["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert p["moves"] in e2e and NAME.match(p["name"])
        assert p["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.exists(bench_run.reader_path(p["name"]))
        for cell in p.get("workloads", []):
            assert cell in e2e[p["moves"]].get("workloads", cells)
    for cell in cells:
        assert len(bench_run.metrics_of(m, "end_to_end", cell)) >= 2
        assert bench_run.metrics_of(m, "per_layer", cell)


def test_metrics_of_lists_a_cell_where_it_is_named_or_no_cell_is():
    m = {
        "end_to_end": [
            {"name": "a", "workloads": ["x"]}, {"name": "setup_s"}],
        "per_layer": [
            {"name": "p", "moves": "a", "workloads": ["x"]},
            {"name": "q.one", "moves": "a", "workloads": ["y"]}],
    }
    assert [e["name"] for e in bench_run.metrics_of(m, "end_to_end", "y")] \
        == ["setup_s"]
    assert [e["name"] for e in bench_run.metrics_of(m, "end_to_end", "x")] \
        == ["a", "setup_s"]
    assert [p["name"] for p in bench_run.metrics_of(m, "per_layer", "y")] \
        == ["q.one"]
    # a quantity split by end-to-end metric shares the reader of its stem
    assert bench_run.reader_path("device.idle_share.loop") \
        == bench_run.reader_path("device.idle_share.learner")
    assert bench_run.reader_path("learner.mfu").endswith("learner.mfu.py")


def test_result_line_has_the_contracts_keys_and_unrounded_values():
    line = json.loads(harness.result_line(
        True, 3, 0, {"setup_s": (1.23456789012, "s")},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 5},
    ))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["metrics"]["setup_s"] == {"value": 1.23456789012, "unit": "s"}


def test_percentile_matches_numpy():
    import numpy as np

    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for q in (50, 95, 100):
        assert harness.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    # second leaf is all but zero: its gap counts against the median norm
    gap = compare.worst_leaf_gap([[1.0], [2e-6], [3.0]], [[1.0], [1e-6], [3.3]])
    assert gap == pytest.approx(0.3 / 3.3)
    # the norm of the difference sees what the gap of the norms cannot
    a, b = [[3.0, 4.0]], [[4.0, 3.0]]
    assert compare.worst_leaf_gap(a, b) == 0.0
    assert compare.worst_leaf_error(a, b) == pytest.approx(2 ** 0.5 / 5)


def test_verdict_needs_every_number_and_at_least_one():
    v = compare.Verdict()
    assert not v.correct
    v.hold("a", 0.1, 0.2)
    v.hold("n", 0, 0, exact=True)
    assert v.correct
    v.hold("nan", float("nan"), 1.0)
    assert not v.correct


def test_trace_readers_on_a_hand_made_summary():
    summary = {
        "window_s": 2.0, "busy_s": 1.5,
        "chips": [
            {"programs": {"jit_step": {"count": 10, "seconds": 1.0},
                          "jit_copy": {"count": 10, "seconds": 0.01}},
             "exposed_collective_s": 0.02},
            {"programs": {"jit_step": {"count": 10, "seconds": 1.2}},
             "exposed_collective_s": 0.05},
        ],
    }
    with open(os.path.join(BENCH, "configs", "impala_deep_atari.json")) as f:
        config = json.load(f)
    readings = {"summary": summary, "steps_per_s": 9.0,
                "frames_per_step_per_chip": 21 * 256}
    ctx = {"config": config, "device": {"kind": "TPU v5 lite",
                                        "memory_peak_bytes": 5_000_000_000}}
    read = lambda name: bench_run.load_reader(name)(readings, ctx)  # noqa: E731
    assert read("learner.device_ms_per_step") == pytest.approx(110.0)
    assert read("device.idle_share.learner") == pytest.approx(25.0)
    assert read("collectives.exposed_ms_per_step") == pytest.approx(5.0)
    assert read("device.peak_hbm_gb") == pytest.approx(5.0)
    flops = 3 * 21 * 256 * 108449280
    assert read("learner.mfu") == pytest.approx(100 * flops * 9.0 / 197e12)
    share = read("kernels.step_roofline_share")
    assert 0 < share < 100
    # an unknown chip is an error, never a default
    ctx["device"]["kind"] = "cpu"
    with pytest.raises(ValueError):
        read("learner.mfu")
    # nothing to read: the reader returns nothing
    assert readers.idle_share({}) is None
    assert bench_run.load_reader("loop.env_wait_share")({}, ctx) is None


def test_loop_invariants_hold_under_any_schedule():
    from benchmark.drivers import vtrace_loop

    nan = float("nan")

    def row(updates, loss=nan, env_steps=0):
        return {"updates": updates, "env_steps": env_steps,
                "total_loss": loss, "entropy": loss, "grad_norm": loss}

    # rows with an update but no gradient step drained (NaN) are not
    # failures while a later row is finite
    rows = [row(2, 0.5), row(3), row(4), row(6, 0.4), row(7)]
    v = compare.Verdict()
    assert vtrace_loop.invariants(rows, 0, 4, v) == 0 and v.correct
    # a poisoned run: every row after the fault is NaN
    rows = [row(2, 0.5), row(4), row(6), row(9), row(12)]
    v = compare.Verdict()
    assert vtrace_loop.invariants(rows, 0, 4, v) == 1 and not v.correct
    # a counter that goes backwards, a window without an update
    v = compare.Verdict()
    vtrace_loop.invariants([row(5, 0.1), row(4, 0.1)], 0, 1, v)
    assert not v.correct
