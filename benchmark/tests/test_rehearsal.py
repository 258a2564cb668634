"""Each driver end to end on the CPU at tiny sizes, through ``run.py`` as
the driver of the check starts it. The rehearsal cells are added the way a
later PR adds a cell: files and manifest entries, no edit to what exists."""

import os
import shutil
import subprocess
import sys

import pytest

from helpers import (BENCH, REPO, RESULT_KEYS, TESTS, cpu_env, last_line,
                     run_cell)


@pytest.mark.parametrize("workload,devices", [
    ("tiny_atari_learner", 1),
    ("tiny_nethack_learner", 1),
    ("tiny_atari_learner_dp4", 4),
    ("tiny_atari_loop", 1),
])
def test_end_to_end_line(workload, devices):
    proc = run_cell(workload, devices=devices)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert set(line["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"
    }
    names = set(line["metrics"])
    assert "setup_s" in names and len(names) >= 2
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert "[compare]" in proc.stdout  # every number beside its limit


@pytest.mark.parametrize("workload", [
    "tiny_atari_learner", "tiny_atari_loop",
])
def test_traced_line(workload):
    proc = run_cell(workload, trace=1, seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert RESULT_KEYS <= set(line) <= RESULT_KEYS | {"breakdown"}
    assert line["correct"] is True
    # No device metric from a CPU run: the trace readers found no device
    # plane and returned nothing.
    for name in line["metrics"]:
        assert not name.startswith(("device.idle", "learner.device",
                                    "kernels.", "collectives."))
    assert "busy_s" not in line["device"]
    if workload == "tiny_atari_loop":
        assert {"loop.env_wait_share", "loop.host_sync_share",
                "loop.grad_reduce_share",
                "loop.dropped_unroll_share"} <= set(line["metrics"])


def test_a_cell_of_the_benchmark_is_refused_on_a_cpu():
    proc = run_cell("atari_learner", manifest=None)
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_too_few_chips_is_refused():
    proc = run_cell("tiny_atari_learner_dp4", devices=1)
    assert proc.returncode != 0
    assert "count=1" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_alone_in_a_directory_it_exits_non_zero(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        BENCH, tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "atari_learner",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=cpu_env(), capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


@pytest.mark.skipif(shutil.which("taskset") is None, reason="no taskset")
def test_loop_correct_on_20_seeds_pinned_to_one_core():
    """``correct`` of the loop cell depends on the seed and the code alone:
    twenty seeds, the whole process tree held to one core (workers, RPC
    threads and the loop then interleave as badly as they can), all agree.
    A check that depends on scheduling fails here before it costs chip
    time."""
    proc = subprocess.run(
        ["taskset", "-c", "0", sys.executable,
         os.path.join(TESTS, "loop_seeds.py"), "20"],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True,
        timeout=1500,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    verdicts = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("[seed]")]
    assert len(verdicts) == 20
    assert all("correct=True" in ln for ln in verdicts), verdicts
