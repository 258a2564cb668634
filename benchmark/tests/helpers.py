"""Shared by the tests: where things are, and how a rehearsal run is
started (its own process, held to the CPU)."""

import json
import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
REPO = os.path.dirname(BENCH)
REHEARSAL = os.path.join(TESTS, "rehearsal", "BENCHMARK.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def cpu_env(devices: int = 1) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("BENCH_RUN", None)
    return env


def run_cell(workload, seed=3, seconds=1.5, trace=0, devices=1,
             manifest=REHEARSAL, script=None, prefix=(), timeout=600):
    cmd = [*prefix, sys.executable,
           script or os.path.join(BENCH, "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    if manifest:
        cmd += ["--manifest", manifest]
    return subprocess.run(
        cmd, cwd=REPO, env=cpu_env(devices), capture_output=True, text=True,
        timeout=timeout,
    )


def last_line(proc) -> dict:
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1])
