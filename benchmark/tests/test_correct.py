"""``correct`` comes out false when it should: the control (the reference at
the nearest lower precision, in the program's place), the program built in
a lower precision than the configuration states, and the timed path broken
underneath a whole run. And true when nothing is wrong (test_rehearsal.py).

The rehearsal configurations state float32, so their nearest lower
precision is bfloat16; on the chip the cells state bfloat16 and the control
is fp8 (``tools/calibrate.py``; the readings are in PERF.md)."""

import json
import os
import subprocess
import sys

import pytest

from helpers import REHEARSAL, REPO, TESTS, cpu_env, last_line

BROKEN = os.path.join(TESTS, "broken_run.py")


def load(kind, name):
    path = os.path.join(TESTS, "rehearsal", "benchmark", kind, name + ".json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("workload,config", [
    ("tiny_atari_learner", "tiny_atari"),
    ("tiny_nethack_learner", "tiny_nethack"),
    ("tiny_atari_loop", "tiny_atari"),
])
def test_control_fails_and_sound_passes(workload, config):
    import importlib

    import jax

    from benchmark.lib import reference_train

    cell, cfg = load("workloads", workload), load("configs", config)
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    handle = driver.calibration(cell, cfg, jax.devices()[:1])
    control = reference_train.CONTROL_OF[cfg["precision"]]
    for seed in (11, 2 ** 31 + 12, 13):
        sound = handle.sound(seed)
        assert all(sound[k] <= cell["limits"][k] for k in sound), sound
        low = handle.control(seed, control)
        failed = [k for k in low if low[k] > cell["limits"][k]]
        assert failed, (seed, low)  # one of the numbers, not each


@pytest.mark.parametrize("fault,workload", [
    ("lower_precision", "tiny_atari_learner"),
    ("lower_precision", "tiny_nethack_learner"),
    ("step_keeps_state", "tiny_atari_learner"),
    ("half_batch", "tiny_nethack_learner"),
    ("apply_keeps_state", "tiny_atari_loop"),
])
def test_a_whole_run_over_a_broken_path_is_not_correct(fault, workload):
    proc = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", workload, "--seed",
         "5", "--seconds", "1.5", "--trace", "0", "--manifest", REHEARSAL],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is False, proc.stdout[-2000:]
    assert "NOT OK" in proc.stdout
